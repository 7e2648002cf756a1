"""Fault-tolerant serving tier (runtime/tier.ServingTier): replica
failure domains over CNNPipelineServer workers.

The headline contracts:
- drain-and-respawn: killing a replica mid-stream re-routes its queued
  AND in-flight microbatches onto healthy replicas, and the delivered
  logits are BITWISE identical to a no-failure run (every microbatch's
  output is a pure function of its content — slots never mix, all
  replicas share one (cfg, params, plan));
- typed degradation: load shedding, deadlines, timeouts, and retry
  exhaustion surface as typed TierError subclasses on results(), never
  as silently dropped or corrupted requests;
- permanent device loss re-plans the reduced pool
  (planner.plan with prev=) and re-places the packed param
  buffer via fault.remesh — the 8->4 degrade test runs under
  XLA_FLAGS=--xla_force_host_platform_device_count=8 (CI's
  fault-injection leg).

Admission/queue/health tests are compute-free (they shed or fail
before any pipeline tick compiles), so this file stays cheap on the
single-device leg.
"""
import numpy as np
import pytest

import jax

from repro.core import planner
from repro.configs import get_config
from repro.models import cnn
from repro.runtime import tier as T
from repro.runtime.fault import FailureInjector, InjectedFailure

ARCH = "mobilenet_v1"          # dense (paper Table IV), cheapest compile
IMG = 32


def _imgs(seed, batch):
    return np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), (batch, IMG, IMG, 3)), np.float32)


def _stream(tier, n_req=3, batch=4, seed0=10):
    rids = [tier.submit(_imgs(seed0 + i, batch)) for i in range(n_req)]
    metrics = tier.run()
    return [tier.results(r) for r in rids], metrics


def _tier(**kw):
    kw.setdefault("n_replicas", 2)
    kw.setdefault("n_stages", 2)
    kw.setdefault("mb_size", 2)
    kw.setdefault("image_size", IMG)
    kw.setdefault("placed", False)
    return T.ServingTier(ARCH, **kw)


class _AlwaysFail(FailureInjector):
    def maybe_fail(self, step):
        raise InjectedFailure("always")


# --- admission queue (pure python, no pipelines) ----------------------------

def test_admission_queue_priority_deadline_fifo():
    q = T.AdmissionQueue()
    mk = lambda rid, pr, dl, seq: T.WorkItem(
        rid=rid, mb_index=0, n_valid=1, images=None, priority=pr,
        deadline_at=dl, seq=seq)
    q.push(mk(0, 0, None, 1))          # plain FIFO
    q.push(mk(1, 0, 5.0, 2))           # deadline beats no-deadline
    q.push(mk(2, 1, None, 3))          # priority beats both
    q.push(mk(3, 0, 2.0, 4))           # earlier deadline beats later
    assert [q.pop().rid for _ in range(4)] == [2, 3, 1, 0]
    assert q.pop() is None


def test_admission_queue_tenant_fairness_on_ties():
    q = T.AdmissionQueue()
    for seq in range(6):
        q.push(T.WorkItem(rid=seq, mb_index=0, n_valid=1, images=None,
                          tenant="a" if seq < 3 else "b", seq=seq))
    # equal priority/deadline: tenants rotate (least recently served
    # first) — tenant a's earlier backlog cannot starve b
    assert [q.pop().tenant for _ in range(6)] == \
        ["a", "b", "a", "b", "a", "b"]


def test_admission_queue_bound_and_recovery_bypass():
    q = T.AdmissionQueue(max_per_tenant=2)
    q.push(T.WorkItem(rid=0, mb_index=0, n_valid=1, images=None, seq=1))
    q.push(T.WorkItem(rid=0, mb_index=1, n_valid=1, images=None, seq=2))
    with pytest.raises(T.QueueFullError):
        q.admit_check("default", 1)
    # recovered (already-admitted) work re-enters past the bound
    q.push(T.WorkItem(rid=1, mb_index=0, n_valid=1, images=None, seq=0),
           front=True)
    assert len(q) == 3
    assert q.pop().rid == 1            # front push drains first


# --- typed shedding (compute-free: no tick ever runs) -----------------------

def test_submit_queue_full_is_request_atomic():
    tier = _tier(n_replicas=1, max_queue_per_tenant=3)
    tier.submit(_imgs(0, 4))           # 2 microbatches admitted
    with pytest.raises(T.QueueFullError):
        tier.submit(_imgs(1, 4))       # 2 more would exceed 3
    assert len(tier.queue) == 2        # nothing half-enqueued
    tier.submit(_imgs(2, 2))           # 1 microbatch still fits


def test_deadline_and_timeout_shed_typed():
    now = [0.0]
    tier = _tier(n_replicas=1, clock=lambda: now[0],
                 request_timeout_s=5.0)
    r_dl = tier.submit(_imgs(0, 2), deadline_s=1.0)
    r_to = tier.submit(_imgs(1, 2))
    now[0] = 6.0                       # past both bounds
    # both requests shed on the run loop's FIRST deadline sweep, so no
    # pipeline tick ever runs (and nothing compiles); the request's
    # own deadline outranks the tier-wide timeout in the error type
    m = tier.run()
    assert m["failed"] == 2
    assert tier.workers[0].server.ticks == 0
    with pytest.raises(T.DeadlineExceededError):
        tier.results(r_dl)
    with pytest.raises(T.RequestTimeoutError):
        tier.results(r_to)
    assert sum(tier._pending.get(r, 0) for r in (r_dl, r_to)) == 0


def test_retry_exhaustion_and_no_healthy_replica():
    tier = _tier(n_replicas=1, injectors={0: _AlwaysFail()},
                 max_retries=1, max_respawns=1,
                 backoff_base_s=0.0, sleep=lambda s: None)
    rid = tier.submit(_imgs(0, 2))
    # failure 1: retries=1 (requeued, respawn 1); failure 2: retries=2
    # > max_retries -> the request fails typed, and consecutive
    # failure 2 > max_respawns retires the replica permanently
    tier.run()
    with pytest.raises(T.ReplicaFailedError):
        tier.results(rid)
    rid2 = tier.submit(_imgs(1, 2))
    with pytest.raises(T.NoHealthyReplicaError):
        tier.run()
    assert rid2 in tier._pending       # work survives the outage


# --- drain-and-respawn: the bitwise acceptance bar --------------------------

@pytest.fixture(scope="module")
def ref_tier():
    """One no-failure tier reused for every reference stream (a
    healthy tier serves arbitrarily many streams; sharing it keeps the
    compile count down)."""
    return _tier()


def test_kill_one_of_two_replicas_bitwise(ref_tier):
    """A FailureInjector kills replica 1 mid-stream; every request
    completes and the logits are bitwise identical to the same stream
    with no failure."""
    ref, m0 = _stream(ref_tier)
    tier = _tier(injectors={1: FailureInjector(fail_at_steps=(2,))})
    got, m1 = _stream(tier)
    assert m1["respawns"] == 1
    assert m1["recovered_microbatches"] > 0
    assert m1["completed"] == m0["completed"] == 3
    assert m1["failed"] == 0
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


def test_killed_replica_respawns_and_serves_again(ref_tier):
    tier = _tier(injectors={0: FailureInjector(fail_at_steps=(1,))},
                 backoff_base_s=0.0)
    _, m = _stream(tier, n_req=2)
    assert m["respawns"] == 1
    assert all(w.alive for w in tier.workers)
    # the respawned replica is healthy: a fresh stream through the
    # same tier still matches the no-failure reference bitwise
    ref, _ = _stream(ref_tier, n_req=2, seed0=50)
    got, _ = _stream(tier, n_req=2, seed0=50)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


# --- degradation re-planning ------------------------------------------------

def test_replan_reuses_feasible_cut():
    cfg = get_config(ARCH)
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    prev = planner.plan(cfg, params, planner.PlanRequest(n_stages=4))
    out = planner.plan(cfg, params,
                       planner.PlanRequest(n_devices=4, prev=prev))
    assert out["reused"] and out["plan"] is prev
    assert (out["n_stages"], out["n_replicas"]) == (4, 1)
    # indivisible pool: falls back to the full co-planner
    out3 = planner.plan(cfg, params,
                        planner.PlanRequest(n_devices=3, prev=prev))
    assert not out3["reused"]
    assert out3["n_stages"] * out3["n_replicas"] <= 3


needs8 = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def placed_ref_tier():
    t = T.ServingTier(ARCH, n_replicas=2, n_stages=4, mb_size=2,
                      image_size=IMG)
    assert t.placed
    return t


@needs8
def test_placed_tier_device_loss_degrades_and_finishes(placed_ref_tier):
    """The 8->4 acceptance bar: a placed 2x4 tier loses 4 devices
    mid-stream (killing BOTH workers), re-plans via
    planner.plan with prev= (cut reused), respawns one worker on the
    surviving slice with a fault.remesh-re-placed param buffer, and
    finishes the stream — logits bitwise equal to the no-failure run
    (stage cuts never change numerics)."""
    ref, _ = _stream(placed_ref_tier, n_req=4, seed0=20)

    tier = T.ServingTier(ARCH, n_replicas=2, n_stages=4, mb_size=2,
                         image_size=IMG)
    rids = [tier.submit(_imgs(20 + i, 4)) for i in range(4)]
    tier.run(max_rounds=2)             # stream is mid-flight
    devs = jax.devices()
    replan = tier.lose_devices(devs[2:6])
    assert replan["reused"]            # S=4 divides the 4 survivors
    assert replan["n_replicas"] == 1
    m = tier.run()
    assert m["failed"] == 0
    assert m["replicas_alive"] == 1
    new = tier.workers[-1]
    assert {d.id for d in new.devices} == \
        {d.id for d in (devs[:2] + devs[6:])}
    got = [tier.results(r) for r in rids]
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


@needs8
def test_placed_tier_kill_replica_bitwise(placed_ref_tier):
    """Placed (device-sliced) edition of the kill test: replica
    workers own disjoint 4-device stage meshes, one dies mid-stream,
    results stay bitwise."""
    ref, _ = _stream(placed_ref_tier, n_req=3, seed0=30)
    tier = T.ServingTier(ARCH, n_replicas=2, n_stages=4, mb_size=2,
                         image_size=IMG,
                         injectors={1: FailureInjector(
                             fail_at_steps=(1,))})
    got, m = _stream(tier, n_req=3, seed0=30)
    assert m["respawns"] == 1 and m["failed"] == 0
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)


# -- one process per chip: process tiers refuse on an accelerator host --------

@pytest.mark.parametrize("tier_cls", ["ProcessServingTier",
                                      "HostServingTier"])
def test_process_tier_refuses_when_supervisor_holds_device(monkeypatch,
                                                            tier_cls):
    """On a TPU host the supervisor has initialized JAX and holds the
    chip, so worker processes could never reach it: the tier raises at
    once, naming the cause, and spawns nothing. The platform is steered
    inside the test; the real backend here is the CPU."""
    import subprocess
    spawned = []
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(subprocess, "Popen",
                        lambda *a, **k: spawned.append(a))
    with pytest.raises(T.DeviceHeldError, match="holds the chip"):
        getattr(T, tier_cls)(ARCH, n_procs=1, n_stages=2, image_size=IMG)
    assert not spawned


def test_serve_procs_refuses_when_supervisor_holds_device(monkeypatch):
    """The same guard on the front door: serve(ServeConfig(procs=...))
    on an accelerator host fails before any worker starts."""
    from repro.launch.serve import ServeConfig, serve
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(T.DeviceHeldError):
        serve(ServeConfig(arch=ARCH, procs=2, n_stages=2, image_size=IMG,
                          verbose=False))
