"""Continuous-batching CNN serving (launch/serve.CNNPipelineServer):
back-to-back requests stream through a never-draining pipeline and must
produce EXACTLY the logits of isolated per-request runs — slots never
mix — while the steady-state bubble beats the single-batch fill bubble
(one S-1-tick fill amortizes over the whole request stream). Runs on
the default single device: the server then uses the ragged
PlacedParams.pack_ragged() rows (packed params, no even-width padding),
so this file also covers the ragged executor path end to end.
"""
import numpy as np
import pytest

import jax

from repro.core import pipeline as pp
from repro.launch.serve import CNNPipelineServer, ServeConfig, serve

ARCH = "mobilenet_v1"          # dense (paper Table IV), cheapest compile
IMG = 32


def _imgs(seed, batch):
    return np.asarray(jax.random.normal(
        jax.random.PRNGKey(seed), (batch, IMG, IMG, 3)), np.float32)


def test_back_to_back_requests_match_isolated_calls():
    """The ISSUE 5 continuous-batching bar: two requests served
    back-to-back (no drain between them) produce the same logits as
    two isolated calls."""
    srv = CNNPipelineServer(ARCH, mb_size=2, n_stages=3, image_size=IMG)
    a, b = _imgs(7, 4), _imgs(8, 4)
    r1, r2 = srv.submit(a), srv.submit(b)
    srv.run()
    iso = CNNPipelineServer(ARCH, mb_size=2, n_stages=3, image_size=IMG)
    q1 = iso.submit(a)
    iso.run()
    l1 = iso.results(q1)
    q2 = iso.submit(b)
    iso.run()
    l2 = iso.results(q2)
    np.testing.assert_array_equal(srv.results(r1), l1)
    np.testing.assert_array_equal(srv.results(r2), l2)


def test_continuous_matches_sequential_interpreter():
    """Continuous pipelined logits == the sequential graph interpreter
    bitwise (the wire/param packing round-trips are lossless; request
    batch == one interpreter batch so conv batch sizes line up with
    the in-process equivalence tests' contract)."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import cnn
    srv = CNNPipelineServer(ARCH, mb_size=2, n_stages=3, image_size=IMG,
                            seed=0)
    imgs = _imgs(9, 2)
    req = srv.submit(imgs)
    srv.run()
    cfg = get_config(ARCH)
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    ref = jax.jit(lambda p, x: cnn.cnn_forward(cfg, p, x))(
        params, jnp.asarray(imgs))
    np.testing.assert_array_equal(srv.results(req), np.asarray(ref))


def test_steady_bubble_beats_single_batch_fill():
    """K back-to-back requests leave (S-1)/(K*M + S-1) of the slots
    empty — strictly less than one batch's fill bubble (S-1)/(M+S-1) —
    and the server's tick accounting reports exactly that."""
    m = serve(ServeConfig(ARCH, continuous=True, n_requests=3, batch=4,
                          mb_size=2, n_stages=3, image_size=IMG,
                          verbose=False))
    k, mm, s = 3, 2, m["n_stages"]
    assert m["ticks"] == k * mm + s - 1
    assert m["injected_microbatches"] == k * mm
    assert m["steady_bubble"] == pytest.approx(
        pp.steady_bubble_fraction(k * mm, s))
    assert m["steady_bubble"] < m["fill_bubble_single_batch"]
    assert m["fill_bubble_single_batch"] == pytest.approx(
        pp.bubble_fraction(mm, s))
    assert [l.shape for l in m["logits"]] == [(4, 1000)] * 3
    assert m["images"] == 12


def test_partial_microbatch_pads_and_drops():
    """A request that doesn't fill its last microbatch gets zero-padded
    on the wire and the pad rows dropped from its logits."""
    srv = CNNPipelineServer(ARCH, mb_size=2, n_stages=3, image_size=IMG)
    imgs = _imgs(11, 3)                      # 3 imgs -> 2 microbatches
    req = srv.submit(imgs)
    srv.run()
    out = srv.results(req)
    assert out.shape == (3, 1000)
    iso = CNNPipelineServer(ARCH, mb_size=2, n_stages=3, image_size=IMG)
    q = iso.submit(_imgs(11, 3)[:2])         # the full first microbatch
    iso.run()
    np.testing.assert_array_equal(out[:2], iso.results(q))


def test_results_before_run_raises():
    srv = CNNPipelineServer(ARCH, mb_size=2, n_stages=3, image_size=IMG)
    req = srv.submit(_imgs(12, 2))
    with pytest.raises(ValueError, match="incomplete"):
        srv.results(req)
    with pytest.raises(KeyError, match="unknown request"):
        srv.results(999)
    with pytest.raises(ValueError, match="!="):
        srv.submit(np.zeros((2, IMG + 1, IMG + 1, 3), np.float32))
    srv.run()
    assert srv.results(req).shape == (2, 1000)


def test_run_compiles_nothing_when_warm():
    """run() reports the backend compiles made while it ran: a second
    identical run() compiles nothing."""
    srv = CNNPipelineServer(ARCH, mb_size=2, n_stages=3, image_size=IMG)
    for _ in range(2):
        reqs = [srv.submit(_imgs(13, 3)), srv.submit(_imgs(14, 2))]
        m = srv.run()
        for r in reqs:
            srv.results(r)
        assert m["injected_microbatches"] == 3
        assert m["ticks"] == 3 + m["n_stages"] - 1
    assert m["compiles"] == 0


def test_spans_nest_in_a_profile(tmp_path):
    """Under the JAX profiler the server's own host spans appear, under
    their bare names, and nest: serve.run holds the ticks, a tick holds
    its dispatch, its staging of the next microbatch and its readback."""
    import glob

    from jax.profiler import ProfileData
    srv = CNNPipelineServer(ARCH, mb_size=2, n_stages=3, image_size=IMG)
    warm = srv.submit(_imgs(15, 4))
    srv.run()
    srv.results(warm)
    jax.profiler.start_trace(str(tmp_path))
    try:
        req = srv.submit(_imgs(16, 4))
        ticks = srv.run()["ticks"]
        srv.results(req)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, e.start_ns, e.end_ns)
             for p in ProfileData.from_file(path).planes
             if p.name == "/host:CPU" for line in p.lines
             for e in line.events if e.name.startswith("serve.")]

    def named(n):
        return [s for s in spans if s[0] == n]

    def within(inner, outer):
        return [i for i in named(inner) if any(
            o[1] <= i[1] and i[2] <= o[2] for o in named(outer))]

    assert len(named("serve.submit")) == len(named("serve.results")) == 1
    assert len(named("serve.run")) == 1
    assert len(within("serve.tick", "serve.run")) == ticks
    for inner in ("serve.dispatch", "serve.stage_next", "serve.collect"):
        assert within(inner, "serve.tick"), inner
    assert len(within("serve.dispatch", "serve.tick")) == ticks
