"""Profile-guided tuning (core/tuning.py): cache persistence, the
measured cost model's coverage/fallback/determinism contracts, kernel
knob autotuning feasibility, and knob-value numerics (every knob value
must be bitwise-identical — knobs change schedule, never math)."""
import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import SparsityConfig
from repro.core import planner, sparsity as S, tuning
from repro.kernels import depthwise_conv as dwk
from repro.kernels import ops as kops
from repro.models import cnn

KEY = jax.random.PRNGKey(0)
ARCH = "resnet50"


def _cfg():
    cfg = get_config(ARCH)
    return dataclasses.replace(
        cfg, sparsity=dataclasses.replace(
            cfg.sparsity, block_m=32, block_n=32))


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    params = cnn.init_cnn(cfg, KEY)
    return cfg, params


# -- cache persistence -------------------------------------------------------

def test_cache_round_trip(tmp_path):
    c = tuning.TuningCache()
    c.put_time("node/x", 12.5)
    c.put_knob("kern/y", "block_c", 16)
    c.meta.update({"device": "cpu:xla", "image_shape": [1, 64, 64, 3]})
    p = tmp_path / "cache.json"
    c.save(p)
    c2 = tuning.TuningCache.load(p)
    assert c2.time_us("node/x") == 12.5
    assert c2.knob("kern/y", "block_c", 0) == 16
    assert c2.meta["image_shape"] == [1, 64, 64, 3]
    assert len(c2) == len(c) == 2
    # the file is stable JSON (sorted keys) -> byte-identical re-save
    p2 = tmp_path / "cache2.json"
    c2.save(p2)
    assert p.read_text() == p2.read_text()


def test_cache_load_missing_file_is_empty(tmp_path):
    c = tuning.TuningCache.load(tmp_path / "nope.json")
    assert len(c) == 0 and c.time_us("anything") is None


# -- measured cost model contracts -------------------------------------------

def test_cold_cache_is_bit_for_bit_analytic(setup):
    """Empty cache: measured == analytic costs exactly, and the plan is
    the identical object graph (the cold-cache contract)."""
    cfg, params = setup
    analytic = planner.cnn_node_costs(cfg, params)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        measured, report = tuning.measured_node_costs(
            cfg, params, cache=tuning.TuningCache())
    assert any("cold-cache" in str(x.message) for x in w)
    np.testing.assert_array_equal(measured, analytic)
    assert report["coverage"] == 0.0 and report["units"] == "cycles"
    pa = planner.plan(cfg, params, planner.PlanRequest(n_stages=4))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pm = planner.plan(cfg, params, planner.PlanRequest(
            n_stages=4, model="measured",
            tuning_cache=tuning.TuningCache()))
    assert pm["stage_of"] == pa["stage_of"]
    np.testing.assert_array_equal(pm["node_cycles"], pa["node_cycles"])


def test_seeded_analytic_cache_plans_identically(setup):
    """seed_from_analytic writes analytic values under node keys; the
    measured path then reproduces the analytic plan (determinism
    contract: the measured pipeline adds no nondeterminism of its own).
    """
    cfg, params = setup
    cache = tuning.seed_from_analytic(cfg, params, (1, 64, 64, 3))
    assert len(cache) > 0 and cache.meta["seeded"] == "analytic"
    pa = planner.plan(cfg, params, planner.PlanRequest(n_stages=4))
    pm = planner.plan(cfg, params, planner.PlanRequest(
        n_stages=4, model="measured", tuning_cache=cache))
    assert pm["stage_of"] == pa["stage_of"]
    assert pm["measured_coverage"]["coverage"] == 1.0
    assert pm["measured_coverage"]["fallback"] == []
    # and twice through the measured path -> identical plan
    pm2 = planner.plan(cfg, params, planner.PlanRequest(
        n_stages=4, model="measured", tuning_cache=cache))
    assert pm2["stage_of"] == pm["stage_of"]
    np.testing.assert_array_equal(pm2["node_cycles"], pm["node_cycles"])


def test_key_mismatch_falls_back_with_loud_report(setup):
    """Entries keyed for another device/shape never match: every node
    falls back to calibrated-analytic and the report says so."""
    cfg, params = setup
    cache = tuning.seed_from_analytic(cfg, params, (1, 64, 64, 3))
    wrong = tuning.TuningCache(
        {k.replace("/cpu", "/tpu"): v for k, v in cache.entries.items()},
        meta=dict(cache.meta))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        costs, report = tuning.measured_node_costs(cfg, params, cache=wrong)
    assert report["coverage"] == 0.0
    assert len(report["fallback"]) == report["n_nodes"]
    assert any("covers 0/" in str(x.message) for x in w)
    # no measurements to fit -> every scale is 1.0 -> analytic values
    np.testing.assert_array_equal(costs, planner.cnn_node_costs(cfg, params))


def test_partial_cache_mixes_measured_and_calibrated(setup):
    """Half the entries dropped: covered nodes priced from the cache,
    the rest at analytic x fitted scale (not raw analytic)."""
    cfg, params = setup
    cache = tuning.seed_from_analytic(cfg, params, (1, 64, 64, 3))
    # double every seeded time so the fit is scale=2 exactly, then drop
    # half the keys
    keys = sorted(cache.entries)
    for k in keys:
        cache.entries[k]["time_us"] *= 2.0
    partial = tuning.TuningCache(
        {k: v for k, v in cache.entries.items() if k in set(keys[::2])},
        meta=dict(cache.meta))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        costs, report = tuning.measured_node_costs(
            cfg, params, cache=partial)
    assert 0.0 < report["coverage"] < 1.0
    assert report["fallback"] and any(
        "analytic fallback" in str(x.message) for x in w)
    # every fitted scale is the doubling we injected
    for kind, s in report["scales"].items():
        assert s == pytest.approx(2.0, rel=1e-6), (kind, s)
    analytic = planner.cnn_node_costs(cfg, params)
    assert np.all(costs >= analytic)          # everything got the 2x


def test_calibration_kind_splits_sparse_from_dense(setup):
    cfg, params = setup
    from repro.core.fusion import fused_graph_for
    g = fused_graph_for(ARCH)
    kinds = {tuning.calibration_kind(n, params) for n in g.nodes}
    assert "conv/sparse" in kinds and "conv/dense" in kinds


# -- kernel knob autotuning --------------------------------------------------

def test_block_c_candidates_respect_vmem_budget():
    """Every candidate the autotuner may pick fits the 8MB VMEM budget
    (the kernel's own feasibility rule), for a sweep of geometries
    including the 112x112 MobileNet layer that used to overflow."""
    for w, c, k, stride in [(112, 128, 3, 1), (112, 128, 3, 2),
                            (56, 256, 3, 1), (7, 1024, 3, 1),
                            (224, 64, 5, 2)]:
        cands = dwk.block_c_candidates(w, c, k, stride, 2)
        assert cands, (w, c)
        for tc in cands:
            assert c % tc == 0
            wo = -(-w // stride)
            wp = (wo - 1) * stride + k
            assert dwk._vmem_bytes(wp, wo, tc, k, 2) \
                <= dwk.VMEM_BUDGET_BYTES, (w, c, tc)
        # pick_block_c is the head of the same lattice
        assert dwk.pick_block_c(w, c, k, stride, 2) == cands[0]


def test_autotune_results_land_in_cache_and_candidate_set():
    cache = tuning.TuningCache()
    x = jax.random.normal(KEY, (1, 16, 16, 8), jnp.float32)
    w = jax.random.normal(KEY, (3, 3, 8), jnp.float32)
    tc = tuning.autotune_depthwise_block_c(x, w, stride=1, cache=cache,
                                           iters=1)
    assert tc in dwk.block_c_candidates(16, 8, 3, 1, 4)
    dwb = jnp.zeros((8,))
    pww = jax.random.normal(KEY, (8, 16), jnp.float32)
    pwb = jnp.zeros((16,))
    hb = tuning.autotune_dw_pw_row_chunk(x, w, dwb, pww, pwb, stride=1,
                                         cache=cache, iters=1)
    assert hb in (4, 8, 16)                   # clipped to ho=16
    assert len(cache) == 2
    for key in cache.entries:
        assert key.startswith("kern/") and cache.time_us(key) > 0


def test_autotune_microbatch_knee_and_cap():
    # flat stages: throughput_rel(M) = M/(M+S-1); with S=4 only M=32 is
    # within 5% of the peak -> knee = 32
    sc = np.ones(4)
    assert tuning.autotune_microbatch(sc, n_replicas=1) == 32
    # a latency cap excludes the tail; the knee re-evaluates among the
    # remaining candidates (peak is now M=8)
    assert tuning.autotune_microbatch(sc, n_replicas=1,
                                      latency_cap_ticks=11) == 8
    # cap below every candidate -> smallest candidate, never an error
    assert tuning.autotune_microbatch(sc, n_replicas=1,
                                      latency_cap_ticks=2) == 2
    # recorded under a kernel key when a cache is given
    cache = tuning.TuningCache()
    tuning.autotune_microbatch(sc, n_replicas=2, cache=cache, arch=ARCH)
    (key,) = cache.entries
    assert key.startswith("kern/microbatch/") and ARCH in key


# -- knob numerics: schedule changes, never math -----------------------------

def test_sparse_conv_block_k_bitwise():
    cin, cout, bm, bn, k, h = 8, 16, 4, 8, 3, 8
    ks = jax.random.split(KEY, 3)
    w = jax.random.normal(ks[0], (k * k * cin, cout), jnp.float32) / 8
    x = jax.random.normal(ks[1], (1, h, h, cin), jnp.float32)
    b = jax.random.normal(ks[2], (cout,), jnp.float32)
    sw = S.to_block_balanced(w, SparsityConfig(
        enabled=True, sparsity=0.5, block_m=bm, block_n=bn))
    n_k = sw.vals.shape[1]
    from repro.kernels.sparse_conv import sparse_conv_pallas
    base = sparse_conv_pallas(x, sw.vals, sw.idx, b, k=k, block_k=1,
                              interpret=True)
    for bk in [t for t in (2, 3, 4) if n_k % t == 0]:
        got = sparse_conv_pallas(x, sw.vals, sw.idx, b, k=k, block_k=bk,
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_dw_pw_row_chunk_bitwise():
    from repro.kernels.dw_pw_fused import dw_pw_xla
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (1, 17, 17, 8), jnp.float32)
    dww = jax.random.normal(ks[1], (3, 3, 8), jnp.float32)
    pww = jax.random.normal(ks[2], (8, 16), jnp.float32)
    dwb, pwb = jnp.zeros((8,)), jnp.zeros((16,))
    base = dw_pw_xla(x, dww, dwb, pww, pwb, row_chunk=0)
    for hb in (4, 8, 32):
        got = dw_pw_xla(x, dww, dwb, pww, pwb, row_chunk=hb)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_depthwise_block_c_bitwise():
    from repro.kernels.depthwise_conv import depthwise_conv_pallas
    x = jax.random.normal(KEY, (1, 16, 16, 16), jnp.float32)
    w = jax.random.normal(KEY, (3, 3, 16), jnp.float32)
    base = depthwise_conv_pallas(x, w, block_c=16, interpret=True)
    for tc in (4, 8):
        got = depthwise_conv_pallas(x, w, block_c=tc, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


# -- knob dispatch through ops.py --------------------------------------------

def test_knob_lookup_respects_active_cache():
    cache = tuning.TuningCache()
    x = jax.random.normal(KEY, (1, 17, 17, 8), jnp.float32)
    key = tuning.kernel_key("dwpw", x.shape, x.dtype, k=3, s=1, co=16)
    cache.put_knob(key, "row_chunk", 4)
    assert kops._knob("dwpw", x.shape, x.dtype, "row_chunk", 0,
                      k=3, s=1, co=16) == 0      # no active cache
    with tuning.set_tuning_cache(cache):
        assert kops._knob("dwpw", x.shape, x.dtype, "row_chunk", 0,
                          k=3, s=1, co=16) == 4
    assert tuning.current_tuning_cache() is None


def test_stale_knob_entries_are_ignored(setup):
    """A cache whose block_c no longer divides C (or block_k no longer
    divides K) must not crash the dispatcher — the guard falls back."""
    cfg, params = setup
    x = jax.random.normal(KEY, (1, 16, 16, 12), jnp.float32)
    w = jax.random.normal(KEY, (3, 3, 12), jnp.float32)
    cache = tuning.TuningCache()
    key = tuning.kernel_key("dw", x.shape, x.dtype, k=3, s=1)
    cache.put_knob(key, "block_c", 5)             # 12 % 5 != 0 -> stale
    with tuning.set_tuning_cache(cache), kops.set_impl("pallas"):
        y = kops.depthwise_conv(x, w, stride=1)
    assert y.shape == (1, 16, 16, 12)


def test_checked_in_cache_beats_analytic_imbalance(setup):
    """The committed tuning cache must actually move the plan: measured
    imbalance strictly below the analytic plan's (the PR headline)."""
    cfg, params = setup
    cache = tuning.TuningCache.load(tuning.DEFAULT_CACHE)
    if not len(cache):
        pytest.skip("no checked-in cache")
    pa = planner.plan(cfg, params, planner.PlanRequest(n_stages=4))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pm = planner.plan(cfg, params, planner.PlanRequest(
            n_stages=4, model="measured", tuning_cache=cache))
    assert pm["imbalance"] < pa["imbalance"] < 1.41
