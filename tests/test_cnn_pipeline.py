"""Heterogeneous CNN layer pipeline: pipelined-vs-sequential exact
equivalence for all three paper CNNs on both executor paths, the
stage-assignment / microbatch contract fixes, and per-stage WEIGHT
PLACEMENT (each stage's params live only on its own devices — HPIPE's
per-layer weight memories).

The GSPMD path needs no mesh, so it runs in-process on the default
single device. The shard_map path needs one device per stage and runs
in a subprocess with a forced host device count (like
test_pipeline.py), executing tests/_cnn_pipeline_sub.py; the placed
checks force EIGHT devices (the CI multi-device job runs this file
under the same flag).
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import pipeline as pp, planner
from repro.launch.mesh import make_mesh
from repro.models import cnn

CNN_ARCHS = ["resnet50", "mobilenet_v1", "mobilenet_v2"]
KEY = jax.random.PRNGKey(0)


def _cfg(arch, sparse):
    cfg = get_config(arch)
    return dataclasses.replace(
        cfg, sparsity=dataclasses.replace(
            cfg.sparsity, enabled=sparse,
            block_m=min(cfg.sparsity.block_m, 32),
            block_n=min(cfg.sparsity.block_n, 32)))


def _sequential(cfg, params, x_mb):
    """The sequential interpreter over the same microbatches the
    pipeline runs. Per microbatch, not on the whole batch: XLA:CPU's
    dense conv may sum in an order that depends on the batch size (the
    oneDNN bf16 convs of AMX hosts do), which no pipeline controls."""
    fwd = jax.jit(lambda p, x: cnn.cnn_forward(cfg, p, x))
    return jnp.concatenate([fwd(params, x) for x in x_mb], 0)


# -- stage assignment from cost-model cycles ---------------------------------

@pytest.mark.parametrize("arch", CNN_ARCHS)
def test_plan_cnn_pipeline_cost_balanced(arch):
    from repro.core.fusion import fused_graph_for
    cfg = _cfg(arch, sparse=(arch == "resnet50"))
    params = cnn.init_cnn(cfg, KEY)
    plan = planner.plan(cfg, params, planner.PlanRequest(n_stages=4))
    assert plan["n_stages"] == 4
    costs = plan["node_cycles"]
    # the planner prices the FUSED graph: one cost per super-node, so a
    # stage cut can never land inside a fusion
    assert len(costs) == len(fused_graph_for(arch).nodes)
    assert len(costs) < len(cnn.specs_for(arch))
    assert (costs > 0).all()
    # cost-balanced, not count-balanced: max stage cycle-sum within 2x
    # of the mean even though per-stage layer counts vary widely
    assert plan["imbalance"] < 2.0
    counts = np.bincount(plan["stage_of"])
    assert counts.min() >= 1
    # cuts follow cycles, not layer count: stages own unequal node counts
    assert counts.max() > counts.min()


def test_assign_stages_clamps_when_overprovisioned():
    """Satellite: n_stages > n_layers used to return fewer stage ids
    than requested, leaving silent empty stages downstream."""
    costs = np.array([3.0, 1.0, 2.0])
    stage_of = planner.assign_stages(costs, 8)
    assert stage_of == [0, 1, 2]              # clamped: one layer each
    assert max(stage_of) + 1 == len(costs)
    with pytest.raises(ValueError):
        planner.assign_stages(costs, 0)
    with pytest.raises(ValueError):
        planner.assign_stages(np.array([]), 2)


def test_stack_stages_rejects_empty_stage():
    blocks = {"w": jnp.arange(6.0).reshape(3, 2)}
    with pytest.raises(ValueError, match="own no layers"):
        pp.stack_stages(blocks, [0, 0, 1], 4)   # stages 2,3 empty
    stacked, mask = pp.stack_stages(blocks, [0, 0, 1], 2)
    assert stacked["w"].shape == (2, 2, 2)


def test_microbatch_contract():
    x = jnp.arange(12.0).reshape(6, 2)
    with pytest.raises(ValueError, match="not divisible"):
        pp.microbatch(x, 4)
    with pytest.raises(ValueError, match=">= 1"):
        pp.microbatch(x, 0)
    padded = pp.microbatch(x, 4, pad=True)
    assert padded.shape == (4, 2, 2)
    np.testing.assert_array_equal(np.asarray(padded.reshape(8, 2)[:6]),
                                  np.asarray(x))
    assert float(jnp.abs(padded.reshape(8, 2)[6:]).sum()) == 0.0
    ok = pp.microbatch(x, 3)
    assert ok.shape == (3, 2, 2)


def test_microbatch_replication_contract():
    """Satellite fix: a batch that divides the microbatch count but not
    n_replicas * n_microbatches used to fail later with an error naming
    only the microbatch divisor; the contract now names BOTH knobs (or
    pads), and the replicated form carries a leading replica dim."""
    x = jnp.arange(12.0).reshape(6, 2)
    with pytest.raises(ValueError) as e:
        pp.microbatch(x, 2, n_replicas=2)       # 6 % 2 == 0, 6 % 4 != 0
    assert "n_replicas 2" in str(e.value)
    assert "n_microbatches 2" in str(e.value)
    with pytest.raises(ValueError, match=">= 1"):
        pp.microbatch(x, 3, n_replicas=0)
    padded = pp.microbatch(x, 2, n_replicas=2, pad=True)
    assert padded.shape == (2, 2, 2, 2)         # (R, M, mb, ...)
    flat = np.asarray(padded.reshape(8, 2))
    np.testing.assert_array_equal(flat[:6], np.asarray(x))
    assert float(np.abs(flat[6:]).sum()) == 0.0
    ok = pp.microbatch(x, 3, n_replicas=1)      # R=1: legacy shape
    assert ok.shape == (3, 2, 2)
    ok2 = pp.microbatch(jnp.arange(16.0).reshape(8, 2), 2, n_replicas=2)
    assert ok2.shape == (2, 2, 2, 2)
    # replica r owns the contiguous batch slice r*B/R:(r+1)*B/R
    np.testing.assert_array_equal(
        np.asarray(ok2[1].reshape(4, 2)),
        np.arange(16.0).reshape(8, 2)[4:])


# -- pipelined == sequential: GSPMD path (in-process, single device) --------

@pytest.mark.parametrize("arch", CNN_ARCHS)
@pytest.mark.parametrize("sparse", [True, False], ids=["sparse", "dense"])
def test_gspmd_pipeline_matches_sequential(arch, sparse):
    cfg = _cfg(arch, sparse)
    params = cnn.init_cnn(cfg, KEY)
    plan = planner.plan(cfg, params, planner.PlanRequest(n_stages=3))
    s = plan["n_stages"]
    imgs = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    x_mb = pp.microbatch(imgs, 2)
    stage_fns, pack_in, unpack_out, width = cnn.stage_programs(
        cfg, params, plan["stage_of"], x_mb.shape[1:])
    x_wire = jax.vmap(pack_in)(x_mb)
    out_w = jax.jit(lambda xw: pp.pipeline_apply_gspmd_hetero(
        stage_fns, xw, n_stages=s))(x_wire)
    logits = jnp.concatenate([unpack_out(out_w[i]) for i in range(2)], 0)
    ref = _sequential(cfg, params, x_mb)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))


# -- pipelined == sequential: shard_map path (subprocess, 4 devices) --------

def _run_sub(arch, mode=None, devices=4):
    sub = os.path.join(os.path.dirname(__file__), "_cnn_pipeline_sub.py")
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    cmd = [sys.executable, sub, arch] + ([mode] if mode else [])
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=900)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr


@pytest.mark.parametrize("arch", CNN_ARCHS)
def test_shardmap_pipeline_matches_sequential(arch):
    _run_sub(arch)


# -- per-stage weight placement (subprocess, 8 devices) ---------------------
#
# Each stage's packed param row must physically live on only its own
# device, per-device live-weight bytes must equal that stage's part
# params (not the full model), sparse ResNet-50 under the 1/4 budget
# must hold <= 1/4 of the replicated bytes per device, and placed
# pipelined logits must match the sequential interpreter BITWISE (the
# byte-packing round-trip is lossless). See _cnn_pipeline_sub.py.

@pytest.mark.parametrize("arch", CNN_ARCHS)
def test_placed_pipeline_8dev(arch):
    _run_sub(arch, mode="placed", devices=8)


# -- stage x data 2-D replication (subprocess, 8 devices = 4 x 2) -----------
#
# Replicated pipelined logits (R=2, placed, shard_map executor) must be
# BITWISE identical to the single-replica placed path at the same
# microbatch size, and every device in stage k's column must hold
# exactly stage k's packed param row (weights replicate only across
# the data axis). See _cnn_pipeline_sub.check_stage_data.

@pytest.mark.parametrize("arch", CNN_ARCHS)
def test_stage_data_pipeline_8dev(arch):
    _run_sub(arch, mode="stagedata", devices=8)


@pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="needs >=4 host devices — runs in the CI multi-device job "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")
def test_placed_pipeline_inprocess_multidev():
    """Placed gspmd pipeline on a real stage mesh IN-PROCESS — coverage
    unique to the multi-device CI leg (the subprocess tests above force
    their own device count, so they run identically in every leg).
    Also exercises launch.shardings.placed_stage_setup end-to-end."""
    from repro.launch.shardings import placed_stage_setup
    cfg = _cfg("mobilenet_v1", sparse=False)
    params = cnn.init_cnn(cfg, KEY)
    plan = planner.plan(cfg, params, planner.PlanRequest(n_stages=4))
    s = plan["n_stages"]
    imgs = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 32, 3))
    x_mb = pp.microbatch(imgs, 2)
    stage_fns, pack_in, unpack_out, _, pparams, mesh, sps = \
        placed_stage_setup(cfg, params, plan, x_mb.shape[1:])
    buf = jax.device_put(pparams.pack(), sps["buffer"])
    assert sps["placed_bytes_per_device"] == max(pparams.stage_bytes)
    x_wire = jax.vmap(pack_in)(x_mb)
    mesh_ctx = jax.set_mesh(mesh)
    with mesh_ctx:
        out_w = jax.jit(lambda xw, pb: pp.pipeline_apply_gspmd_hetero(
            stage_fns, xw, n_stages=s, stage_axis="stage", mesh=mesh,
            stage_params=pb))(x_wire, buf)
    logits = jnp.concatenate([unpack_out(out_w[i]) for i in range(2)], 0)
    ref = _sequential(cfg, params, x_mb)
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))


# -- placement plumbing that needs no mesh ----------------------------------

def test_param_format_roundtrip_bitexact():
    """ParamFormat packs ANY param pytree (mixed dtypes, SparseWeight
    children) into uint8 and unpacks it bit-identically."""
    from repro.models.layers import SparseWeight
    key = jax.random.PRNGKey(0)
    tree = {
        "conv": {"w": jax.random.normal(key, (9, 16)).astype(jnp.bfloat16),
                 "b": jnp.arange(16, dtype=jnp.float32)},
        "fc": {"w": SparseWeight(
            vals=jax.random.normal(key, (2, 3, 4, 4)).astype(jnp.bfloat16),
            idx=jnp.array([[0, 2, 5], [1, 3, 4]], jnp.int32), d_in=24),
            "b": jnp.zeros((8,), jnp.bfloat16)},
        # itemsize-1 leaves must BITCAST (an astype would value-convert
        # float8 and wrap int8)
        "q": {"w8": jnp.array([0.5, -0.25, 1.0], jnp.float8_e4m3fn),
              "i8": jnp.array([-128, -1, 127], jnp.int8)},
    }
    fmt = pp.ParamFormat.for_tree(tree)
    nb = fmt.nbytes
    assert nb == 9 * 16 * 2 + 16 * 4 + 2 * 3 * 4 * 4 * 2 + 2 * 3 * 4 \
        + 8 * 2 + 3 + 3
    buf = fmt.pack(tree, nb + 13)            # padded width
    assert buf.shape == (nb + 13,) and buf.dtype == jnp.uint8
    out = fmt.unpack(buf)
    la, lb = jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(tree)
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert isinstance(out["fc"]["w"], SparseWeight)
    assert out["fc"]["w"].d_in == 24
    with pytest.raises(ValueError, match="width"):
        fmt.pack(tree, nb - 1)


def test_placed_params_ragged_accounting():
    """Satellite: PlacedParams tracks per-stage (ragged) widths next to
    the even (S, P) buffer, so unbalanced nets can stop paying the
    padding on paths that carry rows individually — and the reclaimed
    bytes are visible."""
    trees = [
        {"a": {"w": jnp.ones((4, 8), jnp.bfloat16),
               "b": jnp.zeros((8,), jnp.float32)}},       # 64+32 = 96 B
        {"c": {"w": jnp.ones((32, 32), jnp.bfloat16)}},   # 2048 B
    ]
    fmts = [pp.ParamFormat.for_tree(t) for t in trees]
    width = max(f.nbytes for f in fmts)
    pparams = pp.PlacedParams(formats=tuple(fmts), trees=tuple(trees),
                              width=width)
    assert pparams.stage_widths == (96, 2048)
    assert pparams.padded_buffer_bytes == 2 * 2048
    assert pparams.padding_bytes == 2 * 2048 - (96 + 2048)
    buf = np.asarray(pparams.pack())
    rows = [np.asarray(r) for r in pparams.pack_ragged()]
    assert [r.shape[0] for r in rows] == [96, 2048]
    for s, row in enumerate(rows):
        # ragged row s == the padded row's live prefix
        np.testing.assert_array_equal(row, buf[s, :row.shape[0]])
        assert not buf[s, row.shape[0]:].any()
        # unpack round-trips bit-exactly from the ragged row too
        out = fmts[s].unpack(jnp.asarray(row))
        for a, b in zip(jax.tree_util.tree_leaves(out),
                        jax.tree_util.tree_leaves(trees[s])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_ragged_stage_params_executor_contract():
    """Ragged rows run the single-host packed path; placement on a
    stage mesh still demands the even buffer (unequal widths cannot
    shard), and row-count mismatches fail loudly."""
    fns = [lambda pb, w: w + 1.0]
    xw = jnp.zeros((2, 1, 4))
    rows = (jnp.zeros((8,), jnp.uint8),)
    # mesh-less ragged: allowed (packed, not placed)
    out = pp.pipeline_apply_gspmd_hetero(fns, xw, n_stages=1,
                                         stage_params=rows)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(xw + 1.0))
    mesh = make_mesh((1,), ("stage",))
    with pytest.raises(ValueError, match="unequal widths"):
        pp.pipeline_apply_gspmd_hetero(fns, xw, n_stages=1, mesh=mesh,
                                       stage_axis="stage",
                                       stage_params=rows)
    with pytest.raises(ValueError, match="ragged param rows"):
        pp.pipeline_apply_gspmd_hetero(fns, xw, n_stages=1,
                                       stage_params=(rows[0], rows[0]))
    with pytest.raises(ValueError, match="ragged|unequal widths"):
        pp.pipeline_apply_hetero(fns, xw, mesh=mesh, stage_axis="stage",
                                 n_stages=1, stage_params=rows)


# -- the (stages, replicas) co-planner ---------------------------------------

def test_pipeline_throughput_rel_tradeoff():
    """The ISSUE's co-planner rule: replicating a shallow pipeline Rx
    beats a deeper cut exactly when the deep cut's imbalance exceeds
    the replication overhead (bottleneck + fill-bubble ratios)."""
    m = 8
    # balanced 4-stage halves vs badly imbalanced 8-stage cut of the
    # same total work: 2 x 4-stage wins
    thr_4x2 = planner.pipeline_throughput_rel([25, 25, 25, 25], 2, m)
    thr_8x1 = planner.pipeline_throughput_rel([40, 10, 10, 10, 10, 10,
                                               5, 15], 1, m)
    assert thr_4x2 > thr_8x1
    # at EQUAL balance the deep cut still loses the fill bubble (its
    # bottleneck halves, but so does the replica count's multiplier):
    # under this model deep cuts only win back through the per-stage
    # weight budget (placement), which the 2-D planner passes through
    thr_8x1_bal = planner.pipeline_throughput_rel([12.5] * 8, 1, m)
    assert thr_4x2 > thr_8x1_bal
    assert thr_8x1_bal > thr_8x1          # balance still helps depth 8
    # more microbatches shrink the deep cut's fill penalty
    assert planner.pipeline_throughput_rel([12.5] * 8, 1, 64) > \
        planner.pipeline_throughput_rel([12.5] * 8, 1, 4)


@pytest.mark.parametrize("arch", ["resnet50", "mobilenet_v1"])
def test_plan_cnn_pipeline_2d(arch):
    """The n_devices co-plan enumerates the divisor splits of the device
    count and returns the throughput argmax (with the per-stage plan
    for the winning depth)."""
    cfg = _cfg(arch, sparse=(arch == "resnet50"))
    params = cnn.init_cnn(cfg, KEY)
    pl = planner.plan(cfg, params,
                      planner.PlanRequest(n_devices=8, n_microbatches=8))
    assert pl["n_stages"] * pl["n_replicas"] == 8
    assert pl["n_devices_used"] == 8
    splits = {(c["n_stages"], c["n_replicas"]) for c in pl["candidates"]}
    assert splits == {(1, 8), (2, 4), (4, 2), (8, 1)}
    best = max(pl["candidates"], key=lambda c: c["throughput_rel"])
    assert pl["n_stages"] == best["n_stages"]
    assert pl["n_replicas"] == best["n_replicas"]
    assert pl["throughput_rel"] == best["throughput_rel"]
    assert pl["plan"]["n_stages"] == pl["n_stages"]
    # every candidate's score matches the formula re-applied to its plan
    for c in pl["candidates"]:
        assert c["throughput_rel"] == pytest.approx(
            c["n_replicas"] * (8 / (8 + c["n_stages"] - 1))
            / c["bottleneck_cycles"])


def test_plan_cnn_pipeline_2d_clamped_depth_reports_idle_devices():
    """A divisor depth beyond the graph's node count clamps (one node
    per stage); the candidate keeps the clamped depth and
    n_devices_used records the idled remainder instead of silently
    breaking the S*R == devices invariant."""
    from repro.core.fusion import fused_graph_for
    cfg = _cfg("mobilenet_v1", sparse=False)
    params = cnn.init_cnn(cfg, KEY)
    n_nodes = len(fused_graph_for("mobilenet_v1").nodes)
    pl = planner.plan(cfg, params,
                      planner.PlanRequest(n_devices=2 * n_nodes + 2))
    for c in pl["candidates"]:
        assert c["n_stages"] <= n_nodes
        assert c["n_devices_used"] == c["n_stages"] * c["n_replicas"]
        assert c["n_devices_used"] <= 2 * n_nodes + 2
    assert pl["n_devices_used"] == pl["n_stages"] * pl["n_replicas"]


def test_plan_cnn_pipeline_2d_budget_skips_infeasible():
    """Budget-infeasible depths are skipped, not fatal; an impossible
    budget raises naming the tried splits."""
    from repro.core.costmodel import pytree_param_bytes
    cfg = _cfg("resnet50", sparse=True)
    params = cnn.init_cnn(cfg, KEY)
    total = pytree_param_bytes(params)
    pl = planner.plan(cfg, params, planner.PlanRequest(
        n_devices=8, max_stage_param_bytes=total // 4))
    # S=1 (whole model on one stage) cannot fit 1/4 of the model
    assert all(c["n_stages"] > 1 for c in pl["candidates"])
    assert all(c["placed_bytes_per_device"] <= total // 4
               for c in pl["candidates"])
    with pytest.raises(ValueError, match="no .stages, replicas. split"):
        planner.plan(cfg, params, planner.PlanRequest(
            n_devices=2, max_stage_param_bytes=1))


def test_gspmd_placement_requires_mesh():
    """Satellite fix: requesting per-stage placement with no mesh (or a
    mesh without the stage axis) used to silently replicate the buffer;
    now it raises."""
    fns = [lambda pb, w: w]
    xw = jnp.zeros((2, 1, 4))
    pbuf = jnp.zeros((1, 8), jnp.uint8)
    with pytest.raises(ValueError, match="requires a mesh"):
        pp.pipeline_apply_gspmd_hetero(fns, xw, n_stages=1,
                                       stage_params=pbuf)
    mesh = make_mesh((1,), ("data",))    # no 'stage' axis
    with pytest.raises(ValueError, match="requires a mesh"):
        pp.pipeline_apply_gspmd_hetero(fns, xw, n_stages=1, mesh=mesh,
                                       stage_axis="stage",
                                       stage_params=pbuf)
    # replicated operation stays mesh-optional
    out = pp.pipeline_apply_gspmd_hetero([lambda w: w + 1.0], xw,
                                         n_stages=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(xw + 1.0))


def test_assign_stages_weight_budget_rebalances():
    """Memory-aware planning: the cut DP must reject weight-overweight
    groups even when they are cycle-optimal."""
    costs = np.array([1.0, 1.0, 8.0])
    weights = np.array([6.0, 6.0, 1.0])
    # unbudgeted: cycle-optimal cut groups the two cheap layers
    assert planner.assign_stages(costs, 2) == [0, 0, 1]
    # budgeted: 6+6 > 10 busts the budget -> rebalance around it
    got = planner.assign_stages(costs, 2, weights=weights,
                                weight_budget=10.0)
    assert got == [0, 1, 1]
    # a single layer over budget can never fit a contiguous partition
    with pytest.raises(ValueError, match="alone exceed"):
        planner.assign_stages(costs, 3, weights=np.array([1.0, 20.0, 1.0]),
                              weight_budget=10.0)
    # feasible per-layer but no 2-stage contiguous split fits
    with pytest.raises(ValueError, match="fits the per-stage weight"):
        planner.assign_stages(np.ones(3), 2, weights=np.array([6., 6., 6.]),
                              weight_budget=7.0)


@pytest.mark.parametrize("arch", CNN_ARCHS)
def test_plan_cnn_pipeline_memory_aware(arch):
    """The planner prices weight residency and respects a
    per-stage byte budget; the plan reports the accounting."""
    from repro.core.costmodel import pytree_param_bytes
    cfg = _cfg(arch, sparse=(arch == "resnet50"))
    params = cnn.init_cnn(cfg, KEY)
    total = pytree_param_bytes(params)
    plan = planner.plan(cfg, params, planner.PlanRequest(n_stages=8))
    assert int(sum(plan["stage_param_bytes"])) == total
    # tightest feasible-ish budget: a single IR node is the atomic
    # placement unit (the dense MobileNet heads are ~1/3 of the model)
    budget = max(total // 3, int(plan["node_param_bytes"].max()))
    plan_b = planner.plan(cfg, params, planner.PlanRequest(
        n_stages=8, max_stage_param_bytes=budget))
    assert plan_b["placed_bytes_per_device"] <= budget
    assert plan_b["param_budget_bytes"] == budget
    assert int(sum(plan_b["stage_param_bytes"])) == total
