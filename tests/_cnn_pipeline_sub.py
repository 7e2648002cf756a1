"""Subprocess body for the multi-device CNN-pipeline tests.

Run as:  python _cnn_pipeline_sub.py <arch> [placed|stagedata]
with XLA_FLAGS=--xla_force_host_platform_device_count=N set by the
caller (N=4 for the replicated checks, N=8 for the placed and
stage x data checks).

Default mode checks BOTH sparse and dense params: pipelined logits
through ``pipeline_apply_hetero`` (4-stage mesh) must exactly match
the sequential graph interpreter.

``placed`` mode checks per-stage WEIGHT PLACEMENT on an 8-stage mesh:

- live-weight accounting: each stage's ``ParamFormat`` bytes equal the
  sum of that stage's fused-node part params — a device holds its
  stage's slice, not the model;
- physical placement: device k's shard of the packed (S, P) buffer is
  exactly stage k's packed params;
- sparse ResNet-50 under the 1/4 memory budget: max per-device
  parameter bytes <= 1/4 of the replicated executor's (the ISSUE 4
  acceptance bar);
- placed pipelined logits == sequential interpreter BITWISE on the
  shard_map path (and the gspmd path for resnet50).

``stagedata`` mode checks the 2-D stage x data pipeline on an
8-device host = 4 stages x 2 replicas:

- replicated pipelined logits (R=2, placed, shard_map executor) are
  BITWISE identical to the single-replica placed path at the same
  microbatch size;
- the placed buffer lands per stage COLUMN: all 2 data replicas of
  stage k hold exactly stage k's packed row (params replicated only
  across the data axis — per-device bytes unchanged from 1-replica
  placement).

Prints SUBPROCESS_OK on success.
"""
import dataclasses
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import pipeline as pp, planner
from repro.core.costmodel import pytree_param_bytes
from repro.core.fusion import fused_graph_for
from repro.launch.mesh import make_mesh
from repro.launch.shardings import stage_param_shardings
from repro.models import cnn


def _cfg(arch: str, sparse: bool):
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


def check(arch: str, sparse: bool, *, n_stages=4, img=32, batch=4, m=2):
    cfg = _cfg(arch, sparse)
    key = jax.random.PRNGKey(0)
    params = cnn.init_cnn(cfg, key)
    plan = planner.plan(cfg, params,
                        planner.PlanRequest(n_stages=n_stages))
    s = plan["n_stages"]
    assert s == n_stages, (s, n_stages)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (batch, img, img, 3))
    x_mb = pp.microbatch(imgs, m)
    stage_fns, pack_in, unpack_out, _ = cnn.stage_programs(
        cfg, params, plan["stage_of"], x_mb.shape[1:])
    x_wire = jax.vmap(pack_in)(x_mb)
    mesh = make_mesh((s,), ("stage",))
    mesh_ctx = jax.set_mesh(mesh)
    with mesh_ctx:
        out_w = jax.jit(lambda xw: pp.pipeline_apply_hetero(
            stage_fns, xw, mesh=mesh, stage_axis="stage",
            n_stages=s))(x_wire)
    logits = jnp.concatenate([unpack_out(out_w[i]) for i in range(m)], 0)
    ref = _sequential(cfg, params, x_mb)
    assert logits.shape == ref.shape, (logits.shape, ref.shape)
    diff = float(jnp.abs(logits - ref).max())
    exact = bool(jnp.all(logits == ref))
    tag = "sparse" if sparse else "dense"
    print(f"{arch} {tag}: exact={exact} maxdiff={diff}", flush=True)
    assert exact, f"{arch} {tag}: pipelined != sequential (maxdiff {diff})"


def check_placed(arch: str, sparse: bool, *, n_stages=8, img=32, batch=4,
                 m=2, budget_frac=None, both_paths=False):
    cfg = _cfg(arch, sparse)
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    total = pytree_param_bytes(params)
    budget = int(budget_frac * total) if budget_frac else None
    plan = planner.plan(cfg, params, planner.PlanRequest(
        n_stages=n_stages, max_stage_param_bytes=budget))
    s = plan["n_stages"]
    assert s == n_stages, (s, n_stages)
    g = fused_graph_for(cfg.name)
    imgs = jax.random.normal(jax.random.PRNGKey(1), (batch, img, img, 3))
    x_mb = pp.microbatch(imgs, m)
    stage_fns, pack_in, unpack_out, _, pparams = cnn.stage_programs(
        cfg, params, plan["stage_of"], x_mb.shape[1:], placed=True)

    # -- live-weight accounting: a stage holds ITS part params, period --
    trees = cnn.stage_param_trees(g, plan["stage_of"], params)
    for fmt, tree in zip(pparams.formats, trees):
        assert fmt.nbytes == pytree_param_bytes(tree), \
            (fmt.nbytes, pytree_param_bytes(tree))
    assert pparams.replicated_bytes == total, \
        (pparams.replicated_bytes, total)
    assert tuple(pparams.stage_bytes) == tuple(
        int(b) for b in plan["stage_param_bytes"])
    assert pparams.width < total, "placement must beat replication"
    if budget is not None:
        # the ISSUE 4 acceptance bar: max per-device parameter bytes
        # under placement <= 1/4 of the replicated executor's
        assert pparams.width <= budget, (pparams.width, budget)

    # -- physical placement: device k's shard IS stage k's packed row --
    mesh = make_mesh((s,), ("stage",))
    sps = stage_param_shardings(g, plan, mesh, params=params)
    assert sps["placed_bytes_per_device"] == max(pparams.stage_bytes)
    assert sps["replicated_bytes_per_device"] == total
    buf = jax.device_put(pparams.pack(), sps["buffer"])
    shards = sorted(buf.addressable_shards,
                    key=lambda sh: sh.index[0].start or 0)
    assert len(shards) == s, len(shards)
    host_rows = np.asarray(pparams.pack())
    for k, sh in enumerate(shards):
        row = np.asarray(sh.data)
        assert row.shape == (1, pparams.width), row.shape
        np.testing.assert_array_equal(row[0], host_rows[k])

    # -- placed pipelined == sequential interpreter, BITWISE --
    x_wire = jax.vmap(pack_in)(x_mb)
    ref = _sequential(cfg, params, x_mb)
    mesh_ctx = jax.set_mesh(mesh)
    tag = "sparse" if sparse else "dense"
    with mesh_ctx:
        out_w = jax.jit(lambda xw, pb: pp.pipeline_apply_hetero(
            stage_fns, xw, mesh=mesh, stage_axis="stage", n_stages=s,
            stage_params=pb))(x_wire, buf)
        logits = jnp.concatenate(
            [unpack_out(out_w[i]) for i in range(m)], 0)
        exact = bool(jnp.all(logits == ref))
        print(f"{arch} {tag} placed shard_map: exact={exact} "
              f"bytes/dev {pparams.width}/{total} "
              f"({pparams.width / total:.3f})", flush=True)
        assert exact, f"{arch} {tag}: placed shard_map != sequential"
        if both_paths:
            out_g = jax.jit(lambda xw, pb: pp.pipeline_apply_gspmd_hetero(
                stage_fns, xw, n_stages=s, stage_axis="stage", mesh=mesh,
                stage_params=pb))(x_wire, buf)
            logits_g = jnp.concatenate(
                [unpack_out(out_g[i]) for i in range(m)], 0)
            exact_g = bool(jnp.all(logits_g == ref))
            print(f"{arch} {tag} placed gspmd: exact={exact_g}",
                  flush=True)
            assert exact_g, f"{arch} {tag}: placed gspmd != sequential"


def check_stage_data(arch: str, sparse: bool, *, n_stages=4, n_replicas=2,
                     img=32, batch=8, mb=2):
    """2-D stage x data pipeline (shard_map executor) vs the
    single-replica placed path, bitwise, at the same microbatch size
    (the acceptance bar for PR 5's replication)."""
    from repro.launch.shardings import placed_stage_setup
    cfg = _cfg(arch, sparse)
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    plan = planner.plan(cfg, params,
                        planner.PlanRequest(n_stages=n_stages))
    s = plan["n_stages"]
    assert s == n_stages, (s, n_stages)
    r = n_replicas
    imgs = jax.random.normal(jax.random.PRNGKey(1), (batch, img, img, 3))
    tag = "sparse" if sparse else "dense"

    # -- single-replica placed reference: M = batch/mb microbatches --
    x1 = pp.microbatch(imgs, batch // mb)
    fns1, pin1, pout1, _, pp1, mesh1, sps1 = placed_stage_setup(
        cfg, params, plan, x1.shape[1:])
    buf1 = jax.device_put(pp1.pack(), sps1["buffer"])
    xw1 = jax.vmap(pin1)(x1)
    ctx1 = jax.set_mesh(mesh1)
    with ctx1:
        o1 = jax.jit(lambda xw, pb: pp.pipeline_apply_hetero(
            fns1, xw, mesh=mesh1, stage_axis="stage", n_stages=s,
            stage_params=pb))(xw1, buf1)
    ref = np.concatenate([np.asarray(pout1(o1[i]))
                          for i in range(batch // mb)], 0)

    # -- R=2 placed: same mb, M/R microbatches per replica --
    x2 = pp.microbatch(imgs, batch // mb // r, n_replicas=r)
    assert x2.shape[2:] == x1.shape[1:], (x2.shape, x1.shape)
    fns2, pin2, pout2, _, pp2, mesh2, sps2 = placed_stage_setup(
        cfg, params, plan, x2.shape[2:], n_replicas=r)
    assert tuple(mesh2.shape.values()) == (r, s), dict(mesh2.shape)

    # params replicate ONLY across data: every device in stage k's
    # column holds exactly stage k's packed row, so per-device bytes
    # match the 1-replica placed mode
    buf2 = jax.device_put(pp2.pack(), sps2["buffer"])
    host_rows = np.asarray(pp2.pack())
    shards = list(buf2.addressable_shards)
    assert len(shards) == r * s, len(shards)
    for sh in shards:
        k = sh.index[0].start or 0
        row = np.asarray(sh.data)
        assert row.shape == (1, pp2.width), row.shape
        np.testing.assert_array_equal(row[0], host_rows[k])

    xw2 = jax.vmap(jax.vmap(pin2))(x2)
    ctx2 = jax.set_mesh(mesh2)
    with ctx2:
        o2 = jax.jit(lambda xw, pb: pp.pipeline_apply_hetero(
            fns2, xw, mesh=mesh2, stage_axis="stage", n_stages=s,
            stage_params=pb, n_replicas=r))(xw2, buf2)
    got = np.concatenate([np.asarray(pout2(o2[rr][i])) for rr in range(r)
                          for i in range(batch // mb // r)], 0)
    exact = bool((got == ref).all())
    print(f"{arch} {tag} stage x data {s}x{r}: exact={exact}", flush=True)
    assert exact, f"{arch} {tag}: R={r} replicated != single-replica placed"


if __name__ == "__main__":
    arch = sys.argv[1]
    mode = sys.argv[2] if len(sys.argv) > 2 else "replicated"
    if mode == "stagedata":
        # the paper's sparse net plus the dense MobileNets, each on a
        # 4-stage x 2-replica grid of the 8 host devices
        check_stage_data(arch, sparse=(arch == "resnet50"))
    elif mode == "placed":
        if arch == "resnet50":
            # the paper's sparse net, under the 1/4 memory budget, on
            # both executor paths — the acceptance configuration
            check_placed(arch, sparse=True, budget_frac=0.25,
                         both_paths=True)
        else:
            # the MobileNets are evaluated dense (paper Table IV)
            check_placed(arch, sparse=False)
    else:
        for sparse in (True, False):
            check(arch, sparse)
    print("SUBPROCESS_OK")
