import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
# ^ MUST precede any jax import (device count locks at first init).

# Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell
# and extract the roofline terms from the compiled artifact.
#
# Usage:
#     PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b \
#         --shape train_4k [--multi-pod] [--pipeline] [--out results.json]
#     PYTHONPATH=src python -m repro.launch.dryrun --all [--out file]
import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, all_configs, applicable, get_config
from repro.launch import mesh as meshlib
from repro.launch import steps as steplib

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _op_output_bytes(line: str) -> int:
    """Sum output tensor bytes of an HLO op line ('x = bf16[2,3]{...} ...'
    or tuple 'x = (bf16[2,3], u32[])')."""
    lhs = line.split(" = ", 1)
    if len(lhs) != 2:
        return 0
    rhs = lhs[1]
    # take everything up to the op name's '(' args — shapes appear first
    head = rhs.split(") ", 1)[0] if rhs.startswith("(") else rhs.split(" ", 1)[0]
    total = 0
    for m in _SHAPE_RE.finditer(head):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective output bytes summed over the HLO module."""
    out = {c: 0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        for c in COLLECTIVES:
            # match op name after '=', e.g. "= bf16[..] all-gather(...)"
            if f" {c}(" in s or f" {c}-start(" in s:
                out[c] += _op_output_bytes(s)
                counts[c] += 1
                break
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def roofline(cost: dict, coll: dict, n_chips: int, *, model_flops: float,
             seconds_scale: float = 1.0) -> dict:
    """Three roofline terms (seconds). NOTE: XLA's cost_analysis and the
    post-SPMD HLO are PER-PARTITION (verified against a known matmul),
    so each term divides by per-chip rates, not by n_chips; the global
    figures below are per-device x n_chips."""
    hlo_flops = float(cost.get("flops", 0.0))           # per device
    hlo_bytes = float(cost.get("bytes accessed", 0.0))  # per device
    t_compute = hlo_flops / meshlib.PEAK_FLOPS_BF16
    t_memory = hlo_bytes / meshlib.HBM_BW
    t_coll = coll["total_bytes"] / meshlib.ICI_BW
    dom = max(("compute", t_compute), ("memory", t_memory),
              ("collective", t_coll), key=lambda kv: kv[1])
    denom = max(t_compute, t_memory, t_coll, 1e-30)
    return {
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dom[0],
        "bound_s": dom[1],
        "hlo_flops_per_dev": hlo_flops,
        "hlo_bytes_per_dev": hlo_bytes,
        "collective_bytes_per_dev": coll["total_bytes"],
        "hlo_flops_global": hlo_flops * n_chips,
        "model_flops": model_flops,
        "useful_flops_frac": (model_flops / (hlo_flops * n_chips)
                              if hlo_flops else 0.0),
        "roofline_frac": t_compute / denom,
        # end-to-end quality score: model FLOPs vs what the fleet could
        # do in the bound time = MFU upper bound implied by the terms
        "mfu_bound": model_flops / (n_chips * meshlib.PEAK_FLOPS_BF16
                                    * max(dom[1], 1e-30)),
    }


def model_flops_for(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6ND train / 2ND per generated token."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        d = shape.seq_len * shape.global_batch
        return 6.0 * n * d
    if shape.kind == "prefill":
        d = shape.seq_len * shape.global_batch
        return 2.0 * n * d
    return 2.0 * n * shape.global_batch          # decode: one token


def _lower_cell(cfg, shape, mesh, *, pipeline: bool, unroll: bool = False,
                pure_dp=None):
    """Lower one cell's step program. Returns the Lowered object."""
    abs_params = steplib.abstract_params(cfg, mesh, pure_dp=pure_dp)
    specs = steplib.input_specs(cfg, shape, mesh, pod_is_dp=not pipeline,
                                pure_dp=pure_dp)
    if shape.kind == "train" and pipeline:
        step, restructure, plan = steplib.make_pipeline_train_step(
            cfg, mesh, shape)
        sp_shapes, mask = jax.eval_shape(restructure, abs_params)
        from repro.launch import shardings as sh
        from jax.sharding import NamedSharding, PartitionSpec as P

        def stage_spec(path, leaf):
            names = sh._path_names(path)
            if names and names[0] == "staged":
                base = tuple(sh.param_spec(path[1:], leaf, mesh))
                base += (None,) * (len(leaf.shape) - len(base))
                return P("pod", *base[1:])     # dim0 = stage axis
            return sh.param_spec(path, leaf, mesh)

        flat, tdef = jax.tree_util.tree_flatten_with_path(sp_shapes)
        sparams = jax.tree_util.tree_unflatten(tdef, [
            jax.ShapeDtypeStruct(l.shape, l.dtype,
                                 sharding=NamedSharding(
                                     mesh, stage_spec(p, l)))
            for p, l in flat])
        opt = steplib.abstract_opt_state(sparams, mesh)
        mask_arr = jax.ShapeDtypeStruct(
            mask.shape, mask.dtype,
            sharding=NamedSharding(mesh, P("pod", None)))
        return jax.jit(step).lower(sparams, mask_arr, opt, specs["batch"])
    if shape.kind == "train":
        step = steplib.make_train_step(cfg, unroll=unroll)
        opt = steplib.abstract_opt_state(abs_params, mesh)
        return jax.jit(step, donate_argnums=(0, 1)).lower(
            abs_params, opt, specs["batch"])
    if shape.kind == "prefill":
        step = steplib.make_prefill_step(cfg, unroll=unroll)
        toks = specs.pop("tokens")
        return jax.jit(step).lower(abs_params, toks, **specs)
    step = steplib.make_decode_step(cfg, unroll=unroll)
    return jax.jit(step, donate_argnums=(1,)).lower(
        abs_params, specs["cache"], specs["tokens"], specs["pos"])


def _probe_unit(cfg) -> int:
    """Smallest layer count that captures the repeating cost structure."""
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        return cfg.hybrid_attn_every
    return 1


def probe_costs(cfg, shape, mesh, *, pipeline: bool,
                pure_dp=None) -> dict:
    """XLA counts scan bodies once, so FLOPs/bytes/collectives of the
    full-depth compile are wrong for scanned stacks. Compile two shallow
    *unrolled* variants (L1, 2*L1 layers) at the SAME shape+mesh and
    extrapolate linearly to the real depth."""
    import dataclasses
    u = _probe_unit(cfg)
    out = {}
    for li, L in enumerate((u, 2 * u)):
        c = dataclasses.replace(cfg, n_layers=L,
                                encoder_layers=(
                                    L if cfg.encoder_layers else 0))
        lowered = _lower_cell(c, shape, mesh, pipeline=False, unroll=True,
                              pure_dp=pure_dp)
        comp = lowered.compile()
        cost = comp.cost_analysis() or {}
        coll = collective_bytes(comp.as_text())
        out[L] = {"flops": float(cost.get("flops", 0.0)),
                  "bytes": float(cost.get("bytes accessed", 0.0)),
                  "coll": float(coll["total_bytes"])}
    (l1, c1), (l2, c2) = sorted(out.items())
    full = {}
    for k in ("flops", "bytes", "coll"):
        slope = (c2[k] - c1[k]) / (l2 - l1)
        base = c1[k] - slope * l1
        full[k] = base + slope * cfg.n_layers
    full["per_layer"] = {k: (c2[k] - c1[k]) / (l2 - l1)
                         for k in ("flops", "bytes", "coll")}
    return full


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             pipeline: bool = False, verbose: bool = True,
             probe: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if not applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "inapplicable (see DESIGN.md)"}
    mesh = meshlib.make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    from repro.models import lm as lmlib
    from jax.sharding import PartitionSpec as P
    t0 = time.time()
    from repro.launch import shardings as shl
    pure_dp = shl.use_pure_dp(cfg)
    with jax.set_mesh(mesh):
        bspec = (P(("data", "model"), None, None) if pure_dp
                 else P("data", "model", None))
        lmlib.set_boundary_spec(None if shape.kind == "decode" else bspec,
                                mesh)
        from repro.models import layers as Llib
        Llib.set_accum_dtype(None)   # TPU-like bf16 dots (see layers.py)
        if not pure_dp:
            Llib.set_decode_attn_sharding(mesh)
        dp_deg = mesh.shape.get("data", 1)
        if multi_pod and not pipeline:
            dp_deg *= mesh.shape.get("pod", 1)
        Llib.set_moe_dp(dp_deg)      # DP-local MoE dispatch
        try:
            lowered = _lower_cell(cfg, shape, mesh, pipeline=pipeline,
                                  pure_dp=pure_dp)
            compiled = lowered.compile()
            t1 = time.time()
            if probe and not pipeline:
                pc = probe_costs(cfg, shape, mesh, pipeline=pipeline,
                                 pure_dp=pure_dp)
            else:
                pc = None
        finally:
            lmlib.set_boundary_spec(None)
            Llib.set_decode_attn_sharding(None)
            Llib.set_accum_dtype(jnp.float32)
            Llib.set_moe_dp(1)
    raw_cost = compiled.cost_analysis() or {}
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    if pc is not None:     # layer-loop-corrected collective bytes (HLO probe)
        coll = {"total_bytes": pc["coll"], "bytes": coll["bytes"],
                "counts": coll["counts"], "extrapolated": True}
    # compute/memory terms: analytic model (HLO undercounts loop bodies)
    from repro.core import costmodel as cm
    n_model = mesh.shape.get("model", 1)
    flops_global = cm.step_flops_global(cfg, shape)
    flops_per_dev = flops_global / n_chips
    bytes_per_dev = cm.step_bytes_per_device(
        cfg, shape, n_chips=n_chips, n_model_shards=n_model,
        pure_dp=pure_dp)
    cost = {"flops": flops_per_dev, "bytes accessed": bytes_per_dev}
    rf = roofline(cost, coll, n_chips, model_flops=model_flops_for(cfg, shape))
    rf["hlo_raw_flops_per_dev"] = float(raw_cost.get("flops", 0.0))
    mem_info = {}
    for attr in ("bytes_accessed", "argument_size_in_bytes",
                 "output_size_in_bytes", "temp_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            mem_info[attr] = int(v)
    per_dev_bytes = (mem_info.get("argument_size_in_bytes", 0) +
                     mem_info.get("output_size_in_bytes", 0) +
                     mem_info.get("temp_size_in_bytes", 0))
    hbm_est = cm_hbm = None
    try:
        from repro.core import costmodel as _cm
        from repro.launch import shardings as _sh
        cm_hbm = _cm.hbm_estimate_per_device(
            cfg, shape, n_chips=n_chips,
            n_model_shards=mesh.shape.get("model", 1),
            pure_dp=_sh.use_pure_dp(cfg))
    except Exception:
        pass
    res = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "pipeline": pipeline,
        "status": "ok",
        "compile_s": round(t1 - t0, 1),
        "n_chips": int(n_chips),
        "memory": mem_info,
        "per_device_bytes": int(per_dev_bytes),
        "hbm_est_per_device": None if cm_hbm is None else int(cm_hbm),
        # measured (CPU backend, overstated by hoisted f32 weight copies)
        "hbm_ok_measured": bool(per_dev_bytes < meshlib.CHIP_HBM),
        # TPU-layout analytic estimate (see costmodel.hbm_estimate_*)
        "hbm_ok": bool((cm_hbm if cm_hbm is not None else per_dev_bytes)
                       < meshlib.CHIP_HBM),
        "collectives": coll,
        "roofline": rf,
    }
    if verbose:
        print(json.dumps(res, indent=None, default=float))
    return res


def run_cnn_pipeline_cell(arch: str, *, n_stages: int = 4,
                          n_microbatches: int = 8, batch: int = 16,
                          image_size: int = 64, placed: bool = True,
                          param_budget_frac=None, n_replicas: int = 1,
                          verbose: bool = True, tuning_cache=None,
                          calibrate: bool = False) -> dict:
    """``pipeline_cnn`` mode: lower + compile the heterogeneous CNN
    layer pipeline (shard_map over a stage axis) and extract what the
    LM cells extract — compile stats and per-collective HLO bytes. The
    stage->stage wire hops lower to collective-permute, so
    ``collectives['bytes']['collective-permute']`` is the pipeline's
    ICI traffic; stage balance and the fill/drain bubble come from the
    planner/analytic model.

    Per-stage weight PLACEMENT is on by default: the cell compiles the
    placed pipeline (each stage's packed param row device_put onto its
    own stage device) and reports per-device parameter bytes both ways
    — ``param_bytes_placed_per_device`` (the buffer row each device
    holds) vs ``param_bytes_replicated_per_device`` (what the
    replicated executor would hold everywhere). ``param_budget_frac``
    bounds any stage to that fraction of the model's bytes and lets
    the memory-aware planner rebalance cuts.

    ``n_replicas`` > 1 compiles the stage x data 2-D pipeline (R full
    pipelines on a (data, stage) mesh, batch sharded over replicas,
    placed rows replicated only across data) — the collective-permute
    bytes then cover R in-replica wire streams."""
    from repro.core import pipeline as pp, planner
    from repro.core.costmodel import pytree_param_bytes
    from repro.launch.shardings import placed_stage_setup
    from repro.models import cnn
    cfg = get_config(arch)
    if cfg.family != "cnn":
        return {"arch": arch, "shape": "pipeline_cnn", "status": "skipped",
                "reason": "not a CNN arch"}
    if batch % (n_microbatches * n_replicas) != 0:
        raise ValueError(
            f"batch {batch} must be divisible by n_replicas "
            f"{n_replicas} * n_microbatches {n_microbatches} for the "
            "dry-run cell (serve pads instead)")
    t0 = time.time()
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    total_bytes = pytree_param_bytes(params)
    budget = (int(param_budget_frac * total_bytes)
              if param_budget_frac else None)
    cache, model = None, "analytic"
    if tuning_cache is not None or calibrate:
        # profile-guided stage cuts: plan from a measured tuning cache
        # (cold/missing cache = analytic plan bit-for-bit)
        from repro.core import tuning
        cache_path = tuning_cache if isinstance(tuning_cache, str) else None
        cache = (tuning_cache if isinstance(tuning_cache, tuning.TuningCache)
                 else tuning.TuningCache.load(cache_path)
                 if cache_path else tuning.TuningCache())
        if calibrate:
            cache = tuning.calibrate(
                cfg, params, (1, image_size, image_size, 3), cache=cache,
                path=cache_path, verbose=verbose)
        model = "measured"
        tuning.set_tuning_cache(cache)
    plan = planner.plan(cfg, params, planner.PlanRequest(
        n_stages=n_stages, max_stage_param_bytes=budget,
        model=model, tuning_cache=cache))
    s = plan["n_stages"]
    r = n_replicas
    imgs = jax.ShapeDtypeStruct((batch, image_size, image_size, 3),
                                jnp.float32)
    mb_full = jax.eval_shape(
        lambda x: pp.microbatch(x, n_microbatches, n_replicas=r),
        imgs).shape
    mb_shape = mb_full[2:] if r > 1 else mb_full[1:]

    xmb_spec = jax.ShapeDtypeStruct(mb_full, jnp.float32)
    if placed:
        stage_fns, pack_in, unpack_out, width, pparams, mesh, sps = \
            placed_stage_setup(cfg, params, plan, mb_shape, n_replicas=r)
        placed_bytes = pparams.width
        lower_args = (xmb_spec, jax.ShapeDtypeStruct(
            (s, pparams.width), jnp.uint8, sharding=sps["buffer"]))

        def pipeline(wires, pbuf):
            return pp.pipeline_apply_hetero(
                stage_fns, wires, mesh=mesh, stage_axis="stage",
                n_stages=s, stage_params=pbuf, n_replicas=r)
    else:
        stage_fns, pack_in, unpack_out, width = cnn.stage_programs(
            cfg, params, plan["stage_of"], mb_shape)
        from repro.launch.mesh import make_stage_mesh
        mesh = make_stage_mesh(s, r)
        placed_bytes = int(plan["placed_bytes_per_device"])
        lower_args = (xmb_spec,)

        def pipeline(wires):
            return pp.pipeline_apply_hetero(stage_fns, wires, mesh=mesh,
                                            stage_axis="stage", n_stages=s,
                                            n_replicas=r)
    def step(xmb, *pbuf):
        pack = jax.vmap(jax.vmap(pack_in)) if r > 1 else jax.vmap(pack_in)
        out = pipeline(pack(xmb), *pbuf)
        return pp.concat_hetero_outputs(out, unpack_out, n_microbatches,
                                        n_replicas=r)

    from repro.launch.mesh import mesh_context
    with mesh_context(mesh):
        compiled = jax.jit(step).lower(*lower_args).compile()
    t1 = time.time()
    coll = collective_bytes(compiled.as_text())
    cost = compiled.cost_analysis() or {}
    res = {
        "arch": arch, "shape": "pipeline_cnn", "status": "ok",
        "mesh": (f"{r}x{s}(data,stage)" if r > 1 else f"{s}x1(stage)"),
        "pipeline": True,
        "compile_s": round(t1 - t0, 1),
        "n_stages": int(s),
        "n_replicas": int(r),
        "n_microbatches": int(n_microbatches),
        "image_size": int(image_size),
        "wire_width": int(width),
        "stage_cost_cycles": [float(c) for c in plan["stage_cost"]],
        "imbalance": plan["imbalance"],
        "bubble_fraction": pp.bubble_fraction(n_microbatches, s),
        "hlo_flops_per_dev": float(cost.get("flops", 0.0)),
        "collectives": coll,
        # the placement story: what ONE device holds in weights
        "params_placed": bool(placed),
        "param_budget_bytes": budget,
        "stage_param_bytes": [int(b) for b in plan["stage_param_bytes"]],
        "param_bytes_replicated_per_device": int(total_bytes),
        "param_bytes_placed_per_device": int(placed_bytes),
        "param_placement_ratio": placed_bytes / max(total_bytes, 1),
    }
    if verbose:
        print(json.dumps(res, indent=None, default=float))
    return res


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--pipeline", action="store_true")
    ap.add_argument("--pipeline-cnn", action="store_true",
                    help="CNN layer-pipeline cell (family=cnn archs)")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--replicated-params", action="store_true",
                    help="pipeline-cnn: compile with replicated params "
                         "instead of per-stage placement")
    ap.add_argument("--param-budget-frac", type=float, default=None,
                    help="pipeline-cnn: bound any stage's weight bytes "
                         "to this fraction of the model (memory-aware "
                         "planner rebalances cuts)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="pipeline-cnn: replicate the whole pipeline "
                         "across a data mesh axis (stage x data 2-D)")
    ap.add_argument("--tuning-cache", type=str, default=None,
                    metavar="PATH",
                    help="pipeline-cnn: plan stages from this profiled "
                         "tuning cache (model='measured'; missing file "
                         "= cold cache = analytic plan)")
    ap.add_argument("--calibrate", action="store_true",
                    help="pipeline-cnn: profile every fused node on the "
                         "live device and write --tuning-cache first")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    results = []
    if args.all:
        for arch, cfg in sorted(all_configs().items()):
            if cfg.family == "cnn":
                continue
            for sname in SHAPES:
                for mp in (False, True):
                    try:
                        r = run_cell(arch, sname, multi_pod=mp)
                    except Exception as e:
                        r = {"arch": arch, "shape": sname,
                             "mesh": "2x16x16" if mp else "16x16",
                             "status": "error", "error": f"{e}"[:500]}
                        traceback.print_exc()
                        print(json.dumps(r))
                    results.append(r)
    elif args.pipeline_cnn or (args.arch and
                               get_config(args.arch).family == "cnn"):
        if not args.arch:
            ap.error("--pipeline-cnn requires --arch (resnet50, "
                     "mobilenet_v1 or mobilenet_v2)")
        results.append(run_cnn_pipeline_cell(
            args.arch, n_stages=args.stages,
            n_microbatches=args.microbatches, batch=args.batch,
            image_size=args.image_size,
            placed=not args.replicated_params,
            param_budget_frac=args.param_budget_frac,
            n_replicas=args.replicas,
            tuning_cache=args.tuning_cache, calibrate=args.calibrate))
    else:
        results.append(run_cell(args.arch, args.shape,
                                multi_pod=args.multi_pod,
                                pipeline=args.pipeline))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1, default=float)
    bad = [r for r in results if r.get("status") == "error"]
    sys.exit(1 if bad else 0)


if __name__ == "__main__":
    main()
