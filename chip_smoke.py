#!/usr/bin/env python3
"""Smoke run of the serving path on TPU: the quickest proof that the
system still starts on the chip. Not a benchmark.

    python chip_smoke.py              # phases A-C on one chip
    python chip_smoke.py --chips 4    # the layer pipeline across 4 chips

One chip, through ``serve(ServeConfig(...))`` and the planner, at
224x224 with random weights from ``--seed``:

- A: sparse ResNet-50 (85 % block-sparse), ``mode="latency"``, a few
  single-image requests;
- B: the same model, ``mode="throughput"``, batch 16, 4 stages;
- C: dense MobileNet-V2, ``mode="latency"`` (the fused dw->pw kernel,
  which ResNet-50 never calls).

Each phase compares the served logits with the XLA twin of the same
model on the same chip (``kernels.config(impl="xla")`` under
``jax.default_matmul_precision("highest")``), and checks that the
served program holds Mosaic-compiled Pallas kernels.

``--chips 4`` runs only the scale-out path: sparse ResNet-50 as 4
placed stages on 4 chips and as 2 stages x 2 replicas (both through the
shard_map executor: GSPMD cannot partition a Mosaic kernel), each
compared with the composed single-chip forward on device 0, and checks
that each stage's packed param row lives only on its own stage's
devices.

Every phase prints one JSON line; the last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or without the rest of the repository next to it, the
script exits nonzero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

#: Relative L2 bound between served logits and the XLA twin at
#: "highest" precision. Both paths feed the MXU bf16 activations and
#: weights (exact in f32) and accumulate in f32, so per layer they
#: differ only in summation order; but every layer rounds its output to
#: bf16 (a relative step of 2**-8), so an f32 difference near a rounding
#: boundary flips an activation by one bf16 step, and those flips
#: compound over ~50 layers of random-weight networks. The same
#: comparison at 32-64 px on the CPU (Pallas in interpret mode) stays
#: below 1e-4; 3e-2 leaves room for the chip's own op order while
#: still failing on any wrong block, tap or stride, which moves logits
#: by O(1).
REL_L2_VS_XLA = 3e-2

#: Bound between the pipelined executors and the composed single-chip
#: forward: the same kernels on the same per-image programs, so only
#: XLA's fusion of the glue around them can differ.
REL_L2_VS_COMPOSED = 1e-2

#: the networks' published input size, and requests per latency phase
IMAGE_SIZE = 224
REQUESTS = 4

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "writes"}
_cache_counts = {"hits": 0, "writes": 0}


def _on_event(event, **kw):
    if event in _CACHE_EVENTS:
        _cache_counts[_CACHE_EVENTS[event]] += 1


class SmokeFailure(RuntimeError):
    pass


def _check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def rel_l2(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def xla_reference(arch: str, images, *, seed: int):
    """The XLA twin of the served model on the same weights, at
    "highest" matmul precision, on the default device."""
    import jax
    import numpy as np
    from repro import kernels
    from repro.configs import get_config
    from repro.models import cnn
    cfg = get_config(arch)
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(seed))
    with kernels.config(impl="xla"), jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x: cnn.cnn_forward(cfg, p, x))
        return np.asarray(fwd(params, images))


def check_logits(name: str, got, want, bound: float) -> float:
    import numpy as np
    _check(np.all(np.isfinite(got)), f"{name}: non-finite served logits")
    _check(np.all(np.isfinite(want)), f"{name}: non-finite reference")
    _check(float(np.std(want)) > 0, f"{name}: degenerate reference logits")
    err = rel_l2(got, want)
    _check(err <= bound, f"{name}: relative L2 {err} > bound {bound}")
    return err


def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def run_phase(name: str, scfg, *, kernels_needed, seed: int) -> dict:
    """Serve ``scfg``, compare with the XLA twin, report one line."""
    from repro.launch.serve import serve
    before = dict(_cache_counts)
    t0 = time.time()
    res = serve(scfg)
    wall_s = time.time() - t0
    err = check_logits(name, res["logits"],
                       xla_reference(scfg.arch, res["request_images"],
                                     seed=seed), REL_L2_VS_XLA)
    kern = res["mosaic_kernels"]
    for k in kernels_needed:
        _check(kern.get(k, 0) > 0,
               f"{name}: no Mosaic {k!r} kernel in the served program "
               f"(found {kern})")
    out = {"phase": name, "arch": scfg.arch, "mode": scfg.mode,
           "image_size": scfg.image_size, "device": device_info(),
           "compile_s": res["compile_s"], "serve_wall_s": wall_s,
           "rel_l2_vs_xla_highest": err, "bound": REL_L2_VS_XLA,
           "mosaic_kernels": kern,
           "compile_cache": {k: _cache_counts[k] - before[k]
                             for k in _cache_counts}}
    if scfg.mode == "latency":
        out.update(run_s=float(sum(res["request_latencies_s"])),
                   requests=len(res["request_latencies_s"]),
                   p50_ms=res["latency_p50_s"] * 1e3,
                   p99_ms=res["latency_p99_s"] * 1e3)
    else:
        out.update(run_s=res["run_s"], batch=scfg.batch,
                   n_stages=res["n_stages"],
                   images_per_s=res["images_per_s"])
    print(json.dumps(out), flush=True)
    return res


def one_chip(seed: int, image_size: int = IMAGE_SIZE) -> None:
    from repro.launch.serve import ServeConfig
    common = dict(image_size=image_size, seed=seed, verbose=False)
    run_phase("A", ServeConfig(arch="resnet50", mode="latency",
                               n_requests=REQUESTS, **common),
              kernels_needed=("sparse_conv", "sparse_matmul"), seed=seed)
    run_phase("B", ServeConfig(arch="resnet50", mode="throughput",
                               batch=16, n_microbatches=4, n_stages=4,
                               iters=2, **common),
              kernels_needed=("sparse_conv", "sparse_matmul"), seed=seed)
    run_phase("C", ServeConfig(arch="mobilenet_v2", mode="latency",
                               n_requests=REQUESTS, **common),
              kernels_needed=("dw_pw",), seed=seed)


def check_rows(name: str, rows, n_stages: int, n_replicas: int):
    """Each stage's packed row lives on exactly ``n_replicas`` devices,
    and no device holds two stages' rows."""
    _check(rows is not None and len(rows) == n_stages,
           f"{name}: expected {n_stages} placed rows, got {rows}")
    seen = [d for row in rows for d in row]
    _check(all(len(row) == n_replicas for row in rows)
           and len(set(seen)) == len(seen),
           f"{name}: stage rows not on their own devices: {rows}")


def four_chips(seed: int, image_size: int = IMAGE_SIZE) -> None:
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.launch.serve import ServeConfig, serve
    from repro.models import cnn
    _check(len(jax.devices()) >= 4,
           f"--chips 4 needs 4 devices, have {len(jax.devices())}")
    common = dict(arch="resnet50", mode="throughput", batch=8,
                  placed=True, image_size=image_size, iters=2,
                  seed=seed, verbose=False)
    runs = {"placed_4x1_shard_map": ServeConfig(n_stages=4, n_microbatches=4,
                                            **common),
            "placed_2x2_shard_map": ServeConfig(n_stages=2, replicas=2,
                                                n_microbatches=2, **common)}
    results = {}
    for name, scfg in runs.items():
        before = dict(_cache_counts)
        res = serve(scfg)
        check_rows(name, res["stage_row_devices"], scfg.n_stages,
                   scfg.replicas)
        _check(res["mosaic_kernels"].get("sparse_conv", 0) > 0,
               f"{name}: no Mosaic sparse_conv kernel")
        results[name] = (res, {k: _cache_counts[k] - before[k]
                               for k in _cache_counts})
    images = results["placed_4x1_shard_map"][0]["request_images"]
    for res, _ in results.values():
        _check(np.array_equal(res["request_images"], images),
               "the two executors served different images")

    # the comparison: the composed single-chip forward on device 0
    cfg = get_config("resnet50")
    dev0 = jax.devices()[0]
    params = jax.device_put(cnn.init_cnn(cfg, jax.random.PRNGKey(seed)), dev0)
    t0 = time.time()
    fwd = jax.jit(lambda p, x: cnn.cnn_forward(cfg, p, x))
    composed = np.asarray(fwd(params, jax.device_put(images, dev0)))
    composed_s = time.time() - t0
    for name, (res, cache) in results.items():
        err = check_logits(name, res["logits"], composed,
                           REL_L2_VS_COMPOSED)
        print(json.dumps({
            "phase": name, "arch": "resnet50", "device": device_info(),
            "image_size": image_size, "batch": 8,
            "n_stages": res["n_stages"], "n_replicas": res["n_replicas"],
            "stage_row_devices": res["stage_row_devices"],
            "compile_s": res["compile_s"], "run_s": res["run_s"],
            "images_per_s": res["images_per_s"],
            "rel_l2_vs_composed_device0": err,
            "bound": REL_L2_VS_COMPOSED,
            "mosaic_kernels": res["mosaic_kernels"],
            "compile_cache": cache}), flush=True)
    print(json.dumps({"phase": "composed_device0", "wall_s": composed_s,
                      "device": str(dev0)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and images")
    args = ap.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {platform!r})",
              file=sys.stderr)
        return 2
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    jax.monitoring.register_event_listener(_on_event)
    print(json.dumps({"compile_cache_dir": cache_dir,
                      "LIBTPU_INIT_ARGS": os.environ.get("LIBTPU_INIT_ARGS"),
                      "jax": jax.__version__, "device": device_info()}),
          flush=True)
    t0 = time.time()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    print(json.dumps({"total_s": time.time() - t0}), flush=True)
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
