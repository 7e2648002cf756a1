#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the TPU this starts on.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``, whose
``reference`` names its network's layer table) and a traffic mix
(``bench/traffic/<mix>.json``, read by the one generator in
``bench/traffic.py``). The run builds the program's ``CNNPipelineServer``
for the configuration, with ``arch``, ``image_size``, ``seed`` and a
microbatch of the mix's request size and every other setting at the
program's default, makes a pool of images from the seed, warms up with
one request and one round of the mix, then drives the mix through
``submit``/``run``/``results`` for ``--seconds``. With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace 1``
it traces the first :data:`TRACE_SECONDS` of the window with the JAX
profiler and reports the per-layer metrics, each read by
``bench/metrics/<metric>.py``. Either way it then compares a sample of
the served logits with the plain reference (``bench/check.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``checks``: each number compared with its limit,
which also close standard error. Without a TPU, or with fewer chips
than the cell asks for, it exits nonzero and prints no such line.
"""
from __future__ import annotations

import time

T0 = time.monotonic()   # process start, as near as the interpreter gets

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import check, counts, peaks, spec, trace  # noqa: E402
from bench.traffic import Traffic, make_pool  # noqa: E402

#: seconds at the start of a traced window that the profiler records
TRACE_SECONDS = 2.0


class NoChip(RuntimeError):
    """No TPU, or fewer TPU chips than the cell asks for."""


def accelerator(chips: int) -> list:
    """The TPU devices the run may use; raises :class:`NoChip`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _program_config(arch: str, cfg: dict) -> None:
    """Refuse to run where the program's registered configuration is not
    the one the configuration file states."""
    from repro.configs import get_config
    sp = get_config(arch).sparsity
    stated = (cfg.get("sparsity") or 0.0, tuple(cfg.get("block") or ()))
    have = ((sp.sparsity if sp.enabled else 0.0),
            ((sp.block_m, sp.block_n) if sp.enabled else ()))
    if stated != have:
        raise spec.SpecError(f"{arch}: the program runs sparsity/blocks "
                             f"{have}, the configuration states {stated}")


def serve_window(gen: Traffic, seconds, *, traced_dir=None):
    """Warm-up excluded: run rounds until ``seconds`` have passed.
    Returns (requests, t_start, t_end, traced) where ``traced`` is
    ``(t0, t1, images)`` of the rounds the profiler recorded."""
    import jax
    reqs, traced = [], None
    t_start = time.monotonic()
    gen.start(t_start, seconds)
    until = t_start + seconds
    if traced_dir is not None:
        # host spans from TraceMe only: the Python tracer would slow the
        # host path it is meant to observe
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(traced_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            while time.monotonic() - t_start < min(TRACE_SECONDS, seconds):
                reqs += gen.round(until)
        traced = (t_start, time.monotonic(), sum(
            len(r.logits) for r in reqs if r.logits is not None))
        jax.profiler.stop_trace()
    while time.monotonic() < until or gen.owed(until):
        reqs += gen.round(until)
    return reqs, t_start, time.monotonic(), traced


def run(cell: dict, cfg: dict, mix: dict, bench: dict, *, seed: int,
        seconds: float, traced: bool, t0: float = T0,
        server_kwargs=None) -> tuple[dict, int]:
    """One run of one cell. Returns (result line, exit code)."""
    import jax
    import numpy as np
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.serve import CNNPipelineServer, _mosaic_kernels
    devices = accelerator(cell["chips"])
    use_compile_cache()
    # every program the run compiles goes to the cache, however quick:
    # the server's set-up compiles some hundreds of small programs, each
    # under JAX's default of one second, which a run would otherwise
    # compile afresh every time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _program_config(cfg["arch"], cfg)
    n_img = int(mix["images_per_request"])
    server = CNNPipelineServer(cfg["arch"], image_size=cfg["image_size"],
                               seed=seed, mb_size=n_img,
                               **(server_kwargs or {}))
    print(plan_line(server), flush=True)
    pool = make_pool(seed, int(mix["pool_images"]), cfg["image_size"])
    gen = Traffic(server, pool, mix, seed)
    gen.warm_up()
    setup_s = time.monotonic() - t0
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    reqs, t_start, t_end, trace_rounds = serve_window(gen, seconds,
                                                      traced_dir=tdir)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices)
    served = [r for r in reqs if r.logits is not None]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    out = {"correct": False, "attempted": len(reqs),
           "failed": len(reqs) - len(served), "metrics": {},
           "device": device}
    e2e = {"setup_s": setup_s}
    if served:
        lat_ms = [(r.done - r.submitted) * 1e3 for r in served]
        e2e["images_per_s"] = sum(len(r.logits) for r in served) / (
            t_end - t_start)
        e2e["latency_p50_ms"] = float(np.percentile(lat_ms, 50))
        e2e["latency_p95_ms"] = float(np.percentile(lat_ms, 95))
    run_info = {"counters": {"ticks": gen.ticks, "injected": gen.injected,
                             "replicas": server.n_replicas},
                "config": cfg, "mb_size": n_img, "trace": None}
    if traced:
        step = server._step.lower(server._state, server._zero_wire,
                                  *server._params_arg).compile()
        print("mosaic kernels in the tick: " +
              json.dumps(_mosaic_kernels(step)), flush=True)
        try:
            kernels = {c["kernel"] for c in counts.kernel_calls(cfg)}
            red = trace.reduce(*trace.read_xplane(trace.find_xplane(tdir)),
                               kernels=sorted(kernels))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        ta, tb, n_traced = trace_rounds
        run_info.update(trace=red, peaks=peaks.peaks(device["kind"]),
                        traced_images_per_s=n_traced / (tb - ta))
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out["breakdown"] = red["breakdown"]
    group = "per_layer" if traced else "end_to_end"
    for m in spec.metrics_for(bench, cell["name"], group):
        value = (e2e.get(m["name"]) if not traced
                 else bench["readers"][m["name"]](run_info))
        if value is not None:
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    del server, gen
    checks = check.checks(cfg, seed, reqs, pool, mix)
    out["correct"] = bool(served) and check.passed(checks)
    out["checks"] = checks
    return out, (0 if served else 1)


def plan_line(server) -> str:
    return "plan: " + json.dumps({
        "stages": server.n_stages, "replicas": server.n_replicas,
        "placed": server.placed, "mb_size": server.mb_size,
        "stage_of": list(map(int, server.plan["stage_of"]))})


def main(argv=None, *, root: str = ROOT) -> int:
    args = _parse(argv)
    try:
        bench = spec.load(root)
    except (spec.SpecError, KeyError, json.JSONDecodeError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    cell = bench["cells"].get(args.workload)
    if cell is None:
        print(f"bench: unknown workload {args.workload!r}; known: "
              f"{sorted(bench['cells'])}", file=sys.stderr)
        return 2
    cfg = bench["config_files"][cell["config"]]
    mix = bench["traffic"][cell["traffic"]]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        out, code = run(cell, cfg, mix, bench, seed=args.seed,
                        seconds=args.seconds, traced=bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
