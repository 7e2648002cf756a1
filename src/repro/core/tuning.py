"""Profile-guided planner calibration + kernel autotuning.

HPIPE §IV's lesson is that stage cuts are only as good as the cycle
estimates behind them (the partition-aware model bought 23% throughput
over the naive one at <1% estimate error). Our analytic cost model
(core/costmodel.py) predicts RELATIVE node costs well but knows nothing
about the live device — XLA fusion quality, dispatch overhead, cache
behavior. This module closes the loop:

1. **Profile** — :func:`measure_graph` times each *fused* IR node in
   isolation on the live device (jit + warmup + ``block_until_ready``,
   median-of-k) and persists the result in a JSON :class:`TuningCache`
   keyed on ``(op kind, shape, sparsity, dtype, device kind)``.
2. **Calibrate** — :func:`costmodel.fit_scale_factors` fits a per-op-
   kind scale (geometric mean of measured/analytic ratios) so shapes
   the cache has never seen still benefit from the device's measured
   rates.
3. **Retune** — :func:`autotune_graph` searches the small candidate
   lattices of the Pallas/XLA kernel knobs (depthwise ``block_c``,
   sparse-conv ``block_k``, dw_pw ``row_chunk``) plus the serving
   microbatch width M, recording winners in the same cache; the kernel
   dispatchers (``kernels/ops.py``) consult the active cache at trace
   time.

The planner consumes all of this through ``model="measured"``
(:func:`measured_node_costs`): cached nodes are priced at their
measured wall time (µs), uncached nodes at analytic-cycles x
calibrated scale, and an EMPTY cache degrades to the analytic costs
bit-for-bit — so planning from a cache file is deterministic (no wall
clock), and cold starts behave exactly like today.

Cache keys embed :func:`device_signature` (device kind + active kernel
impl): measurements taken on one device kind never leak into plans on
another — two hosts with different caches may legally cut different
stages, but the SAME cache file always reproduces the same plan.
"""
from __future__ import annotations

import json
import time
import warnings
from typing import Optional

import numpy as np

__all__ = [
    "TuningCache", "device_signature", "node_key", "kernel_key",
    "graph_node_keys", "measure_graph", "seed_from_analytic",
    "measured_node_costs", "autotune_depthwise_block_c",
    "autotune_dw_pw_row_chunk", "autotune_sparse_conv_block_k",
    "autotune_microbatch", "autotune_graph", "calibrate",
    "set_tuning_cache", "current_tuning_cache",
]

#: default on-disk location of the checked-in CPU cache (repo-relative)
DEFAULT_CACHE = "tuning/resnet50_cpu.json"


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------

class TuningCache:
    """JSON-persisted map from op keys to measured times and tuned
    kernel knobs.

    ``entries[key] = {"time_us": float, "knobs": {name: value}}`` —
    either field may be absent (a node key usually carries only a time,
    a kernel key only knobs). ``meta`` records how the measurements
    were taken (image_shape, device signature, iters) so a consumer can
    rebuild the exact same keys without re-tracing the profiler's
    choices."""

    def __init__(self, entries: Optional[dict] = None,
                 meta: Optional[dict] = None):
        self.entries: dict = dict(entries or {})
        self.meta: dict = dict(meta or {})

    # -- persistence -------------------------------------------------------

    @classmethod
    def load(cls, path) -> "TuningCache":
        """Load a cache file; a missing file is a valid COLD cache (the
        measured model then degrades to analytic costs bit-for-bit)."""
        try:
            with open(path) as f:
                doc = json.load(f)
        except FileNotFoundError:
            return cls()
        return cls(doc.get("entries", {}), doc.get("meta", {}))

    def save(self, path) -> None:
        doc = {"meta": self.meta,
               "entries": {k: self.entries[k]
                           for k in sorted(self.entries)}}
        with open(path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")

    # -- access ------------------------------------------------------------

    def __len__(self) -> int:
        return len(self.entries)

    def __contains__(self, key: str) -> bool:
        return key in self.entries

    def time_us(self, key: str) -> Optional[float]:
        e = self.entries.get(key)
        return None if e is None else e.get("time_us")

    def put_time(self, key: str, us: float) -> None:
        self.entries.setdefault(key, {})["time_us"] = float(us)

    def knob(self, key: str, name: str, default=None):
        e = self.entries.get(key)
        if e is None:
            return default
        return e.get("knobs", {}).get(name, default)

    def put_knob(self, key: str, name: str, value) -> None:
        self.entries.setdefault(key, {}).setdefault("knobs", {})[name] = value


# process-global active cache consulted by the kernel dispatchers
# (kernels/ops.py) at trace time. Set it BEFORE compiling; knobs are
# baked into the traced program, so changing the cache later never
# silently changes numerics of an already-compiled function.
_ACTIVE: Optional[TuningCache] = None


class _CacheGuard:
    def __init__(self, prev):
        self._prev = prev

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        global _ACTIVE
        _ACTIVE = self._prev
        return False


def set_tuning_cache(cache: Optional[TuningCache]) -> _CacheGuard:
    """Install ``cache`` as the process-global tuning cache (``None``
    clears it). Usable as a context manager to scope the override."""
    global _ACTIVE
    guard = _CacheGuard(_ACTIVE)
    _ACTIVE = cache
    return guard


def current_tuning_cache() -> Optional[TuningCache]:
    return _ACTIVE


# ---------------------------------------------------------------------------
# keys
# ---------------------------------------------------------------------------

def device_signature() -> str:
    """``<device kind>:<kernel impl>`` — the validity domain of a
    measurement. Times measured under the XLA reference path say
    nothing about the Pallas path and vice versa, so the impl is part
    of the key, same as the device kind."""
    import jax
    from repro.kernels import ops as kops
    d = jax.devices()[0]
    kind = str(getattr(d, "device_kind", None) or d.platform)
    return f"{kind.lower().replace(' ', '-')}:{kops.impl()}"


def _shp(shape) -> str:
    return "x".join(str(int(s)) for s in shape)


def _weight_sig(node, params) -> str:
    """Sparsity signature of the node's MXU weight: block geometry +
    kept-block count for a SparseWeight, ``dense`` otherwise, ``-`` for
    param-free companions (add/pool)."""
    from repro.core.fusion import conv_part
    from repro.models.layers import SparseWeight
    if node.kind not in ("conv", "dw_pw", "fc", "avgpool_fc", "dw"):
        return "-"
    try:
        w = params[conv_part(node).name]["w"]
    except (StopIteration, KeyError):
        return "-"
    if isinstance(w, SparseWeight):
        ob, K, bm, bn = w.vals.shape
        return f"b{bm}x{bn}K{K}"
    return "dense"


def calibration_kind(node, params) -> str:
    """Scale-fit class of a node: ``kind/sparse`` vs ``kind/dense``.

    Sparsity must split the class — the analytic model prices a sparse
    conv at its K surviving blocks while the XLA block-gather scan pays
    a far higher per-MAC constant than the dense conv lowering, so one
    scale per ``kind`` alone is off by two orders of magnitude between
    the two populations (see benchmarks/planner_accuracy.py)."""
    ws = _weight_sig(node, params)
    if ws == "-":
        return node.kind
    return node.kind + ("/sparse" if ws.startswith("b") else "/dense")


def node_key(node, in_shape, dtype, wsig: str,
             device: Optional[str] = None) -> str:
    """Cache key of one fused IR node: ``(op kind, shape, sparsity,
    dtype, device kind)`` — deliberately NOT the node name, so two
    nodes with identical work (ResNet's repeated block shapes) share
    one measurement."""
    kind = node.kind
    if node.residual_from and node.kind != "add":
        kind += ".res"                      # fused residual epilogue
    if node.pool_k:
        kind += f".pool{node.pool_k}s{node.pool_stride}"
    dev = device or device_signature()
    return (f"node/{kind}/in{_shp(in_shape)}/k{node.k}s{node.stride}"
            f"/co{node.cout}/{wsig}/{np.dtype(dtype).name}/{dev}")


def kernel_key(op: str, in_shape, dtype, *, device: Optional[str] = None,
               **fields) -> str:
    """Cache key of one kernel-knob site (``op`` in dw | dwpw | sconv |
    microbatch), same tail schema as node keys."""
    dev = device or device_signature()
    tail = "/".join(f"{k}{v}" for k, v in sorted(fields.items()))
    return (f"kern/{op}/in{_shp(in_shape)}/{tail}"
            f"/{np.dtype(dtype).name}/{dev}")


def graph_node_keys(cfg, params, image_shape, graph=None,
                    device: Optional[str] = None):
    """``[(node, key), ...]`` for every fused node at a concrete image
    shape (input shapes via eval_shape — no device work)."""
    from repro.core.fusion import fused_graph_for
    from repro.models import cnn
    g = graph if graph is not None else fused_graph_for(cfg.name)
    shapes = cnn.node_shapes(cfg, params, image_shape, graph=g)
    dev = device or device_signature()
    out = []
    for node, edge in zip(g.nodes, g.inputs):
        s_in = shapes[edge[0]]
        out.append((node, node_key(node, s_in.shape, s_in.dtype,
                                   _weight_sig(node, params), device=dev)))
    return out


# ---------------------------------------------------------------------------
# the micro-benchmark harness (profile)
# ---------------------------------------------------------------------------

def _time_call(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall time of ``fn(*args)`` in microseconds: ``warmup``
    untimed calls (compile + caches), then median-of-``iters`` with
    ``block_until_ready`` inside the timed region (async dispatch would
    otherwise return before the device finishes)."""
    import jax
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts))


def measure_graph(cfg, params, image_shape, *, graph=None,
                  cache: Optional[TuningCache] = None, iters: int = 5,
                  warmup: int = 2, verbose: bool = False) -> TuningCache:
    """Time every fused IR node in isolation on the live device and
    record ``time_us`` under its :func:`node_key`. Inputs are synthetic
    (ones at the node's true shapes/dtypes) — sparse-conv runtime is
    data-independent, only shapes and the weight structure matter.
    Repeated shapes (ResNet's stacked blocks) are measured once."""
    import jax
    import jax.numpy as jnp
    from repro.core.fusion import fused_graph_for
    from repro.models import cnn
    g = graph if graph is not None else fused_graph_for(cfg.name)
    shapes = cnn.node_shapes(cfg, params, image_shape, graph=g)
    cache = cache if cache is not None else TuningCache()
    cache.meta.update({
        "image_shape": [int(s) for s in image_shape],
        "device": device_signature(),
        "iters": int(iters),
    })
    for (node, key), edge in zip(
            graph_node_keys(cfg, params, image_shape, graph=g), g.inputs):
        if key in cache and cache.time_us(key) is not None:
            continue
        args = [jnp.ones(shapes[src].shape, shapes[src].dtype)
                for src in edge]
        fn = jax.jit(lambda *a, _n=node: cnn.run_node(_n, params, *a))
        us = _time_call(fn, *args, warmup=warmup, iters=iters)
        cache.put_time(key, us)
        if verbose:
            print(f"  {node.name:<16} {us:>12.1f} us   {key}")
    return cache


def seed_from_analytic(cfg, params, image_shape, *, graph=None,
                       cache: Optional[TuningCache] = None) -> TuningCache:
    """Fill the cache with the ANALYTIC costs as if they were measured
    (no device work, no wall clock). Two uses: the determinism contract
    test (a cache seeded this way must reproduce the analytic plan
    exactly) and CI smoke legs that need a populated cache without
    timing anything."""
    from repro.core import planner
    from repro.core.fusion import fused_graph_for
    g = graph if graph is not None else fused_graph_for(cfg.name)
    analytic = planner.cnn_node_costs(cfg, params, graph=g)
    cache = cache if cache is not None else TuningCache()
    cache.meta.update({
        "image_shape": [int(s) for s in image_shape],
        "device": device_signature(),
        "seeded": "analytic",
    })
    for (node, key), c in zip(
            graph_node_keys(cfg, params, image_shape, graph=g), analytic):
        cache.put_time(key, float(c))
    return cache


# ---------------------------------------------------------------------------
# the measured cost model (calibrate)
# ---------------------------------------------------------------------------

def measured_node_costs(cfg, params, *, graph=None,
                        cache: Optional[TuningCache] = None):
    """Per-node costs for ``planner.cnn_node_costs(model="measured")``.

    Cached nodes are priced at their measured wall time (µs); uncached
    nodes at ``analytic_cycles x scale[calibration_kind]`` (sparse and
    dense convs are separate classes) where the scales are the
    calibration fit over the nodes that WERE measured
    (:func:`costmodel.fit_scale_factors`). With an empty/absent cache
    there are no ratios to fit, every scale is 1.0, and the result is
    the analytic cost vector bit-for-bit.

    Returns ``(costs, report)``; the report is the loud part — it names
    every fallback node, and a partially-covered cache also warns."""
    from repro.core import planner
    from repro.core.costmodel import fit_scale_factors
    from repro.core.fusion import fused_graph_for
    g = graph if graph is not None else fused_graph_for(cfg.name)
    cache = cache if cache is not None else (_ACTIVE or TuningCache())
    analytic = planner.cnn_node_costs(cfg, params, graph=g)
    image_shape = tuple(cache.meta.get("image_shape") or (1, 224, 224, 3))

    keyed = graph_node_keys(cfg, params, image_shape, graph=g)
    measured = [cache.time_us(key) for _, key in keyed]
    kinds = [calibration_kind(node, params) for node, _ in keyed]
    scales = fit_scale_factors(measured, analytic, kinds)

    costs, fallback = [], []
    for (node, _key), t, a, ck in zip(keyed, measured, analytic, kinds):
        if t is not None and t > 0:
            costs.append(float(t))
        else:
            costs.append(float(a) * scales.get(ck, scales.get("*", 1.0)))
            fallback.append(node.name)
    n = len(keyed)
    report = {
        "model": "measured",
        "n_nodes": n,
        "n_measured": n - len(fallback),
        "coverage": (n - len(fallback)) / max(n, 1),
        "fallback": fallback,
        "scales": scales,
        "cache_entries": len(cache),
        "units": "us" if len(cache) else "cycles",
    }
    if fallback and len(cache):
        warnings.warn(
            f"tuning cache covers {report['n_measured']}/{n} nodes of "
            f"{cfg.name}; analytic fallback (x calibrated scale) for: "
            f"{', '.join(fallback[:8])}"
            f"{'...' if len(fallback) > 8 else ''}", stacklevel=2)
    elif not len(cache):
        warnings.warn(
            f"tuning cache is empty: {cfg.name} planned from analytic "
            "costs (cold-cache fallback)", stacklevel=2)
    return np.asarray(costs), report


# ---------------------------------------------------------------------------
# kernel-knob autotuners (retune)
# ---------------------------------------------------------------------------

def autotune_depthwise_block_c(x, w, *, stride: int = 1,
                               cache: TuningCache, iters: int = 3) -> int:
    """Search the depthwise Pallas kernel's channel tile over the
    divisors of C that fit the VMEM budget (pick_block_c's own
    feasibility rule — every candidate respects the 8MB budget by
    construction), record the winner."""
    from repro.kernels import depthwise_conv as dw
    from repro.kernels import ops as kops
    c = x.shape[-1]
    cands = dw.block_c_candidates(x.shape[2], c, w.shape[1], stride,
                                  np.dtype(x.dtype).itemsize)
    key = kernel_key("dw", x.shape, x.dtype, k=w.shape[1], s=stride)
    best, best_us = cands[0], float("inf")
    import jax
    for tc in cands:
        fn = jax.jit(lambda a, _tc=tc: dw.depthwise_conv_pallas(
            a, w, stride=stride, block_c=_tc,
            interpret=kops.interpret_mode()))
        us = _time_call(fn, x, warmup=1, iters=iters)
        if us < best_us:
            best, best_us = tc, us
    cache.put_knob(key, "block_c", int(best))
    cache.put_time(key, best_us)
    return int(best)


def autotune_dw_pw_row_chunk(x, dw_w, dw_b, pw_w, pw_b, *, stride: int = 1,
                             cache: TuningCache, iters: int = 3,
                             candidates=(4, 8, 16, 32)) -> int:
    """Search the fused dw->pw XLA path's row-chunk cap (how many
    output rows of the depthwise slab live in flight per scan step)."""
    from repro.kernels import dw_pw_fused as f
    import jax
    ho = -(-x.shape[1] // stride)
    cands = sorted({min(c, ho) for c in candidates}) or [ho]
    key = kernel_key("dwpw", x.shape, x.dtype,
                     k=dw_w.shape[1], s=stride, co=pw_w.shape[-1])
    best, best_us = cands[-1], float("inf")
    for hb in cands:
        fn = jax.jit(lambda a, _hb=hb: f.dw_pw_xla(
            a, dw_w, dw_b, pw_w, pw_b, stride=stride, row_chunk=_hb))
        us = _time_call(fn, x, warmup=1, iters=iters)
        if us < best_us:
            best, best_us = hb, us
    cache.put_knob(key, "row_chunk", int(best))
    cache.put_time(key, best_us)
    return int(best)


def autotune_sparse_conv_block_k(x, sw, bias, *, k: int, stride: int = 1,
                                 relu: bool = True, cache: TuningCache,
                                 iters: int = 3) -> int:
    """Search the sparse-conv Pallas kernel's K-tile (how many weight
    blocks each grid step gathers+accumulates) over the divisors of the
    node's kept-block count."""
    from repro.kernels import ops as kops
    from repro.kernels import sparse_conv as sc
    import jax
    n_k = sw.vals.shape[1]
    cands = [t for t in (1, 2, 3, 4) if n_k % t == 0] or [1]
    ob, _, bm, bn = sw.vals.shape
    key = kernel_key("sconv", x.shape, x.dtype, k=k, s=stride,
                     b=f"{bm}x{bn}K{n_k}", co=ob * bn)
    best, best_us = 1, float("inf")
    for t in cands:
        fn = jax.jit(lambda a, _t=t: sc.sparse_conv_pallas(
            a, sw.vals, sw.idx, bias, k=k, stride=stride, relu=relu,
            block_k=_t, interpret=kops.interpret_mode()))
        us = _time_call(fn, x, warmup=1, iters=iters)
        if us < best_us:
            best, best_us = t, us
    cache.put_knob(key, "block_k", int(best))
    cache.put_time(key, best_us)
    return int(best)


def autotune_microbatch(stage_cost, *, n_replicas: int = 1,
                        candidates=(2, 4, 8, 16, 32),
                        rel_tol: float = 0.05,
                        latency_cap_ticks: Optional[int] = None,
                        cache: Optional[TuningCache] = None,
                        arch: str = "") -> int:
    """Pick the serving microbatch count M from measured stage costs:
    throughput (``planner.pipeline_throughput_rel``) rises monotonically
    in M as the fill bubble amortizes, but batch latency is M + S - 1
    ticks — so take the SMALLEST M within ``rel_tol`` of the largest
    candidate's throughput (the knee of the fill curve), optionally
    bounded by a hard latency cap in ticks. Deterministic: pure
    arithmetic over the (measured or analytic) stage costs."""
    from repro.core.planner import pipeline_throughput_rel
    s = len(np.asarray(stage_cost))
    cands = [m for m in sorted(set(candidates))
             if latency_cap_ticks is None or m + s - 1 <= latency_cap_ticks]
    if not cands:
        cands = [min(candidates)]
    thr = {m: pipeline_throughput_rel(stage_cost, n_replicas, m)
           for m in cands}
    peak = max(thr.values())
    best = next(m for m in cands if thr[m] >= (1.0 - rel_tol) * peak)
    if cache is not None:
        key = kernel_key("microbatch", (s, n_replicas), np.float32,
                         arch=arch or "any")
        cache.put_knob(key, "n_microbatches", int(best))
    return int(best)


def autotune_graph(cfg, params, image_shape, *, graph=None,
                   cache: Optional[TuningCache] = None, iters: int = 3,
                   verbose: bool = False) -> TuningCache:
    """Walk the fused graph and tune every knob that applies to the
    CURRENT kernel impl (Pallas: depthwise block_c + sparse-conv
    block_k; XLA: dw_pw row_chunk). Winners land under kernel keys in
    the same cache the profiler uses; repeated shapes are tuned once."""
    import jax.numpy as jnp
    from repro.core.fusion import conv_part, fused_graph_for
    from repro.kernels import ops as kops
    from repro.models import cnn
    from repro.models.layers import SparseWeight
    g = graph if graph is not None else fused_graph_for(cfg.name)
    shapes = cnn.node_shapes(cfg, params, image_shape, graph=g)
    cache = cache if cache is not None else TuningCache()
    seen = set()
    for node, edge in zip(g.nodes, g.inputs):
        x = jnp.ones(shapes[edge[0]].shape, shapes[edge[0]].dtype)
        sig = (node.kind, x.shape, node.k, node.stride, node.cout)
        if sig in seen:
            continue
        seen.add(sig)
        if node.kind == "dw" and kops.impl() == "pallas":
            p = params[node.name]
            best = autotune_depthwise_block_c(
                x, p["w"], stride=node.stride, cache=cache, iters=iters)
        elif node.kind == "dw_pw" and kops.impl() == "xla":
            dw_p = params[node.parts[0].name]
            pw_p = params[conv_part(node).name]
            if isinstance(pw_p["w"], SparseWeight):
                continue                    # sparse pw: two-op fallback
            best = autotune_dw_pw_row_chunk(
                x, dw_p["w"], dw_p["b"], pw_p["w"], pw_p["b"],
                stride=node.stride, cache=cache, iters=iters)
        elif node.kind == "conv" and kops.impl() == "pallas":
            p = params[conv_part(node).name]
            if not isinstance(p["w"], SparseWeight):
                continue
            best = autotune_sparse_conv_block_k(
                x, p["w"], p["b"], k=node.k, stride=node.stride,
                relu=node.relu and not node.residual_from,
                cache=cache, iters=iters)
        else:
            continue
        if verbose:
            print(f"  tuned {node.name:<16} -> {best}")
    return cache


# ---------------------------------------------------------------------------
# the end-to-end loop
# ---------------------------------------------------------------------------

def calibrate(cfg, params, image_shape, *, graph=None, path=None,
              cache: Optional[TuningCache] = None, measure: bool = True,
              autotune: bool = False, iters: int = 5,
              verbose: bool = False) -> TuningCache:
    """Profile -> calibrate -> (optionally) retune in one call:
    measure every fused node, optionally autotune the kernel knobs, and
    persist to ``path``. The returned cache plugs straight into
    ``planner.plan(..., PlanRequest(model="measured", tuning_cache=...))``
    and :func:`set_tuning_cache` for kernel dispatch."""
    cache = cache if cache is not None else (
        TuningCache.load(path) if path else TuningCache())
    if measure:
        cache = measure_graph(cfg, params, image_shape, graph=graph,
                              cache=cache, iters=iters, verbose=verbose)
    if autotune:
        cache = autotune_graph(cfg, params, image_shape, graph=graph,
                               cache=cache, iters=max(iters // 2, 2),
                               verbose=verbose)
    if path:
        cache.save(path)
    return cache
