"""Serving launcher: batched prefill + decode with a KV/state cache —
the paper's deployment mode (HPIPE is an inference accelerator; its
batch-size-1 throughput story maps to continuous batched decode here).

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b \
        --batch 4 --prompt-len 32 --gen 16 --reduced

CNN archs serve images through the heterogeneous layer pipeline
(``pipeline_cnn`` mode): microbatches stream through cost-balanced
stage programs exactly as HPIPE streams partitions through per-layer
hardware.

    PYTHONPATH=src python -m repro.launch.serve --arch resnet50 \
        --batch 16 --microbatches 4 --stages 4 --image-size 64

Scale-out past one pipeline: ``--replicas R`` runs R full pipelines on
a (data, stage) 2-D mesh (batch sharded across replicas, stage weights
replicated only across data), ``--auto-split`` lets the co-planner
pick (stages, replicas) for the host, and ``--continuous`` serves
back-to-back requests through a never-draining pipeline
(``CNNPipelineServer``): one microbatch injected per tick, H2D of the
next microbatch overlapped with the current step, fill bubble
amortized over the whole request stream.

    PYTHONPATH=src python -m repro.launch.serve --arch resnet50 \
        --continuous --requests 8 --batch 8 --mb-size 2 --replicas 2

THE serving entry point is ``serve(ServeConfig(...))``: one frozen
config names the mode (``latency`` | ``throughput``), the scale-out
(replicas / OS-process workers), and the stored weight dtype
(``quantize``), and ``serve()`` dispatches to the right executor. The
old per-mode functions (``serve_cnn`` / ``serve_cnn_continuous`` /
``serve_cnn_tier``) survive as DeprecationWarning shims.

Batch-1 latency mode (``mode="latency"``): HPIPE's headline number is
single-image latency — no batch to fill, no microbatch fill bubble.
One (1, H, W, 3) request runs the whole stage chain in ONE jit (the
stage programs composed back-to-back; the wire protocol is unchanged,
there is just no pipeline between the stages) and the next request is
not admitted until its logits are on the host. ``serve()`` reports the
measured p50/p99 over ``n_requests`` single-image requests.

    PYTHONPATH=src python -m repro.launch.serve --arch resnet50 \
        --mode latency --requests 16 --quantize int8
"""
from __future__ import annotations

import argparse
import dataclasses
import time
import warnings
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.launch.mesh import mesh_context as _mesh_ctx
from repro.models import lm


def serve_lm(arch: str, *, batch: int = 4, prompt_len: int = 32,
             gen_tokens: int = 16, max_seq: int = 128,
             use_reduced: bool = True, seed: int = 0, greedy: bool = True,
             verbose: bool = True):
    """Prefill a batch of prompts token-by-token-free (single forward),
    then decode ``gen_tokens`` greedily. Returns tokens + timings."""
    cfg = get_config(arch)
    if use_reduced:
        cfg = reduced(cfg)
    key = jax.random.PRNGKey(seed)
    params = lm.init_params(cfg, key)
    prompts = jax.random.randint(key, (batch, prompt_len), 0,
                                 cfg.vocab_size)
    extra = {}
    if cfg.family == "audio":
        extra["frames"] = jax.random.normal(
            key, (batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        extra["patches"] = jax.random.normal(
            key, (batch, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)

    decode = jax.jit(
        lambda p, c, t, pos: lm.decode_step(cfg, p, c, t, pos, extra=extra))

    cache = lm.init_cache(cfg, batch, max_seq)
    # prefill by stepping the prompt through the decode path (state
    # archs) — exactness vs forward() is covered by tests
    t0 = time.time()
    logits = None
    for i in range(prompt_len):
        logits, cache = decode(params, cache, prompts[:, i:i + 1],
                               jnp.int32(i))
    prefill_s = time.time() - t0

    out_tokens = []
    t0 = time.time()
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    for i in range(gen_tokens):
        out_tokens.append(np.asarray(tok)[:, 0])
        logits, cache = decode(params, cache, tok,
                               jnp.int32(prompt_len + i))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    decode_s = time.time() - t0
    toks_per_s = batch * gen_tokens / max(decode_s, 1e-9)
    if verbose:
        print(f"{arch}: prefill {prompt_len} toks in {prefill_s:.2f}s, "
              f"decode {gen_tokens} toks/seq at {toks_per_s:.1f} tok/s "
              f"(batch={batch})")
    return {"tokens": np.stack(out_tokens, 1), "prefill_s": prefill_s,
            "decode_s": decode_s, "tokens_per_s": toks_per_s}


# ---------------------------------------------------------------------------
# the unified serving API: ONE frozen config, ONE dispatcher
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Everything ``serve()`` needs, in one frozen value.

    ``mode`` picks the executor: ``"throughput"`` (the default — the
    batched / continuous / tiered pipelines, selected by ``continuous``
    / ``tier`` / ``procs``) or ``"latency"`` (batch-1: one image in
    flight, whole stage chain in one jit, measured p50/p99).
    ``quantize`` is the stored weight dtype (core/quant.py
    ``STORE_DTYPES``): every executor re-stores the weights through the
    same ``quantize_tree``, so a placed int8 pipeline and its
    single-process int8 reference read the identical quantized tree.
    """
    arch: str
    mode: str = "throughput"            # "latency" | "throughput"
    continuous: bool = False
    tier: bool = False
    replicas: int = 1
    procs: int = 0                      # >0: OS-process replica workers
    hosts: int = 0                      # >0: TCP dial-in replica workers
    listen: Optional[str] = None        # hosts mode: "host:port" to bind
    quantize: str = "native"            # core/quant.py store dtype
    batch: int = 16
    n_requests: int = 4
    n_microbatches: int = 4
    mb_size: int = 2
    n_stages: int = 4
    image_size: int = 64
    iters: int = 3
    seed: int = 0
    placed: Optional[bool] = None
    param_budget_frac: Optional[float] = None
    auto_split: bool = False
    # fault-injection knobs (tier / procs modes)
    fail_replica: Optional[int] = None
    fail_at_tick: Optional[int] = None
    kill_worker: Optional[int] = None
    kill_at_tick: int = 1
    # procs-mode liveness knobs
    heartbeat_interval_s: float = 0.1
    suspect_after_s: float = 0.5
    dead_after_s: float = 10.0
    ledger_dir: Optional[str] = None
    # profile-guided planning
    tuning_cache: Optional[object] = None
    calibrate: bool = False
    verbose: bool = True

    def __post_init__(self):
        from repro.core.quant import STORE_DTYPES
        if self.mode not in ("latency", "throughput"):
            raise ValueError(f"mode={self.mode!r}: expected 'latency' "
                             "or 'throughput'")
        if self.quantize not in STORE_DTYPES:
            raise ValueError(f"quantize={self.quantize!r}: expected one "
                             f"of {STORE_DTYPES}")
        if self.mode == "latency" and (self.continuous or self.tier or
                                       self.procs or self.hosts):
            raise ValueError("mode='latency' serves one image at a time "
                             "— continuous/tier/procs/hosts are "
                             "throughput-mode knobs")
        if self.procs and self.hosts:
            raise ValueError("procs and hosts are exclusive: same-host "
                             "socketpair workers OR TCP dial-in workers")
        if self.listen is not None and not self.hosts:
            raise ValueError("listen= names a bind address for hosts "
                             "mode; set hosts > 0")


def serve(cfg, **kw):
    """THE serving entry point: ``serve(ServeConfig(...)) -> dict``.

    Dispatch: LM archs run the prefill+decode loop; CNN archs run the
    heterogeneous layer pipeline in the mode the config names —
    ``latency`` (batch-1, p50/p99), or ``throughput`` via the tiered
    (``tier``/``procs``), continuous (``continuous``) or one-shot
    batched executor.

    ``serve("arch-name", ...)`` (the pre-ServeConfig signature) still
    works as a DeprecationWarning shim over the LM path."""
    if isinstance(cfg, str):
        warnings.warn(
            "serve(arch, ...) is deprecated; LM serving moved to "
            "serve_lm(arch, ...) and serve() now takes a ServeConfig",
            DeprecationWarning, stacklevel=2)
        return serve_lm(cfg, **kw)
    if kw:
        raise TypeError(f"serve(ServeConfig) takes no extra kwargs "
                        f"(got {sorted(kw)})")
    if get_config(cfg.arch).family != "cnn":
        return serve_lm(cfg.arch, batch=cfg.batch, seed=cfg.seed,
                        verbose=cfg.verbose)
    if cfg.mode == "latency":
        return _serve_cnn_latency(cfg)
    if cfg.tier or cfg.procs or cfg.hosts:
        return _serve_cnn_tier(
            cfg.arch, n_requests=cfg.n_requests, batch=cfg.batch,
            mb_size=cfg.mb_size, n_stages=cfg.n_stages,
            n_replicas=cfg.replicas, image_size=cfg.image_size,
            seed=cfg.seed, fail_replica=cfg.fail_replica,
            fail_at_tick=cfg.fail_at_tick, procs=cfg.procs,
            hosts=cfg.hosts, listen=cfg.listen,
            kill_worker=cfg.kill_worker, kill_at_tick=cfg.kill_at_tick,
            heartbeat_interval_s=cfg.heartbeat_interval_s,
            suspect_after_s=cfg.suspect_after_s,
            dead_after_s=cfg.dead_after_s, ledger_dir=cfg.ledger_dir,
            quantize=cfg.quantize, verbose=cfg.verbose)
    if cfg.continuous:
        return _serve_cnn_continuous(
            cfg.arch, n_requests=cfg.n_requests, batch=cfg.batch,
            mb_size=cfg.mb_size, n_stages=cfg.n_stages,
            n_replicas=cfg.replicas, image_size=cfg.image_size,
            seed=cfg.seed, placed=cfg.placed,
            param_budget_frac=cfg.param_budget_frac,
            auto_split=cfg.auto_split, tuning_cache=cfg.tuning_cache,
            calibrate=cfg.calibrate, quantize=cfg.quantize,
            verbose=cfg.verbose)
    return _serve_cnn(
        cfg.arch, batch=cfg.batch, n_microbatches=cfg.n_microbatches,
        n_stages=cfg.n_stages, image_size=cfg.image_size,
        iters=cfg.iters, seed=cfg.seed, placed=cfg.placed,
        param_budget_frac=cfg.param_budget_frac,
        n_replicas=cfg.replicas, auto_split=cfg.auto_split,
        tuning_cache=cfg.tuning_cache, calibrate=cfg.calibrate,
        quantize=cfg.quantize, verbose=cfg.verbose)


def _plan_cnn_serving(arch: str, *, n_stages: int, n_replicas: int,
                      n_microbatches: int, param_budget_frac,
                      auto_split: bool, seed: int,
                      tuning_cache=None, calibrate: bool = False,
                      image_size: int = 64, store_dtype: str = "native",
                      verbose: bool = False):
    """Shared serving preamble (every CNN executor): init params,
    resolve the weight budget, and pick the (stages, replicas) split —
    the co-planner's when ``auto_split``, the caller's otherwise. One
    copy so the entry points cannot drift. Returns ``(cfg, params,
    plan, n_replicas, total_bytes)``; ``total_bytes`` is priced at
    ``store_dtype``, and so is the budget the planner balances against
    (a quantized deployment's budget constrains its QUANTIZED
    residency — that is what lets int8 plan deeper cuts).

    Profile-guided planning: ``tuning_cache`` (a path or a TuningCache)
    switches the planner to ``model="measured"`` over that cache's
    profiled node times; ``calibrate=True`` first measures every fused
    node on the live device at ``image_size`` (and writes the cache
    back if a path was given). A missing/cold cache degrades to the
    analytic plan bit-for-bit."""
    from repro.core import planner, tuning
    from repro.core.costmodel import pytree_param_bytes
    from repro.models import cnn
    cfg = get_config(arch)
    if cfg.family != "cnn":
        raise ValueError(f"{arch} is not a CNN arch")
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(seed))
    total_bytes = pytree_param_bytes(params, store_dtype)
    budget = (int(param_budget_frac * total_bytes)
              if param_budget_frac else None)
    cache, model = None, "analytic"
    if tuning_cache is not None or calibrate:
        cache_path = tuning_cache if isinstance(tuning_cache, str) else None
        cache = (tuning_cache if isinstance(tuning_cache, tuning.TuningCache)
                 else tuning.TuningCache.load(cache_path)
                 if cache_path else tuning.TuningCache())
        if calibrate:
            if verbose:
                print(f"[serve] calibrating {arch} at {image_size}px "
                      f"({len(cache)} cached entries)...")
            cache = tuning.calibrate(
                cfg, params, (1, image_size, image_size, 3), cache=cache,
                path=cache_path, verbose=verbose)
        model = "measured"
        tuning.set_tuning_cache(cache)  # kernel knobs at trace time
    if auto_split:
        plan2d = planner.plan(cfg, params, planner.PlanRequest(
            n_devices=len(jax.devices()),
            n_microbatches=n_microbatches, max_stage_param_bytes=budget,
            model=model, tuning_cache=cache, store_dtype=store_dtype))
        plan, n_replicas = plan2d["plan"], plan2d["n_replicas"]
    else:
        plan = planner.plan(cfg, params, planner.PlanRequest(
            n_stages=n_stages, max_stage_param_bytes=budget,
            model=model, tuning_cache=cache, store_dtype=store_dtype))
    return cfg, params, plan, n_replicas, total_bytes


def _serve_cnn(arch: str, *, batch: int = 16, n_microbatches: int = 4,
               n_stages: int = 4, image_size: int = 64, iters: int = 3,
               seed: int = 0, verbose: bool = True, placed=None,
               param_budget_frac=None, n_replicas: int = 1,
               auto_split: bool = False, tuning_cache=None,
               calibrate: bool = False, quantize: str = "native"):
    """Batched image serving through the heterogeneous layer pipeline
    (``pipeline_cnn`` mode).

    Plans cost-balanced stage cuts over the layer-graph IR
    (planner.plan, cycle estimates from the pruned weights), compiles
    per-stage wire programs, and streams microbatches through the
    GSPMD pipeline executor on one device, or the shard_map executor
    when the stages are placed on their own devices.

    Weight placement: with one device per stage available, each stage's
    param slice is packed and ``jax.device_put`` onto ONLY that stage's
    device (``stage_param_shardings``) — per-device parameter residency
    drops from the whole model to the largest stage (both are reported
    either way, so the win — or the replication cost — is visible).
    ``placed=None`` auto-enables placement when the host has enough
    devices; ``param_budget_frac`` bounds any stage's weight bytes to
    that fraction of the model and lets the planner rebalance cuts
    (memory-aware planning). Batches that don't divide the microbatch
    count are zero-padded and the padded outputs dropped.

    2-D scale-out: ``n_replicas`` > 1 runs R full pipelines side by
    side on a ``(data, stage)`` mesh — the batch shards across
    replicas, each replica's stage column holds its own stage's
    weights (replicated ONLY across data: per-device bytes unchanged),
    and throughput scales toward Rx the single pipeline's.
    ``auto_split=True`` lets the (stages, replicas) co-planner
    (``planner.plan_cnn_pipeline_2d``) pick the split for the host's
    device count instead of taking ``n_stages``/``n_replicas``
    literally."""
    from repro.core import pipeline as pp
    cfg, params, plan, n_replicas, total_bytes = _plan_cnn_serving(
        arch, n_stages=n_stages, n_replicas=n_replicas,
        n_microbatches=n_microbatches or 8,
        param_budget_frac=param_budget_frac, auto_split=auto_split,
        seed=seed, tuning_cache=tuning_cache, calibrate=calibrate,
        image_size=image_size, store_dtype=quantize, verbose=verbose)
    from repro.models import cnn
    s = plan["n_stages"]
    r = n_replicas
    if not n_microbatches:
        # n_microbatches=0: autotune the microbatch width from the
        # plan's (measured or analytic) stage costs — the knee of the
        # fill curve (core/tuning.autotune_microbatch)
        from repro.core import tuning as _tuning
        n_microbatches = _tuning.autotune_microbatch(
            plan["stage_cost"], n_replicas=r,
            cache=_tuning.current_tuning_cache(), arch=arch)
        if verbose:
            print(f"[serve] autotuned n_microbatches={n_microbatches}")
    use_placed = (len(jax.devices()) >= s * r) if placed is None else placed
    images = jax.random.normal(jax.random.PRNGKey(seed),
                               (batch, image_size, image_size, 3))
    x_mb = pp.microbatch(images, n_microbatches, pad=True, n_replicas=r)
    mb_shape = x_mb.shape[2:] if r > 1 else x_mb.shape[1:]

    if use_placed:
        if len(jax.devices()) < s * r:
            raise ValueError(
                f"placed=True needs >= {s * r} devices ({s} stages x "
                f"{r} replicas), have {len(jax.devices())}; run under "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={s * r} "
                "or drop placement/replication")
        from repro.launch.shardings import placed_stage_setup
        stage_fns, pack_in, unpack_out, width, pparams, mesh, sps = \
            placed_stage_setup(cfg, params, plan, mb_shape, n_replicas=r,
                               quantize=quantize)
        placed_bytes = pparams.width
        run_args = (x_mb, jax.device_put(pparams.pack(), sps["buffer"]))

        def pipeline(wires, pb):
            # shard_map: every device runs literally its own stage's
            # program (lax.switch + ppermute). GSPMD cannot partition a
            # Mosaic kernel, so the gspmd executor cannot hold the
            # Pallas path on a placed mesh; and replicated logits stay
            # BITWISE equal to the 1-replica placed path
            return pp.pipeline_apply_hetero(
                stage_fns, wires, mesh=mesh, stage_axis="stage",
                n_stages=s, stage_params=pb, n_replicas=r)
    else:
        stage_fns, pack_in, unpack_out, width = cnn.stage_programs(
            cfg, params, plan["stage_of"], mb_shape, quantize=quantize)
        placed_bytes = int(plan["placed_bytes_per_device"])  # what
        #                                     placement WOULD hold
        mesh = None
        run_args = (x_mb,)

        def pipeline(wires):
            return pp.pipeline_apply_gspmd_hetero(stage_fns, wires,
                                                  n_stages=s, n_replicas=r)

    pack = jax.vmap(jax.vmap(pack_in)) if r > 1 else jax.vmap(pack_in)

    def run_fn(xmb, *pb):
        out = pipeline(pack(xmb), *pb)
        return pp.concat_hetero_outputs(out, unpack_out, n_microbatches,
                                        n_replicas=r)

    with _mesh_ctx(mesh):
        t0 = time.time()
        run = jax.jit(run_fn).lower(*run_args).compile()
        compile_s = time.time() - t0
        logits = jax.block_until_ready(run(*run_args))     # warmup
        t0 = time.time()
        for _ in range(iters):
            logits = jax.block_until_ready(run(*run_args))
        run_s = (time.time() - t0) / max(iters, 1)

    logits = logits[:batch]                      # drop pad rows
    ims_per_s = batch / max(run_s, 1e-9)
    bub = pp.bubble_fraction(n_microbatches, s)
    if verbose:
        rep = f" x{r} replicas" if r > 1 else ""
        print(f"{arch}: {batch} imgs @{image_size}px through {s} stages"
              f"{rep} (M={n_microbatches}): {ims_per_s:.1f} im/s "
              f"(compile {compile_s:.1f}s, bubble {bub:.2f}, "
              f"imbalance {plan['imbalance']:.2f})")
        x = total_bytes / max(placed_bytes, 1)
        if use_placed:
            print(f"{arch}: params/device: {placed_bytes / 1e6:.2f} MB "
                  f"placed vs {total_bytes / 1e6:.2f} MB replicated "
                  f"(x{x:.1f} smaller)")
        else:
            print(f"{arch}: params/device: {total_bytes / 1e6:.2f} MB "
                  f"replicated (placement would hold "
                  f"{placed_bytes / 1e6:.2f} MB, x{x:.1f} smaller)")
    return {"logits": np.asarray(logits), "images_per_s": ims_per_s,
            "request_images": np.asarray(images),
            "compile_s": compile_s, "run_s": run_s,
            "mosaic_kernels": _mosaic_kernels(run),
            "bubble_fraction": bub, "n_stages": s,
            "n_replicas": r,
            "imbalance": plan["imbalance"],
            "placed": use_placed,
            "stage_row_devices": (_row_devices(run_args[1])
                                  if use_placed else None),
            "quantize": quantize,
            "param_bytes_replicated_per_device": int(total_bytes),
            "param_bytes_placed_per_device": int(placed_bytes),
            "param_placement_ratio": placed_bytes / max(total_bytes, 1)}


def _serve_cnn_latency(cfg: ServeConfig) -> dict:
    """Batch-1 latency serving — the paper's headline regime.

    HPIPE's claim is single-image latency WITHOUT batching: every layer
    has its own hardware, so one image flows through the whole chain
    with no batch to fill. The TPU mapping: compile the plan's stage
    programs COMPOSED back-to-back into one jit (the wire protocol —
    pack, stage chain, unpack — is identical to the pipelined
    executors; there is simply no pipeline register between stages) and
    admit exactly one (1, H, W, 3) request at a time: the next request
    is not submitted until this one's logits are on the host. Each
    request's wall time therefore IS its latency — no queueing, no
    microbatch fill, no deferred D2H — and the reported p50/p99 are
    measured over ``n_requests`` such round trips (H2D + forward + D2H
    inclusive). Throughput mode at batch 1 pays the fill bubble and
    the tick scheduler on top; the serving benchmark asserts this
    mode's p50 beats it."""
    from repro.models import cnn
    mcfg, params, plan, _, total_bytes = _plan_cnn_serving(
        cfg.arch, n_stages=cfg.n_stages, n_replicas=1,
        n_microbatches=1, param_budget_frac=cfg.param_budget_frac,
        auto_split=False, seed=cfg.seed, tuning_cache=cfg.tuning_cache,
        calibrate=cfg.calibrate, image_size=cfg.image_size,
        store_dtype=cfg.quantize, verbose=cfg.verbose)
    img_shape = (1, cfg.image_size, cfg.image_size, 3)
    stage_fns, pack_in, unpack_out, width = cnn.stage_programs(
        mcfg, params, plan["stage_of"], img_shape, quantize=cfg.quantize)

    def request_fn(img):
        wire = pack_in(img)
        for fn in stage_fns:          # composed, not pipelined: one jit
            wire = fn(wire)
        return unpack_out(wire)

    # compile ahead of time, then one warmup request; the timed loop
    # measures the steady single-image round trip
    t0 = time.time()
    request = jax.jit(request_fn).lower(
        jax.ShapeDtypeStruct(img_shape, jnp.float32)).compile()
    compile_s = time.time() - t0
    jax.block_until_ready(request(jnp.zeros(img_shape, jnp.float32)))
    key = jax.random.PRNGKey(cfg.seed + 1)
    reqs = np.asarray(jax.random.normal(
        key, (cfg.n_requests,) + img_shape[1:]), np.float32)
    lats, logits = [], []
    for i in range(cfg.n_requests):
        t0 = time.time()
        y = request(jnp.asarray(reqs[i][None]))   # H2D in the timed path
        logits.append(np.asarray(y))              # D2H blocks: round trip
        lats.append(time.time() - t0)
    logits = np.concatenate(logits, 0)
    p50 = float(np.percentile(lats, 50))
    p99 = float(np.percentile(lats, 99))
    if cfg.verbose:
        print(f"{cfg.arch}: batch-1 latency through "
              f"{plan['n_stages']} composed stages "
              f"(quantize={cfg.quantize}): p50 {p50 * 1e3:.1f}ms / "
              f"p99 {p99 * 1e3:.1f}ms over {cfg.n_requests} requests "
              f"(compile {compile_s:.1f}s)")
    return {"mode": "latency", "quantize": cfg.quantize,
            "latency_p50_s": p50, "latency_p99_s": p99,
            "request_latencies_s": lats, "logits": logits,
            "request_images": reqs,
            "n_stages": int(plan["n_stages"]), "compile_s": compile_s,
            "mosaic_kernels": _mosaic_kernels(request),
            "param_bytes_stored": int(total_bytes)}


def _mosaic_kernels(compiled) -> dict:
    """Mosaic-compiled Pallas kernel calls in a compiled program, by
    kernel name (``{"sparse_conv": 53, ...}``; empty off TPU, where
    Pallas kernels are interpreted into plain HLO)."""
    counts = {}
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.split("=", 1)[0].strip().lstrip("%")
            name = name.rsplit(".", 1)[0] if "." in name else name
            counts[name] = counts.get(name, 0) + 1
    return counts


def _row_devices(buf) -> list:
    """Per stage row of a placed ``(S, P)`` param buffer, the ids of
    the devices that hold it — the physical evidence of placement."""
    rows = [[] for _ in range(buf.shape[0])]
    for sh in buf.addressable_shards:
        start = sh.index[0].start or 0
        stop = sh.index[0].stop or buf.shape[0]
        for k in range(start, stop):
            rows[k].append(sh.device.id)
    return [sorted(r) for r in rows]


# marks a microbatch slot owned by the serving tier rather than a
# local submit(): its logits go to ``on_result`` instead of results()
_EXTERNAL = object()


class CNNPipelineServer:
    """Continuous-batching image server over the heterogeneous layer
    pipeline — the steady-state deployment HPIPE's throughput numbers
    describe (a pipeline that is always full, not one that fills and
    drains per batch).

    The wire protocol: ``submit()`` packs each request's images into
    fixed-size microbatches (the last one zero-padded, the pad rows
    tracked and dropped on output) and appends them to one queue;
    ``run()`` ticks the pipeline (``pipeline.pipeline_step_hetero``)
    once per queued microbatch — injecting request K+1's first
    microbatch on the tick right after request K's last, so the
    pipeline NEVER drains between requests and the S-1-tick fill
    amortizes over the whole stream (``steady_bubble_fraction``), plus
    S-1 trailing zero-wire ticks to flush the tail. The pipeline state
    is threaded through a ``donate_argnums=(0,)`` jit, so the
    steady-state loop reuses one state buffer; the NEXT tick's wire is
    packed and ``jax.device_put`` right after the current tick is
    dispatched — host->device transfer overlaps the step instead of
    serializing in front of it.

    Params: with one device per (replica, stage) grid cell the packed
    ``(S, P)`` buffer places each stage's weights on its stage column
    (replicated only across data); on a single host the ragged
    ``PlacedParams.pack_ragged()`` rows are used instead — same
    bit-exact packed execution, none of the even-width padding.

    Bitwise contract: continuous serving is bit-identical to isolated
    requests and to the sequential interpreter WITHIN a configuration
    (slots never mix). The placed R>1 tick runs the gspmd-style
    ``pipeline_step_hetero`` — like batch-mode gspmd it may drift
    ~1e-10 from the 1-replica program under the 2-D GSPMD partition
    (see ``pipeline_apply_gspmd_hetero``); the batch path's shard_map
    routing is the one that guarantees cross-replica-count bitwise
    equality.
    """

    def __init__(self, arch: str, *, mb_size: int = 2, n_stages: int = 4,
                 n_replicas: int = 1, image_size: int = 64, seed: int = 0,
                 placed=None, param_budget_frac=None,
                 auto_split: bool = False, verbose: bool = False,
                 devices=None, injector=None, cfg=None, params=None,
                 plan=None, param_buffer=None, tuning_cache=None,
                 calibrate: bool = False, quantize: str = "native"):
        from repro.core import pipeline as pp
        from repro.models import cnn
        if plan is not None:
            # the serving tier plans ONCE and hands every replica the
            # same (cfg, params, plan): identical weights + identical
            # stage cuts are what make failure replay bitwise-equal
            if cfg is None or params is None:
                raise ValueError("plan= requires cfg= and params=")
        else:
            cfg, params, plan, n_replicas, _ = _plan_cnn_serving(
                arch, n_stages=n_stages, n_replicas=n_replicas,
                # the co-planner's fill-bubble term wants the
                # microbatches one REQUEST contributes; continuous
                # injection amortizes the fill across the stream, so
                # score with a generous stream length, not one batch
                n_microbatches=32,
                param_budget_frac=param_budget_frac,
                auto_split=auto_split, seed=seed,
                tuning_cache=tuning_cache, calibrate=calibrate,
                image_size=image_size, store_dtype=quantize,
                verbose=verbose)
        self.cfg = cfg
        self.quantize = quantize
        self.n_stages = s = plan["n_stages"]
        self.n_replicas = r = n_replicas
        self.mb_size = mb_size
        self.image_size = image_size
        self.plan = plan
        self.devices = list(devices) if devices is not None else None
        n_dev = len(self.devices) if self.devices is not None \
            else len(jax.devices())
        mb_shape = (mb_size, image_size, image_size, 3)
        use_placed = (n_dev >= s * r) if placed is None else placed
        self.param_buffer = None
        if use_placed:
            from repro.launch.shardings import placed_stage_setup
            stage_fns, pack_in, unpack_out, width, pparams, mesh, sps = \
                placed_stage_setup(cfg, params, plan, mb_shape,
                                   n_replicas=r, devices=self.devices,
                                   quantize=quantize)
            if param_buffer is not None:
                # a pre-placed (S, P) buffer (the tier's remesh path on
                # degraded respawn) — skip the host-side repack
                self.param_buffer = param_buffer
            else:
                self.param_buffer = jax.device_put(pparams.pack(),
                                                   sps["buffer"])
            self._params_arg = (self.param_buffer,)
            self.mesh = mesh
        else:
            # single host: ragged packed rows — bit-exact packed
            # execution without the (S, P) buffer's even-width padding
            stage_fns, pack_in, unpack_out, width, pparams = \
                cnn.stage_programs(cfg, params, plan["stage_of"],
                                   mb_shape, placed=True,
                                   quantize=quantize)
            self._params_arg = (pparams.pack_ragged(),)
            self.mesh = None
        self.placed = use_placed
        self.pparams = pparams
        self.width = width
        # jit both wire codecs once: the serving loop calls them every
        # tick, and op-by-op dispatch would land in the timed region
        self._unpack_out = jax.jit(unpack_out)
        self._pack = jax.jit(jax.vmap(pack_in) if r > 1 else pack_in)
        wire_shape = (r, mb_size, width) if r > 1 else (mb_size, width)
        self._zero_wire = jnp.zeros(wire_shape, jnp.float32)
        self._state_shape = (s, r, mb_size, width) if r > 1 \
            else (s, mb_size, width)
        self._state = jnp.zeros(self._state_shape, jnp.float32)

        def tick(state, wire, pparams_arg):
            return pp.pipeline_step_hetero(
                stage_fns, state, wire, n_stages=s, stage_axis="stage",
                mesh=self.mesh, stage_params=pparams_arg, n_replicas=r)

        self._step = jax.jit(tick, donate_argnums=(0,))
        # FIFO of (req_id, mb_index, n_valid, images) microbatch slots
        # (deque: the steady-state loop front-pops once per tick)
        self._queue = deque()
        self._results = {}
        self._pending = {}
        self._next_req = 0
        self.ticks = 0
        self.injected_slots = 0
        self.verbose = verbose
        # incremental-tick pipeline tracking (the tier drives
        # _tick_once directly; run() loops it): _staged is the next
        # packed (slots, wire), _inflight the per-tick slot lists still
        # inside the pipe, _emitted the last tick's (slots, out) whose
        # D2H readback is deferred one tick
        self._staged = None
        self._inflight = deque()
        self._emitted = None
        # failure injection fires in the tick path (maybe_fail(ticks)),
        # so an injected fault surfaces exactly as a mid-stream crash
        self.injector = injector
        # tier hook: externally-keyed slots (enqueue()) deliver through
        # on_result(key, logits) instead of the results() store
        self.on_result = None
        # request-latency accounting (submit -> last microbatch out)
        self._req_submit = {}
        self._req_done = {}

    @property
    def idle_slots(self) -> int:
        """Pipeline slots that ran empty over the server's lifetime
        (fill/flush ticks + unfilled replica slots) — derived from the
        tick counters, so it always agrees with the reported bubble."""
        return self.ticks * self.n_replicas - self.injected_slots

    # -- request intake ----------------------------------------------------

    def submit(self, images) -> int:
        """Queue one request (B, H, W, 3). Returns a request id whose
        logits ``results()`` yields after ``run()``."""
        images = np.asarray(images, np.float32)
        b = images.shape[0]
        if b == 0:
            raise ValueError("empty request (batch 0)")
        if images.shape[1:] != (self.image_size, self.image_size, 3):
            raise ValueError(f"request shape {images.shape[1:]} != "
                             f"({self.image_size}, {self.image_size}, 3)")
        req = self._next_req
        self._next_req += 1
        n_mb = -(-b // self.mb_size)
        self._pending[req] = n_mb
        self._results[req] = [None] * n_mb
        # monotonic, not wall time: request latencies are durations,
        # and an NTP step must never produce negative (or day-long)
        # p50s — wall clocks are for logs only
        self._req_submit[req] = time.monotonic()
        for i in range(n_mb):
            chunk = images[i * self.mb_size:(i + 1) * self.mb_size]
            n_valid = chunk.shape[0]
            if n_valid < self.mb_size:
                chunk = np.concatenate(
                    [chunk, np.zeros((self.mb_size - n_valid,)
                                     + chunk.shape[1:], np.float32)])
            self._queue.append((req, i, n_valid, chunk))
        return req

    def enqueue(self, key, images, *, n_valid=None):
        """Tier hook: queue ONE microbatch whose logits are delivered
        to ``on_result(key, logits)`` instead of the results() store.
        ``images`` may be short (padded here) or already the padded
        ``(mb_size, H, W, 3)`` chunk with ``n_valid`` real rows."""
        if self.on_result is None:
            raise ValueError("enqueue() needs on_result set")
        images = np.asarray(images, np.float32)
        if images.shape[0] > self.mb_size:
            raise ValueError(f"enqueue() takes one microbatch "
                             f"(<= {self.mb_size} rows), got "
                             f"{images.shape[0]}")
        if n_valid is None:
            n_valid = images.shape[0]
        if images.shape[0] < self.mb_size:
            images = np.concatenate(
                [images, np.zeros((self.mb_size - images.shape[0],)
                                  + images.shape[1:], np.float32)])
        self._queue.append((_EXTERNAL, key, n_valid, images))

    @property
    def busy(self) -> bool:
        """True while any microbatch is queued, staged, in flight, or
        emitted-but-uncollected — the tier ticks a replica only while
        this holds."""
        return bool(self._queue) or self._staged is not None or \
            any(s is not None for s in self._inflight) or \
            self._emitted is not None

    # -- the serving loop --------------------------------------------------

    def _stage_next(self):
        """Pop the next tick's worth of slots (R microbatches) and pack
        + device_put their wire — called right after the CURRENT tick
        is dispatched, so the H2D transfer overlaps the step."""
        if not self._queue:
            return None
        r = self.n_replicas
        slots = [self._queue.popleft() if self._queue else None
                 for _ in range(r)] if r > 1 else [self._queue.popleft()]
        imgs = np.stack([s[3] if s is not None else
                         np.zeros((self.mb_size, self.image_size,
                                   self.image_size, 3), np.float32)
                         for s in slots])
        wire = self._pack(jnp.asarray(imgs) if r > 1
                          else jnp.asarray(imgs[0]))
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            spec = P("data") if r > 1 else P()
            wire = jax.device_put(wire, NamedSharding(self.mesh, spec))
        return slots, wire

    def _collect(self, slots, out_wire):
        """Record one tick's emitted microbatch(es). Blocks on the
        device value — run() defers this one tick, so the NEXT tick is
        already dispatched and the D2H readback overlaps its compute."""
        if slots is None:
            return
        r = self.n_replicas
        for k, slot in enumerate(slots):
            if slot is None:
                continue
            req, i, n_valid, _ = slot
            logits = np.asarray(self._unpack_out(
                out_wire[k] if r > 1 else out_wire))[:n_valid]
            if req is _EXTERNAL:
                self.on_result(i, logits)      # i is the tier's key
                continue
            self._results[req][i] = logits
            self._pending[req] -= 1
            if self._pending[req] == 0:
                self._req_done[req] = time.monotonic()

    def _tick_once(self) -> bool:
        """One pipeline tick, instance-state edition: the serving tier
        drives this directly (inside ``mesh_context(self.mesh)``);
        run() loops it. Returns True if a device tick was dispatched,
        False when the pipe was idle and only the trailing emitted
        output remained to collect. The FailureInjector hook fires
        FIRST — the tick path — so an injected replica failure
        surfaces exactly where a real mid-stream crash would."""
        if self.injector is not None:
            self.injector.maybe_fail(self.ticks)
        if self._staged is None:
            self._staged = self._stage_next()
        if self._staged is None and not any(
                s is not None for s in self._inflight):
            # nothing queued or in flight: just flush the deferred
            # readback (run()'s trailing collect), no zero-wire tick
            if self._emitted is not None:
                self._collect(*self._emitted)
                self._emitted = None
            return False
        slots, wire = self._staged if self._staged is not None \
            else (None, self._zero_wire)
        self._state, out = self._step(self._state, wire,
                                      *self._params_arg)
        self.ticks += 1
        if slots is not None:
            self.injected_slots += sum(1 for s in slots
                                       if s is not None)
        self._inflight.append(slots)
        self._staged = self._stage_next()     # H2D overlaps the step
        # collect the PREVIOUS tick's output only now, after this tick
        # is dispatched: its D2H readback overlaps the in-flight
        # compute instead of serializing it
        if self._emitted is not None:
            self._collect(*self._emitted)
            self._emitted = None
        if len(self._inflight) >= self.n_stages:
            self._emitted = (self._inflight.popleft(), out)
        return True

    def run(self) -> dict:
        """Drain the queue: one pipeline tick per queued microbatch
        (continuous injection — no drain between requests) plus S-1
        flush ticks. Returns throughput/bubble metrics for the run."""
        t0 = time.monotonic()
        n_imgs = sum(s[2] for s in self._queue)
        ticks_before = self.ticks
        injected_before = self.injected_slots
        done_before = set(self._req_done)
        with _mesh_ctx(self.mesh):
            if self._staged is None:
                self._staged = self._stage_next()
            while self._staged is not None or any(
                    s is not None for s in self._inflight):
                self._tick_once()
            if self._emitted is not None:
                self._collect(*self._emitted)
                self._emitted = None
        elapsed = time.monotonic() - t0
        ticks = self.ticks - ticks_before
        injected = self.injected_slots - injected_before
        # measured SCHEDULE bubble: the fraction of pipeline slots this
        # run left empty (fill + drain + any idle replica slots). For
        # K*M microbatches on one replica this is exactly
        # steady_bubble_fraction(K*M, S); it is tick-count-derived, so
        # deterministic (benchmarks gate on it, unlike wall-clock)
        slot_ticks = ticks * self.n_replicas
        bubble = 1.0 - injected / max(slot_ticks, 1)
        # per-request latency (submit -> last microbatch collected) for
        # the requests that COMPLETED during this run — the tail the
        # benchmark's p50/p99 gate watches
        lat = [self._req_done[r] - self._req_submit[r]
               for r in self._req_done if r not in done_before]
        metrics = {
            "request_latencies_s": lat,
            "images": int(n_imgs),
            "ticks": int(ticks),
            "injected_microbatches": int(injected),
            "images_per_s": n_imgs / max(elapsed, 1e-9),
            "elapsed_s": elapsed,
            "steady_bubble": bubble,
            "fill_bubble_single_batch": None,
            "n_stages": self.n_stages,
            "n_replicas": self.n_replicas,
        }
        if self.verbose:
            print(f"{self.cfg.name}: served {n_imgs} imgs in {ticks} "
                  f"ticks ({metrics['images_per_s']:.1f} im/s, steady "
                  f"bubble {bubble:.3f})")
        return metrics

    def results(self, req: int) -> np.ndarray:
        """(B, 1000) logits of a completed request. One-shot: the
        entry is evicted on delivery, so a long-running server's
        memory stays bounded by in-flight requests, not its history
        (a second call raises the unknown-request error)."""
        if req not in self._pending:
            raise KeyError(f"unknown request id {req}")
        if self._pending[req] != 0:
            raise ValueError(f"request {req} incomplete "
                             f"({self._pending[req]} microbatches "
                             "outstanding); call run() first")
        del self._pending[req]
        self._req_submit.pop(req, None)
        self._req_done.pop(req, None)
        return np.concatenate(self._results.pop(req), axis=0)

    # -- failure recovery (the tier's drain-and-respawn contract) ----------

    def recover_work(self):
        """Drain every undelivered microbatch after a failure, in
        submission order: emitted-but-uncollected first (its device
        value may be poisoned — recompute, don't trust it), then
        in-flight, staged, and queued. Internal (submit()) slots are
        re-queued here; external (enqueue()) slots are RETURNED as
        ``[(key, n_valid, padded_chunk)]`` for the tier to re-route
        onto a healthy replica. Pipeline tracking is cleared either
        way — after this the server is drained and ``respawn()`` makes
        it serve again."""
        drained = []
        if self._emitted is not None:
            slots, _ = self._emitted          # never read the output
            if slots is not None:
                drained.extend(s for s in slots if s is not None)
            self._emitted = None
        for slots in self._inflight:
            if slots is not None:
                drained.extend(s for s in slots if s is not None)
        self._inflight.clear()
        if self._staged is not None:
            slots, _ = self._staged
            drained.extend(s for s in slots if s is not None)
            self._staged = None
        drained.extend(self._queue)
        self._queue.clear()
        external = []
        for req, i, n_valid, chunk in drained:
            if req is _EXTERNAL:
                external.append((i, n_valid, chunk))
            else:
                self._queue.append((req, i, n_valid, chunk))
        return external

    def respawn(self) -> None:
        """Reset the pipeline after a failure: fresh zero state buffer
        (the donated one may hold poisoned partials), empty tracking.
        Queued work (anything recover_work() re-queued) survives; the
        compiled tick and placed params are reused as-is."""
        self._state = jnp.zeros(self._state_shape, jnp.float32)
        self._staged = None
        self._inflight.clear()
        self._emitted = None

    def purge(self, pred) -> int:
        """Drop queued EXTERNAL microbatches whose key matches
        ``pred`` (tier-side request shedding: timeout/deadline).
        Returns the number removed; in-flight slots are left to finish
        and dropped at delivery."""
        kept, n = deque(), 0
        for slot in self._queue:
            if slot[0] is _EXTERNAL and pred(slot[1]):
                n += 1
            else:
                kept.append(slot)
        self._queue = kept
        return n


def _serve_cnn_continuous(arch: str, *, n_requests: int = 4,
                          batch: int = 8, mb_size: int = 2,
                          n_stages: int = 4, n_replicas: int = 1,
                          image_size: int = 64, seed: int = 0,
                          placed=None, param_budget_frac=None,
                          auto_split: bool = False,
                          verbose: bool = True, tuning_cache=None,
                          calibrate: bool = False,
                          quantize: str = "native") -> dict:
    """Continuous-batching serving run: K back-to-back requests through
    one CNNPipelineServer (the pipeline never drains between them),
    returning the per-request logits plus throughput and the
    steady-state bubble — which beats the single-batch fill bubble
    (S-1)/(M+S-1) for K > 1 because one fill amortizes over the whole
    stream."""
    from repro.core import pipeline as pp
    srv = CNNPipelineServer(arch, mb_size=mb_size, n_stages=n_stages,
                            n_replicas=n_replicas, image_size=image_size,
                            seed=seed, placed=placed,
                            param_budget_frac=param_budget_frac,
                            auto_split=auto_split, verbose=False,
                            tuning_cache=tuning_cache, calibrate=calibrate,
                            quantize=quantize)
    # warm the jitted tick before the timed stream (compile would
    # otherwise swamp the measured im/s)
    warm = srv.submit(np.zeros((mb_size, image_size, image_size, 3),
                               np.float32))
    srv.run()
    srv.results(warm)
    key = jax.random.PRNGKey(seed + 1)
    reqs = []
    for _ in range(n_requests):
        key, sub = jax.random.split(key)
        imgs = jax.random.normal(sub, (batch, image_size, image_size, 3))
        reqs.append(srv.submit(np.asarray(imgs)))
    metrics = srv.run()
    m_per_req = -(-batch // mb_size)
    metrics["fill_bubble_single_batch"] = pp.bubble_fraction(
        m_per_req, srv.n_stages)
    metrics["logits"] = [srv.results(rq) for rq in reqs]
    lat = metrics.get("request_latencies_s") or []
    metrics["latency_p50_s"] = float(np.percentile(lat, 50)) if lat \
        else None
    metrics["latency_p99_s"] = float(np.percentile(lat, 99)) if lat \
        else None
    if verbose:
        print(f"{arch}: continuous {n_requests} x {batch} imgs: "
              f"{metrics['images_per_s']:.1f} im/s, steady bubble "
              f"{metrics['steady_bubble']:.3f} vs single-batch fill "
              f"{metrics['fill_bubble_single_batch']:.3f}, latency "
              f"p50 {metrics['latency_p50_s']:.3f}s / p99 "
              f"{metrics['latency_p99_s']:.3f}s")
    return metrics


def _serve_cnn_tier(arch: str, *, n_requests: int = 8, batch: int = 8,
                    mb_size: int = 2, n_stages: int = 4,
                    n_replicas: int = 2, image_size: int = 64,
                    seed: int = 0, fail_replica=None, fail_at_tick=None,
                    procs: int = 0, hosts: int = 0, listen=None,
                    kill_worker=None,
                    kill_at_tick: int = 1,
                    heartbeat_interval_s: float = 0.1,
                    suspect_after_s: float = 0.5,
                    dead_after_s: float = 10.0,
                    ledger_dir=None, quantize: str = "native",
                    verbose: bool = True) -> dict:
    """Fault-tolerant serving demo: K requests through a ServingTier
    of R pipeline replicas, optionally killing one mid-stream with a
    FailureInjector (``--fail-replica R --fail-at-tick T``) to watch
    drain-and-respawn keep every request's logits intact.

    ``procs > 0`` promotes the tier to OS-process replica workers
    (:class:`~repro.runtime.tier.ProcessServingTier`): real heartbeat
    liveness, crash-safe framed transport, and — with ``--kill-worker
    W`` — a genuine mid-tick ``SIGKILL`` of worker W at serving tick
    ``--kill-at-tick``, recovered bitwise by supervisor-side replay.

    ``hosts > 0`` goes one step further
    (:class:`~repro.runtime.tier.HostServingTier`): workers dial the
    supervisor over TCP (``--listen host:port``; default a loopback
    ephemeral port), handshake on a model fingerprint, and fetch the
    packed param blob by SHA-256 over the channel before warming up."""
    from repro.runtime.fault import FailureInjector
    from repro.runtime.tier import (HostServingTier, ProcessServingTier,
                                    ServingTier)
    if hosts > 0:
        hooks = {}
        if kill_worker is not None:
            hooks[kill_worker] = {"kill_at_tick": kill_at_tick}
        bind = ("127.0.0.1", 0)
        if listen:
            host, _, port = str(listen).rpartition(":")
            bind = (host or "127.0.0.1", int(port))
        tier = HostServingTier(
            arch, n_procs=hosts, listen=bind, n_stages=n_stages,
            mb_size=mb_size, image_size=image_size, seed=seed,
            worker_hooks=hooks,
            heartbeat_interval_s=heartbeat_interval_s,
            suspect_after_s=suspect_after_s, dead_after_s=dead_after_s,
            ledger_dir=ledger_dir, quantize=quantize, verbose=verbose)
    elif procs > 0:
        hooks = {}
        if kill_worker is not None:
            hooks[kill_worker] = {"kill_at_tick": kill_at_tick}
        tier = ProcessServingTier(
            arch, n_procs=procs, n_stages=n_stages, mb_size=mb_size,
            image_size=image_size, seed=seed, worker_hooks=hooks,
            heartbeat_interval_s=heartbeat_interval_s,
            suspect_after_s=suspect_after_s, dead_after_s=dead_after_s,
            ledger_dir=ledger_dir, quantize=quantize, verbose=verbose)
    else:
        injectors = {}
        if fail_replica is not None and fail_at_tick is not None:
            injectors[fail_replica] = FailureInjector(
                fail_at_steps=(fail_at_tick,))
        tier = ServingTier(arch, n_replicas=n_replicas,
                           n_stages=n_stages, mb_size=mb_size,
                           image_size=image_size, seed=seed,
                           injectors=injectors, quantize=quantize,
                           verbose=verbose)
    key = jax.random.PRNGKey(seed + 1)
    rids = []
    for _ in range(n_requests):
        key, sub = jax.random.split(key)
        imgs = jax.random.normal(sub, (batch, image_size, image_size, 3))
        rids.append(tier.submit(np.asarray(imgs)))
    try:
        metrics = tier.run()
        metrics["logits"] = [tier.results(r) for r in rids]
    finally:
        if procs > 0 or hosts > 0:
            tier.close()
    return metrics


# --- deprecated per-mode entry points (use serve(ServeConfig(...))) --------

def _serve_deprecated(old: str) -> None:
    warnings.warn(f"{old}() is deprecated; use "
                  "serve(ServeConfig(arch=..., ...)) — one config, one "
                  "dispatcher", DeprecationWarning, stacklevel=3)


def serve_cnn(arch: str, **kw):
    """Deprecated shim: ``serve(ServeConfig(arch, mode='throughput'))``."""
    _serve_deprecated("serve_cnn")
    return _serve_cnn(arch, **kw)


def serve_cnn_continuous(arch: str, **kw):
    """Deprecated shim:
    ``serve(ServeConfig(arch, continuous=True))``."""
    _serve_deprecated("serve_cnn_continuous")
    return _serve_cnn_continuous(arch, **kw)


def serve_cnn_tier(arch: str, **kw):
    """Deprecated shim: ``serve(ServeConfig(arch, tier=True))``."""
    _serve_deprecated("serve_cnn_tier")
    return _serve_cnn_tier(arch, **kw)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--microbatches", type=int, default=4,
                    help="microbatches per batch (0 = autotune the "
                         "width from the plan's stage costs)")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--image-size", type=int, default=64)
    ap.add_argument("--placed", action="store_true", default=None,
                    help="force per-stage weight placement (needs one "
                         "device per stage; default: auto)")
    ap.add_argument("--replicated-params", dest="placed",
                    action="store_false",
                    help="force replicated params")
    ap.add_argument("--param-budget-frac", type=float, default=None,
                    help="bound any stage's weight bytes to this "
                         "fraction of the model (memory-aware planner)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="replicate the whole pipeline across a data "
                         "mesh axis (stage x data 2-D scale-out; needs "
                         "stages*replicas devices for placement)")
    ap.add_argument("--auto-split", action="store_true",
                    help="let the (stages, replicas) co-planner pick "
                         "the split for the host's device count")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching serving loop: requests "
                         "stream through a never-draining pipeline")
    ap.add_argument("--requests", type=int, default=4,
                    help="continuous mode: back-to-back request count")
    ap.add_argument("--mb-size", type=int, default=2,
                    help="continuous mode: images per microbatch")
    ap.add_argument("--tier", action="store_true",
                    help="fault-tolerant serving tier: route requests "
                         "across --replicas pipeline replica workers "
                         "with drain-and-respawn recovery")
    ap.add_argument("--fail-replica", type=int, default=None,
                    help="tier mode: replica index to kill via "
                         "FailureInjector")
    ap.add_argument("--fail-at-tick", type=int, default=None,
                    help="tier mode: tick at which the injected "
                         "replica failure fires")
    ap.add_argument("--procs", type=int, default=0,
                    help="tier mode: serve through THIS many OS-"
                         "process replica workers (heartbeat "
                         "liveness + crash-safe transport) instead "
                         "of in-process replicas")
    ap.add_argument("--hosts", type=int, default=0,
                    help="tier mode: serve through THIS many TCP "
                         "dial-in replica workers (cross-host tier: "
                         "fingerprint handshake + blob-by-hash param "
                         "distribution) instead of socketpair workers")
    ap.add_argument("--listen", type=str, default=None,
                    metavar="HOST:PORT",
                    help="hosts mode: bind the worker listener here "
                         "(default 127.0.0.1 on an ephemeral port)")
    ap.add_argument("--dial", type=str, default=None,
                    metavar="HOST:PORT",
                    help="run as a cross-host WORKER instead of a "
                         "supervisor: dial this serve.py --hosts "
                         "listener and join its tier (pair with "
                         "--token/--blob-sha/--blob-cache)")
    ap.add_argument("--token", type=int, default=0,
                    help="--dial: worker slot token to register as")
    ap.add_argument("--blob-sha", type=str, default=None,
                    help="--dial: SHA-256 of the supervisor's packed "
                         "param blob (fetched over the channel and "
                         "verified before warmup)")
    ap.add_argument("--blob-cache", type=str, default=None,
                    help="--dial: content-addressed blob cache dir")
    ap.add_argument("--seed", type=int, default=0,
                    help="model-init seed (must match across the "
                         "supervisor and every --dial worker: it is "
                         "part of the handshake fingerprint)")
    ap.add_argument("--kill-worker", type=int, default=None,
                    help="procs mode: worker index that SIGKILLs "
                         "itself mid-tick (drain-and-respawn demo)")
    ap.add_argument("--kill-at-tick", type=int, default=1,
                    help="procs mode: serving tick at which "
                         "--kill-worker fires")
    ap.add_argument("--heartbeat-interval", type=float, default=0.1,
                    help="procs mode: worker heartbeat period (s)")
    ap.add_argument("--suspect-after", type=float, default=0.5,
                    help="procs mode: silence that flags a worker as "
                         "a straggler (s)")
    ap.add_argument("--dead-after", type=float, default=10.0,
                    help="procs mode: silence/stall that declares a "
                         "worker dead (s; must exceed 2x the "
                         "heartbeat interval)")
    ap.add_argument("--ledger-dir", type=str, default=None,
                    help="procs mode: persist the supervisor replay "
                         "ledger here (a restarted supervisor "
                         "resumes the stream)")
    ap.add_argument("--tuning-cache", type=str, default=None,
                    metavar="PATH",
                    help="plan stages from this profiled tuning cache "
                         "(model='measured'); missing file = cold cache "
                         "= analytic plan")
    ap.add_argument("--calibrate", action="store_true",
                    help="profile every fused node on the live device "
                         "first and write the results to --tuning-cache "
                         "(then plan from them)")
    ap.add_argument("--mode", choices=("latency", "throughput"),
                    default="throughput",
                    help="latency: batch-1 single-image serving, "
                         "measured p50/p99; throughput: the batched / "
                         "continuous / tiered pipelines")
    ap.add_argument("--quantize", choices=("native", "f32", "bf16",
                                           "int8"), default="native",
                    help="stored weight dtype (core/quant.py): int8 "
                         "packs per-channel-scaled codes into the "
                         "placed param rows")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.dial:
        # worker side of the cross-host tier: delegate to the worker
        # entry point with the model args this CLI already knows.
        from repro.runtime import worker as worker_mod
        wargv = ["--dial", args.dial, "--token", str(args.token),
                 "--arch", args.arch, "--stages", str(args.stages),
                 "--mb-size", str(args.mb_size),
                 "--image-size", str(args.image_size),
                 "--seed", str(args.seed), "--quantize", args.quantize,
                 "--heartbeat-interval", str(args.heartbeat_interval)]
        if args.blob_sha:
            wargv += ["--blob-sha", args.blob_sha]
        if args.blob_cache:
            wargv += ["--blob-cache", args.blob_cache]
        return worker_mod.main(wargv)
    if get_config(args.arch).family == "cnn":
        serve(ServeConfig(
            arch=args.arch, mode=args.mode, continuous=args.continuous,
            tier=args.tier, procs=args.procs,
            hosts=args.hosts, listen=args.listen,
            replicas=(max(args.replicas, 2)
                      if args.tier or args.procs or args.hosts
                      else args.replicas),
            quantize=args.quantize, batch=args.batch,
            n_requests=args.requests, n_microbatches=args.microbatches,
            mb_size=args.mb_size, n_stages=args.stages,
            image_size=args.image_size, seed=args.seed,
            placed=args.placed,
            param_budget_frac=args.param_budget_frac,
            auto_split=args.auto_split,
            fail_replica=args.fail_replica,
            fail_at_tick=args.fail_at_tick,
            kill_worker=args.kill_worker,
            kill_at_tick=args.kill_at_tick,
            heartbeat_interval_s=args.heartbeat_interval,
            suspect_after_s=args.suspect_after,
            dead_after_s=args.dead_after, ledger_dir=args.ledger_dir,
            tuning_cache=args.tuning_cache, calibrate=args.calibrate))
    else:
        serve_lm(args.arch, batch=args.batch, prompt_len=args.prompt_len,
                 gen_tokens=args.gen, use_reduced=args.reduced)


if __name__ == "__main__":
    main()
