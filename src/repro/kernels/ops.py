"""jit'd dispatch wrappers for the kernels.

Two execution paths per op:
- ``xla``: pure-JAX formulation with the same zero-block skipping
  semantics; shards cleanly under pjit/GSPMD, runs on every backend,
  and is the reference twin the Pallas path is checked against.
- ``pallas``: the HPIPE kernels (kernels/*.py).

The path is chosen by platform (DESIGN.md §2): Mosaic-compiled Pallas
on ``tpu``, the XLA twins on ``cpu``. Any other platform raises. The
``REPRO_KERNEL_IMPL`` environment variable, ``set_impl`` and
``config(impl=)`` override the choice (tests drive the Pallas path on
the CPU that way, and the chip smoke runs the XLA twin on the TPU as
its reference); an unknown value raises. The Pallas kernels run in
the interpreter exactly when the platform is ``cpu``.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

IMPLS = ("xla", "pallas")

#: platform -> (default impl, whether Pallas kernels run interpreted)
_PLATFORMS = {"tpu": ("pallas", False), "cpu": ("xla", True)}


def _check_impl(impl):
    if impl is not None and impl not in IMPLS:
        raise ValueError(f"kernel impl {impl!r}: expected one of {IMPLS}")
    return impl


# explicit override of the platform's choice (None: choose by platform)
_IMPL: Optional[str] = _check_impl(os.environ.get("REPRO_KERNEL_IMPL"))

# int8 execution strategy for quantized weights (core/quant.py). True:
# the codes feed the matmul unmodified (int8 MXU operands, f32
# accumulate) and the per-channel scale multiplies the ACCUMULATOR at
# the epilogue — one multiply per output channel instead of one per
# weight element. False: dequantize at op entry (the reference path the
# fast path is tested against).
_INT8_FAST = True


def _platform() -> tuple[str, bool]:
    p = jax.default_backend()
    if p not in _PLATFORMS:
        raise RuntimeError(f"no kernel path for platform {p!r}: expected "
                           f"one of {sorted(_PLATFORMS)}")
    return _PLATFORMS[p]


def impl() -> str:
    """The kernel path ops dispatch to: the override if one is set,
    else the platform's default. Read at TRACE time."""
    return _IMPL or _platform()[0]


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter (cpu) rather than
    compiled by Mosaic (tpu)."""
    return _platform()[1]


class _ImplGuard:
    """Returned by :func:`set_impl`; restores the previous impl on
    ``__exit__`` so tests can scope the global dispatch:

        with set_impl("pallas"):
            ...   # pallas path
        # previous impl restored
    """

    def __init__(self, prev: str):
        self._prev = prev

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        global _IMPL
        _IMPL = self._prev
        return False


def set_impl(impl: str) -> _ImplGuard:
    """Set the kernel dispatch path. Usable bare (``set_impl("xla")``)
    or as a context manager that restores the prior impl on exit."""
    global _IMPL
    prev = _IMPL
    _IMPL = _check_impl(impl)
    return _ImplGuard(prev)


def set_tuning_cache(cache):
    """Install a :class:`repro.core.tuning.TuningCache` consulted by
    the dispatchers below for autotuned kernel knobs (block_c,
    row_chunk). Knobs are read at TRACE time, so set the cache before
    compiling. Returns a context-manager guard; ``None`` clears."""
    from repro.core import tuning
    return tuning.set_tuning_cache(cache)


@contextlib.contextmanager
def config(*, impl: Optional[str] = None, tuning_cache=None,
           int8_fast_path: Optional[bool] = None):
    """ONE scoped override for the kernel-dispatch knobs that used to
    be three separate globals threaded ad hoc (``set_impl``,
    ``set_tuning_cache``, and the int8 strategy):

        with kernels.config(impl="pallas", tuning_cache=cache,
                            int8_fast_path=False):
            ...   # all three scoped together

    ``None`` leaves a knob untouched. Knobs are read at TRACE time —
    enter the context before compiling. Restores every previous value
    on exit (exceptions included)."""
    global _IMPL, _INT8_FAST
    prev_impl, prev_fast = _IMPL, _INT8_FAST
    cache_guard = None
    try:
        if impl is not None:
            _IMPL = _check_impl(impl)
        if int8_fast_path is not None:
            _INT8_FAST = bool(int8_fast_path)
        if tuning_cache is not None:
            from repro.core import tuning
            cache_guard = tuning.set_tuning_cache(tuning_cache)
        yield
    finally:
        _IMPL, _INT8_FAST = prev_impl, prev_fast
        if cache_guard is not None:
            cache_guard.__exit__(None, None, None)


def _knob(op: str, in_shape, dtype, name: str, default, **fields):
    """Autotuned-knob lookup against the active tuning cache (identity
    default when no cache is installed — today's hard-coded behavior)."""
    from repro.core import tuning
    cache = tuning.current_tuning_cache()
    if cache is None:
        return default
    key = tuning.kernel_key(op, in_shape, dtype, **fields)
    return cache.knob(key, name, default)


def sparse_matmul(x: jax.Array, sw) -> jax.Array:
    """x: (..., d_in) @ block-balanced SparseWeight -> (..., d_out).

    int8-quantized ``sw`` (vals = codes + per-output-channel scale):
    the codes feed the same matmul (upcast like bf16 would be — int8
    MXU operands, f32 accumulate) and the scale multiplies the
    accumulator once per output channel at the end."""
    if sw.scale is not None and not _INT8_FAST:
        sw = sw.dequantized()           # reference path: dequant at entry
    scale = sw.scale                    # (ob, bn) f32 or None
    *lead, d_in = x.shape
    ob, n_k, bm, bn = sw.vals.shape
    if impl() == "pallas":
        xm = x.reshape(-1, d_in)
        from repro.kernels.sparse_matmul import sparse_matmul_pallas
        out = sparse_matmul_pallas(xm, sw.vals, sw.idx,
                                   interpret=interpret_mode())
        if scale is not None:
            # no fused bias in this kernel, so the epilogue scale is
            # safe outside it: out still carries the raw code dot
            out = (out.astype(jnp.float32)
                   * scale.reshape(-1)).astype(out.dtype)
        return out.reshape(*lead, ob * bn)

    # XLA path: lax.scan over the K surviving blocks per output column.
    # Each step gathers one input block per output column (working set ==
    # output size, never the KxM blowup a naive take would produce) and
    # accumulates in f32 — gather-not-scatter, as in the paper.
    xb = x.reshape(-1, d_in // bm, bm)
    t = xb.shape[0]

    def step(acc, inp):
        idx_k, vals_k = inp                      # (ob,), (ob, bm, bn)
        xg = jnp.take(xb, idx_k, axis=1)         # (t, ob, bm)
        # bf16 inputs + f32 accumulation via preferred_element_type: an
        # explicit astype would be hoisted out of the layer scan by XLA
        # and materialize an f32 copy of the whole weight stack.
        from repro.models.layers import fdot
        acc = acc + fdot("tjb,jbn->tjn", xg, vals_k)
        return acc, None

    from repro.models.layers import accum_dtype as _ad
    acc0 = jnp.zeros((t, ob, bn), _ad() or x.dtype)
    acc, _ = lax.scan(step, acc0,
                      (sw.idx.swapaxes(0, 1), sw.vals.swapaxes(0, 1)))
    if scale is not None:
        acc = acc * scale.astype(acc.dtype)     # (t, ob, bn) * (ob, bn)
    return acc.reshape(*lead, ob * bn).astype(x.dtype)


def sparse_conv(x, sw, bias, *, k: int, stride: int = 1,
                relu: bool = True, residual=None) -> jax.Array:
    """Fused implicit-GEMM block-sparse conv (HPIPE conv unit).

    x: (N, H, W, C) NHWC; sw: block-balanced SparseWeight over the
    HWIO-flattened (k*k*C, Cout) matrix (block rows must divide C);
    bias: (Cout,). SAME padding, fused bias + optional ReLU epilogue.
    ``residual`` (optional, (N, Ho, Wo, Cout)): fused skip tensor added
    before the activation — the graph fusion pass (core/fusion.py)
    folds ResNet's ``c3 -> add -> relu`` tail in here so the pre-add
    conv output never round-trips HBM. Neither path materializes the
    (N*Ho*Wo, k*k*C) im2col tensor.

    int8-quantized ``sw``: the codes accumulate exactly like float
    vals would, and the per-output-channel scale multiplies the
    accumulator in the epilogue BEFORE bias/residual/ReLU (those are
    real-valued terms; only the code dot is scaled).
    """
    if sw.scale is not None and not _INT8_FAST:
        sw = sw.dequantized()           # reference path: dequant at entry
    scale = sw.scale                    # (ob, bn) f32 or None
    n, h, w, c = x.shape
    ob, _, bm, bn = sw.vals.shape
    assert sw.d_in == k * k * c, (sw.d_in, k, c)
    assert c % bm == 0, (c, bm)
    if impl() == "pallas":
        from repro.kernels.sparse_conv import sparse_conv_pallas
        return sparse_conv_pallas(x, sw.vals, sw.idx, bias, residual,
                                  scale, k=k, stride=stride, relu=relu,
                                  interpret=interpret_mode())

    # XLA path: lax.scan over the K surviving blocks per output column.
    # Each step gathers one shifted (ky, kx) window slice of the
    # UNEXPANDED activation per output column (working set == output
    # size x bm/bn, never the k^2 im2col blowup) and accumulates in f32
    # — gather-not-scatter, same semantics as the Pallas index map, so
    # it shards cleanly under pjit/GSPMD and runs on the CPU dry-run.
    from repro.kernels.sparse_conv import conv_block_coords, same_pads
    ho, ph_lo, ph_hi = same_pads(h, k, stride)
    wo, pw_lo, pw_hi = same_pads(w, k, stride)
    xp = jnp.pad(x, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    ky, kx, cb = conv_block_coords(sw.idx.astype(jnp.int32), k, c, bm)
    sh, sw_ = (ho - 1) * stride + 1, (wo - 1) * stride + 1

    def step(acc, inp):
        ky_l, kx_l, cb_l, vals_l = inp           # (ob,) x3, (ob, bm, bn)

        def gather(ky1, kx1, cb1):
            sl = lax.dynamic_slice(xp, (0, ky1, kx1, cb1 * bm),
                                   (n, sh, sw_, bm))
            return sl[:, ::stride, ::stride]     # (N, Ho, Wo, bm)

        a = jax.vmap(gather)(ky_l, kx_l, cb_l)   # (ob, N, Ho, Wo, bm)
        from repro.models.layers import fdot
        return acc + fdot("jnhwm,jmo->nhwjo", a, vals_l), None

    from repro.models.layers import accum_dtype as _ad
    ad = _ad() or x.dtype
    if residual is None or scale is not None:
        # int8 can't pre-seed: the scale must multiply ONLY the code
        # accumulation, so bias/residual join after the scan instead
        acc0 = jnp.zeros((n, ho, wo, ob, bn), ad)
    else:
        # fused residual epilogue: seed the accumulator with skip + bias
        # so no full-tensor add follows the scan (the jaxpr regression
        # in tests/test_fusion.py checks this)
        acc0 = residual.astype(ad).reshape(n, ho, wo, ob, bn) \
            + bias.astype(ad).reshape(ob, bn)
    acc, _ = lax.scan(step, acc0,
                      (ky.T, kx.T, cb.T, sw.vals.swapaxes(0, 1)))
    if scale is not None:
        acc = acc * scale.astype(acc.dtype)     # (..., ob, bn) * (ob, bn)
    y = acc.reshape(n, ho, wo, ob * bn)
    if residual is None:
        y = y + bias.astype(acc.dtype)
    elif scale is not None:
        y = y + bias.astype(acc.dtype) + residual.astype(acc.dtype)
    if relu:
        y = jax.nn.relu(y)
    return y.astype(x.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, q_offset=0):
    """Dispatch: Pallas flash kernel (TPU target) or blockwise XLA."""
    if impl() == "pallas":
        from repro.kernels.flash_attention import flash_attention_pallas
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset,
                                      interpret=interpret_mode())
    from repro.models.layers import blockwise_attention
    return blockwise_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset)


def depthwise_conv(x, w, *, stride: int = 1):
    """NHWC depthwise conv dispatch (HPIPE's DepthwiseConv2D unit)."""
    if impl() == "pallas":
        from repro.kernels.depthwise_conv import depthwise_conv_pallas
        # block_c=0: the kernel clamps the channel tile to its VMEM
        # budget (the 112x112 MobileNet layers used to overflow at 128);
        # an autotuned cache overrides within the same budget lattice
        tc = _knob("dw", x.shape, x.dtype, "block_c", 0,
                   k=w.shape[0], s=stride)
        if tc and x.shape[-1] % tc:     # stale cache entry: C changed
            tc = 0
        return depthwise_conv_pallas(x, w, stride=stride, block_c=tc,
                                     interpret=interpret_mode())
    from repro.kernels.depthwise_conv import depthwise_conv_ref
    return depthwise_conv_ref(x, w, stride=stride)


def dw_pw_conv(x, dw_w, dw_b, pw_w, pw_b, *, stride: int = 1,
               dw_relu: bool = True, relu: bool = True, residual=None):
    """Fused depthwise -> pointwise MobileNet block body (graph fusion
    pass, core/fusion.py): one HBM read and one write — the depthwise
    intermediate lives only in VMEM on both paths (DESIGN.md §5).

    x: (N, H, W, C); dw_w: (k, k, C); dw_b: (C,); pw_w: (C, Cout) dense
    2D (or a :class:`~repro.core.quant.QuantizedWeight` — int8 codes
    feed the MXU dot and the per-channel scale joins the flush
    epilogue); pw_b: (Cout,); residual: optional fused (N, Ho, Wo,
    Cout) skip. A quantized dw_w dequantizes at entry: the depthwise
    runs on the VPU as per-channel MAC chains, where there is no
    wide-accumulator epilogue to factor a scale out to.
    """
    from repro.core.quant import QuantizedWeight
    if isinstance(dw_w, QuantizedWeight):
        dw_w = dw_w.dequant()
    pw_scale = None
    if isinstance(pw_w, QuantizedWeight):
        if _INT8_FAST:
            pw_scale, pw_w = pw_w.scale, pw_w.codes      # (Cout,) f32
        else:
            pw_w = pw_w.dequant()       # reference path: dequant at entry
    if impl() == "pallas":
        from repro.kernels.dw_pw_fused import dw_pw_pallas
        return dw_pw_pallas(x, dw_w, dw_b, pw_w, pw_b, residual, pw_scale,
                            stride=stride, dw_relu=dw_relu, relu=relu,
                            interpret=interpret_mode())
    from repro.kernels.dw_pw_fused import dw_pw_xla
    hb = _knob("dwpw", x.shape, x.dtype, "row_chunk", 0,
               k=dw_w.shape[1], s=stride, co=pw_w.shape[-1])
    return dw_pw_xla(x, dw_w, dw_b, pw_w, pw_b, residual,
                     stride=stride, dw_relu=dw_relu, relu=relu,
                     row_chunk=hb, pw_scale=pw_scale)
