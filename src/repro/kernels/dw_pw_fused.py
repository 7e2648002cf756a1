"""Fused depthwise -> pointwise (1x1) convolution — the MobileNet block
body as ONE memory pass.

HPIPE gives every layer its own hardware and streams activations
producer->consumer, so a MobileNet dw->pw pair never parks its
intermediate in DRAM: the depthwise unit's output line feeds the 1x1
conv's dot units directly. The unfused TPU mapping betrayed that with
four full-tensor HBM passes per block (dw read, dw write, pw read, pw
write); this kernel restores the paper's dataflow with one read and one
write — the depthwise intermediate lives only as a VMEM line slab
feeding the MXU matmul.

TPU mapping (mirrors kernels/sparse_conv.py):

- line buffer -> one padded input row (1, 1, Wp, C) resident in VMEM;
  the ky shift is folded into the HBM row address by the index map
  (H-block size 1 => absolute row), the kx shift is a strided window
  read of the row;
- depthwise unit -> f32 (Wo, C) VPU accumulator revisited across the k
  innermost grid steps (shifted multiply-accumulate, no channel
  reduction);
- dw->pw handoff -> at ky = k-1 the accumulated line gets bias+ReLU,
  rounds to the activation dtype (the same bf16 boundary the unfused
  graph has, so fused == unfused to accumulation rounding) and
  immediately enters the (C, Cout) MXU matmul — the (N, Ho, Wo, C)
  depthwise tensor never exists in HBM;
- epilogue -> pw bias, optional fused residual line (core/fusion.py
  folds MobileNet-V2's linear-bottleneck add in here) and optional ReLU
  are applied before the single output write.

Grid: (N, Ho, k); k innermost so the (Wo, C) depthwise accumulator and
the (Wo, Cout) output line stay resident while the k input rows stream
through.

The XLA twin (``dw_pw_xla``) keeps the same no-HBM-intermediate
contract (DESIGN.md §2): it scans over row chunks, running the
depthwise on a (N, rows+halo, Wp, C) slab and feeding the chunk
straight into the pointwise matmul — the full-height depthwise tensor
never appears in the program (tests/test_fusion.py scans the jaxpr).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.depthwise_conv import LANES, shifted_row_mac
from repro.kernels.sparse_conv import pad_same_hw


def _kernel(x_ref, dww_ref, dwb_ref, pww_ref, pwb_ref, *rest,
            k: int, wo: int, stride: int, dw_relu: bool, relu: bool,
            has_res: bool, has_scale: bool, out_dtype):
    scale_ref = None
    if has_scale:
        scale_ref, rest = rest[0], rest[1:]
    if has_res:
        res_ref, o_ref, acc_ref, row_ref = rest
    else:
        o_ref, acc_ref, row_ref = rest
    ky = pl.program_id(2)

    @pl.when(ky == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    shifted_row_mac(x_ref, row_ref, acc_ref, dww_ref[ky], k, wo, stride)

    @pl.when(ky == k - 1)
    def _flush():
        d = acc_ref[...] + dwb_ref[...].astype(jnp.float32)     # (wo, c)
        if dw_relu:
            d = jnp.maximum(d, 0.0)
        # the dw->pw boundary rounds to the activation dtype exactly as
        # the unfused graph's node boundary does — but in VMEM, not HBM
        d = d.astype(out_dtype)
        y = jnp.dot(d.astype(jnp.float32), pww_ref[...].astype(jnp.float32),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
        if has_scale:
            # int8 pw: y holds the raw code dot — the per-channel scale
            # re-reals it before the (real-valued) bias joins
            y = y * scale_ref[...].astype(jnp.float32)
        y = y + pwb_ref[...].astype(jnp.float32)                # (wo, co)
        if has_res:
            y = y + res_ref[0, 0].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("stride", "dw_relu", "relu",
                                             "interpret"))
def dw_pw_pallas(x: jax.Array, dw_w: jax.Array, dw_b: jax.Array,
                 pw_w: jax.Array, pw_b: jax.Array,
                 residual: jax.Array = None,
                 pw_scale: jax.Array = None, *, stride: int = 1,
                 dw_relu: bool = True, relu: bool = True,
                 interpret: bool) -> jax.Array:
    """x: (N, H, W, C); dw_w: (k, k, C); dw_b: (C,); pw_w: (C, Cout);
    pw_b: (Cout,); residual: optional (N, Ho, Wo, Cout) fused skip.
    ``pw_scale`` (optional, (Cout,) f32) marks ``pw_w`` as int8 codes
    (core/quant.py): the MXU dot is unchanged and the scale multiplies
    its output at the flush, before pw bias. SAME padding on the
    depthwise. Returns (N, Ho, Wo, Cout). ``interpret`` runs the kernel
    body in the Pallas interpreter (CPU) instead of Mosaic (TPU);
    ``kernels.ops`` picks it from the platform."""
    n, h, w, c = x.shape
    k = dw_w.shape[0]
    co = pw_w.shape[-1]
    xp, ho, wo = pad_same_hw(x, k, stride)
    wp = xp.shape[2]

    has_res = residual is not None
    has_scale = pw_scale is not None
    kernel = functools.partial(_kernel, k=k, wo=wo, stride=stride,
                               dw_relu=dw_relu, relu=relu, has_res=has_res,
                               has_scale=has_scale, out_dtype=x.dtype)
    in_specs = [
        pl.BlockSpec((1, 1, wp, c),
                     lambda i, oy, ky: (i, oy * stride + ky, 0, 0)),
        pl.BlockSpec((k, k, c), lambda i, oy, ky: (0, 0, 0)),
        pl.BlockSpec((1, c), lambda i, oy, ky: (0, 0)),
        pl.BlockSpec((c, co), lambda i, oy, ky: (0, 0)),
        pl.BlockSpec((1, co), lambda i, oy, ky: (0, 0)),
    ]
    operands = [xp, dw_w, dw_b.reshape(1, c), pw_w, pw_b.reshape(1, co)]
    if has_scale:
        # per-channel scale rides the pw-bias layout: one (1, co) line
        in_specs.append(pl.BlockSpec((1, co), lambda i, oy, ky: (0, 0)))
        operands.append(pw_scale.reshape(1, co))
    if has_res:
        in_specs.append(pl.BlockSpec((1, 1, wo, co),
                                     lambda i, oy, ky: (i, oy, 0, 0)))
        operands.append(residual)
    return pl.pallas_call(
        kernel,
        grid=(n, ho, k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, wo, co),
                               lambda i, oy, ky: (i, oy, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, ho, wo, co), x.dtype),
        scratch_shapes=[pltpu.VMEM((wo, c), jnp.float32),
                        pltpu.VMEM((wp, min(c, LANES)), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dw_pw",
    )(*operands)


def _row_chunk(ho: int, cap: int = 16) -> int:
    """Largest divisor of ho <= cap (output rows per XLA-twin chunk)."""
    for d in range(min(ho, cap), 0, -1):
        if ho % d == 0:
            return d
    return 1


def dw_pw_xla(x: jax.Array, dw_w: jax.Array, dw_b: jax.Array,
              pw_w: jax.Array, pw_b: jax.Array,
              residual: jax.Array = None, *, stride: int = 1,
              dw_relu: bool = True, relu: bool = True,
              row_chunk: int = 0, pw_scale: jax.Array = None) -> jax.Array:
    """Pure-JAX twin: scan over output-row chunks; each chunk runs the
    depthwise on its (rows + halo) input slab and feeds the result
    straight into the pointwise matmul. Working set = one chunk; the
    full-height depthwise intermediate never materializes. Shards
    cleanly under GSPMD (slices + matmuls only, batch dim untouched).
    ``row_chunk`` caps the chunk height (0 = the default 16); the
    autotuner (core/tuning.py) searches it — numerics are identical at
    any cap, only the working-set/step-count tradeoff moves."""
    n, h, w, c = x.shape
    k = dw_w.shape[0]
    co = pw_w.shape[-1]
    xp, ho, wo = pad_same_hw(x, k, stride)
    hb = _row_chunk(ho, cap=row_chunk or 16)
    rows_in = (hb - 1) * stride + k       # input rows per chunk (with halo)

    from repro.models.layers import fdot

    def chunk(carry, r0):
        sl = lax.dynamic_slice(
            xp, (0, r0 * stride, 0, 0),
            (n, rows_in, xp.shape[2], c))                   # (n, rows, wp, c)
        # depthwise as k^2 shifted multiply-accumulates in f32 — the
        # same dataflow as the Pallas kernel body (and the paper's
        # shift unit); XLA:CPU's grouped conv would execute channel-
        # by-channel here and dominate the whole block
        acc = jnp.zeros((n, hb, wo, c), jnp.float32)
        for ky in range(k):
            for kx in range(k):
                win = lax.slice(
                    sl, (0, ky, kx, 0),
                    (n, ky + (hb - 1) * stride + 1,
                     kx + (wo - 1) * stride + 1, c),
                    (1, stride, stride, 1))                 # (n, hb, wo, c)
                acc = acc + win.astype(jnp.float32) * \
                    dw_w[ky, kx].astype(jnp.float32)
        d = acc + dw_b.astype(jnp.float32)
        if dw_relu:
            d = jax.nn.relu(d)
        d = d.astype(x.dtype)                 # the dw->pw boundary round
        y = fdot("nhwc,co->nhwo", d, pw_w)
        if pw_scale is not None:              # int8 pw: re-real the code dot
            y = y * pw_scale.astype(y.dtype)
        y = y + pw_b.astype(y.dtype)
        if residual is not None:
            res = lax.dynamic_slice(residual, (0, r0, 0, 0),
                                    (n, hb, wo, co))
            y = y + res.astype(y.dtype)
        if relu:
            y = jax.nn.relu(y)
        return carry, y.astype(x.dtype)

    _, ys = lax.scan(chunk, None, jnp.arange(0, ho, hb))    # (L, n, hb, wo, co)
    return jnp.moveaxis(ys, 0, 1).reshape(n, ho, wo, co)


def dw_pw_ref(x, dw_w, dw_b, pw_w, pw_b, residual=None, *, stride=1,
              dw_relu=True, relu=True):
    """Unfused oracle: depthwise_conv_ref -> bias/relu -> 1x1 matmul."""
    from repro.kernels.depthwise_conv import depthwise_conv_ref
    d = depthwise_conv_ref(x, dw_w, stride=stride)
    d = d + dw_b
    if dw_relu:
        d = jax.nn.relu(d)
    d = d.astype(x.dtype)
    y = jnp.einsum("nhwc,co->nhwo", d.astype(jnp.float32),
                   pw_w.astype(jnp.float32))
    y = y + pw_b.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if relu:
        y = jax.nn.relu(y)
    return y.astype(x.dtype)
