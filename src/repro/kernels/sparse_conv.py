"""Fused implicit-GEMM block-sparse convolution — the HPIPE conv unit
without the im2col materialization.

The FPGA decodes each layer's runlength weight stream into gather
addresses against a *line buffer* of the unexpanded activation: a 3x3
conv never writes a 9x-duplicated patch tensor anywhere. The TPU
mapping of that dataflow:

- runlength stream -> scalar-prefetched ``(ky, kx, cb)`` coordinate
  arrays (one triple per surviving weight block): the BlockSpec
  ``index_map`` reads them to choose which *input row* and channel
  block of the activation to DMA into VMEM — the patch gather happens
  in the memory system, per grid step, and the im2col tensor never
  exists in HBM;
- line buffer -> one padded input row (Wp, bm) resident in VMEM; the
  ky shift is folded into the HBM row address by the index map, the kx
  shift (and the stride) is a strided window read of the row;
- DSP accumulator chain -> f32 VMEM scratch revisited across the K
  innermost grid steps, with the bias + ReLU epilogue fused into the
  flush so the elementwise follow-ups never round-trip HBM either.

Layout: the kernel sees the activation channel-block-major, (N, C/bm,
Hp, Wp, bm), and writes its output the same way, (N, Cout/bn, Ho, Wo,
bn): every block's last two dims are then whole array dims, which is
what Mosaic's (8, 128) tiling rule asks of a 32-wide sparsity block.
The wrapper converts from and to NHWC.

Weight layout: the 2D conv weight is (k*k*cin, cout) with rows in
HWIO order — row f = (ky*k + kx)*cin + c — pruned block-balanced by
``repro.core.sparsity.to_block_balanced``. The block-row size ``bm``
must divide ``cin`` so every surviving block maps to exactly one
(ky, kx, channel-block) gather.

Grid: (N, Ho, out_blocks, K); K innermost so the (Wo, bn) output line
stays resident while its K gathered input rows stream through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def conv_block_coords(idx, k: int, cin: int, bm: int):
    """Decompose flat HWIO block ids -> (ky, kx, cb) gather coordinates.

    idx: (ob, K) ints in [0, k*k*cin/bm). Works on numpy and jax arrays
    (used by both the kernels and the planner's cost model).
    """
    cpb = cin // bm                      # channel blocks per kernel position
    pos = idx // cpb
    return pos // k, pos % k, idx % cpb


def same_pads(size: int, k: int, stride: int) -> tuple[int, int, int]:
    """(out_size, pad_lo, pad_hi) matching lax SAME padding."""
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return out, total // 2, total - total // 2


def pad_same_hw(x, k: int, stride: int):
    """SAME-pad the H/W axes of NHWC ``x``; returns (xp, ho, wo)."""
    n, h, w, _ = x.shape
    ho, ph_lo, ph_hi = same_pads(h, k, stride)
    wo, pw_lo, pw_hi = same_pads(w, k, stride)
    xp = jnp.pad(x, ((0, 0), (ph_lo, ph_hi), (pw_lo, pw_hi), (0, 0)))
    return xp, ho, wo


def row_window(row_ref, start, wo: int, stride: int):
    """The (wo, C) window of a resident f32 input row at column
    ``start`` (static or dynamic), every ``stride``-th column. Mosaic
    reads strided and dynamically offset windows only from 32-bit
    refs, so callers stage the row in f32 VMEM first."""
    if stride == 1:
        return row_ref[pl.ds(start, wo), :]
    return row_ref[pl.ds(start, wo, stride=stride), :]


def _kernel(ky_ref, kx_ref, cb_ref, *refs,
            n_k: int, n_steps: int, wo: int, stride: int, relu: bool,
            has_res: bool, has_scale: bool, block_k: int):
    x_refs = refs[:block_k]
    vals_ref, b_ref = refs[block_k], refs[block_k + 1]
    rest = refs[block_k + 2:]
    scale_ref = None
    if has_scale:
        scale_ref, rest = rest[0], rest[1:]
    if has_res:
        res_ref, o_ref, acc_ref, row_ref = rest
    else:
        o_ref, acc_ref, row_ref = rest
    j = pl.program_id(2)
    l = pl.program_id(3)

    @pl.when(l == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # kx shift: strided window of the resident input row. The ky/cb
    # part of the gather already happened in the index_map (the DMA
    # fetched the right HBM row/channel block). With a K-tile
    # (block_k > 1, the autotuner's knob) each grid step holds block_k
    # gathered rows and retires block_k weight blocks into the same
    # resident accumulator line — fewer grid steps, same arithmetic.
    for t in range(block_k):
        kx = kx_ref[j * n_k + l * block_k + t]
        row_ref[...] = x_refs[t][0, 0, 0].astype(jnp.float32)   # (wp, bm)
        win = row_window(row_ref, kx, wo, stride)               # (wo, bm)
        acc_ref[...] += jnp.dot(
            win, vals_ref[0, t].astype(jnp.float32),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)

    @pl.when(l == n_steps - 1)
    def _flush():
        y = acc_ref[...]                                        # (wo, bn)
        if has_scale:
            # int8 epilogue: the accumulator holds the raw CODE dot —
            # the per-output-channel scale re-reals it before the
            # (real-valued) bias/residual join
            y = y * scale_ref[0].astype(jnp.float32)
        y = y + b_ref[0].astype(jnp.float32)
        if has_res:
            # fused residual epilogue (core/fusion.py R2): the skip
            # tensor's (wo, bn) line is gathered here, at the flush —
            # the pre-add conv output never exists in HBM
            y = y + res_ref[0, 0, 0].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[0, 0, 0] = y.astype(o_ref.dtype)


def to_blocks(x, b: int):
    """(N, H, W, C) -> channel-block-major (N, C/b, H, W, b)."""
    n, h, w, c = x.shape
    return x.reshape(n, h, w, c // b, b).transpose(0, 3, 1, 2, 4)


def from_blocks(x):
    """(N, C/b, H, W, b) -> (N, H, W, C)."""
    n, nb, h, w, b = x.shape
    return x.transpose(0, 2, 3, 1, 4).reshape(n, h, w, nb * b)


@functools.partial(jax.jit, static_argnames=("k", "stride", "relu",
                                             "block_k", "interpret"))
def sparse_conv_pallas(x: jax.Array, vals: jax.Array, idx: jax.Array,
                       bias: jax.Array, residual: jax.Array = None,
                       scale: jax.Array = None, *,
                       k: int, stride: int = 1, relu: bool = True,
                       block_k: int = 1, interpret: bool) -> jax.Array:
    """y[n, oy, ox, j*bn:+bn] = act(sum_l win(x; ky,kx,cb)[oy,ox] @ vals[j,l] + b).

    x: (N, H, W, C) NHWC; vals: (ob, K, bm, bn); idx: (ob, K) int32 flat
    HWIO block ids; bias: (ob*bn,). SAME padding. ``residual``
    (optional, (N, Ho, Wo, ob*bn)) is a fused skip tensor added in the
    K-1 flush epilogue before the activation (core/fusion.py residual
    rule). ``scale`` (optional, (ob, bn) f32) marks ``vals`` as int8
    codes (core/quant.py): the accumulate is unchanged (the MXU dot
    upcasts codes the way it upcasts bf16) and the scale multiplies the
    accumulator at the flush, before bias/residual. ``block_k``
    (autotuned, must divide K) is the K-tile: how many weight blocks
    each grid step gathers and accumulates — identical numerics at any
    value, fewer grid steps at larger ones. ``interpret`` runs the
    kernel body in the Pallas interpreter (CPU) instead of Mosaic (TPU);
    ``kernels.ops`` picks it from the platform.
    """
    n, h, w, c = x.shape
    ob, n_k, bm, bn = vals.shape
    assert c % bm == 0, (c, bm)
    bk = max(block_k, 1)
    assert n_k % bk == 0, (n_k, bk)
    xp, ho, wo = pad_same_hw(x, k, stride)
    wp = xp.shape[2]
    # flat (ob*K,) coordinate streams: 1-D scalar-prefetch arrays keep
    # SMEM padding to a minimum
    ky, kx, cb = (a.reshape(-1) for a in
                  conv_block_coords(idx.astype(jnp.int32), k, c, bm))

    n_steps = n_k // bk
    grid = (n, ho, ob, n_steps)
    has_res = residual is not None
    has_scale = scale is not None
    kernel = functools.partial(_kernel, n_k=n_k, n_steps=n_steps, wo=wo,
                               stride=stride, relu=relu, has_res=has_res,
                               has_scale=has_scale, block_k=bk)
    in_specs = [
        # H-block size 1 => the index map's H coordinate is an
        # absolute row: oy*stride + ky is the implicit-GEMM
        # gather, computed from the prefetched stream. One spec per
        # K-tile entry: step l DMAs the bk rows its weight blocks read.
        pl.BlockSpec(
            (1, 1, 1, wp, bm),
            lambda i, oy, j, l, ky, kx, cb, _t=t:
                (i, cb[j * n_k + l * bk + _t],
                 oy * stride + ky[j * n_k + l * bk + _t], 0, 0))
        for t in range(bk)
    ] + [
        pl.BlockSpec((1, bk, bm, bn),
                     lambda i, oy, j, l, ky, kx, cb: (j, l, 0, 0)),
        pl.BlockSpec((1, 1, bn),
                     lambda i, oy, j, l, ky, kx, cb: (j, 0, 0)),
    ]
    operands = [ky, kx, cb] + [to_blocks(xp, bm)] * bk + \
        [vals, bias.reshape(ob, 1, bn)]
    if has_scale:
        # per-output-channel scale rides the bias layout: one (1, bn)
        # line per output block, read at the flush
        in_specs.append(pl.BlockSpec(
            (1, 1, bn), lambda i, oy, j, l, ky, kx, cb: (j, 0, 0)))
        operands.append(scale.reshape(ob, 1, bn))
    line = pl.BlockSpec((1, 1, 1, wo, bn),
                        lambda i, oy, j, l, ky, kx, cb: (i, j, oy, 0, 0))
    if has_res:
        # skip line DMA'd only for the flush step's output block
        in_specs.append(line)
        operands.append(to_blocks(residual, bn))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=in_specs,
            out_specs=line,
            scratch_shapes=[pltpu.VMEM((wo, bn), jnp.float32),
                            pltpu.VMEM((wp, bm), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, ob, ho, wo, bn), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        interpret=interpret,
        name="sparse_conv",
    )(*operands)
    return from_blocks(out)
