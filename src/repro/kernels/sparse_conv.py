"""Fused implicit-GEMM block-sparse convolution — the HPIPE conv unit
without the im2col materialization.

The FPGA decodes each layer's runlength weight stream into gather
addresses against a *line buffer* of the unexpanded activation: a 3x3
conv never writes a 9x-duplicated patch tensor anywhere. The TPU
mapping of that dataflow:

- line buffer -> a band of padded input rows, every channel block,
  resident in VMEM: ``(C/bm, rows, Wp, bm)`` with ``rows = (band-1) *
  stride + k``, DMA'd once per band and staged once in f32 (Mosaic
  reads strided and dynamically offset windows only from 32-bit refs);
- runlength stream -> scalar-prefetched ``(ky, kx, cb)`` coordinate
  arrays (one triple per surviving weight block): the kernel indexes
  the resident band with them — dynamic ``cb`` and ``ky`` on leading
  dims, a (strided) ``pl.ds`` window for ``kx`` — so the patch gather
  happens in VMEM and the im2col tensor never exists in HBM;
- DSP accumulator chain -> an f32 accumulator over all K kept blocks of
  one output block, with the bias + ReLU (+ int8 scale, + residual)
  epilogue fused into the flush so the elementwise follow-ups never
  round-trip HBM either.

Grid: ``grid(...)`` = (N, bands, out_blocks). One step computes one
output block's ``(band, Wo, bn)`` tile from all K of its kept blocks;
out_blocks is innermost, so the band's input tile is fetched and staged
once and reused by every output block. The band is the whole output
plane where the step's working set fits the VMEM budget
(``VMEM_BUDGET_BYTES``), else the fewest equal bands that fit
(``tiling``): a pure function of the shapes, no knob.

The dot: each kept block's window is cast back to the operands' stored
dtype before the MXU dot (exact: the f32 staging holds the stored
values), so bf16 operands take one MXU pass; int8 codes are
integer-valued in bf16 and keep their scale for the flush. f32 operands
dot at ``Precision.HIGHEST``. Accumulation is f32, over the K blocks in
the stream's order.

Layout: the kernel sees the activation channel-block-major, (N, C/bm,
Hp, Wp, bm), and writes its output the same way, (N, Cout/bn, Ho', Wo',
bn), with Wo' rounded up to the 8-row sublane tile (so a band's
(rows, Wo', bn) window merges into one (rows*Wo', bn) dot operand) and
Ho' to whole bands; the wrapper pads the input to match, converts from
and to NHWC and drops the padding. A 1x1 conv at stride s reads every
s-th row and column only, so the wrapper subsamples its input first.

Weight layout: the 2D conv weight is (k*k*cin, cout) with rows in
HWIO order — row f = (ky*k + kx)*cin + c — pruned block-balanced by
``repro.core.sparsity.to_block_balanced``. The block-row size ``bm``
must divide ``cin`` so every surviving block maps to exactly one
(ky, kx, channel-block) gather.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: per-core VMEM budget the kernels' tiles are clamped against; half
#: the hardware's ~16 MB so double-buffered DMAs fit too
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

#: Mosaic's lane width and 32-bit sublane count
LANES, SUBLANES = 128, 8

#: rows of one dot's left operand (output pixels a chunk retires):
#: enough to amortize the MXU's weight load, few enough to stay in vregs
DOT_ROWS = 256


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


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) tile as Mosaic lays it out: rows
    padded to the sublane tile (8 32-bit words, packed for narrower
    dtypes), cols to whole lanes."""
    sub = SUBLANES * max(4 // itemsize, 1)
    return (_cdiv(rows, sub) * sub) * (_cdiv(cols, LANES) * LANES) * itemsize


class Tiling(NamedTuple):
    """How one call tiles its output: ``n_bands`` bands of ``band``
    output rows (the last may run past ``ho``), each reading ``rows``
    padded input rows of width ``wp``; ``wo_pad`` output columns a row
    (``wo`` rounded up to the sublane tile); ``stride`` after a 1x1
    conv's input subsampling."""
    ho: int
    wo: int
    wo_pad: int
    band: int
    n_bands: int
    rows: int
    wp: int
    stride: int


def _step_vmem_bytes(band: int, rows: int, wp: int, wo_pad: int,
                     nbc: int, n_k: int, bm: int, bn: int,
                     itemsize: int) -> int:
    """One grid step's VMEM working set: the double-buffered input band
    and its f32 staging, the double-buffered K weight blocks (counted
    at 32 bits, whatever they are stored in), and the double-buffered
    output and residual tiles."""
    return (2 * nbc * rows * _tile_bytes(wp, bm, itemsize)
            + nbc * rows * _tile_bytes(wp, bm, 4)
            + 2 * n_k * _tile_bytes(bm, bn, 4)
            + 2 * 2 * band * _tile_bytes(wo_pad, bn, itemsize))


def tiling(x_shape, vals_shape, k: int, stride: int, *,
           itemsize: int = 2, budget: Optional[int] = None) -> Tiling:
    """The output tiling of one call, from its shapes: the whole output
    plane in one band where the step's working set fits ``budget``
    (``VMEM_BUDGET_BYTES``, read at call time), else the fewest equal
    bands that fit (one row a band at least)."""
    budget = VMEM_BUDGET_BYTES if budget is None else budget
    _, h, w, c = x_shape
    _, n_k, bm, bn = vals_shape
    if k == 1 and stride > 1:            # the wrapper subsamples first
        h, w, stride = _cdiv(h, stride), _cdiv(w, stride), 1
    ho, wo = _cdiv(h, stride), _cdiv(w, stride)
    wo_pad = _cdiv(wo, SUBLANES) * SUBLANES
    wp = (wo_pad - 1) * stride + k
    band = ho
    for n_bands in range(1, ho + 1):
        band = _cdiv(ho, n_bands)
        rows = (band - 1) * stride + k
        if _step_vmem_bytes(band, rows, wp, wo_pad, c // bm, n_k, bm, bn,
                            itemsize) <= budget:
            break
    return Tiling(ho, wo, wo_pad, band, _cdiv(ho, band),
                  (band - 1) * stride + k, wp, stride)


def grid(x_shape, vals_shape, k: int, stride: int, *,
         itemsize: int = 2) -> tuple[int, int, int]:
    """The call's grid, (N, bands, out_blocks): one step per output
    block tile. Its product is the number of grid steps a call takes."""
    t = tiling(x_shape, vals_shape, k, stride, itemsize=itemsize)
    return (x_shape[0], t.n_bands, vals_shape[0])


def _ds(start, size: int, stride: int):
    return pl.ds(start, size) if stride == 1 else \
        pl.ds(start, size, stride=stride)


def _kernel(ky_ref, kx_ref, cb_ref, x_ref, vals_ref, b_ref, *rest,
            n_k: int, band: int, wo: int, stride: int, relu: bool,
            has_res: bool, has_scale: bool, dot_dtype):
    rest = list(rest)
    scale_ref = rest.pop(0) if has_scale else None
    res_ref = rest.pop(0) if has_res else None
    o_ref, xs_ref = rest
    j = pl.program_id(2)
    bm, bn = vals_ref.shape[-2:]
    precision = (jax.lax.Precision.HIGHEST if dot_dtype == jnp.float32
                 else None)

    # the band is the same for every output block: stage it in f32 once
    @pl.when(j == 0)
    def _stage():
        xs_ref[...] = x_ref[...].astype(jnp.float32)

    chunk = min(band, max(DOT_ROWS // wo, 1))   # output rows a dot takes
    for r0 in range(0, band, chunk):
        cr = min(chunk, band - r0)

        def mac(l, acc, r0=r0, cr=cr):
            # one kept block: its (ky, kx, cb) window of the band, cast
            # back to the stored dtype, into the f32 accumulator
            t = j * n_k + l
            win = xs_ref[cb_ref[t], _ds(ky_ref[t] + r0 * stride, cr, stride),
                         _ds(kx_ref[t], wo, stride), :]
            lhs = win.reshape(cr * wo, bm).astype(dot_dtype)
            return acc + jnp.dot(lhs, vals_ref[l].astype(dot_dtype),
                                 preferred_element_type=jnp.float32,
                                 precision=precision)

        y = jax.lax.fori_loop(0, n_k, mac,
                              jnp.zeros((cr * wo, bn), jnp.float32))
        if has_scale:
            # int8 epilogue: the accumulator holds the raw CODE dot —
            # the per-output-channel scale re-reals it before the
            # (real-valued) bias/residual join
            y = y * scale_ref[...].astype(jnp.float32)
        y = y + b_ref[...].astype(jnp.float32)
        if has_res:
            # fused residual epilogue (core/fusion.py R2): the skip
            # tensor's tile is added here — the pre-add conv output
            # never exists in HBM
            y = y + res_ref[r0:r0 + cr].astype(jnp.float32).reshape(
                cr * wo, bn)
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[r0:r0 + cr] = y.reshape(cr, wo, bn).astype(o_ref.dtype)


def to_blocks(x, b: int):
    """(N, H, W, C) -> channel-block-major (N, C/b, H, W, b)."""
    n, h, w, c = x.shape
    return x.reshape(n, h, w, c // b, b).transpose(0, 3, 1, 2, 4)


def from_blocks(x):
    """(N, C/b, H, W, b) -> (N, H, W, C)."""
    n, nb, h, w, b = x.shape
    return x.transpose(0, 2, 3, 1, 4).reshape(n, h, w, nb * b)


def _pad_to(x, h: int, w: int, lo=(0, 0)):
    """Zero-pad NHWC ``x`` to (h, w) rows and columns, ``lo`` of them
    before the data; crop what lies beyond."""
    return jnp.pad(x, ((0, 0), (lo[0], max(h - x.shape[1] - lo[0], 0)),
                       (lo[1], max(w - x.shape[2] - lo[1], 0)),
                       (0, 0)))[:, :h, :w]


@functools.partial(jax.jit, static_argnames=("k", "stride", "relu",
                                             "interpret"))
def sparse_conv_pallas(x: jax.Array, vals: jax.Array, idx: jax.Array,
                       bias: jax.Array, residual: jax.Array = None,
                       scale: jax.Array = None, *,
                       k: int, stride: int = 1, relu: bool = True,
                       interpret: bool) -> jax.Array:
    """y[n, oy, ox, j*bn:+bn] = act(sum_l win(x; ky,kx,cb)[oy,ox] @ vals[j,l] + b).

    x: (N, H, W, C) NHWC; vals: (ob, K, bm, bn); idx: (ob, K) int32 flat
    HWIO block ids; bias: (ob*bn,). SAME padding. ``residual``
    (optional, (N, Ho, Wo, ob*bn)) is a fused skip tensor added in the
    flush epilogue before the activation (core/fusion.py residual
    rule). ``scale`` (optional, (ob, bn) f32) marks ``vals`` as int8
    codes (core/quant.py): the accumulate is unchanged (the codes are
    exact in the activations' dtype) and the scale multiplies the
    accumulator at the flush, before bias/residual. The tiling comes
    from the shapes alone (``tiling``). ``interpret`` runs the kernel
    body in the Pallas interpreter (CPU) instead of Mosaic (TPU);
    ``kernels.ops`` picks it from the platform.
    """
    n, h, w, c = x.shape
    ob, n_k, bm, bn = vals.shape
    assert c % bm == 0, (c, bm)
    t = tiling(x.shape, vals.shape, k, stride, itemsize=x.dtype.itemsize)
    steps = grid(x.shape, vals.shape, k, stride, itemsize=x.dtype.itemsize)
    if k == 1 and stride > 1:
        x = x[:, ::stride, ::stride]     # a 1x1 window reads no more
        h, w = x.shape[1:3]
    _, ph_lo, _ = same_pads(h, k, t.stride)
    _, pw_lo, _ = same_pads(w, k, t.stride)
    ho_pad = t.n_bands * t.band
    hp = (ho_pad - 1) * t.stride + k
    xb = to_blocks(_pad_to(x, hp, t.wp, (ph_lo, pw_lo)), bm)
    # flat (ob*K,) coordinate streams: 1-D scalar-prefetch arrays keep
    # SMEM padding to a minimum
    ky, kx, cb = (a.reshape(-1) for a in
                  conv_block_coords(idx.astype(jnp.int32), k, c, bm))

    has_res = residual is not None
    has_scale = scale is not None
    dot_dtype = jnp.result_type(x.dtype, vals.dtype)
    kernel = functools.partial(
        _kernel, n_k=n_k, band=t.band, wo=t.wo_pad, stride=t.stride,
        relu=relu, has_res=has_res, has_scale=has_scale,
        dot_dtype=dot_dtype)
    band_step = t.band * t.stride
    in_specs = [
        # the band's input rows, every channel block: element-indexed
        # rows, since neighbouring bands share (k - stride) halo rows
        pl.BlockSpec((None, pl.Element(c // bm), pl.Element(t.rows),
                      pl.Element(t.wp), pl.Element(bm)),
                     lambda i, b, j, *_: (i, 0, b * band_step, 0, 0)),
        pl.BlockSpec((None, n_k, bm, bn), lambda i, b, j, *_: (j, 0, 0, 0)),
        pl.BlockSpec((None, 1, bn), lambda i, b, j, *_: (j, 0, 0)),
    ]
    operands = [ky, kx, cb, xb, vals, bias.reshape(ob, 1, bn)]
    if has_scale:
        # per-output-channel scale rides the bias layout: one (1, bn)
        # line per output block, read at the flush
        in_specs.append(pl.BlockSpec((None, 1, bn),
                                     lambda i, b, j, *_: (j, 0, 0)))
        operands.append(scale.reshape(ob, 1, bn))
    tile = pl.BlockSpec((None, None, t.band, t.wo_pad, bn),
                        lambda i, b, j, *_: (i, j, b, 0, 0))
    if has_res:
        in_specs.append(tile)
        operands.append(to_blocks(_pad_to(residual, ho_pad, t.wo_pad), bn))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=steps,
            in_specs=in_specs,
            out_specs=tile,
            scratch_shapes=[pltpu.VMEM((c // bm, t.rows, t.wp, bm),
                                       jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n, ob, ho_pad, t.wo_pad, bn),
                                       x.dtype),
        compiler_params=pltpu.CompilerParams(
            # the staged band carries across the output blocks
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="sparse_conv",
    )(*operands)
    return from_blocks(out[:, :, :t.ho, :t.wo])
