"""Depthwise 2D convolution Pallas TPU kernel.

HPIPE implements DepthwiseConv2D as its own hardware unit (Sec. V,
MobileNets); on TPU the op is VPU-bound (no channel reduction for the
MXU), so the kernel is line-buffered like the paper's shift unit: one
padded input row (1, 1, Wp, C-tile) resident in VMEM per grid step, a
f32 (Wo, C-tile) accumulator revisited across the k innermost steps
(the ky shift is folded into the HBM row address by the index map, the
kx shift is a strided window read of the row).

The previous formulation kept a full (H, W, C-tile) image slab plus a
full f32 accumulator resident per step — the 112x112 MobileNet layers
overflowed the ~16 MB VMEM budget at block_c=128 (114*114*128 bf16 in
+ 112*112*128 f32 acc + out ~ 13 MB, f32 input ~ 23 MB). Row tiling
caps the working set at a few hundred KB regardless of H, and
``pick_block_c`` clamps the channel tile from an explicit VMEM budget
for pathological widths.

Grid: (batch, out-row, channel-tiles, k); k innermost so the
accumulator line stays resident while the k input rows stream through.
SAME padding is applied by the wrapper so the kernel body is pure
shifted multiply-accumulate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sparse_conv import (LANES, VMEM_BUDGET_BYTES,
                                      pad_same_hw)


def row_window(row_ref, start, wo: int, stride: int):
    """The (wo, C) window of a resident f32 input row at column
    ``start`` (static or dynamic), every ``stride``-th column. Mosaic
    reads strided and dynamically offset windows only from 32-bit
    refs, so callers stage the row in f32 VMEM first."""
    if stride == 1:
        return row_ref[pl.ds(start, wo), :]
    return row_ref[pl.ds(start, wo, stride=stride), :]


def shifted_row_mac(x_ref, row_ref, acc_ref, taps_ky, k: int,
                    wo: int, stride: int):
    """One ky step of the line-buffered depthwise unit: the k shifted
    strided (wo, C) windows of the resident input row, multiplied by
    that kernel row's taps and added in f32 into ``acc_ref`` (wo, C).
    ``x_ref``: the (1, 1, wp, C) input row; ``row_ref``: (wp, <=128) f32
    VMEM scratch the row is staged in, 128 lanes at a time (Mosaic's
    strided window read wants a 128-lane buffer); ``taps_ky``: (k, C).
    Shared by the depthwise and the fused dw->pw kernels so the
    window/stride math lives in exactly one place."""
    c = x_ref.shape[-1]
    lanes = row_ref.shape[-1]
    for c0 in range(0, c, lanes):
        w = min(lanes, c - c0)
        row_ref[:, :w] = x_ref[0, 0, :, c0:c0 + w].astype(jnp.float32)
        acc = acc_ref[:, c0:c0 + w]
        for kx in range(k):
            acc = acc + row_window(row_ref, kx, wo, stride)[:, :w] * \
                taps_ky[kx:kx + 1, c0:c0 + w].astype(jnp.float32)
        acc_ref[:, c0:c0 + w] = acc


def _vmem_bytes(wp: int, wo: int, tc: int, k: int, itemsize: int) -> int:
    """Per-grid-step working set of the row kernel: input row + its f32
    staging lanes + f32 accumulator + output row + the (k, k, tc) taps."""
    return (wp * tc * itemsize          # resident input row
            + wp * min(tc, LANES) * 4   # f32 staging lanes of the row
            + wo * tc * 4               # f32 accumulator line
            + wo * tc * itemsize        # output line
            + k * k * tc * itemsize)    # taps


def pick_block_c(w: int, c: int, k: int, stride: int, itemsize: int,
                 budget: int = VMEM_BUDGET_BYTES) -> int:
    """The default channel tile: the head of ``block_c_candidates``."""
    return block_c_candidates(w, c, k, stride, itemsize, budget)[0]


def block_c_candidates(w: int, c: int, k: int, stride: int, itemsize: int,
                       budget: int = VMEM_BUDGET_BYTES,
                       limit: int = 4) -> list[int]:
    """The autotuner's channel-tile lattice: the lane-legal tiles of
    ``c`` — multiples of 128 that divide it, smallest first, or all of
    ``c`` when 128 does not divide it — whose row working set fits the
    VMEM budget, capped at ``limit`` entries. ``pick_block_c`` is by
    construction the head of this list, so ANY choice the autotuner
    records respects the same budget the heuristic does."""
    wo = -(-w // stride)
    wp = (wo - 1) * stride + k
    legal = ([tc for tc in range(LANES, c + 1, LANES) if c % tc == 0]
             if c % LANES == 0 else [c])
    cands = [tc for tc in legal
             if _vmem_bytes(wp, wo, tc, k, itemsize) <= budget]
    return cands[:limit] or legal[:1]


def _kernel(x_ref, w_ref, o_ref, acc_ref, row_ref, *, k: int, wo: int,
            stride: int):
    ky = pl.program_id(3)

    @pl.when(ky == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    shifted_row_mac(x_ref, row_ref, acc_ref, w_ref[ky], k, wo, stride)

    @pl.when(ky == k - 1)
    def _flush():
        o_ref[0, 0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("stride", "block_c", "interpret"))
def depthwise_conv_pallas(x: jax.Array, w: jax.Array, *, stride: int = 1,
                          block_c: int = 0,
                          interpret: bool) -> jax.Array:
    """x: (N, H, W, C) NHWC; w: (k, k, C). SAME padding. Returns
    (N, ceil(H/stride), ceil(W/stride), C). ``block_c=0`` (default)
    picks the channel tile with ``pick_block_c``. ``interpret`` runs
    the kernel body in the Pallas interpreter (CPU) instead of Mosaic
    (TPU); ``kernels.ops`` picks it from the platform."""
    n, h, wd, c = x.shape
    k = w.shape[0]
    xp, h_out, w_out = pad_same_hw(x, k, stride)
    wp = xp.shape[2]
    tc = block_c or pick_block_c(wd, c, k, stride, x.dtype.itemsize)
    tc = min(tc, c)
    assert c % tc == 0, (c, tc)
    kernel = functools.partial(_kernel, k=k, wo=w_out, stride=stride)
    return pl.pallas_call(
        kernel,
        grid=(n, h_out, c // tc, k),
        in_specs=[
            # H-block size 1 => absolute input row oy*stride + ky
            pl.BlockSpec((1, 1, wp, tc),
                         lambda b, oy, ci, ky: (b, oy * stride + ky, 0, ci)),
            pl.BlockSpec((k, k, tc), lambda b, oy, ci, ky: (0, 0, ci)),
        ],
        out_specs=pl.BlockSpec((1, 1, w_out, tc),
                               lambda b, oy, ci, ky: (b, oy, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((n, h_out, w_out, c), x.dtype),
        scratch_shapes=[pltpu.VMEM((w_out, tc), jnp.float32),
                        pltpu.VMEM((wp, min(tc, LANES)), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="depthwise_conv",
    )(xp, w)


def depthwise_conv_ref(x: jax.Array, w: jax.Array, *,
                       stride: int = 1) -> jax.Array:
    """lax.conv_general_dilated oracle (feature-grouped)."""
    c = x.shape[-1]
    return jax.lax.conv_general_dilated(
        x, w[:, :, None, :], (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c)
