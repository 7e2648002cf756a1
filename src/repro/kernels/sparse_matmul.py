"""Gather-based block-sparse matmul — the HPIPE convolution unit on TPU.

The FPGA version decodes runlengths into gather addresses for the input
activation buffers and accumulates in DSP chains without ever leaving
the hardened datapath. The TPU mapping:

- runlength stream  -> scalar-prefetched ``idx`` array: the BlockSpec
  ``index_map`` reads ``idx[j, k]`` to choose which HBM block of ``x``
  to DMA into VMEM (the gather happens in the memory system, activations
  are never duplicated in HBM);
- DSP chain accumulation -> f32 VMEM scratch accumulator revisited
  across the K grid steps (never scattered to HBM, exactly the paper's
  gather-not-scatter argument);
- channel splits -> the j/k grid dimensions.

Layout: ``x`` is viewed feature-block-major, (d_in/bm, M, bm), and the
output is written (d_out/bn, M, bn), so every block's last two dims are
whole array dims (Mosaic's (8, 128) tiling rule) whatever the sparsity
block — the paper's 32x32 blocks, or the 32x25 blocks of the
1000-class classifier. The wrapper converts from and to (M, d).

Grid: (m_tiles, out_blocks, K); K is the innermost (fastest) dimension
so the output tile stays resident while its K gathered input blocks
stream through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, x_ref, vals_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[0].astype(jnp.float32),
        vals_ref[0, 0].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m_x", "interpret"))
def sparse_matmul_pallas(x: jax.Array, vals: jax.Array, idx: jax.Array,
                         *, block_m_x: int = 128,
                         interpret: bool) -> jax.Array:
    """y[m, j*bn:(j+1)bn] = sum_k x[m, idx[j,k]*bm:+bm] @ vals[j,k].

    x: (M, d_in); vals: (ob, K, bm, bn); idx: (ob, K) int32.
    ``block_m_x`` tiles M when it divides M (else one tile holds all
    of M). ``interpret`` runs the kernel body in the Pallas interpreter
    (CPU) instead of Mosaic (TPU); ``kernels.ops`` picks it from the
    platform.
    """
    m, d_in = x.shape
    ob, n_k, bm, bn = vals.shape
    assert d_in % bm == 0, (d_in, bm)
    tm = block_m_x if m % block_m_x == 0 else m

    grid = (m // tm, ob, n_k)
    kernel = functools.partial(_kernel, n_k=n_k)
    xb = x.reshape(m, d_in // bm, bm).transpose(1, 0, 2)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, tm, bm),
                             lambda i, j, k, idx: (idx[j * n_k + k], i, 0)),
                pl.BlockSpec((1, 1, bm, bn),
                             lambda i, j, k, idx: (j, k, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, tm, bn),
                                   lambda i, j, k, idx: (j, i, 0)),
            scratch_shapes=[pltpu.VMEM((tm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((ob, m, bn), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="sparse_matmul",
    )(idx.reshape(-1).astype(jnp.int32), xb, vals)
    return out.transpose(1, 0, 2).reshape(m, ob * bn)
