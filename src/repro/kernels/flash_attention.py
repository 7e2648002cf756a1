"""Blockwise causal attention (flash-style) Pallas TPU kernel.

The LM archs' prefill hot spot. Online-softmax over KV blocks with the
running (m, l, acc) state in VMEM scratch; the q tile stays resident
while KV blocks stream HBM->VMEM. Grid: (batch*heads, q_tiles, kv_tiles),
kv innermost. Causality is enforced two ways: masked lanes inside a
block, and (as a perf iteration would on real HW) blocks entirely above
the diagonal are skipped with a predicated no-op.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale, block_q, block_k, n_kv, causal, window, q_offset):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    run = True
    if causal:
        # whole block above diagonal -> skip (predicated out)
        run = (ik * block_k) <= (q_offset + iq * block_q + block_q - 1)

    @pl.when(run)
    def _step():
        s = jnp.dot(q_ref[0].astype(jnp.float32),
                    k_ref[0].astype(jnp.float32).T,
                    preferred_element_type=jnp.float32) * scale
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > (qpos - window)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p, v_ref[0].astype(jnp.float32),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                             "block_q", "block_k", "interpret"))
def flash_attention_pallas(q, k, v, *, causal=True, window=0, q_offset=0,
                           block_q=128, block_k=128, interpret: bool):
    """q: (B, Tq, H, D); k, v: (B, Tk, H, D) (GQA pre-expanded).
    ``interpret`` runs the kernel body in the Pallas interpreter (CPU)
    instead of Mosaic (TPU); ``kernels.ops`` picks it from the
    platform."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    assert tq % block_q == 0 and tk % block_k == 0
    scale = 1.0 / math.sqrt(d)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, tq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, tk, d)
    grid = (b * h, tq // block_q, tk // block_k)
    kernel = functools.partial(
        _kernel, scale=scale, block_q=block_q, block_k=block_k,
        n_kv=tk // block_k, causal=causal, window=window, q_offset=q_offset)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
