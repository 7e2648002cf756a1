"""Per-kernel allclose vs the pure-jnp oracles, sweeping shapes/dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SparsityConfig
from repro.core import sparsity as S
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.sparse_matmul import sparse_matmul_pallas
from repro.models.layers import blockwise_attention


@pytest.mark.parametrize("d_in,d_out,bm,bn,sp", [
    (64, 64, 16, 16, 0.5),
    (128, 96, 16, 32, 0.75),
    (256, 128, 32, 16, 0.85),
    (64, 256, 8, 64, 0.25),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_matmul_allclose(d_in, d_out, bm, bn, sp, dtype):
    cfg = SparsityConfig(enabled=True, sparsity=sp, block_m=bm, block_n=bn)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    w = jax.random.normal(k1, (d_in, d_out), jnp.float32).astype(dtype)
    sw = S.to_block_balanced(w, cfg)
    x = jax.random.normal(k2, (24, d_in), jnp.float32).astype(dtype)
    y_ref = ref.sparse_matmul_ref(x, sw)
    y_xla = ops.sparse_matmul(x, sw)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y_xla, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)
    y_pal = sparse_matmul_pallas(x, sw.vals, sw.idx, block_m_x=8,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)


def test_sparse_matmul_batched_input():
    cfg = SparsityConfig(enabled=True, sparsity=0.5, block_m=16, block_n=16)
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    sw = S.to_block_balanced(w, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 64))
    y = ops.sparse_matmul(x, sw)
    assert y.shape == (2, 5, 32)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(ref.sparse_matmul_ref(x, sw)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tq,tk,causal,window", [
    (128, 128, True, 0),
    (128, 128, False, 0),
    (64, 256, True, 0),     # cross-length
    (128, 128, True, 48),   # sliding window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_allclose(tq, tk, causal, window, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    B, H, D = 2, 3, 32
    q = jax.random.normal(k1, (B, tq, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(k2, (B, tk, H, D), jnp.float32).astype(dtype)
    v = jax.random.normal(k3, (B, tk, H, D), jnp.float32).astype(dtype)
    offset = tk - tq if tq != tk else 0
    want = ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=offset)
    got = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=offset, block_q=32, block_k=64,
                                 interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    got2 = blockwise_attention(q, k, v, causal=causal, window=window,
                               q_offset=offset, block_q=32, block_k=64)
    np.testing.assert_allclose(np.asarray(got2, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_padded_lengths():
    # tq/tk not multiples of block sizes (XLA path handles padding)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(k1, (1, 100, 2, 16))
    k = jax.random.normal(k2, (1, 100, 2, 16))
    v = jax.random.normal(k3, (1, 100, 2, 16))
    want = ref.attention_ref(q, k, v, causal=True)
    got = blockwise_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("k,stride,c,hw", [
    (3, 1, 8, 16), (3, 2, 16, 17), (5, 1, 8, 12), (5, 2, 8, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_depthwise_conv_allclose(k, stride, c, hw, dtype):
    from repro.kernels.depthwise_conv import (depthwise_conv_pallas,
                                              depthwise_conv_ref)
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    x = jax.random.normal(k1, (2, hw, hw, c), jnp.float32).astype(dtype)
    w = jax.random.normal(k2, (k, k, c), jnp.float32).astype(dtype)
    want = depthwise_conv_ref(x, w, stride=stride)
    got = depthwise_conv_pallas(x, w, stride=stride, block_c=min(c, 8),
                                interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_depthwise_conv_largest_mobilenet_shape_fits_vmem():
    """The 112x112 MobileNet layers used to overflow VMEM: the old
    kernel kept a full (H, W, block_c) slab + f32 accumulator resident
    (~13-23 MB at block_c=128). The row kernel's working set must fit
    the budget for EVERY MobileNet dw layer at the auto-picked tile,
    and the largest shape must still be numerically right."""
    from repro.kernels.depthwise_conv import (VMEM_BUDGET_BYTES,
                                              _vmem_bytes,
                                              depthwise_conv_pallas,
                                              depthwise_conv_ref,
                                              pick_block_c)
    from repro.models import cnn as cnn_mod
    for arch in ("mobilenet_v1", "mobilenet_v2"):
        for s in cnn_mod.specs_for(arch):
            if s.kind != "dw":
                continue
            for itemsize in (2, 4):                  # bf16 and f32 inputs
                tc = pick_block_c(s.in_hw, s.cin, s.k, s.stride, itemsize)
                assert s.cin % tc == 0
                wo = -(-s.in_hw // s.stride)
                wp = (wo - 1) * s.stride + s.k
                assert _vmem_bytes(wp, wo, tc, s.k, itemsize) \
                    <= VMEM_BUDGET_BYTES, (arch, s.name, tc)
    # the worst offender end-to-end: 112x112, C=128 (old kernel: ~13 MB
    # bf16 / ~23 MB f32 resident; row kernel: a few hundred KB)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    x = jax.random.normal(k1, (1, 112, 112, 128), jnp.float32)
    w = jax.random.normal(k2, (3, 3, 128), jnp.float32)
    want = depthwise_conv_ref(x, w, stride=1)
    got = depthwise_conv_pallas(x, w, stride=1,      # auto block_c
                                interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_mobilenet_forward_with_pallas_depthwise():
    """End-to-end MobileNet-V1 with the Pallas depthwise path."""
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models import cnn
    cfg = get_config("mobilenet_v1")
    params = cnn.init_cnn(cfg, jax.random.PRNGKey(0))
    img = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 32, 3))
    ref_logits = cnn.cnn_forward(cfg, params, img)
    with ops.set_impl("pallas"):
        # only the depthwise/dw_pw dispatch differs; sparse matmuls need
        # aligned token counts for the pallas path, keep xla for them by
        # checking shapes inside ops (pallas sparse needs M%8==0; 32x32
        # image gives M=1024 ✓)
        pal_logits = cnn.cnn_forward(cfg, params, img)
    np.testing.assert_allclose(np.asarray(pal_logits), np.asarray(ref_logits),
                               rtol=2e-2, atol=2e-2)


# -- kernel path chosen by platform -----------------------------------------

@pytest.mark.parametrize("platform,impl,interpret", [
    ("tpu", "pallas", False), ("cpu", "xla", True)])
def test_kernel_path_follows_platform(monkeypatch, platform, impl,
                                      interpret):
    """Mosaic-compiled Pallas on TPU, the XLA twins on CPU (where a
    Pallas kernel only ever runs interpreted). The platform is steered
    inside the test."""
    monkeypatch.setattr(ops, "_IMPL", None)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert ops.impl() == impl
    assert ops.interpret_mode() is interpret
    with ops.set_impl("xla"):               # an explicit choice wins
        assert ops.impl() == "xla"


def test_unknown_platform_or_impl_raises(monkeypatch):
    monkeypatch.setattr(ops, "_IMPL", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no kernel path"):
        ops.impl()
    with pytest.raises(RuntimeError, match="no kernel path"):
        ops.interpret_mode()
    with pytest.raises(ValueError, match="kernel impl"):
        ops.set_impl("mosaic")
    with pytest.raises(ValueError, match="kernel impl"):
        with ops.config(impl="mosaic"):
            pass
    assert ops._IMPL is None                # nothing half-applied


def test_unknown_impl_env_raises_at_import():
    """REPRO_KERNEL_IMPL is checked, never silently replaced by xla."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, REPRO_KERNEL_IMPL="mosaic")
    r = subprocess.run([sys.executable, "-c", "import repro.kernels.ops"],
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "kernel impl 'mosaic'" in r.stderr


def test_no_kernel_defaults_to_interpret():
    """Every Pallas kernel takes ``interpret`` explicitly: a default of
    True would run the interpreter on a TPU."""
    import inspect
    from repro.kernels.depthwise_conv import depthwise_conv_pallas
    from repro.kernels.dw_pw_fused import dw_pw_pallas
    from repro.kernels.sparse_conv import sparse_conv_pallas
    for fn in (sparse_matmul_pallas, flash_attention_pallas,
               depthwise_conv_pallas, dw_pw_pallas, sparse_conv_pallas):
        p = inspect.signature(fn).parameters["interpret"]
        assert p.default is inspect.Parameter.empty, fn
