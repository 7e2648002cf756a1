"""Compile each Pallas kernel with Mosaic for a described TPU v5e chip,
at the widths the 224 px networks give it (sparse ResNet-50 with its
32x32 blocks, dense MobileNet-V2).

Interpret mode on the CPU checks numerics, not whether the TPU's
compiler accepts a kernel: block shapes off the (8, 128) tiling,
unaligned or strided VMEM reads and over-budget scratch are refused
only here. Nothing runs; each test checks that the compiled program
holds the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and under
pytest-xdist every worker imports this file. Keep these tests in this
one file so a single worker loads the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.depthwise_conv import depthwise_conv_pallas
from repro.kernels.dw_pw_fused import dw_pw_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.sparse_conv import sparse_conv_pallas
from repro.kernels.sparse_matmul import sparse_matmul_pallas

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_tpu(one_chip):
    """``compile_tpu(fn, *(shape, dtype))`` -> compiled HLO text, with
    JAX's persistent cache off: an entry compiled for a described chip
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()

    def compile_(fn, *specs):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in specs]
        return jax.jit(fn).lower(*args).compile().as_text()

    yield compile_
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sconv_specs(h, cin, cout, k_keep, stride, *, bm=32, bn=32,
                 wdt=BF16, residual=False, scale=False):
    ob = cout // bn
    ho = -(-h // stride)
    specs = [((1, h, h, cin), BF16), ((ob, k_keep, bm, bn), wdt),
             ((ob, k_keep), I32), ((cout,), BF16)]
    if residual:
        specs.append(((1, ho, ho, cout), BF16))
    if scale:
        specs.append(((ob, bn), F32))
    return specs


# (h, cin, cout, k, stride, kept blocks K, block_k): ResNet-50 layers
# with the paper's 32x32 blocks at 85 % sparsity
SPARSE_CONVS = {
    "s0_c2_3x3_56x56x64": (56, 64, 64, 3, 1, 3, 1),
    "s1b0_c2_3x3_stride2_56to28": (56, 128, 128, 3, 2, 5, 1),
    "s2_c1_1x1_14x14_1024to256": (14, 1024, 256, 1, 1, 5, 1),
    "s3_c2_3x3_7x7x512_block_k2": (7, 512, 512, 3, 1, 22, 2),
    # the stem's 7x7 stride-2 window at 224 px (one 32-wide block of
    # input channels: the RGB stem itself is too narrow to prune)
    "stem_7x7_stride2_224": (224, 32, 64, 7, 2, 7, 1),
}


@pytest.mark.parametrize("name", sorted(SPARSE_CONVS))
def test_sparse_conv_compiles_for_v5e(compile_tpu, name):
    h, cin, cout, k, stride, kk, bk = SPARSE_CONVS[name]
    txt = compile_tpu(
        lambda x, v, i, b: sparse_conv_pallas(
            x, v, i, b, k=k, stride=stride, block_k=bk, interpret=False),
        *_sconv_specs(h, cin, cout, kk, stride))
    assert "tpu_custom_call" in txt


def test_sparse_conv_int8_residual_epilogue_compiles_for_v5e(compile_tpu):
    """int8 codes + per-channel scale + fused residual (s3 c3 tail)."""
    txt = compile_tpu(
        lambda x, v, i, b, r, s: sparse_conv_pallas(
            x, v, i, b, r, s, k=1, stride=1, relu=True, interpret=False),
        *_sconv_specs(7, 512, 2048, 2, 1, wdt=I8, residual=True,
                      scale=True))
    assert "tpu_custom_call" in txt


# (h, c, stride): MobileNet-V2 depthwise layers, including strided ones
# wider than one 128-lane tile
DEPTHWISE = {"112x112x32": (112, 32, 1), "112x112x96_stride2": (112, 96, 2),
             "56x56x144_stride2": (56, 144, 2),
             "14x14x576_stride2": (14, 576, 2), "7x7x960": (7, 960, 1)}


@pytest.mark.parametrize("name", sorted(DEPTHWISE))
def test_depthwise_conv_compiles_for_v5e(compile_tpu, name):
    h, c, stride = DEPTHWISE[name]
    txt = compile_tpu(
        lambda x, w: depthwise_conv_pallas(x, w, stride=stride,
                                           interpret=False),
        ((1, h, h, c), BF16), ((3, 3, c), BF16))
    assert "tpu_custom_call" in txt


# (h, c, cout, stride, residual): fused dw->pw blocks
DW_PW = {"mbv1_14x14x512": (14, 512, 512, 1, False),
         "mbv2_56x56x144_res": (56, 144, 24, 1, True),
         "mbv2_112x112x96_stride2": (112, 96, 24, 2, False),
         "mbv2_7x7x960": (7, 960, 320, 1, False)}


@pytest.mark.parametrize("name", sorted(DW_PW))
def test_dw_pw_compiles_for_v5e(compile_tpu, name):
    h, c, co, stride, res = DW_PW[name]
    ho = -(-h // stride)
    specs = [((1, h, h, c), BF16), ((3, 3, c), BF16), ((c,), BF16),
             ((c, co), BF16), ((co,), BF16)]
    if res:
        specs.append(((1, ho, ho, co), BF16))
    txt = compile_tpu(
        lambda x, dw, db, pw, pb, *r: dw_pw_pallas(
            x, dw, db, pw, pb, *r, stride=stride, interpret=False),
        *specs)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("m", [1, 16])
def test_sparse_classifier_compiles_for_v5e(compile_tpu, m):
    """The 2048 -> 1000 ResNet-50 classifier: 32x25 blocks (1000 has no
    32-wide split), f32 pooled features."""
    txt = compile_tpu(
        lambda x, v, i: sparse_matmul_pallas(x, v, i, interpret=False),
        ((m, 2048), F32), ((40, 10, 32, 25), BF16), ((40, 10), I32))
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles_for_v5e(compile_tpu):
    spec = ((1, 256, 4, 64), BF16)
    txt = compile_tpu(
        lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
        spec, spec, spec)
    assert "tpu_custom_call" in txt
