"""Fused implicit-GEMM sparse conv: equivalence vs the dense oracle and
the no-im2col-materialization regression (jaxpr shape scan)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from repro.configs import get_config
from repro.configs.base import SparsityConfig
from repro.core import sparsity as S
from repro.kernels import ops as kops
from repro.kernels import sparse_conv as sc
from repro.models import cnn
from repro.models.layers import SparseWeight

# (cin, cout, bm, bn, N, H) per kernel size — small so the Pallas
# interpret grid stays cheap; bm always divides cin (fused-conv rule)
_SHAPES = {1: (16, 16, 8, 8, 2, 8),
           3: (8, 16, 4, 8, 2, 8),
           7: (4, 8, 4, 8, 1, 8)}


def _dense_oracle(x, w4, b, stride, relu):
    """lax.conv_general_dilated on the bf16 operands, f32 accumulation."""
    y = lax.conv_general_dilated(
        x.astype(jnp.float32), w4.astype(jnp.float32),
        (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")) + b.astype(jnp.float32)
    return jax.nn.relu(y) if relu else y


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("k", [1, 3, 7])
@pytest.mark.parametrize("sp", [0.0, 0.5, 0.85])
def test_fused_conv_matches_dense_oracle(impl, k, stride, sp):
    cin, cout, bm, bn, n, h = _SHAPES[k]
    ks = jax.random.split(jax.random.PRNGKey(k * 10 + stride), 3)
    w = (jax.random.normal(ks[0], (k * k * cin, cout), jnp.float32)
         / np.sqrt(k * k * cin)).astype(jnp.bfloat16)
    x = jax.random.normal(ks[1], (n, h, h, cin), jnp.float32).astype(
        jnp.bfloat16)
    b = (jax.random.normal(ks[2], (cout,), jnp.float32) * 0.1).astype(
        jnp.bfloat16)
    spec = cnn.ConvSpec("t", "conv", cin, cout, k, stride, h)
    if sp == 0.0:
        # dense fallback: conv2d routes straight to the native conv
        if impl == "pallas":
            pytest.skip("dense fallback has no pallas path")
        want = _dense_oracle(x, w.reshape(k, k, cin, cout), b, stride, True)
        got = cnn.conv2d(x, {"w": w, "b": b}, spec)
    else:
        cfg = SparsityConfig(enabled=True, sparsity=sp, block_m=bm,
                             block_n=bn)
        sw = S.to_block_balanced(w, cfg)
        w4 = S.densify(sw).reshape(k, k, cin, cout)
        want = _dense_oracle(x, w4, b, stride, True)
        with kops.set_impl(impl):
            got = cnn.conv2d(x, {"w": sw, "b": b}, spec)
    err = float(jnp.abs(got.astype(jnp.float32) - want).max())
    assert err <= 2e-2, err


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_fused_conv_no_relu_epilogue(impl):
    """relu=False must skip the epilogue clamp (residual-branch convs)."""
    cin, cout, bm, bn, n, h = _SHAPES[3]
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    w = jax.random.normal(ks[0], (9 * cin, cout), jnp.float32) / 8.0
    x = jax.random.normal(ks[1], (n, h, h, cin), jnp.float32)
    b = jax.random.normal(ks[2], (cout,), jnp.float32)
    sw = S.to_block_balanced(w, SparsityConfig(
        enabled=True, sparsity=0.5, block_m=bm, block_n=bn))
    want = _dense_oracle(x, S.densify(sw).reshape(3, 3, cin, cout), b, 1,
                         False)
    with kops.set_impl(impl):
        got = kops.sparse_conv(x, sw, b, k=3, stride=1, relu=False)
    assert float(jnp.min(want)) < 0.0          # oracle actually goes negative
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-4, atol=1e-4)


# --- no-im2col regression -------------------------------------------------

def _iter_shapes(jaxpr):
    """All intermediate shapes in a jaxpr, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", None)
            if shape is not None:
                yield tuple(shape)
        for val in eqn.params.values():
            for sub in _subjaxprs(val):
                yield from _iter_shapes(sub)


def _subjaxprs(val):
    if hasattr(val, "jaxpr"):            # ClosedJaxpr
        yield val.jaxpr
    elif hasattr(val, "eqns"):           # raw Jaxpr
        yield val
    elif isinstance(val, (list, tuple)):
        for v in val:
            yield from _subjaxprs(v)


@pytest.mark.parametrize("arch", ["resnet50", "mobilenet_v1",
                                  "mobilenet_v2"])
def test_cnn_forward_materializes_no_im2col_patches(arch):
    """No (N,Ho,Wo,k^2*C) / (N*Ho*Wo, k^2*C) patch tensor may appear
    anywhere in the traced forward pass for any k>1 conv."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda key: cnn.init_cnn(cfg, key),
                            jax.random.PRNGKey(0))
    img = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda p, x: cnn.cnn_forward(cfg, p, x))(
        params, img)
    forbidden = set()
    n_sparse = 0
    for s in cnn.specs_for(arch):
        if s.kind != "conv" or s.k <= 1:
            continue
        if isinstance(params[s.name]["w"], SparseWeight):
            n_sparse += 1
        f = s.k * s.k * s.cin
        forbidden.add((1, s.out_hw, s.out_hw, f))
        forbidden.add((1 * s.out_hw * s.out_hw, f))
    if arch == "resnet50":
        assert n_sparse > 0              # the claim is non-vacuous there
    seen = set(_iter_shapes(jaxpr.jaxpr))
    hits = seen & forbidden
    assert not hits, f"im2col patch tensors materialized: {sorted(hits)}"


def test_im2col_path_would_fail_the_shape_scan():
    """Sanity: the scan actually detects an im2col materialization."""
    def im2col(x):
        return lax.conv_general_dilated_patches(
            x, (3, 3), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
    jaxpr = jax.make_jaxpr(im2col)(
        jax.ShapeDtypeStruct((1, 8, 8, 4), jnp.float32))
    assert (1, 8, 8, 36) in set(_iter_shapes(jaxpr.jaxpr))


# --- the tiled kernel: one grid step per output-block tile ------------------

def _tiled(x, sw, b, residual=None, *, k, stride, band, relu=True,
           monkeypatch):
    """The Pallas kernel in interpret mode, with the VMEM budget set to
    the least that gives tiles of ``band`` output rows (built unjitted,
    so the tiling is read for this call)."""
    def band_at(budget):
        return sc.tiling(x.shape, sw.vals.shape, k, stride,
                         itemsize=x.dtype.itemsize, budget=budget).band

    lo, hi = 1, 1 << 40
    while lo < hi:
        mid = (lo + hi) // 2
        lo, hi = (lo, mid) if band_at(mid) >= band else (mid + 1, hi)
    assert band_at(lo) == band
    monkeypatch.setattr(sc, "VMEM_BUDGET_BYTES", lo)
    return sc.sparse_conv_pallas.__wrapped__(
        x, sw.vals, sw.idx, b, residual, sw.scale, k=k, stride=stride,
        relu=relu, interpret=True)


def _case(seed, cin, cout, bm, bn, k, h, sp, *, int8=False):
    """bf16 activations, block-balanced weights (or int8 codes with a
    per-output-channel scale) and a bias."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = jax.random.normal(ks[0], (k * k * cin, cout), jnp.float32)
    sw = S.to_block_balanced(w / np.sqrt(k * k * cin), SparsityConfig(
        enabled=True, sparsity=sp, block_m=bm, block_n=bn))
    if int8:
        codes = jnp.clip(jnp.round(sw.vals * 127), -127, 127)
        scale = jax.random.uniform(ks[3], (cout // bn, bn), jnp.float32,
                                   0.5, 1.5) / 127
        sw = SparseWeight(codes.astype(jnp.int8), sw.idx, sw.d_in,
                          scale=scale, orig_dtype="bfloat16")
    else:
        sw = SparseWeight(sw.vals.astype(jnp.bfloat16), sw.idx, sw.d_in)
    x = jax.random.normal(ks[1], (1, h, h, cin), jnp.float32).astype(
        jnp.bfloat16)
    b = (jax.random.normal(ks[2], (cout,), jnp.float32) * 0.1).astype(
        jnp.bfloat16)
    return x, sw, b


def _oracle(x, sw, b, residual, *, k, stride, relu):
    """Dense conv of the (code) weights in f32, then scale, bias,
    residual and ReLU in the kernel's epilogue order."""
    cin = x.shape[-1]
    w4 = S.densify(SparseWeight(sw.vals.astype(jnp.float32), sw.idx,
                                sw.d_in)).reshape(k, k, cin, -1)
    y = lax.conv_general_dilated(
        x.astype(jnp.float32), w4, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)
    if sw.scale is not None:
        y = y * sw.scale.reshape(-1)
    y = y + b.astype(jnp.float32)
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    return jax.nn.relu(y) if relu else y


# name: (cin, cout, bm, bn, k, stride, h, sparsity, band, residual, int8)
TILED_CASES = {
    "1x1_s1_K1_whole_plane": (16, 16, 4, 8, 1, 1, 8, 0.75, 8, False, False),
    "1x1_s2_K2_band2_of_5": (16, 16, 4, 8, 1, 2, 9, 0.5, 2, False, False),
    "3x3_s1_K9_band3_of_8": (8, 16, 4, 8, 3, 1, 8, 0.5, 3, False, False),
    "3x3_s2_K9_band2_of_5": (8, 16, 4, 8, 3, 2, 9, 0.5, 2, False, False),
    "3x3_s1_K1_band4_of_7": (8, 16, 4, 8, 3, 1, 7, 0.95, 4, False, False),
    "7x7_s2_stem_band3_of_8": (4, 8, 4, 8, 7, 2, 16, 0.5, 3, False, False),
    "3x3_s1_residual_band3": (8, 16, 4, 8, 3, 1, 8, 0.5, 3, True, False),
    "1x1_s1_int8_scale_residual": (16, 32, 4, 8, 1, 1, 7, 0.5, 3, True,
                                   True),
    "3x3_s2_int8_scale_band2": (8, 16, 4, 8, 3, 2, 9, 0.5, 2, False,
                                True),
    # 64 output columns: a band's rows split into several dots
    "1x1_s1_residual_chunks_band6_of_64": (8, 8, 4, 8, 1, 1, 64, 0.5, 6,
                                           True, False),
    "3x3_s2_chunks_band5_of_64": (4, 8, 4, 8, 3, 2, 128, 0.5, 5, False,
                                  False),
}


@pytest.mark.parametrize("name", sorted(TILED_CASES))
def test_tiled_conv_matches_dense_oracle(name, monkeypatch):
    cin, cout, bm, bn, k, stride, h, sp, band, res, int8 = TILED_CASES[name]
    x, sw, b = _case(len(name), cin, cout, bm, bn, k, h, sp, int8=int8)
    ho = -(-h // stride)
    residual = (jax.random.normal(jax.random.PRNGKey(7), (1, ho, ho, cout),
                                  jnp.float32).astype(jnp.bfloat16)
                if res else None)
    relu = not res or int8
    got = _tiled(x, sw, b, residual, k=k, stride=stride, band=band,
                 relu=relu, monkeypatch=monkeypatch)
    want = _oracle(x, sw, b, residual, k=k, stride=stride, relu=relu)
    assert got.shape == want.shape == (1, ho, ho, cout)
    # one bf16 rounding of the stored output apart
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=2 ** -7, atol=1e-6)


# name: (cin, cout, bm, bn, k, stride, h, sparsity, bands, residual)
BAND_CASES = {
    "3x3_s1_K9": (8, 16, 4, 8, 3, 1, 8, 0.5, (8, 3, 1), False),
    "3x3_s2_K9": (8, 16, 4, 8, 3, 2, 9, 0.5, (5, 2, 1), False),
    "1x1_s2_K2_residual": (16, 16, 4, 8, 1, 2, 9, 0.5, (5, 3, 1), True),
    "3x3_s1_K9_chunks": (4, 8, 4, 8, 3, 1, 40, 0.5, (40, 7, 3), False),
}


@pytest.mark.parametrize("name", sorted(BAND_CASES))
def test_tiled_conv_band_size_is_bitwise_invisible(name, monkeypatch):
    """The band is a schedule, never math: the whole plane and every
    band size give the same output bit for bit."""
    cin, cout, bm, bn, k, stride, h, sp, bands, res = BAND_CASES[name]
    x, sw, b = _case(len(name), cin, cout, bm, bn, k, h, sp)
    assert sw.vals.shape[1] > 1
    ho = -(-h // stride)
    residual = (jax.random.normal(jax.random.PRNGKey(7), (1, ho, ho, cout),
                                  jnp.float32).astype(jnp.bfloat16)
                if res else None)
    whole, *banded = [np.asarray(_tiled(x, sw, b, residual, k=k,
                                        stride=stride, band=band,
                                        monkeypatch=monkeypatch))
                      for band in bands]
    for got in banded:
        np.testing.assert_array_equal(got, whole)


def test_resnet50_sparse_conv_grid_steps_per_image():
    """One grid step per output-block tile: sparse ResNet-50's 47
    ``sparse_conv`` calls at 224 px take 824 steps an image, from the
    parameter shapes, against 35,616 at one (Wo, 32) x (32, 32) dot a
    step (Ho x out blocks x K)."""
    cfg = get_config("resnet50")
    params = jax.eval_shape(lambda key: cnn.init_cnn(cfg, key),
                            jax.random.PRNGKey(0))
    calls = one_dot = steps = 0
    for s in cnn.specs_for("resnet50"):
        w = params.get(s.name, {}).get("w")
        if s.kind != "conv" or not isinstance(w, SparseWeight):
            continue
        x_shape = (1, s.in_hw, s.in_hw, s.cin)
        ob, n_k = w.vals.shape[:2]
        calls += 1
        one_dot += -(-s.in_hw // s.stride) * ob * n_k
        steps += int(np.prod(sc.grid(x_shape, w.vals.shape, s.k, s.stride)))
    assert (calls, one_dot) == (47, 35616)
    assert steps == 824
    assert steps * 10 < one_dot
