"""Plain float32 reference of the benchmark's networks.

Written from the published descriptions, independently of the program:
it imports nothing from ``src/`` and takes no weight, scale or table the
program made. Each configuration names its network's layer table under
``reference``, a file ``bench/networks/<network>.py`` whose ``layers(cfg)``
builds the table from the configuration's numbers; everything here is
driven by that table alone. The weights are drawn from the seed by the
recipe the configuration states under ``assumed``, and the forward pass
runs in ``jax.numpy`` float32 under ``jax.default_matmul_precision(
"highest")``, with block-pruned weights expanded to dense (pruned blocks
are zeros).

Departures from the published networks, each one the configuration's
own (they are what the configuration file describes):

- no batch normalisation: every conv has a bias instead (batch norm
  folds into the weights and a bias at inference);
- ``SAME`` padding everywhere, TensorFlow style (asymmetric for even
  inputs at stride 2), where the Caffe ResNet-50 pads symmetrically;
- MobileNet-V2 uses ReLU where the paper uses ReLU6;
- weights are random (see :func:`init_params`), not trained.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import os
from typing import NamedTuple, Optional

import numpy as np

INPUT = "input"
#: the checkout: a configuration's ``reference`` path is relative to it
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Layer(NamedTuple):
    name: str
    kind: str            # conv | dw | fc | maxpool | add | avgpool
    cin: int
    cout: int
    k: int = 1
    stride: int = 1
    hw: int = 1          # input height and width
    relu: bool = True
    src: Optional[str] = None       # input value (default: previous layer)
    residual: Optional[str] = None  # second operand of an add


def out_hw(hw: int, stride: int) -> int:
    """Output height of a ``SAME``-padded window at ``stride``."""
    return -(-hw // stride)


@functools.lru_cache(maxsize=None)
def _table_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "bench_network_" + os.path.splitext(os.path.basename(path))[0], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def network(cfg: dict) -> list[Layer]:
    """The configuration's layer table, from the file its ``reference``
    names (relative to the checkout, or absolute)."""
    return _table_module(os.path.join(ROOT, cfg["reference"])).layers(cfg)


def weighted(net: list[Layer]) -> list[Layer]:
    return [l for l in net if l.kind in ("conv", "dw", "fc")]


# ---------------------------------------------------------------------------
# block pruning, as the configuration states it
# ---------------------------------------------------------------------------

def _largest_divisor(n: int, cap: int) -> int:
    return next(b for b in range(min(cap, n), 0, -1) if n % b == 0)


def block_shape(layer: Layer, cfg: dict) -> Optional[tuple[int, int]]:
    """(bm, bn) of a pruned layer, or None where the layer stays dense.

    Blocks are ``block`` (32x32) where the widths allow; a block row
    divides the layer's input channels (so each block is one kernel tap
    of one channel block), else the largest divisor under 32 is taken;
    a layer with fewer than 4 block rows, or a block side under 4,
    stays dense (the RGB stem, and 1x1 convs over 64 channels)."""
    if not cfg.get("sparsity") or layer.kind == "dw":
        return None
    bm0, bn0 = cfg["block"]
    d_in = layer.k * layer.k * layer.cin
    unit = layer.cin
    bm = bm0 if unit % bm0 == 0 else _largest_divisor(unit, bm0)
    bn = bn0 if layer.cout % bn0 == 0 else _largest_divisor(layer.cout, bn0)
    if bm < 4 or bn < 4 or d_in // bm < 4:
        return None
    return bm, bn


def kept_blocks(n_in_blocks: int, sparsity: float) -> int:
    """Blocks kept per output block column (the same count in every
    column: block-balanced pruning)."""
    return max(1, round((1.0 - sparsity) * n_in_blocks))


def _prune(w, bm: int, bn: int, sparsity: float):
    """Zero all but the K largest-norm (bm, bn) blocks in each output
    block column of ``w`` (d_in, d_out). A block's norm is the float32
    sum of the squares of its weights."""
    import jax
    import jax.numpy as jnp
    d_in, d_out = w.shape
    ib, ob = d_in // bm, d_out // bn
    blocks = w.reshape(ib, bm, ob, bn).transpose(2, 0, 1, 3)
    norms = jnp.sum(jnp.square(blocks.astype(jnp.float32)), axis=(2, 3))
    _, idx = jax.lax.top_k(norms, kept_blocks(ib, sparsity))   # (ob, K)
    keep = np.zeros((ob, ib), bool)
    keep[np.arange(ob)[:, None], np.asarray(idx)] = True
    mask = np.repeat(np.repeat(keep.T, bm, axis=0), bn, axis=1)
    return jnp.where(mask, w, jnp.zeros_like(w))


def init_params(cfg: dict, seed: int) -> dict:
    """The configuration's weights from ``seed``.

    The recipe (the configuration's ``assumed``): one key per weighted
    layer, split from ``PRNGKey(seed)`` in the order of the layer table;
    each weight uniform in +-1/sqrt(fan_in), drawn in float32 and stored
    in bfloat16, with conv weights laid out (k*k*cin, cout) in HWIO row
    order and depthwise weights (k, k, C); biases zero; then the
    block-balanced magnitude pruning of :func:`_prune`.

    Each step runs op by op, not fused into one program: a block's
    norm is then one float32 sum of squares however the chip would fuse
    it, so near ties among block norms fall as the recipe's arithmetic
    says. Returns ``{layer: (w, b)}`` with ``w`` float32 holding the
    bfloat16 values."""
    import jax
    import jax.numpy as jnp
    layers = weighted(network(cfg))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(layers))
    out = {}
    for l, k in zip(layers, keys):
        if l.kind == "dw":
            shape, fan = (l.k, l.k, l.cin), l.k * l.k
        else:
            shape = (l.k * l.k * l.cin, l.cout)
            fan = shape[0]
        s = 1.0 / math.sqrt(fan)
        w = jax.random.uniform(k, shape, jnp.float32, -s, s)
        w = w.astype(jnp.bfloat16)
        blk = block_shape(l, cfg)
        if blk is not None:
            w = _prune(w, *blk, cfg["sparsity"])
        out[l.name] = (w.astype(jnp.float32),
                       jnp.zeros((l.cout,), jnp.float32))
    return out


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _apply(l: Layer, params, x, skip):
    import jax
    import jax.numpy as jnp
    from jax import lax
    if l.kind == "conv":
        w, b = params[l.name]
        w4 = w.reshape(l.k, l.k, l.cin, l.cout)
        y = lax.conv_general_dilated(
            x, w4, (l.stride, l.stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b
    elif l.kind == "dw":
        w, b = params[l.name]
        y = lax.conv_general_dilated(
            x, w.reshape(l.k, l.k, 1, l.cin), (l.stride, l.stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=l.cin) + b
    elif l.kind == "fc":
        w, b = params[l.name]
        y = x @ w + b
    elif l.kind == "maxpool":
        return lax.reduce_window(x, -jnp.inf, lax.max, (1, l.k, l.k, 1),
                                 (1, l.stride, l.stride, 1), "SAME")
    elif l.kind == "avgpool":
        return x.mean(axis=(1, 2))
    elif l.kind == "add":
        y = x + skip
    else:
        raise ValueError(f"unknown layer kind {l.kind!r}")
    return jax.nn.relu(y) if l.relu else y


def forward(cfg: dict, params, images):
    """images (N, H, W, 3) float32 -> logits (N, classes) float32.

    Arithmetic is float32. Where the configuration stores activations in
    a narrower type (``activation_dtype``), the input image and every
    layer's output but the logits are rounded to it, as stored, except
    the convs the configuration lists under ``fused_add``: their
    residual add is taken in float32 and only the sum is stored."""
    import jax
    import jax.numpy as jnp
    act = jnp.dtype(cfg.get("activation_dtype", "float32"))

    def store(x):
        return x.astype(act).astype(jnp.float32)

    net = network(cfg)
    unstored = set(cfg.get("fused_add", ()))
    env = {INPUT: store(images.astype(jnp.float32))}
    prev = INPUT
    with jax.default_matmul_precision("highest"):
        for l in net:
            x = env[l.src or prev]
            skip = env[l.residual] if l.residual else None
            y = _apply(l, params, x, skip)
            keep = l.kind == "fc" or l.name in unstored
            env[l.name] = y if keep else store(y)
            prev = l.name
    return env[prev]


def logits(cfg: dict, params, images: np.ndarray, *,
           block: int = 16) -> np.ndarray:
    """Reference logits for ``images``, ``block`` images per call so that
    the activations of a whole sample never sit on the device at once."""
    import jax
    fn = jax.jit(lambda p, x: forward(cfg, p, x))
    out = []
    for i in range(0, len(images), block):
        chunk = images[i:i + block]
        if len(chunk) < block:      # one compiled shape for every block
            pad = np.zeros((block - len(chunk),) + chunk.shape[1:],
                           chunk.dtype)
            chunk = np.concatenate([chunk, pad])
        out.append(np.asarray(fn(params, chunk)))
    return np.concatenate(out)[:len(images)]
