"""Operations and bytes of the networks, from their layer tables.

Counts are the algorithm's, not the kernel's tiling: 2 operations per
multiply-add that a kept weight takes part in (a pruned block does no
work), and the bytes of each kernel call's operands and result in the
dtypes the configuration stores them (bfloat16 activations and weights,
int32 block ids), each read or written once.
"""
from __future__ import annotations

from bench import reference as R

ACT_BYTES = 2        # bfloat16 activations
W_BYTES = 2          # bfloat16 weights and biases
IDX_BYTES = 4        # int32 block ids


def out_hw(l: R.Layer) -> int:
    return R.out_hw(l.hw, l.stride)


def macs(l: R.Layer, cfg: dict) -> int:
    """Multiply-adds of one image through layer ``l``, counting only the
    kept blocks of a pruned weight."""
    ho = out_hw(l)
    if l.kind == "conv":
        d_in = l.k * l.k * l.cin
        blk = R.block_shape(l, cfg)
        if blk is not None:
            bm, _ = blk
            d_in = R.kept_blocks(d_in // bm, cfg["sparsity"]) * bm
        return ho * ho * d_in * l.cout
    if l.kind == "dw":
        return ho * ho * l.k * l.k * l.cin
    if l.kind == "fc":
        d_in = l.cin
        blk = R.block_shape(l, cfg)
        if blk is not None:
            d_in = R.kept_blocks(d_in // blk[0], cfg["sparsity"]) * blk[0]
        return d_in * l.cout
    return 0


def image_ops(cfg: dict) -> int:
    """Operations one image needs: 2 x multiply-adds of every conv,
    depthwise and fully connected layer (pooling and adds not counted)."""
    return 2 * sum(macs(l, cfg) for l in R.network(cfg))


def _weight_bytes(l: R.Layer, cfg: dict) -> int:
    if l.kind == "dw":
        return (l.k * l.k * l.cin + l.cin) * W_BYTES
    d_in = l.k * l.k * l.cin
    blk = R.block_shape(l, cfg)
    if blk is None:
        return (d_in * l.cout + l.cout) * W_BYTES
    bm, bn = blk
    n_k = R.kept_blocks(d_in // bm, cfg["sparsity"])
    ob = l.cout // bn
    return (ob * n_k * bm * bn + l.cout) * W_BYTES + ob * n_k * IDX_BYTES


def _in_bytes(l: R.Layer, n: int) -> int:
    # a 1x1 conv at stride s reads only every s-th row and column
    hw = out_hw(l) if l.k == 1 else l.hw
    return n * hw * hw * l.cin * ACT_BYTES


def kernel_calls(cfg: dict, n: int = 1) -> list[dict]:
    """The Pallas kernel calls one forward pass of ``n`` images makes,
    in layer order: ``{"kernel", "layer", "ops", "bytes"}``.

    - ``sparse_conv``: every pruned conv;
    - ``sparse_matmul``: a pruned fully connected layer;
    - ``dw_pw``: a depthwise conv followed by a dense 1x1 conv at
      stride 1, as one call;
    - ``depthwise_conv``: any other depthwise conv.

    A conv that the configuration lists under ``fused_add`` reads its
    residual operand in the call's epilogue.
    """
    net = R.network(cfg)
    fed = set(cfg.get("fused_add", ()))
    calls = []
    for i, l in enumerate(net):
        blk = R.block_shape(l, cfg)
        if l.kind == "conv" and blk is not None:
            ho = out_hw(l)
            res = l.name in fed
            out = n * ho * ho * l.cout * ACT_BYTES
            calls.append(dict(
                kernel="sparse_conv", layer=l.name,
                ops=2 * n * macs(l, cfg),
                bytes=_in_bytes(l, n) + _weight_bytes(l, cfg) + out
                + (out if res else 0)))
        elif l.kind == "fc" and blk is not None:
            calls.append(dict(
                kernel="sparse_matmul", layer=l.name,
                ops=2 * n * macs(l, cfg),
                bytes=n * l.cin * 4 + _weight_bytes(l, cfg)
                - l.cout * W_BYTES + n * l.cout * 4))
        elif l.kind == "dw":
            pw = net[i + 1] if i + 1 < len(net) else None
            ho = out_hw(l)
            if not (pw and pw.kind == "conv" and pw.k == 1 and pw.stride == 1
                    and pw.src is None and R.block_shape(pw, cfg) is None):
                calls.append(dict(
                    kernel="depthwise_conv", layer=l.name,
                    ops=2 * n * macs(l, cfg),
                    bytes=_in_bytes(l, n) + _weight_bytes(l, cfg)
                    + n * ho * ho * l.cin * ACT_BYTES))
                continue
            out = n * ho * ho * pw.cout * ACT_BYTES
            res = pw.name in fed
            calls.append(dict(
                kernel="dw_pw", layer=f"{l.name}+{pw.name}",
                ops=2 * n * (macs(l, cfg) + macs(pw, cfg)),
                bytes=_in_bytes(l, n) + _weight_bytes(l, cfg)
                + _weight_bytes(pw, cfg) + out + (out if res else 0)))
    return calls


def least_seconds(call: dict, peak: dict) -> float:
    """The least time the chip could take for one call: the larger of
    its operations over the peak rate and its bytes over the bandwidth."""
    return max(call["ops"] / peak["flops_bf16"],
               call["bytes"] / peak["hbm_bytes_per_s"])


def roofline_pct(run: dict, kernel: str):
    """Least time of the kernel's traced calls over their device time.

    Each forward pass makes the calls :func:`kernel_calls`
    lists; the traced calls are taken as whole forward passes of them,
    so their least time is (calls / calls per pass) x the least time of
    one pass. Nothing traced, or a kernel the pass never calls, gives
    None."""
    t = run["trace"]
    if t is None:
        return None
    k = t["kernels"].get(kernel)
    per_pass = [c for c in kernel_calls(run["config"], run["mb_size"])
                if c["kernel"] == kernel]
    if not k or not k["calls"] or not per_pass or k["seconds"] <= 0:
        return None
    least = sum(least_seconds(c, run["peaks"]) for c in per_pass)
    return 100.0 * least * k["calls"] / len(per_pass) / k["seconds"]
