"""Operation and byte counters, the peaks table and the trace reduction."""
import json
import os
from collections import Counter

import pytest

from bench import counts, peaks, trace
from bench import reference as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TRACE = os.path.join(ROOT, "bench", "testdata", "mbv2_alone.xplane.pb")


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


R50, MBV2 = _cfg("resnet50_sparse85_224"), _cfg("mobilenet_v2_224")


def _call(cfg, layer):
    return next(c for c in counts.kernel_calls(cfg) if c["layer"] == layer)


def test_sparse_3x3_layer_by_hand():
    """ResNet-50 s1b1_c2: 3x3, 128 -> 128 channels at 28x28. 9 x 128 =
    1152 input rows in 36 blocks of 32; 85 % pruned keeps round(5.4) = 5
    blocks (160 rows) in each of the 4 output block columns."""
    c = _call(R50, "s1b1_c2")
    assert c["kernel"] == "sparse_conv"
    assert c["ops"] == 2 * 28 * 28 * 160 * 128
    weights = 4 * 5 * 32 * 32 * 2 + 128 * 2 + 4 * 5 * 4   # vals, bias, ids
    act = 28 * 28 * 128 * 2
    assert c["bytes"] == act + weights + act


def test_dw_pw_layer_by_hand():
    """MobileNet-V2 s2b1: 32 -> 192 expanded channels, 3x3 depthwise at
    28x28, 1x1 projection back to 32, plus the residual add."""
    c = _call(MBV2, "s2b1_dw+s2b1_pj")
    assert c["kernel"] == "dw_pw"
    assert c["ops"] == 2 * (28 * 28 * 9 * 192 + 28 * 28 * 192 * 32)
    out = 28 * 28 * 32 * 2
    assert c["bytes"] == (28 * 28 * 192 * 2 + (9 * 192 + 192) * 2
                          + (192 * 32 + 32) * 2 + out + out)


def test_kernel_calls_match_the_program():
    """The counter's kernel calls are the program's: one sparse_conv per
    pruned conv node of the fused graph, one sparse_matmul for the pruned
    classifier, one dw_pw per fused depthwise-pointwise node."""
    import jax
    from repro.configs import get_config
    from repro.core.fusion import conv_part, fused_graph_for
    from repro.models import cnn
    from repro.models.layers import SparseWeight
    for arch, cfg in (("resnet50", R50), ("mobilenet_v2", MBV2)):
        params = cnn.init_cnn(get_config(arch), jax.random.PRNGKey(0))
        prog = Counter()
        for n in fused_graph_for(arch).nodes:
            if n.kind == "dw_pw":
                prog["dw_pw"] += 1
            elif n.kind in ("conv", "fc", "avgpool_fc") and isinstance(
                    params[conv_part(n).name]["w"], SparseWeight):
                prog["sparse_matmul" if n.kind != "conv"
                     else "sparse_conv"] += 1
        assert Counter(c["kernel"] for c in counts.kernel_calls(cfg)) == prog
    assert prog == {"dw_pw": 17}


def test_image_ops_counts_kept_blocks_only():
    dense = dict(R50, sparsity=0.0)
    assert counts.image_ops(R50) < 0.5 * counts.image_ops(dense)
    # dense ResNet-50 v1 (stride on the 1x1 conv): about 3.86 G
    # multiply-adds per 224 px image
    assert 7.6e9 < counts.image_ops(dense) < 7.8e9


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_reduce_synthetic_trace():
    S = trace.Span
    host = [S(trace.WINDOW, 0, 100), S("bench.run", 10, 90),
            S("PjitFunction(tick)", 20, 30)]
    dev = {"/device:TPU:0": [S("sparse_conv.1", 0, 20), S("fusion.3", 15, 25),
                             S("sparse_conv.2", 40, 50), S("copy.1", 95, 120)]}
    red = trace.reduce(dev, host, kernels=("sparse_conv", "dw_pw"))
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)      # [0,25] [40,50] [95,100]
    assert red["kernels"]["sparse_conv"] == {"seconds": pytest.approx(30e-9),
                                             "calls": 2}
    assert red["kernels"]["dw_pw"]["calls"] == 0
    gaps = dict(red["breakdown"]["idle_gaps"])
    # gaps 25-40 (midpoint 32: inside bench.run only) and 50-95
    assert gaps == {"bench.run": pytest.approx(60e-9)}
    assert red["breakdown"]["device_ops"] == [
        ["sparse_conv", pytest.approx(30e-9)], ["fusion", pytest.approx(10e-9)],
        ["copy", pytest.approx(5e-9)]]


def test_reduce_recorded_chip_trace():
    """A trace recorded on one TPU v5e chip by a traced run of
    mbv2.b1_alone (0.25 s traced), trimmed to what the reduction reads:
    the TPU's ``XLA Ops`` line and the host thread that opened the
    window, without per-event stats, and with the recording host's
    checkout path renamed in its source locations. The run itself
    reported busy_s
    0.20978565400000002 and window_s 0.228116579."""
    red = trace.reduce(*trace.read_xplane(TRACE), kernels=("dw_pw",))
    assert red["window_s"] == pytest.approx(0.228116579, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.20978565400000002, rel=1e-9)
    calls = red["kernels"]["dw_pw"]["calls"]
    assert calls == 24 * 17                    # whole ticks, 17 calls each
    assert 0 < red["kernels"]["dw_pw"]["seconds"] < red["busy_s"]
    for key in ("device_ops", "idle_gaps"):
        rows = red["breakdown"][key]
        assert 0 < len(rows) <= trace.TOP
        assert all(isinstance(n, str) and s > 0 for n, s in rows)
    ops = dict(red["breakdown"]["device_ops"])
    assert {"reshape", "dw_pw"} <= set(ops)
    run = {"trace": red, "config": MBV2, "mb_size": 1,
           "peaks": peaks.peaks("TPU v5 lite")}
    assert 0 < counts.roofline_pct(run, "dw_pw") < 100
