"""The sparse ResNet-50 cell ``r50_sparse.b1_stream``: what it reports,
its two kernel rooflines, and one run of it through the harness on the
CPU at 32 px (the chip check is skipped by steering
``bench.run.accelerator`` inside the test)."""
import json

import jax
import pytest

from bench import counts, peaks, spec
from bench import run as bench_run
from bench.tests._tiny import ROOT, copy_bench
from bench.tests.test_bench_harness import _answer_altered

CELL = "r50_sparse.b1_stream"


@pytest.fixture(scope="module")
def bench():
    return spec.load(ROOT)


def _names(bench, cell, group):
    return {m["name"] for m in spec.metrics_for(bench, cell, group)}


def test_committed_benchmark_holds_the_cell(bench):
    cell = bench["cells"][CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "resnet50_sparse85_224", "b1_closed_rounds", 1)
    cfg = bench["config_files"]["resnet50_sparse85_224"]
    assert (cfg["arch"], cfg["image_size"], cfg["sparsity"],
            cfg["reduced"]) == ("resnet50", 224, 0.85, [])


@pytest.mark.parametrize("cell,e2e,per_layer", [
    (CELL, {"images_per_s", "setup_s"},
     {"sparse_conv_roofline", "sparse_matmul_roofline", "mfu_pct",
      "empty_slot_pct.stream", "device_idle_pct.stream"}),
    ("mbv2.b1_stream", {"images_per_s", "setup_s"},
     {"empty_slot_pct.stream", "dw_pw_roofline", "mfu_pct",
      "device_idle_pct.stream"}),
    ("mbv2.b1_alone", {"latency_p50_ms", "latency_p95_ms", "setup_s"},
     {"empty_slot_pct.alone", "device_idle_pct.alone"}),
])
def test_metrics_each_cell_reports(bench, cell, e2e, per_layer):
    assert _names(bench, cell, "end_to_end") == e2e
    assert _names(bench, cell, "per_layer") == per_layer


def _run(bench, kernels, config="resnet50_sparse85_224", traced=True):
    return {"trace": {"kernels": kernels} if traced else None,
            "config": bench["config_files"][config], "mb_size": 1,
            "peaks": peaks.peaks("TPU v5 lite")}


@pytest.mark.parametrize("kernel,per_pass", [("sparse_conv", 47),
                                             ("sparse_matmul", 1)])
def test_roofline_reader_by_hand(bench, kernel, per_pass):
    """A pass makes ``per_pass`` calls of the kernel; traced calls that
    take ten times their least time read 10 %."""
    read = bench["readers"][kernel + "_roofline"]
    cfg = bench["config_files"]["resnet50_sparse85_224"]
    calls = [c for c in counts.kernel_calls(cfg) if c["kernel"] == kernel]
    assert len(calls) == per_pass
    least = sum(counts.least_seconds(c, peaks.peaks("TPU v5 lite"))
                for c in calls)
    for passes in (1, 3):
        got = read(_run(bench, {kernel: {"calls": passes * per_pass,
                                          "seconds": passes * 10 * least}}))
        assert got == pytest.approx(10.0, rel=1e-12)


@pytest.mark.parametrize("kernel", ["sparse_conv", "sparse_matmul"])
def test_roofline_reader_reads_nothing_where_nothing_ran(bench, kernel):
    read = bench["readers"][kernel + "_roofline"]
    timed = {kernel: {"calls": 47, "seconds": 1e-3}}
    assert read(_run(bench, timed, traced=False)) is None
    assert read(_run(bench, {})) is None
    assert read(_run(bench, {kernel: {"calls": 0, "seconds": 0.0}})) is None
    # MobileNet-V2's pass calls neither kernel
    assert read(_run(bench, timed, config="mobilenet_v2_224")) is None


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """A 32 px copy of the benchmark whose run takes the CPU for a chip."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setattr(bench_run, "accelerator",
                        lambda chips: jax.devices())
    root = copy_bench(str(tmp_path), image_size=32)
    got = spec.load(root)
    cell = got["cells"][CELL]
    yield (cell, got["config_files"][cell["config"]],
           got["traffic"][cell["traffic"]], got)
    for k, v in prev.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("fault", [None, _answer_altered])
def test_cell_runs_through_the_harness(tiny, monkeypatch, fault):
    """The program's sparse ResNet-50 at 32 px agrees with the plain
    reference through the harness; logits scaled by 1.1 do not."""
    cell, cfg, mix, got = tiny
    if fault is not None:
        fault(monkeypatch)
    res, rc = bench_run.run(cell, cfg, mix, got, seed=2**31 + 23,
                            seconds=0.3, traced=False)
    json.dumps(res)
    assert rc == 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}
    assert res["correct"] is (fault is None), res["checks"]
