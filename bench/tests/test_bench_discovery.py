"""The harness finds a new cell from new files and entries alone."""
import hashlib
import json
import os

import pytest

from bench import counts, reference, spec
from bench.tests._tiny import ROOT, copy_bench


def _digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


MBV1_TABLE = '''"""MobileNet-V1 (Howard et al., arXiv:1704.04861, Table 1)."""
from bench.reference import Layer, out_hw


def layers(cfg):
    hw, cin = cfg["image_size"], cfg["stem_width"]
    net = [Layer("conv1", "conv", 3, cin, 3, 2, hw)]
    hw = out_hw(hw, 2)
    for i, (cout, s) in enumerate(cfg["pointwise_c_s"]):
        net += [Layer(f"b{i}_dw", "dw", cin, cin, 3, s, hw),
                Layer(f"b{i}_pw", "conv", cin, cout, 1, 1, out_hw(hw, s))]
        cin, hw = cout, out_hw(hw, s)
    return net + [Layer("avgpool", "avgpool", cin, cin, hw, hw=hw),
                  Layer("fc", "fc", cin, cfg["num_classes"], relu=False)]
'''

MBV1_CONFIG = {
    "arch": "mobilenet_v1", "source": "https://arxiv.org/abs/1704.04861",
    "image_size": 32, "num_classes": 1000, "stem_width": 32,
    "pointwise_c_s": [[64, 1], [128, 2], [128, 1], [256, 2], [256, 1],
                      [512, 2]] + [[512, 1]] * 5 + [[1024, 2], [1024, 1]],
    "sparsity": 0.0, "activation_dtype": "bfloat16", "fused_add": [],
    "reduced": ["image_size"], "assumed": ["random weights"],
    "reference": "bench/networks/mobilenet_v1.py",
    "limits": {"logits_rel_l2_max": 0.006}}


def _add_cell(root):
    """A new network, configuration, open-loop mix, metric and cell, from
    new files and new entries in BENCHMARK.json alone."""
    b = os.path.join(root, "bench")
    with open(os.path.join(b, "networks", "mobilenet_v1.py"), "w") as f:
        f.write(MBV1_TABLE)
    with open(os.path.join(b, "configs", "mobilenet_v1_32.json"), "w") as f:
        json.dump(MBV1_CONFIG, f)
    with open(os.path.join(b, "traffic", "b1_poisson.json"), "w") as f:
        json.dump({"loop": "open", "rate_per_s": 40, "arrival": "poisson",
                   "images_per_request": 1, "pool_images": 4,
                   "sample_requests": 4}, f)
    with open(os.path.join(b, "metrics", "ticks_per_round.py"), "w") as f:
        f.write("def read(run):\n    return run['counters']['ticks']\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "mobilenet_v1_32", "source": MBV1_CONFIG["source"],
        "file": "bench/configs/mobilenet_v1_32.json",
        "reduced": ["image_size"], "why": "a network of its own"})
    bench["workloads"].append({
        "name": "mbv1.b1_poisson", "config": "mobilenet_v1_32",
        "traffic": "b1_poisson", "chips": 1, "why": "open-loop arrivals"})
    lat = next(m for m in bench["end_to_end"]
               if m["name"] == "latency_p95_ms")
    lat["workloads"].append("mbv1.b1_poisson")
    bench["per_layer"].append({
        "name": "ticks_per_round", "unit": "ticks", "better": "lower",
        "source": "program_counter", "layer": "pipeline executor",
        "moves": "latency_p95_ms", "workloads": ["mbv1.b1_poisson"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_new_config_mix_metric_and_cell_are_found(tmp_path):
    root = copy_bench(str(tmp_path))
    before = _digests(root)
    _add_cell(root)

    got = spec.load(root)
    cfg = got["config_files"]["mobilenet_v1_32"]
    net = reference.network(cfg)
    assert [l.name for l in net][-3:] == ["b12_pw", "avgpool", "fc"]
    assert {c["kernel"] for c in counts.kernel_calls(cfg)} == {"dw_pw"}
    assert got["traffic"]["b1_poisson"]["loop"] == "open"
    assert got["readers"]["ticks_per_round"]({"counters": {"ticks": 7}}) == 7
    names = [m["name"] for m in spec.metrics_for(got, "mbv1.b1_poisson",
                                                 "per_layer")]
    assert names == ["ticks_per_round"]
    e2e = [m["name"] for m in spec.metrics_for(got, "mbv1.b1_poisson",
                                               "end_to_end")]
    assert e2e == ["latency_p95_ms", "setup_s"]
    new = _digests(root)
    assert {k: new[k] for k in before} == before     # no file was edited


def test_new_network_and_open_loop_run_end_to_end(tmp_path, monkeypatch,
                                                  capsys):
    """The new cell runs through the harness unchanged: the program's
    MobileNet-V1 at 32 px, driven by open-loop arrivals, agrees with the
    new layer table's reference."""
    import jax
    from bench import run as bench_run
    monkeypatch.setattr(bench_run, "accelerator",
                        lambda chips: jax.devices())
    root = copy_bench(str(tmp_path), image_size=32)
    _add_cell(root)
    rc = bench_run.main(["--workload", "mbv1.b1_poisson", "--seed",
                         str(2**31 + 17), "--seconds", "0.5"], root=root)
    res = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rc == 0 and res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] == 20
    assert set(res["metrics"]) == {"latency_p95_ms", "setup_s"}


def test_committed_benchmark_is_valid():
    got = spec.load(ROOT)
    for cell in got["cells"]:
        assert spec.metrics_for(got, cell, "per_layer")
        e2e = [m["name"] for m in spec.metrics_for(got, cell, "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
    for m in got["end_to_end"] + got["per_layer"]:
        spec.check_name(m["name"], "metric")
        spec.check_unit(m["unit"], m["name"])


@pytest.mark.parametrize("bad", ["has space", "a,b", "a/b", ".dot", "",
                                 "x" * 65, "µs"])
def test_bad_names_are_refused(bad):
    with pytest.raises(spec.SpecError):
        spec.check_name(bad, "metric")


@pytest.mark.parametrize("bad", ["tokens per second", "µs", "", "x" * 17])
def test_bad_units_are_refused(bad):
    with pytest.raises(spec.SpecError):
        spec.check_unit(bad, "metric")


def test_one_reader_serves_names_split_by_cell(tmp_path):
    root = copy_bench(str(tmp_path))
    got = spec.load(root)
    assert (spec.reader_path(root, "bench", "empty_slot_pct.alone")
            == spec.reader_path(root, "bench", "empty_slot_pct.stream"))
    assert got["readers"]["device_idle_pct.alone"]({"trace": None}) is None


def test_missing_reader_is_refused(tmp_path):
    root = copy_bench(str(tmp_path))
    os.remove(os.path.join(root, "bench", "metrics", "mfu_pct.py"))
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.load(root)
