"""The harness's refusals, and ``correct`` against the control and the
planted faults, on the CPU at 32 px (the chip check is skipped by
steering ``bench.run.accelerator`` inside the test)."""
import json

import jax
import numpy as np
import pytest

from bench import control
from bench import run as bench_run
from bench.tests._tiny import copy_bench


@pytest.fixture(autouse=True)
def _restore_jax_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    prev = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """A 32 px copy of the benchmark whose run takes the CPU for a chip."""
    monkeypatch.setattr(bench_run, "accelerator",
                        lambda chips: jax.devices())
    return copy_bench(str(tmp_path), image_size=32)


def _result_lines(out: str):
    return [l for l in out.splitlines() if l.startswith("{")]


def test_off_tpu_exits_nonzero_without_result(capsys):
    assert jax.devices()[0].platform != "tpu"
    rc = bench_run.main(["--workload", "mbv2.b1_alone", "--seed", "1",
                         "--seconds", "1"])
    assert rc != 0
    assert not _result_lines(capsys.readouterr().out)


def test_unknown_workload_fails(capsys):
    rc = bench_run.main(["--workload", "no_such.cell", "--seed", "1",
                         "--seconds", "1"])
    assert rc != 0
    cap = capsys.readouterr()
    assert not _result_lines(cap.out) and "unknown workload" in cap.err


def test_window_that_served_nothing_is_failed(tiny, capsys):
    rc = bench_run.main(["--workload", "mbv2.b1_alone", "--seed", "2",
                         "--seconds", "0"], root=tiny)
    res = json.loads(_result_lines(capsys.readouterr().out)[-1])
    assert rc != 0 and res["correct"] is False
    assert "latency_p50_ms" not in res["metrics"]
    assert res["checks"]["logits_rel_l2_max"]["value"] is None


def test_sound_run_is_correct(tiny, capsys):
    rc = bench_run.main(["--workload", "mbv2.b1_stream", "--seed",
                         str(2**31 + 9), "--seconds", "0.5"], root=tiny)
    res = json.loads(_result_lines(capsys.readouterr().out)[-1])
    assert rc == 0 and res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"images_per_s", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("cell", ["mbv2.b1_stream", "mbv2.b1_alone"])
def test_int8_control_is_not_correct(tiny, cell):
    """The program's int8 weight path, one precision below the
    configuration's bfloat16, fails the limit."""
    (res,) = control.readings(cell, [4], 0.3, "int8", root=tiny)
    assert res["correct"] is False
    c = res["checks"]["logits_rel_l2_max"]
    assert c["value"] > c["limit"]


def _tick_keeps_state(monkeypatch):
    from repro.core import pipeline
    monkeypatch.setattr(pipeline, "pipeline_step_hetero",
                        lambda fns, state, wire, **kw: (state, state[-1]))


def _half_never_answered(monkeypatch):
    from repro.launch.serve import CNNPipelineServer
    submit = CNNPipelineServer.submit

    def dropping(self, images):
        req = submit(self, images)
        if req % 2:             # its microbatches never reach the pipe
            while self._queue and self._queue[-1][0] == req:
                self._queue.pop()
        return req

    monkeypatch.setattr(CNNPipelineServer, "submit", dropping)


def _answer_altered(monkeypatch):
    from repro.models import cnn
    programs = cnn.stage_programs

    def altered(*a, **kw):
        out = programs(*a, **kw)
        unpack = out[2]
        return out[:2] + (lambda w: unpack(w) * 1.1,) + out[3:]

    monkeypatch.setattr(cnn, "stage_programs", altered)


@pytest.mark.parametrize("fault", [_tick_keeps_state, _half_never_answered,
                                   _answer_altered])
def test_planted_fault_is_not_correct(tiny, monkeypatch, capsys, fault):
    fault(monkeypatch)
    bench_run.main(["--workload", "mbv2.b1_stream", "--seed", "11",
                    "--seconds", "0.3"], root=tiny)
    res = json.loads(_result_lines(capsys.readouterr().out)[-1])
    assert res["correct"] is False


def test_benchmark_alone_exits_nonzero(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ has no
    program to measure: the run fails and prints no result."""
    import os
    import subprocess
    import sys
    root = copy_bench(str(tmp_path))
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mbv2.b1_alone",
         "--seed", "1", "--seconds", "1"], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not _result_lines(proc.stdout)
