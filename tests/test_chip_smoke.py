"""chip_smoke.py on the CPU: it must refuse to run without a TPU (and
without the repository around it), and its checks must hold on the
serving path at a tiny size. The chip run itself happens on a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _has_ok_line(stdout):
    for line in stdout.splitlines():
        try:
            if json.loads(line).get("ok"):
                return True
        except (ValueError, AttributeError):
            pass
    return False


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_smoke_fails_without_tpu(tmp_path, where):
    cwd = ROOT
    if where == "alone":
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    r = _run(cwd)
    assert r.returncode != 0
    assert not _has_ok_line(r.stdout)
    assert "no TPU" in r.stderr


def test_check_rows_accepts_own_devices_only():
    cs.check_rows("4x1", [[0], [1], [2], [3]], 4, 1)
    cs.check_rows("2x2", [[0, 2], [1, 3]], 2, 2)
    for rows, s, r in (([[0], [0], [2], [3]], 4, 1),  # 2 stages on dev 0
                       ([[0, 1], [1, 3]], 2, 2),      # dev 1 holds both
                       ([[0], [1]], 4, 1),            # stages missing
                       (None, 4, 1)):                 # nothing placed
        with pytest.raises(cs.SmokeFailure):
            cs.check_rows("bad", rows, s, r)


def test_check_logits_bounds_and_finiteness():
    rng = np.random.default_rng(0)
    want = rng.normal(size=(4, 1000))
    assert cs.check_logits("ok", want * (1 + 1e-3), want, 1e-2) < 1e-2
    with pytest.raises(cs.SmokeFailure, match="relative L2"):
        cs.check_logits("far", want + 1.0, want, 1e-2)
    bad = want.copy()
    bad[0, 0] = np.nan
    with pytest.raises(cs.SmokeFailure, match="non-finite"):
        cs.check_logits("nan", bad, want, 1e-2)
    with pytest.raises(cs.SmokeFailure, match="degenerate"):
        cs.check_logits("flat", np.zeros((4, 10)), np.zeros((4, 10)), 1e-2)


def test_phase_serves_and_matches_xla_reference(capsys):
    """One latency phase end to end at 32 px on the CPU (XLA twins):
    the served logits come back with their images and agree with the
    "highest"-precision reference."""
    from repro.launch.serve import ServeConfig
    res = cs.run_phase("T", ServeConfig(arch="mobilenet_v2",
                                        mode="latency", n_requests=2,
                                        image_size=32, verbose=False),
                       kernels_needed=(), seed=0)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "T" and line["rel_l2_vs_xla_highest"] <= \
        cs.REL_L2_VS_XLA
    assert res["request_images"].shape == (2, 32, 32, 3)
    assert res["mosaic_kernels"] == {}       # interpreted/XLA off TPU
