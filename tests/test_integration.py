"""End-to-end: training converges, survives failures; serving decodes;
shardings are well-formed for every arch."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs, get_config, reduced
from repro.launch.mesh import make_mesh


def test_train_loss_decreases():
    from repro.launch.train import train
    out = train("smollm-360m", steps=40, batch=8, seq=64, verbose=False,
                lr=3e-3)
    first = np.mean([l for _, l in out["losses"][:3]])
    last = np.mean([l for _, l in out["losses"][-5:]])
    assert last < first - 0.05, (first, last)


def test_train_restart_reaches_same_final_state():
    """Determinism: a run interrupted twice and restarted from
    checkpoints must end at the same loss as an uninterrupted run."""
    from repro.launch.train import train
    with tempfile.TemporaryDirectory() as d1:
        clean = train("smollm-360m", steps=25, batch=4, seq=32,
                      ckpt_dir=d1, ckpt_every=5, verbose=False)
    with tempfile.TemporaryDirectory() as d2:
        faulty = train("smollm-360m", steps=25, batch=4, seq=32,
                       ckpt_dir=d2, ckpt_every=5, fail_at=(8, 17),
                       verbose=False)
    assert faulty["restarts"] == 2
    clean_last = clean["losses"][-1]
    faulty_last = faulty["losses"][-1]
    assert clean_last[0] == faulty_last[0]
    assert abs(clean_last[1] - faulty_last[1]) < 1e-3


def test_train_with_grad_compression():
    from repro.launch.train import train
    out = train("smollm-360m", steps=30, batch=8, seq=64, verbose=False,
                grad_compress=True, lr=3e-3)
    first = np.mean([l for _, l in out["losses"][:3]])
    last = np.mean([l for _, l in out["losses"][-5:]])
    assert last < first


@pytest.mark.parametrize("arch", ["smollm-360m", "rwkv6-1.6b",
                                  "granite-moe-3b-a800m"])
def test_serve_generates(arch):
    from repro.launch.serve import serve_lm
    out = serve_lm(arch, batch=2, prompt_len=6, gen_tokens=4, max_seq=32,
                   verbose=False)
    assert out["tokens"].shape == (2, 4)
    assert out["tokens"].dtype.kind in "iu"


def test_param_shardings_consistent_all_archs():
    """Every param/cache leaf gets a spec whose sharded dims divide."""
    from repro.launch import shardings as sh
    from repro.models import lm
    mesh = make_mesh((1,), ("model",))

    class FakeMesh:                      # 16x16 shape lookup only
        axis_names = ("data", "model")
        shape = {"data": 16, "model": 16}

    for name, cfg in all_configs().items():
        if cfg.family == "cnn":
            continue
        shapes = lm.abstract_params(cfg)
        flat, _ = jax.tree_util.tree_flatten_with_path(shapes)
        pure_dp = sh.use_pure_dp(cfg)
        for path, leaf in flat:
            spec = sh.param_spec(path, leaf, FakeMesh(), pure_dp=pure_dp)
            for i, p in enumerate(tuple(spec)):
                if p is not None:
                    assert leaf.shape[i] % 16 == 0, (name, path, leaf.shape)
        cache = jax.eval_shape(lambda: lm.init_cache(cfg, 128, 1024))
        cflat, _ = jax.tree_util.tree_flatten_with_path(cache)
        for path, leaf in cflat:
            spec = sh.cache_spec(path, leaf, FakeMesh(), pure_dp=pure_dp)
            sizes = {"data": 16, "model": 16}
            for i, p in enumerate(tuple(spec)):
                if p is None:
                    continue
                k = 1
                for ax in (p if isinstance(p, tuple) else (p,)):
                    k *= sizes[ax]
                assert leaf.shape[i] % k == 0, (name, path, leaf.shape, spec)


def test_analytic_costs_positive_all_cells():
    from repro.configs import SHAPES, applicable
    from repro.core import costmodel as cm
    for name, cfg in all_configs().items():
        if cfg.family == "cnn":
            continue
        for sname, shape in SHAPES.items():
            if not applicable(cfg, shape):
                continue
            f = cm.step_flops_global(cfg, shape)
            b = cm.step_bytes_per_device(cfg, shape, n_chips=256,
                                         n_model_shards=16, pure_dp=False)
            h = cm.hbm_estimate_per_device(cfg, shape, n_chips=256,
                                           n_model_shards=16, pure_dp=False)
            assert f > 0 and b > 0 and h > 0, (name, sname)


def test_hlo_collective_parser():
    from repro.launch.dryrun import collective_bytes, _op_output_bytes
    hlo = """
  %ag = bf16[4,8]{1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[16]{0} all-reduce-start(%y), to_apply=%add
  %cp = (u32[], bf16[2,2]) collective-permute(%z), source_target_pairs={{0,1}}
  %dot = f32[4,4]{1,0} dot(%a, %b)
"""
    out = collective_bytes(hlo)
    assert out["bytes"]["all-gather"] == 4 * 8 * 2
    assert out["bytes"]["all-reduce"] == 16 * 4
    assert out["bytes"]["collective-permute"] == 4 + 2 * 2 * 2
    assert out["total_bytes"] == sum(out["bytes"].values())
