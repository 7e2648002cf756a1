"""Optimizer, data, checkpoint, fault tolerance, compression."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # container has no hypothesis
    from _hypothesis_stub import given, settings, strategies as st

from repro.checkpoint import ckpt
from repro.configs.base import SparsityConfig
from repro.core import sparsity as S
from repro.data.pipeline import DataConfig, MarkovStream, image_batch
from repro.launch.mesh import make_mesh
from repro.optim import adamw
from repro.runtime import fault


def test_adamw_minimizes_quadratic():
    cfg = adamw.AdamWConfig(lr=0.1, warmup_steps=1, total_steps=100,
                            weight_decay=0.0)
    params = {"x": jnp.ones((4,)) * 5.0}
    st_ = adamw.init(params)
    for _ in range(100):
        g = jax.grad(lambda p: jnp.sum(p["x"] ** 2))(params)
        params, st_, _ = adamw.update(cfg, params, g, st_)
    assert float(jnp.abs(params["x"]).max()) < 0.5


def test_adamw_skips_integer_leaves():
    w = jax.random.normal(jax.random.PRNGKey(0), (32, 32))
    sw = S.to_block_balanced(
        w, SparsityConfig(enabled=True, sparsity=0.5, block_m=8, block_n=8))
    params = {"s": sw}
    st_ = adamw.init(params)
    g = jax.tree.map(lambda a: jnp.ones_like(a), params)
    p2, _, _ = adamw.update(adamw.AdamWConfig(), params, g, st_)
    assert (np.asarray(p2["s"].idx) == np.asarray(sw.idx)).all()
    assert not np.allclose(np.asarray(p2["s"].vals), np.asarray(sw.vals))


def test_schedule_warmup_and_decay():
    cfg = adamw.AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_frac=0.1)
    lrs = [float(adamw.schedule(cfg, jnp.int32(i))) for i in range(101)]
    assert lrs[0] == 0.0
    assert abs(lrs[10] - 1.0) < 1e-6
    assert lrs[100] == pytest.approx(0.1, abs=1e-6)
    assert all(a >= b - 1e-9 for a, b in zip(lrs[10:], lrs[11:]))


def test_data_determinism_across_instances():
    dc = DataConfig(vocab_size=64, seq_len=8, global_batch=4, n_shards=2,
                    shard_id=1, seed=3)
    a, b = MarkovStream(dc), MarkovStream(dc)
    for step in (0, 7, 123):
        assert (a.batch(step)["tokens"] == b.batch(step)["tokens"]).all()


def test_data_shards_disjoint():
    mk = lambda sid: MarkovStream(DataConfig(
        vocab_size=64, seq_len=8, global_batch=4, n_shards=2, shard_id=sid))
    t0, t1 = mk(0).batch(5)["tokens"], mk(1).batch(5)["tokens"]
    assert not (t0 == t1).all()


def test_image_batch_shapes():
    b = image_batch(0, batch=2, size=32)
    assert b["images"].shape == (2, 32, 32, 3)
    assert b["labels"].shape == (2,)


def test_checkpoint_roundtrip_and_gc():
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.float32),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    with tempfile.TemporaryDirectory() as d:
        for step in (1, 2, 3, 4, 5):
            ckpt.save(tree, d, step, keep=2)
        assert ckpt.latest_step(d) == 5
        assert len([x for x in os.listdir(d) if x.startswith("step_")]) == 2
        got, step = ckpt.restore(tree, d)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(got["a"]),
                                      np.asarray(tree["a"]))
        assert got["b"]["c"].dtype == jnp.bfloat16


def test_checkpoint_async_saver():
    tree = {"x": jnp.ones((8, 8))}
    with tempfile.TemporaryDirectory() as d:
        sv = ckpt.AsyncSaver()
        sv.save(tree, d, 1)
        sv.save(tree, d, 2)      # waits for first
        sv.wait()
        assert ckpt.latest_step(d) == 2


def test_run_with_restarts_recovers():
    with tempfile.TemporaryDirectory() as d:
        inj = fault.FailureInjector(fail_at_steps=(7, 13))
        mk = lambda: {"x": jnp.zeros(())}
        state, restarts, executed = fault.run_with_restarts(
            mk, lambda s, i: {"x": s["x"] + 1}, n_steps=20,
            ckpt_dir=d, ckpt_every=5, injector=inj)
        assert restarts == 2
        assert float(state["x"]) == 20.0     # correct despite replays
        assert executed > 20                 # replay happened


def test_run_with_restarts_gives_up():
    with tempfile.TemporaryDirectory() as d:
        inj = fault.FailureInjector(fail_at_steps=(3,))
        inj._fired = set()

        class Always(fault.FailureInjector):
            def maybe_fail(self, step):
                if step == 3:
                    raise fault.InjectedFailure("always")
        with pytest.raises(fault.InjectedFailure):
            fault.run_with_restarts(
                lambda: {"x": jnp.zeros(())},
                lambda s, i: {"x": s["x"] + 1}, n_steps=10, ckpt_dir=d,
                ckpt_every=100, max_restarts=2, injector=Always())


def test_straggler_detection():
    sd = fault.StragglerDetector(threshold=2.0)
    for i in range(8):
        assert not sd.record(0, i, 1.0 + 0.01 * i)
    assert sd.record(3, 8, 10.0)
    assert len(sd.flagged) == 1
    assert sd.flagged[0][0] == 3


def test_straggler_persistent_slow_host_not_masked():
    """A host that is ALWAYS slow — and reports more often than its
    peers — must still be flagged. The old pooled window let such a
    host fill the shared median with its own samples (3 slow samples
    per 1 fast one -> pooled median 10.0 -> 10.0 looks normal);
    per-host windows judged against the OTHER hosts' medians keep the
    reference clean."""
    sd = fault.StragglerDetector(threshold=2.0, window=8)
    flags = []
    for step in range(6):
        sd.record(0, step, 1.0)          # one healthy sample...
        for k in range(3):               # ...vs three slow ones
            flags.append(sd.record(3, step, 10.0))
    # every slow sample after warmup (4 total samples) is flagged
    assert flags[2:] == [True] * len(flags[2:])
    assert all(f[0] == 3 for f in sd.flagged)
    # and the healthy host never is
    assert not sd.record(0, 99, 1.0)


@settings(max_examples=20, deadline=None)
@given(scale=st.floats(1e-3, 1e3))
def test_compression_roundtrip_error_bounded(scale):
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal(64),
                          jnp.float32) * scale}
    err = fault.init_error(g)
    qg, err2 = fault.compress_grads(g, err)
    deq = fault.decompress_grads(qg)
    max_abs = float(jnp.abs(g["w"]).max())
    # int8 symmetric: error bounded by half a quantization step
    assert float(jnp.abs(deq["w"] - g["w"]).max()) <= max_abs / 127.0


def test_compression_error_feedback_unbiased():
    """With error feedback the *accumulated* quantized sum converges to
    the accumulated true sum."""
    rng = np.random.default_rng(1)
    g = {"w": jnp.asarray(rng.standard_normal(128), jnp.float32) * 1e-3}
    err = fault.init_error(g)
    total_q = np.zeros(128, np.float32)
    for _ in range(50):
        qg, err = fault.compress_grads(g, err)
        total_q += np.asarray(fault.decompress_grads(qg)["w"])
    total_true = np.asarray(g["w"]) * 50
    assert np.abs(total_q - total_true).max() < np.abs(
        np.asarray(g["w"])).max() * 2


def test_remesh_changes_device_layout():
    mesh = make_mesh((1,), ("data",))
    tree = {"w": jnp.ones((8, 4))}
    from jax.sharding import PartitionSpec as P
    out = fault.remesh(tree, mesh, mesh, lambda p, l: P())
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(tree["w"]))
