"""Layer-pipeline executor: correctness vs sequential execution.

Multi-device cases run in a subprocess with forced host device count so
the rest of the suite keeps the default single device.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import pipeline as pp


def test_stack_stages_heterogeneous():
    blocks = {"w": jnp.arange(8 * 3).reshape(8, 3).astype(jnp.float32)}
    stage_of = [0, 0, 0, 1, 1, 2, 2, 3]
    stacked, mask = pp.stack_stages(blocks, stage_of, 4)
    assert stacked["w"].shape == (4, 3, 3)
    assert mask.tolist() == [[True, True, True], [True, True, False],
                             [True, True, False], [True, False, False]]
    np.testing.assert_array_equal(np.asarray(stacked["w"][0]),
                                  np.asarray(blocks["w"][:3]))
    np.testing.assert_array_equal(np.asarray(stacked["w"][3][0]),
                                  np.asarray(blocks["w"][7]))


def test_bubble_fraction():
    assert pp.bubble_fraction(1, 1) == 0.0
    assert abs(pp.bubble_fraction(4, 4) - 3 / 7) < 1e-9
    assert pp.bubble_fraction(64, 2) < 0.02


_SUB = textwrap.dedent("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core import pipeline as pp
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("pod", "data"))
    L, D = 8, 16
    key = jax.random.PRNGKey(0)
    blocks = {"w": jax.random.normal(key, (L, D, D)) * 0.1}
    def block_fn(p, x):
        return x + jnp.tanh(x @ p["w"])
    stage_of = [0,0,0,1,1,2,2,3]
    stacked, mask = pp.stack_stages(blocks, stage_of, 4)
    stacked = jax.device_put(stacked, NamedSharding(mesh, P("pod")))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, D))
    x_mb = pp.microbatch(x, 4)
    stage_fn = pp.make_stage_fn(block_fn)
    ref = x
    for l in range(L):
        ref = block_fn({"w": blocks["w"][l]}, ref)
    mesh_ctx = jax.set_mesh(mesh)
    with mesh_ctx:
        out1 = jax.jit(lambda sp, m, xmb: pp.pipeline_apply(
            stage_fn, sp, m, xmb, mesh=mesh, stage_axis="pod",
            n_stages=4))(stacked, mask, x_mb).reshape(8, 4, D)
        out2 = jax.jit(lambda sp, m, xmb: pp.pipeline_apply_gspmd(
            stage_fn, sp, m, xmb, n_stages=4, stage_axis="pod",
            mesh=mesh))(stacked, mask, x_mb).reshape(8, 4, D)
        def loss(sp, xmb):
            o = pp.pipeline_apply_gspmd(stage_fn, sp, mask, xmb,
                                        n_stages=4, mesh=mesh)
            return (o ** 2).mean()
        g = jax.jit(jax.grad(loss))(stacked, x_mb)
    assert float(jnp.abs(out1 - ref).max()) < 1e-5, "shard_map pipeline"
    assert float(jnp.abs(out2 - ref).max()) < 1e-5, "gspmd pipeline"
    assert bool(jnp.isfinite(g["w"]).all()), "grads"
    print("SUBPROCESS_OK")
""")


def test_pipeline_matches_sequential_multidevice():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(os.path.dirname(__file__), "..", "src"),
                    os.environ.get("PYTHONPATH", "")]))
    r = subprocess.run([sys.executable, "-c", _SUB], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "SUBPROCESS_OK" in r.stdout, r.stdout + r.stderr
