"""The plain reference against the program, on the CPU at small sizes."""
import json
import os

import jax
import numpy as np
import pytest

from bench import reference as R

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIGS = {"resnet50": "resnet50_sparse85_224",
           "mobilenet_v2": "mobilenet_v2_224"}


def _cfg(arch):
    with open(os.path.join(ROOT, "bench", "configs",
                           CONFIGS[arch] + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_layer_table_matches_program_specs(arch):
    from repro.models import cnn
    def row(name, kind, cin, cout, k, stride, hw, relu):
        # the ReLU flag means something only on convs and adds
        return (name, kind, cin, cout, k, stride, hw,
                relu if kind in ("conv", "dw", "add") else None)

    prog = [row(s.name, s.kind, s.cin, s.cout, s.k, s.stride, s.in_hw,
                s.relu) for s in cnn.specs_for(arch)]
    ref = [row(l.name, l.kind, l.cin, l.cout, l.k, l.stride, l.hw, l.relu)
           for l in R.network(_cfg(arch))]
    assert len(ref) == len(prog)
    assert ref == prog


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_reference_weights_equal_program_weights(arch):
    """The reference draws, from the seed and by its own code, exactly the
    weights the program initialises (pruned blocks as zeros)."""
    from repro.configs import get_config
    from repro.core.sparsity import densify
    from repro.models import cnn
    from repro.models.layers import SparseWeight
    seed = 2**31 + 5
    prog = cnn.init_cnn(get_config(arch), jax.random.PRNGKey(seed))
    ref = R.init_params(_cfg(arch), seed)
    assert set(prog) == set(ref)
    for name, p in prog.items():
        w = densify(p["w"]) if isinstance(p["w"], SparseWeight) else p["w"]
        np.testing.assert_array_equal(np.asarray(w, np.float32),
                                      np.asarray(ref[name][0]), err_msg=name)


def test_epilogue_adds():
    """The configurations state as ``fused_add`` the convs whose add the
    program takes into a kernel's epilogue, which skip their own
    rounding: ResNet-50's pruned c3 (stages 1-3, not the dense c3 of
    stage 0) and MobileNet-V2's projections before an add. Each feeds
    the add that follows it."""
    r50 = _cfg("resnet50")
    assert set(r50["fused_add"]) == {
        f"s{s}b{b}_c3" for s, n in ((1, 4), (2, 6), (3, 3))
        for b in range(n)}
    assert all(R.block_shape(l, r50) is not None for l in R.network(r50)
               if l.name in r50["fused_add"])
    mb = _cfg("mobilenet_v2")
    net = R.network(mb)
    adds = [l.name[:-4] for l in net if l.kind == "add"]
    assert set(mb["fused_add"]) == {a + "_pj" for a in adds}
    assert len(mb["fused_add"]) == 10
    for cfg in (r50, mb):
        net = R.network(cfg)
        first_operand = {l.src or net[i - 1].name
                         for i, l in enumerate(net) if l.kind == "add"}
        assert set(cfg["fused_add"]) <= first_operand


@pytest.mark.parametrize("arch", sorted(CONFIGS))
def test_reference_agrees_with_server(arch):
    """Logits that ``CNNPipelineServer`` serves at 32 px on the CPU agree
    with the reference to 1e-5 relative L2. On the CPU the server runs
    the XLA twins of its kernels, which round to bfloat16 at the same
    points as the reference, so the two differ only in the order of
    float32 sums (about 1e-7 measured); a wrong tap, block, stride or
    skip moves logits by 1e-1 or more."""
    from repro.launch.serve import CNNPipelineServer
    cfg = dict(_cfg(arch), image_size=32)
    seed = 3
    srv = CNNPipelineServer(arch, image_size=32, seed=seed, mb_size=1)
    imgs = np.asarray(jax.random.normal(jax.random.PRNGKey(9),
                                        (3, 32, 32, 3)), np.float32)
    ids = [srv.submit(imgs[i:i + 1]) for i in range(3)]
    srv.run()
    got = np.concatenate([srv.results(i) for i in ids])
    want = R.logits(cfg, R.init_params(cfg, seed), imgs, block=2)
    err = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert err < 1e-5, err
