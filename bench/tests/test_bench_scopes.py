"""``bench/scopes.py``: the program's named scopes reach its compiled
tick (CPU, 32 px), and the reduction splits a trace by them, on
synthetic spans and HLO and on a recorded chip trace."""
import re
from collections import Counter

import pytest

from bench import scopes
from bench.trace import Span
from bench.tests._tiny import ROOT

OPCODE = re.compile(r" = \S+ ([\w-]+)\(")


def _scoped_opcodes(hlo_text, scope):
    """Opcodes of the instructions whose op_name matches ``scope``."""
    out = Counter()
    for line in hlo_text.splitlines():
        name, code = re.search(r'op_name="([^"]*)"', line), OPCODE.search(line)
        if name and code and code.group(1) != "parameter" \
                and re.search(scope, name.group(1)):
            out[code.group(1)] += 1
    return out


@pytest.fixture(scope="module")
def server():
    """The benchmark's MobileNet-V2 server, cut to 32 px."""
    from repro.launch.serve import CNNPipelineServer
    return CNNPipelineServer("mobilenet_v2", mb_size=1, n_stages=4,
                             image_size=32)


def test_every_weight_decode_op_is_in_its_stage_params_scope(server):
    """Every op that ``ParamFormat.unpack`` emits for stage k, traced on
    its own, appears in the tick under ``stage<k>/params`` and nowhere
    else; every op of a graph node lies in the stage the plan gives the
    node, and every node appears there."""
    import jax
    from repro.core.fusion import fused_graph_for
    low = server._step.lower(server._state, server._zero_wire,
                             *server._params_arg)
    tick = low.as_text(dialect="hlo", debug_info=True)
    rows = server._params_arg[0]
    for k, fmt in enumerate(server.pparams.formats):
        alone = jax.jit(fmt.unpack).lower(rows[k]).as_text(
            dialect="hlo", debug_info=True)
        want = _scoped_opcodes(alone, ".")
        assert want and _scoped_opcodes(
            tick, rf"(^|/)stage{k}/params/") == want
    stage_of = {n.name: int(s) for n, s in zip(
        fused_graph_for("mobilenet_v2").nodes, server.plan["stage_of"])}
    seen = set()
    for name in re.findall(r'op_name="([^"]*)"', tick):
        m = re.search(r"(?:^|/)stage(\d+)/([^/]+)", name)
        if m and m.group(2) in stage_of:
            assert stage_of[m.group(2)] == int(m.group(1)), name
            seen.add(m.group(2))
    assert seen == set(stage_of)


def test_compiled_tick_ops_map_to_their_stage(server):
    """On the compiled tick, as the reduction reads it: every op that
    reads stage k's weight row counts toward stage k, each stage has
    weight-decode ops, and every dot, convolution or kernel call lies in
    a stage (what no stage holds is the executor's inject and shift)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    # JAX's persistent-cache key leaves out debug info, so an entry that
    # another build of the tick wrote would load with that build's
    # op_names: compile this build's afresh
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        text = server._step.lower(server._state, server._zero_wire,
                                  *server._params_arg).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()
    names = scopes.op_names(text)
    assert scopes.module_name(text) == "jit_tick"
    entry = text[text.index("\nENTRY"):].splitlines()[1:]
    rows = {}
    for line in entry:
        m = re.search(r'%(\S+) = .* parameter\(\d+\).*op_name='
                      r'"pparams_arg\[(\d+)\]"', line)
        if m:
            rows[m.group(1)] = int(m.group(2))
    assert sorted(rows.values()) == [0, 1, 2, 3]
    decode = Counter()
    for line in entry:
        m = scopes._INSTRUCTION.match(line)
        if not m or "parameter(" in line:
            continue
        stage, dec = scopes.classify(names[m.group(1)])
        operands = line.split(" = ", 1)[1].split(", metadata=")[0]
        for row, k in rows.items():
            if re.search(rf"%{re.escape(row)}\b", operands):
                assert stage == k, m.group(1)
        decode[stage] += dec
        if stage is None:
            fused = " ".join(names[m.group(1)]) + " " + operands
            assert not re.search(r"dot_general|conv_general|pallas|"
                                 r"custom-call", fused), line
    assert all(decode[k] for k in range(4))


NODE = '"jit(tick)/stage1/s1b0_pj/dot_general"'
HLO = f"""HloModule jit_tick, is_scheduled=true

%fused_computation.1 (param_0: u8[64]) -> bf16[32] {{
  %param_0 = u8[64]{{0}} parameter(0)
  %slice.1 = u8[64]{{0}} slice(%param_0), metadata={{op_name="jit(tick)/stage0/params/slice"}}
  ROOT %bitcast.1 = bf16[32]{{0}} bitcast(%slice.1), metadata={{op_name="jit(tick)/stage0/params/bitcast_convert_type"}}
}}

ENTRY %main.9 (p: u8[64]) -> bf16[32] {{
  %p = u8[64]{{0}} parameter(0), metadata={{op_name="pparams_arg[0]"}}
  %fusion.1 = bf16[32]{{0}} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(tick)/stage0/s0b0_pj/reshape"}}
  %dot.2 = bf16[32]{{0}} dot(%fusion.1, %fusion.1), metadata={{op_name={NODE}}}
  ROOT %copy.1 = bf16[32]{{0}} copy(%dot.2), metadata={{op_name="jit(tick)/scatter"}}
}}
"""


def test_op_names_read_fused_instructions():
    names = scopes.op_names(HLO)
    assert names["fusion.1"] == [
        "jit(tick)/stage0/s0b0_pj/reshape", "jit(tick)/stage0/params/slice",
        "jit(tick)/stage0/params/bitcast_convert_type"]
    assert scopes.classify(names["fusion.1"]) == (0, True)
    assert scopes.classify(names["dot.2"]) == (1, False)
    assert scopes.classify(names["copy.1"]) == (None, False)
    # a tie of stages goes to the op's own name
    assert scopes.classify(["a/stage2/x", "a/stage1/y"]) == (2, False)


def _ops(*spans):
    return [Span(f"%{n} = bf16[32] op()", s, e) for n, s, e in spans]


def test_device_scopes_split_the_tick_by_stage_and_decode():
    """Ops inside a run of the tick's module count toward their stage
    (``fusion.1`` decodes stage 0's weights); ops outside it, though
    named like a tick op (``copy.1``), are other modules' time."""
    devices = {"/device:TPU:0": _ops(("fusion.1", 0, 10), ("dot.2", 10, 40),
                                     ("copy.1", 40, 45),
                                     ("copy.1", 200, 210))}
    modules = {"/device:TPU:0": [Span("jit_tick(3)", 0, 50),
                                 Span("jit_pack_in(4)", 195, 215)]}
    got = scopes.device_scopes(devices, modules, "jit_tick",
                               scopes.op_names(HLO), 0, 300)
    want = {"tick_s": 45e-9, "stage_s": [10e-9, 30e-9], "params_s": 10e-9,
            "params_stage_s": [10e-9, 0.0], "unscoped_s": 5e-9,
            "other_s": 10e-9}
    assert set(got) == set(want)
    for k, v in want.items():
        assert got[k] == pytest.approx(v), k


def test_host_spans_give_waits_and_tick_self_time():
    host = [Span("serve.tick", -5, 5),             # starts before the window
            Span("serve.tick", 10, 40), Span("serve.collect", 20, 35),
            Span("serve.tick", 50, 80), Span("serve.dispatch", 51, 55),
            Span("serve.collect", 60, 62),
            Span("serve.collect", 85, 90)]          # run()'s trailing one
    got = scopes.host_spans(host, 0, 100)
    assert got["ticks"] == 2
    assert got["collect_s"] == pytest.approx(22e-9)
    assert got["tick_self_s"] == pytest.approx((15 + 28) * 1e-9)


def test_reduce_recorded_chip_trace():
    """A trace recorded on one TPU v5e chip by a traced run of
    mbv2.b1_stream with the program's spans and scopes, trimmed to five
    ticks (the host thread that opened the window, every span clipped to
    the five ticks, and the TPU's ``XLA Ops`` and ``XLA Modules`` lines,
    without stats), and beside it the ``{instruction: op_names}`` of the
    compiled tick for the ops in it."""
    import json
    import os

    from bench import trace
    base = os.path.join(ROOT, "bench", "testdata", "mbv2_stream_scoped")
    with open(base + ".op_names.json") as f:
        names = json.load(f)
    devices, host = trace.read_xplane(base + ".xplane.pb")
    red = trace.reduce(devices, host, kernels=("dw_pw",))
    assert red["kernels"]["dw_pw"]["calls"] == 5 * 17
    (window,) = [s for s in host if s.name == trace.WINDOW]
    got = dict(scopes.device_scopes(
        devices, scopes.read_modules(base + ".xplane.pb"), names["module"],
        names["op_names"], window.start, window.end),
        **scopes.host_spans(host, window.start, window.end))
    assert got["ticks"] == 5
    assert sum(got["stage_s"]) + got["unscoped_s"] == pytest.approx(
        got["tick_s"])
    # ops on one TPU do not overlap: tick and other ops make up busy time
    assert got["tick_s"] + got["other_s"] == pytest.approx(red["busy_s"])
    assert got["unscoped_s"] < 0.05 * red["busy_s"]
    assert got["params_stage_s"] == pytest.approx(
        [0.003622488, 0.007785271, 0.009177141, 0.02091923])
    assert got["collect_s"] == pytest.approx(0.042080069)
    # the issue's per-layer quantities, read off the reduction
    assert 100 * got["params_s"] / red["busy_s"] == pytest.approx(
        94.4538615814673)
    assert max(got["stage_s"]) * 4 / sum(got["stage_s"]) == pytest.approx(
        4 * 0.021030854 / (0.005374355 + 0.007948446 + 0.009257236
                           + 0.021030854))
    assert 1e3 * got["collect_s"] / got["ticks"] == pytest.approx(8.4160138)
    assert 0 < 1e3 * got["tick_self_s"] / got["ticks"] < 10
    # the device's idle time shows under the program's own spans
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert not any(name.startswith("bench.") for name in gaps)
    assert {"serve.stage_next", "serve.dispatch"} <= set(gaps)
