"""Reduce a traced window to the program's own spans and named scopes.

The program names its work itself:

- host spans (``launch/serve.py``): ``serve.tick`` around each pipeline
  tick and, inside it, ``serve.stage_next`` (packing and staging the
  next microbatch), ``serve.dispatch`` (the tick's call) and
  ``serve.collect`` (the readback of a tick's logits, which blocks);
- named scopes on the tick's device ops (``core/pipeline.py``,
  ``models/cnn.py``): ``stage<k>`` around each pipeline stage and, inside
  it, ``params`` (decoding the stage's packed weight row), ``wire_in``,
  ``wire_out`` and one scope per graph node.

A scope reaches the compiled program as the ``op_name`` in each HLO
instruction's metadata (``jit(tick)/stage2/params/slice``), and a TPU op
in the trace is named by the instruction it runs (``%fusion.12 = ...``).
So the tick's compiled HLO text maps each device op to its scopes. A
fusion carries its root's ``op_name`` at most, so it is assigned by the
instructions fused into it as well, and an instruction that XLA made
itself (a relayout, an async slice) by its operands' (:func:`op_names`,
:func:`classify`). The ``op_name`` is used, not a trace event's stats.

Only the tick's ops count toward a stage: those inside an execution of
the tick's module on the device's ``XLA Modules`` line (an op name such
as ``copy.1`` also occurs in other modules). Ops of the tick in no
stage scope (the executor's inject and shift) are ``unscoped_s``.

On a trace kept from a traced window, with the tick's compiled HLO
text beside it (``server._step.lower(...).compile().as_text()``, from
a compile cache that this build filled: JAX's persistent cache keys a
program without its debug info, so a tick loaded from an entry that
another build wrote carries that build's op_names)::

    python -m bench.scopes <trace.xplane.pb> <tick.hlo>

prints :func:`reduce`'s result as one JSON line.
"""
from __future__ import annotations

import bisect
import re
from collections import Counter

from bench import trace

MODULES_LINE = "XLA Modules"
TICK_SPAN = "serve.tick"
COLLECT_SPAN = "serve.collect"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([^\s(]+)")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FUSED = re.compile(r"\bcalls=%?([^\s,}]+)")
_OPERAND = re.compile(r"%([^\s,(){}]+)")
_STAGE = re.compile(r"(?:^|/)stage(\d+)(/|$)")
_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)


def module_name(hlo_text: str) -> str | None:
    """The module's name (``jit_tick``) from its HLO text."""
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else None


def op_names(hlo_text: str) -> dict[str, list[str]]:
    """``{instruction: op_names}`` of a compiled module's HLO text: the
    instruction's own ``op_name`` first, then those of every instruction
    fused into it (through ``calls=``, recursively). An instruction that
    carries none takes its operands' (XLA's own copies, layout changes
    and async slices carry no ``op_name``; they belong to the values
    they move)."""
    own, calls, args, comps, comp = {}, {}, {}, {}, None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and comp is not None:
            name = m.group(1)
            comps[comp].append(name)
            o = _OP_NAME.search(line)
            own[name] = o.group(1) if o else None
            calls[name] = _FUSED.findall(line)
            args[name] = _OPERAND.findall(line.split(" = ", 1)[1])
            continue
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            comps[comp] = []

    def fused(name, seen):
        out = []
        for c in calls.get(name, ()):
            if c in seen:
                continue
            seen.add(c)
            for inner in comps.get(c, ()):
                if own[inner]:
                    out.append(own[inner])
                out += fused(inner, seen)
        return out

    out = {}
    for name in own:            # operands come before their users
        names = ([own[name]] if own[name] else []) + fused(name, set())
        if not names:
            names = list(dict.fromkeys(
                n for a in args[name] for n in out.get(a, ())))
        out[name] = names
    return out


def classify(names: list[str]) -> tuple[int | None, bool]:
    """``(stage, weight_decode)`` of one device op from its op_names:
    the stage that most of its names lie in (the op's own name breaks a
    tie; None where no name lies in a stage), and whether most of that
    stage's names lie in its ``params`` scope. A fusion that mixes
    scopes counts whole toward its majority."""
    stages, params = Counter(), Counter()
    for n in names:
        m = _STAGE.search(n)
        if m:
            k = int(m.group(1))
            stages[k] += 1
            params[k] += n[m.end():].startswith("params/")
    if not stages:
        return None, False
    (k, n), = stages.most_common(1)
    return k, 2 * params[k] > n


def instruction(op: str) -> str:
    """The HLO instruction a TPU op event runs: ``%fusion.12 = ...``
    gives ``fusion.12``."""
    return op.split(" = ", 1)[0].strip().lstrip("%")


def read_modules(path: str) -> dict[str, list[trace.Span]]:
    """``{device plane: [module executions]}`` from each TPU's
    ``XLA Modules`` line."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            out[plane.name] = sorted(
                (trace.Span(e.name, int(e.start_ns), int(e.end_ns))
                 for line in plane.lines if line.name == MODULES_LINE
                 for e in line.events), key=lambda s: s.start)
    return out


def _inside(op: trace.Span, runs: list[trace.Span], starts: list[int]
            ) -> bool:
    """Whether ``op`` lies inside one of ``runs`` (disjoint, in order of
    their ``starts``)."""
    i = bisect.bisect_right(starts, op.start) - 1
    return i >= 0 and op.end <= runs[i].end


def device_scopes(devices: dict, modules: dict, tick: str,
                  names: dict[str, list[str]], lo: int, hi: int) -> dict:
    """Device seconds in the window ``[lo, hi]`` ns, averaged over
    chips, of the ops that run inside the module ``tick``, given its
    ``{instruction: op_names}``: ``tick_s``, per stage ``stage_s``,
    weight decode ``params_s`` (per stage ``params_stage_s``),
    ``unscoped_s`` (tick ops in no stage), and ``other_s`` (every op
    outside the tick's module)."""
    n_stages = 1 + max((s for s in (classify(v)[0] for v in names.values())
                        if s is not None), default=-1)
    stage_ns, params_ns = [0] * n_stages, [0] * n_stages
    tick_ns = unscoped_ns = other_ns = 0
    for plane, ops in devices.items():
        runs = [r for r in modules.get(plane, ())
                if r.name.split("(", 1)[0] == tick]
        starts = [r.start for r in runs]
        for op in trace._clip(ops, lo, hi):
            dt = op.end - op.start
            if not _inside(op, runs, starts):
                other_ns += dt
                continue
            tick_ns += dt
            stage, decode = classify(names.get(instruction(op.name), []))
            if stage is None:
                unscoped_ns += dt
                continue
            stage_ns[stage] += dt
            if decode:
                params_ns[stage] += dt
    k = 1e-9 / max(len(devices), 1)
    return {"tick_s": tick_ns * k, "stage_s": [t * k for t in stage_ns],
            "params_s": sum(params_ns) * k,
            "params_stage_s": [t * k for t in params_ns],
            "unscoped_s": unscoped_ns * k, "other_s": other_ns * k}


def host_spans(host: list[trace.Span], lo: int, hi: int) -> dict:
    """From the host spans of the window: ``ticks`` (``serve.tick``
    spans inside it), ``collect_s`` (every ``serve.collect`` inside it)
    and ``tick_self_s`` (the ticks' time less their ``serve.collect``
    children)."""
    inside = [s for s in host if lo <= s.start and s.end <= hi]
    ticks = [s for s in inside if s.name == TICK_SPAN]
    collects = [s for s in inside if s.name == COLLECT_SPAN]
    child = sum(c.end - c.start for c in collects
                if any(t.start <= c.start and c.end <= t.end for t in ticks))
    return {"ticks": len(ticks),
            "collect_s": sum(c.end - c.start for c in collects) * 1e-9,
            "tick_self_s": (sum(t.end - t.start for t in ticks) - child)
            * 1e-9}


def reduce(path: str, hlo_text: str) -> dict:
    """The program's scopes and spans in the traced window of the
    ``.xplane.pb`` at ``path``, given the tick's compiled HLO text."""
    devices, host = trace.read_xplane(path)
    windows = [s for s in host if s.name == trace.WINDOW]
    if not windows:
        raise ValueError("trace holds no window span")
    lo = min(s.start for s in windows)
    hi = max(s.end for s in windows)
    return dict(device_scopes(devices, read_modules(path),
                              module_name(hlo_text), op_names(hlo_text),
                              lo, hi),
                **host_spans(host, lo, hi))


if __name__ == "__main__":
    import json
    import sys
    with open(sys.argv[2]) as f:
        print(json.dumps(reduce(sys.argv[1], f.read())))
