"""Reduce a JAX profiler trace to the benchmark's device numbers.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes; it is read
with ``jax.profiler.ProfileData`` and nothing else. From it come:

- the traced window: the host span named :data:`WINDOW` (the harness
  wraps the traced rounds in it);
- device busy time: the union of the intervals of the ops on each TPU's
  ``XLA Ops`` line inside the window, averaged over the chips;
- each kernel's device time and call count: the ops whose name, less a
  trailing ``.<n>``, is the kernel's name;
- ``breakdown``: the ops that took the most device time, and the device's
  idle gaps summed by the innermost host span that was open at each
  gap's midpoint (what the host was doing meanwhile).
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

WINDOW = "bench.window"
DEVICE_PLANE = re.compile(r"/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
_SUFFIX = re.compile(r"\.\d+$")
TOP = 10


class Span(NamedTuple):
    name: str
    start: int      # ns
    end: int        # ns


def base_name(op: str) -> str:
    """The op's name less its ``.<n>`` suffix. A TPU op event is named by
    its HLO instruction, ``%sparse_conv.12 = bf16[...] custom-call(...)``:
    that gives ``sparse_conv``."""
    name = op.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", name)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def read_xplane(path: str) -> tuple[dict, list[Span]]:
    """``({device plane: [op spans]}, [host spans])`` of one trace; the
    host spans are those of the host thread that opened the window."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [Span(e.name, int(e.start_ns), int(e.end_ns))
                            for e in line.events]
            devices[plane.name] = sorted(ops, key=lambda s: s.start)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans = [Span(e.name, int(e.start_ns), int(e.end_ns))
                         for e in line.events if e.duration_ns > 0]
                if any(s.name == WINDOW for s in spans):
                    host += spans
    return devices, host


def _clip(spans, lo, hi):
    return [Span(s.name, max(s.start, lo), min(s.end, hi))
            for s in spans if s.end > lo and s.start < hi]


def _union(spans) -> list[tuple[int, int]]:
    out = []
    for s in sorted(spans, key=lambda s: s.start):
        if out and s.start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], s.end))
        else:
            out.append((s.start, s.end))
    return out


def _innermost(host: list[Span], times: list[int]) -> list[str]:
    """For each of the ascending ``times``, the name of the innermost
    span of one host thread (its spans nest) open at that time."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(host) and host[i].start <= t:
            while stack and stack[-1].end <= host[i].start:
                stack.pop()
            stack.append(host[i])
            i += 1
        while stack and stack[-1].end <= t:
            stack.pop()
        out.append(stack[-1].name if stack else "(no host span)")
    return out


def reduce(devices: dict, host: list[Span], kernels=()) -> dict:
    """The device numbers of the window: ``window_s``, ``busy_s``
    (averaged over chips), per-kernel ``{name: {"seconds", "calls"}}``
    (summed over chips), and ``breakdown``."""
    windows = [s for s in host if s.name == WINDOW]
    if not windows or not devices:
        raise ValueError("trace holds no window span or no TPU ops line")
    lo = min(s.start for s in windows)
    hi = max(s.end for s in windows)
    # outer spans first where two start together, so the stack nests
    host = sorted(_clip(host, lo, hi), key=lambda s: (s.start, -s.end))
    busy_ns, by_op, gaps = 0, {}, {}
    kern = {k: {"seconds": 0.0, "calls": 0} for k in kernels}
    for ops in devices.values():
        ops = _clip(ops, lo, hi)
        busy = _union(ops)
        busy_ns += sum(e - s for s, e in busy)
        for op in ops:
            name = base_name(op.name)
            by_op[name] = by_op.get(name, 0) + op.end - op.start
            if name in kern:
                kern[name]["seconds"] += (op.end - op.start) * 1e-9
                kern[name]["calls"] += 1
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        names = _innermost(host, [(s + e) // 2 for s, e in idle])
        for name, (s, e) in zip(names, idle):
            gaps[name] = gaps.get(name, 0) + e - s
    n = len(devices)
    top = lambda d: [[k, v * 1e-9 / n] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy_ns * 1e-9 / n,
            "kernels": kern,
            "breakdown": {"device_ops": top(by_op), "idle_gaps": top(gaps)}}
