"""Load ``BENCHMARK.json`` and find each cell's files by name.

Nothing here knows a particular cell: a cell, configuration, network,
traffic mix or per-layer metric is added by adding its files and its
entry in ``BENCHMARK.json``, and :func:`load` finds and validates them.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

from bench import traffic as traffic_gen

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("device_trace", "host_clock")
CONFIG_KEYS = ("arch", "image_size", "source", "reduced", "assumed",
               "reference")


class SpecError(ValueError):
    """BENCHMARK.json, or a file it names, breaks the benchmark's rules."""


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.fullmatch(name):
        raise SpecError(f"{what} {name!r}: a name is 1-64 of letters, "
                        "digits, '_', '.', '-' and starts with a letter, "
                        "digit or '_'")
    return name


def check_unit(unit: str, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.fullmatch(unit):
        raise SpecError(f"{what} unit {unit!r}: 1-16 of letters, digits, "
                        "'_', '/', '%', '.', '-'")
    return unit


def _read_json(path: str, what: str):
    if not os.path.isfile(path):
        raise SpecError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def _check_metric(m: dict, e2e: bool, cells: set, e2e_names: set) -> None:
    name = check_name(m.get("name"), "metric")
    check_unit(m.get("unit"), name)
    if m.get("better") not in ("lower", "higher"):
        raise SpecError(f"{name}: better must be lower or higher")
    allowed = E2E_SOURCES if e2e else SOURCES
    if m.get("source") not in allowed:
        raise SpecError(f"{name}: source {m.get('source')!r} not in "
                        f"{allowed}")
    for w in m.get("workloads", []):
        if w not in cells:
            raise SpecError(f"{name}: unknown workload {w!r}")
    if e2e:
        b = m.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            raise SpecError(f"{name}: bound {b!r} outside [0.01, 0.25]")
    else:
        if m.get("moves") not in e2e_names:
            raise SpecError(f"{name}: moves {m.get('moves')!r} is not an "
                            "end-to-end metric")
        if not m.get("layer") or "\n" in m["layer"]:
            raise SpecError(f"{name}: layer must be one line")


def _load_reader(path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path}: a metric reader defines read(run)")
    return mod.read


def reader_path(root: str, base: str, name: str) -> str:
    """The reader of metric ``name``: ``metrics/<name>.py``, or else that
    of the name's first part, ``metrics/<part>.py`` for ``<part>.<rest>``,
    so that one reader serves a quantity split by cells."""
    d = os.path.join(root, base, "metrics")
    for stem in (name, name.split(".", 1)[0]):
        if os.path.isfile(os.path.join(d, stem + ".py")):
            return os.path.join(d, stem + ".py")
    raise SpecError(f"metric {name}: no reader {d}/{name}.py")


def load(root: str) -> dict:
    """Parse and validate ``<root>/BENCHMARK.json`` and every file it
    names. Returns the parsed benchmark with ``configs``, ``traffic`` and
    ``readers`` resolved by name; each configuration's ``reference`` is
    made an absolute path."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"), "benchmark")
    base = bench["paths"][0]
    configs = {}
    for c in bench["configs"]:
        name = check_name(c["name"], "config")
        for k in c.get("reduced", []):
            check_name(k, f"{name} reduced key")
        cfg = _read_json(os.path.join(root, c["file"]), f"config {name}")
        missing = [k for k in CONFIG_KEYS if k not in cfg]
        if missing:
            raise SpecError(f"config {name}: missing {missing}")
        if sorted(cfg["reduced"]) != sorted(c.get("reduced", [])):
            raise SpecError(f"config {name}: reduced differs from "
                            "BENCHMARK.json")
        ref = os.path.normpath(os.path.join(root, cfg["reference"]))
        if (not ref.startswith(os.path.normpath(os.path.join(root, base))
                               + os.sep)
                or not os.path.isfile(ref)):
            raise SpecError(f"config {name}: no layer table "
                            f"{cfg['reference']} under {base}/")
        configs[name] = dict(cfg, reference=ref)
    cells = {}
    traffic = {}
    for w in bench["workloads"]:
        name = check_name(w["name"], "workload")
        if name in cells:
            raise SpecError(f"workload {name} appears twice")
        if w["config"] not in configs:
            raise SpecError(f"workload {name}: unknown config "
                            f"{w['config']!r}")
        t = check_name(w["traffic"], f"{name} traffic")
        if t not in traffic:
            mix = _read_json(os.path.join(root, base, "traffic",
                                          t + ".json"), f"traffic {t}")
            missing = traffic_gen.missing_keys(mix)
            if missing:
                raise SpecError(f"traffic {t}: missing {missing}")
            traffic[t] = mix
        if w.get("chips") not in (1, 4):
            raise SpecError(f"workload {name}: chips must be 1 or 4")
        cells[name] = w
    pairs = {(w["config"], w["traffic"]) for w in cells.values()}
    if len(pairs) != len(cells):
        raise SpecError("a (config, traffic) pair appears twice")
    e2e_names = {check_name(m["name"], "metric")
                 for m in bench["end_to_end"]}
    if "setup_s" not in e2e_names:
        raise SpecError("end_to_end must hold setup_s")
    seen = set()
    for group, e2e in (("end_to_end", True), ("per_layer", False)):
        for m in bench[group]:
            if m["name"] in seen:
                raise SpecError(f"metric {m['name']} appears twice")
            seen.add(m["name"])
            _check_metric(m, e2e, set(cells), e2e_names)
    readers = {}
    for m in bench["per_layer"]:
        readers[m["name"]] = _load_reader(
            reader_path(root, base, m["name"]), m["name"])
    return dict(bench, cells=cells, config_files=configs, traffic=traffic,
                readers=readers)


def metrics_for(bench: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` that ``cell`` reports: those that list
    it, or that list no workloads and move a metric the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]
