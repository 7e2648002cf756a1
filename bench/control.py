#!/usr/bin/env python3
"""Readings that the limits in ``configs/*.json`` are set from.

    python bench/control.py --workload <cell> --seconds <s> \
        --quantize int8 --seeds 1 2 3

runs the cell as ``bench/run.py`` does, once per seed in one process
(set-up is long, and one process holds the chip), with the program's
weights stored at ``--quantize``: ``native`` gives the program's own
readings (the lower end of a limit), ``int8`` the program's int8 path,
the control, which computes below the configuration's bfloat16 and has
to come out not correct (the upper end). ``--round-every`` compares
with a reference that rounds every layer's output, the configuration's
``fused_add`` convs too: how far a run that rounds at other points
than the configuration states would read. Prints one JSON line per
seed with every number compared. The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run as bench_run  # noqa: E402
from bench import spec  # noqa: E402


def readings(cell_name: str, seeds, seconds: float, quantize: str,
             root: str = bench_run.ROOT, round_every: bool = False
             ) -> list[dict]:
    bench = spec.load(root)
    cell = bench["cells"][cell_name]
    cfg = bench["config_files"][cell["config"]]
    if round_every:
        cfg = dict(cfg, fused_add=[])
    mix = bench["traffic"][cell["traffic"]]
    out = []
    for seed in seeds:
        res, _ = bench_run.run(cell, cfg, mix, bench, seed=seed,
                               seconds=seconds, traced=False,
                               server_kwargs={"quantize": quantize})
        line = {"workload": cell_name, "seed": seed, "quantize": quantize,
                "round_every": round_every, "correct": res["correct"],
                "attempted": res["attempted"],
                "checks": res["checks"]}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--quantize", default="int8")
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--round-every", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    readings(args.workload, args.seeds, args.seconds, args.quantize,
             round_every=args.round_every)
    return 0


if __name__ == "__main__":
    sys.exit(main())
