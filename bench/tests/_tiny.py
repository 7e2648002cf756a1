"""A copy of the benchmark at a size a CPU test run can hold."""
from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def copy_bench(dest: str, *, image_size=None, pool=4, per_round=4,
               sample=4) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/`` under ``dest``, shrinking
    images, pools, rounds and samples where asked. Returns ``dest``."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    if image_size is None:
        return dest
    for sub, edit in (("configs", lambda c: c.update(image_size=image_size)),
                      ("traffic", lambda c: c.update(
                          pool_images=pool, sample_requests=sample,
                          **({"requests_per_round": min(
                              c["requests_per_round"], per_round)}
                             if "requests_per_round" in c else {})))):
        d = os.path.join(dest, "bench", sub)
        for f in os.listdir(d):
            with open(os.path.join(d, f)) as fh:
                c = json.load(fh)
            edit(c)
            with open(os.path.join(d, f), "w") as fh:
                json.dump(c, fh)
    return dest
