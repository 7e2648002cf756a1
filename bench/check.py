"""Decide ``correct``: served logits against the plain reference.

After the window, a sample of the finished requests, drawn from the
seed, is run through :mod:`bench.reference` on the same images, and each
request's logits are compared with the reference's by their relative L2
distance. The run is correct when the largest distance of the sample is
within the configuration's limit and no request went unanswered.
"""
from __future__ import annotations

import numpy as np

from bench import reference, traffic


def sample(requests: list, n: int, seed: int) -> list:
    """Up to ``n`` of the answered requests, drawn from ``seed``."""
    done = [r for r in requests if r.logits is not None]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(done), size=min(n, len(done)), replace=False)
    return [done[i] for i in sorted(pick)]


def rel_l2(y: np.ndarray, ref: np.ndarray) -> float:
    y = np.asarray(y, np.float64).ravel()
    ref = np.asarray(ref, np.float64).ravel()
    return float(np.linalg.norm(y - ref) / np.linalg.norm(ref))


def distances(cfg: dict, seed: int, picked: list, pool: np.ndarray,
              n_img: int) -> list[float]:
    """Relative L2 distance of each picked request from the reference."""
    images = np.concatenate([traffic.pool_images(pool, r.first_image, n_img)
                             for r in picked])
    params = reference.init_params(cfg, seed)
    ref = reference.logits(cfg, params, images)
    ref = ref.reshape(len(picked), n_img, -1)
    return [rel_l2(r.logits, ref[i]) for i, r in enumerate(picked)]


def checks(cfg: dict, seed: int, requests: list, pool: np.ndarray,
           mix: dict) -> dict:
    """``{name: {"value", "limit"}}`` of every number compared."""
    failed = sum(r.logits is None for r in requests)
    picked = sample(requests, int(mix["sample_requests"]), seed)
    worst = None            # nothing answered: nothing can pass
    if picked:
        worst = max(distances(cfg, seed, picked, pool,
                              int(mix["images_per_request"])))
    return {"logits_rel_l2_max": {"value": worst,
                                  "limit": cfg["limits"]["logits_rel_l2_max"]},
            "unanswered": {"value": failed, "limit": 0}}


def passed(result: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in result.values())
