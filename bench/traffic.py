"""The one traffic generator: image requests, in closed or open loop.

A mix is a data file, ``traffic/<mix>.json``, of these parameters:

- ``images_per_request``: images in each request (the server's
  microbatch is this size);
- ``pool_images``: distinct images made from the seed before the
  window; requests take them in turn, so no image is made in the window;
- ``sample_requests``: finished requests whose logits are compared with
  the reference after the window;
- ``loop``: ``"closed"`` or ``"open"``.

A closed loop also gives ``requests_per_round``: requests submitted
before each ``run()``; the next round starts once every result of this
one has been collected (1 is a single client waiting for each reply).

An open loop also gives ``rate_per_s``, the mean requests per second,
``arrival``, ``"poisson"`` or ``"uniform"`` gaps between arrivals, and
``burst``, the requests that arrive together (default 1). Arrivals do
not wait for replies: each round submits every request that has come
due, calls ``run()`` and collects the results, so requests that come
due meanwhile queue. Every seed gets the same set of gaps (quantiles of
the gap distribution), in an order drawn from the seed.

Every request goes through the server's own ``submit``, ``run`` and
``results``. Its latency runs on the host's monotonic clock from when it
was sent (closed) or came due (open) to just after ``results`` returns.
"""
from __future__ import annotations

import math
import time
from typing import NamedTuple

import numpy as np

SPANS = ("bench.submit", "bench.run", "bench.results")
LOOPS = {"closed": ("requests_per_round",),
         "open": ("rate_per_s", "arrival")}
COMMON = ("loop", "images_per_request", "pool_images", "sample_requests")


class Request(NamedTuple):
    first_image: int        # index into the pool
    submitted: float        # host monotonic seconds: sent, or came due
    done: float             # host monotonic seconds, or nan if it failed
    logits: object          # (images_per_request, classes) or None


def missing_keys(mix: dict) -> list[str]:
    """Parameters the mix lacks (an unknown ``loop`` lacks its own)."""
    need = COMMON + LOOPS.get(mix.get("loop"), ("a loop of " +
                                                "/".join(LOOPS),))
    return [k for k in need if k not in mix]


def make_pool(seed: int, n: int, image_size: int) -> np.ndarray:
    """``n`` images (n, H, W, 3) float32 from ``seed``, made on the device
    in one call. The program's weights come from ``PRNGKey(seed)``; the
    images from a key folded off it, so the two never share bits."""
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0x1A6E)
    make = jax.jit(lambda k: jax.random.normal(
        k, (n, image_size, image_size, 3), jax.numpy.float32))
    return np.asarray(make(key))


def pool_images(pool: np.ndarray, first: int, count: int) -> np.ndarray:
    """``count`` images from the pool, from ``first`` on and wrapping
    round; a view, not a copy, where they do not wrap."""
    start = first % len(pool)
    if start + count <= len(pool):
        return pool[start:start + count]
    return pool[(start + np.arange(count)) % len(pool)]


def arrivals(mix: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times, in seconds from the window's start, of every request
    of an open-loop mix over ``seconds``: the same gaps for every seed,
    in an order drawn from it; ``burst`` requests share each time."""
    burst = int(mix.get("burst", 1))
    per_s = float(mix["rate_per_s"]) / burst
    n = max(1, math.ceil(per_s * seconds))
    if mix["arrival"] == "poisson":
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / per_s
    elif mix["arrival"] == "uniform":
        gaps = np.full(n, 1.0 / per_s)
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    gaps = np.random.default_rng([seed, 1]).permutation(gaps)
    return np.repeat(np.cumsum(gaps), burst)


class Traffic:
    """Drives one server with one mix. :meth:`warm_up` runs the mix's
    shapes once, untimed; :meth:`start` opens the window; each
    :meth:`round` then submits, runs and collects one round."""

    def __init__(self, server, pool: np.ndarray, mix: dict, seed: int,
                 clock=time.monotonic, sleep=time.sleep):
        self.server, self.pool, self.mix, self.seed = server, pool, mix, seed
        self.n_img = int(mix["images_per_request"])
        self.clock, self.sleep = clock, sleep
        self.next_image = self.ticks = self.injected = 0
        self.due = None         # open loop: absolute due times, in order
        self.next_due = 0

    def warm_up(self) -> None:
        """One request, then one round of as many requests as a round of
        the mix can hold; the counters start afresh after it."""
        size = int(self.mix.get("requests_per_round",
                                self.mix.get("burst", 1)))
        for n in (1, size):
            self._serve([None] * n)
        self.next_image = self.ticks = self.injected = 0

    def start(self, t0: float, seconds: float) -> None:
        if self.mix["loop"] == "open":
            self.due = t0 + arrivals(self.mix, self.seed, seconds)
            self.next_due = 0

    def owed(self, until: float) -> bool:
        """Whether a request that came due by ``until`` is still unsent
        (an open loop's window can close while ``run()`` is busy)."""
        return (self.due is not None and self.next_due < len(self.due)
                and self.due[self.next_due] <= until)

    def round(self, until: float) -> list[Request]:
        """One round; an open loop waits, up to ``until``, for the next
        request to come due, and sends none that comes due after it."""
        if self.due is None:
            return self._serve([None] * int(self.mix["requests_per_round"]))
        if self.next_due < len(self.due):
            wait = min(self.due[self.next_due], until) - self.clock()
            if wait > 0:
                self.sleep(wait)
        now = min(self.clock(), until)
        first = self.next_due
        while self.next_due < len(self.due) and self.due[self.next_due] <= now:
            self.next_due += 1
        return self._serve([float(t) for t in self.due[first:self.next_due]])

    def _serve(self, due: list) -> list[Request]:
        """Submit one request per entry of ``due`` (its due time, or None
        to time it from its submission), run, and collect."""
        from jax.profiler import TraceAnnotation
        srv, clock = self.server, self.clock
        if not due:
            return []
        sent = []
        for t_due in due:
            imgs = pool_images(self.pool, self.next_image, self.n_img)
            with TraceAnnotation(SPANS[0]):
                t = clock() if t_due is None else t_due
                sent.append((self.next_image, t, srv.submit(imgs)))
            self.next_image += self.n_img
        with TraceAnnotation(SPANS[1]):
            stats = srv.run()
        self.ticks += stats["ticks"]
        self.injected += stats["injected_microbatches"]
        out = []
        for first, t, rid in sent:
            with TraceAnnotation(SPANS[2]):
                try:
                    y = srv.results(rid)
                except (KeyError, ValueError):
                    out.append(Request(first, t, float("nan"), None))
                    continue
                out.append(Request(first, t, clock(), y))
        return out
