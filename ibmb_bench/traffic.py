"""The one request generator: open-loop arrivals read from a workload's
``traffic`` parameters.

    {"rate_per_s": 40.0,                  # mean arrival rate
     "burst": {"on_s": 1.0, "off_s": 1.0, "off_rate_per_s": 5.0},  # optional
     "ids_per_request": {"min": 1, "max": 64},      # log-uniform, whole ids
     "zipf_exponent": 1.1,                # id popularity over the pool
     "base_seed": 7}                      # fixes the multiset of work

Every run of a cell gets the same multiset of inter-arrival gaps and
request sizes, drawn from ``base_seed`` for the window's length; the run's
``--seed`` shuffles their order, ranks the id pool for popularity, and
draws the ids. So seeds change which nodes are asked for and when, not how
much work arrives.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np


def _gaps(params: dict, seconds: float, rng: np.random.Generator):
    """Poisson inter-arrival gaps filling ``seconds``; with ``burst``, the
    rate alternates between ``rate_per_s`` (on) and ``off_rate_per_s``."""
    burst = params.get("burst")
    t, out = 0.0, []
    while True:
        rate = params["rate_per_s"]
        if burst:
            period = burst["on_s"] + burst["off_s"]
            if (t % period) >= burst["on_s"]:
                rate = burst["off_rate_per_s"]
        gap = rng.exponential(1.0 / rate)
        if t + gap >= seconds:
            return np.asarray(out)
        out.append(gap)
        t += gap


def schedule(params: dict, seconds: float, seed: int,
             pool: np.ndarray) -> List[Tuple[float, np.ndarray]]:
    """``[(due time in s from the window's start, node ids), ...]``."""
    base = np.random.default_rng(int(params["base_seed"]))
    gaps = _gaps(params, seconds, base)
    size = params["ids_per_request"]
    sizes = np.exp(base.uniform(np.log(size["min"]), np.log(size["max"] + 1),
                                len(gaps))).astype(np.int64)
    sizes = np.clip(sizes, size["min"], size["max"])

    rng = np.random.default_rng(seed)
    if not params.get("burst"):          # a burst's phases keep their order
        gaps = rng.permutation(gaps)
    sizes = rng.permutation(sizes)
    ranked = rng.permutation(np.asarray(pool))
    p = np.arange(1, len(ranked) + 1, dtype=np.float64) ** -float(
        params["zipf_exponent"])
    p /= p.sum()
    due = np.cumsum(gaps)
    return [(float(t), ranked[rng.choice(len(ranked), int(k), replace=False,
                                         p=p)])
            for t, k in zip(due, sizes)]
