"""The one traffic generator: turns a mix file of parameters and a seed
into the order in which a cell's requests are due.

Every seed gets the same multiset of work and of inter-arrival gaps, in
another order, so runs with different seeds offer the same load:

  closed loop  {"loop": "closed", "pool": n, ["ahead_s": s]}: step i
               uses pool entry order[i % n]; a step is sent when the one
               s seconds of work before it has ended.
  open loop    {"loop": "open", "rate_per_s": r, "pool": n,
               ["arrival_seed": a]}: Poisson arrivals at rate r.  The gaps
               are the exponential distribution's quantiles at
               (i + 1/2) / N, with N = round(r * seconds) (scaled down
               where their sum would pass the window), in an order drawn
               from ``arrival_seed`` where the mix fixes one, so that
               every run offers the same arrivals, and else from the seed;
               each pool entry is used equally often, in an order drawn
               from the seed.
"""
from __future__ import annotations

import numpy as np

SEED_BITS = 64


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for any whole-number seed (negative or beyond 64 bits
    included) and an independent stream number."""
    s = int(seed) % (1 << SEED_BITS)
    return np.random.default_rng([s, int(stream)])


def closed_order(mix: dict, seed: int) -> np.ndarray:
    """Pool-entry order of a closed loop (one cycle through the pool)."""
    return rng(seed, 1).permutation(int(mix["pool"]))


def open_schedule(mix: dict, seed: int, seconds: float
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(due times in seconds from the window start, pool index) of every
    arrival of an open loop; all fall inside [0, seconds)."""
    rate = float(mix["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    # the gaps' sum is the same for every seed: keep the last one inside
    gaps *= min(1.0, seconds * n / (n + 0.5) / gaps.sum())
    due = np.cumsum(rng(mix.get("arrival_seed", seed), 2).permutation(gaps))
    r = rng(seed, 2)
    pool = int(mix["pool"])
    idx = r.permutation(np.resize(np.arange(pool), n))
    return due, idx
