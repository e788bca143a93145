"""Multicast groups for plan serving: (source, destination set) requests.

A group is drawn from its own ``random.Random`` keyed by (seed, rank): a
uniform source on the ``n`` x ``n`` mesh, one of the paper's destination
ranges chosen uniformly (arXiv:2108.00566, Fig. 6: 2-5, 4-8, 7-10, 10-16),
a size uniform within it, and that many distinct other nodes. A pool is
the groups of ranks ``0 .. pool-1``; a stream draws ranks Zipf(s) over the
pool, so a few groups repeat often and most rarely, and arrives as a
Poisson process. Everything follows from the seed.
"""
from __future__ import annotations

import random

import numpy as np


def group(n: int, seed: int, rank: int, ranges) -> tuple:
    rng = random.Random((seed << 24) + rank)
    src = (rng.randrange(n), rng.randrange(n))
    lo, hi = ranges[rng.randrange(len(ranges))]
    k = min(rng.randint(lo, hi), n * n - 1)
    picks = rng.sample(range(n * n - 1), k)
    idx = n * src[1] + src[0]  # row-major; the others skip the source
    nodes = [p + (p >= idx) for p in picks]
    return src, sorted((q % n, q // n) for q in nodes)


def distinct(n: int, seed: int, count: int, ranges, first: int = 0) -> list:
    """``count`` distinct groups, from rank ``first`` on."""
    out, seen, rank = [], set(), first
    while len(out) < count:
        src, dests = group(n, seed, rank, ranges)
        rank += 1
        key = (src, tuple(dests))
        if key not in seen:
            seen.add(key)
            out.append((src, dests))
    return out


def zipf_ranks(seed: int, pool: int, s: float, count: int) -> np.ndarray:
    """``count`` ranks in ``[0, pool)`` with P(rank r) ~ 1 / (r + 1)^s."""
    w = 1.0 / np.arange(1, pool + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w)
    cdf /= cdf[-1]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
    return np.minimum(np.searchsorted(cdf, rng.random(count)), pool - 1)


def poisson_times(seed: int, rate: float, count: int) -> np.ndarray:
    """Arrival offsets (s) of ``count`` requests at ``rate`` per second."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2])
    return np.cumsum(rng.exponential(1.0 / rate, count))
