"""The paper's synthetic NoC traffic (arXiv:2108.00566, Section IV).

Every node injects a packet with probability ``rate`` in each cycle; a
share ``multicast`` of packets are multicasts to a uniformly drawn set of
``lo..hi`` other nodes, the rest unicasts to one uniform other node. The
benchmark's own copy of the program's ``synthetic_workload``: the same
draw order from ``random.Random(seed)`` over row-major nodes, so one seed
gives the same requests here and there.
"""
from __future__ import annotations

import random


def requests(n: int, rate: float, cycles: int, seed: int,
             multicast: float, dest_range) -> list:
    """``[(cycle, src, dests), ...]`` on an ``n`` x ``n`` mesh."""
    lo, hi = dest_range
    rng = random.Random(seed)
    nodes = [(x, y) for y in range(n) for x in range(n)]
    others = {s: [d for d in nodes if d != s] for s in nodes}
    out = []
    for t in range(cycles):
        for src in nodes:
            if rng.random() >= rate:
                continue
            if rng.random() < multicast:
                dests = rng.sample(others[src], rng.randint(lo, hi))
            else:
                dests = [rng.choice(others[src])]
            out.append((t, src, dests))
    return out
