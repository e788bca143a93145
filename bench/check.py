"""The comparisons that decide ``correct``, and the seeded samples they use.

Each function takes what the timed path produced and what the benchmark's
reference (``bench/ref``) says, and returns numbers that are compared with
limits: a count of answers that differ (limit 0), or a gap.
"""
from __future__ import annotations

import random


def sample_indices(seed: int, count: int, k: int, salt: int = 7) -> list:
    """The indices, in order, of a seeded sample of at most ``k`` of
    ``count`` items: known before the items exist."""
    if count <= k:
        return list(range(count))
    return sorted(random.Random((seed << 8) + salt).sample(range(count), k))


def sample(seed: int, items: list, k: int, salt: int = 7) -> list:
    """A seeded sample of at most ``k`` of ``items``, in their order."""
    return [items[i] for i in sample_indices(seed, len(items), k, salt)]


def plan_triples(p) -> list:
    """A program ``MulticastPlan`` as the reference's worm triples."""
    return [(tuple(map(tuple, w.hops)), tuple(map(tuple, w.deliveries)),
             w.parent) for w in p.paths]


def plans_differing(algo: str, n: int, answered: list) -> int:
    """How many ``(src, dests, plan)`` answers differ from the reference
    plan of their instance (a missing plan differs)."""
    from bench.ref import planner as ref

    bad = 0
    for src, dests, p in answered:
        want = ref.plan(algo, n, src, dests)
        if (p is None or tuple(p.src) != tuple(src)
                or [tuple(d) for d in p.dests] != sorted(map(tuple, dests))
                or plan_triples(p) != want):
            bad += 1
    return bad
