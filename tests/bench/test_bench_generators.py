"""The benchmark's generators give the same inputs for the same seed, and
its copy of the paper's traffic draws what the program's generator draws."""
from __future__ import annotations

import numpy as np
import pytest

from bench.gen import noc_synthetic, plan_groups

RANGES = [[2, 5], [4, 8], [7, 10], [10, 16]]
BIG = 2**40 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_groups_repeat_for_a_seed(seed):
    a = [plan_groups.group(8, seed, r, RANGES) for r in range(200)]
    assert a == [plan_groups.group(8, seed, r, RANGES) for r in range(200)]
    assert a != [plan_groups.group(8, seed + 1, r, RANGES) for r in range(200)]
    for src, dests in a:
        assert src not in dests and len(set(dests)) == len(dests)
        assert 2 <= len(dests) <= 16
        assert all(0 <= x < 8 and 0 <= y < 8 for x, y in dests)


def test_distinct_is_distinct():
    out = plan_groups.distinct(4, BIG, 300, RANGES)
    assert len({(s, tuple(d)) for s, d in out}) == 300
    assert out == plan_groups.distinct(4, BIG, 300, RANGES)


def test_zipf_and_poisson_repeat_and_follow_their_law():
    r = plan_groups.zipf_ranks(BIG, 2**20, 1.0, 50_000)
    assert np.array_equal(r, plan_groups.zipf_ranks(BIG, 2**20, 1.0, 50_000))
    assert r.min() >= 0 and r.max() < 2**20
    # P(rank 0) = 1 / H(2^20) ~ 1 / 14.44
    assert abs((r == 0).mean() - 1 / 14.44) < 0.01
    t = plan_groups.poisson_times(BIG, 1000.0, 20_000)
    assert np.array_equal(t, plan_groups.poisson_times(BIG, 1000.0, 20_000))
    assert abs(t[-1] - 20.0) < 1.0 and np.all(np.diff(t) > 0)


def test_noc_traffic_matches_the_program_generator():
    from repro.noc import NoCConfig, synthetic_workload

    cfg = NoCConfig(n=8, dest_range=(10, 16))
    wl = synthetic_workload(cfg, 0.05, 50, seed=BIG)
    ours = noc_synthetic.requests(8, 0.05, 50, BIG, 0.1, (10, 16))
    assert ours == [(r.time, r.src, r.dests) for r in wl.requests]
