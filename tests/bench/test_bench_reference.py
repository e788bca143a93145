"""The benchmark's references agree with the program where the program is
sound, at sizes a CPU holds: so a cell that reads ``correct`` false points
at the program, not at the yardstick."""
from __future__ import annotations

import random

import numpy as np
import pytest

from bench import check
from bench.gen import noc_synthetic
from bench.ref import planner as ref_plan
from bench.ref import wormhole


def _instances(n, count, seed):
    rng = random.Random(seed)
    nodes = [(x, y) for y in range(n) for x in range(n)]
    out = []
    for _ in range(count):
        src = rng.choice(nodes)
        k = rng.randint(1, min(24, n * n - 1))
        out.append((src, rng.sample([d for d in nodes if d != src], k)))
    return out


@pytest.mark.parametrize("algo", ["MU", "MP", "NMP", "DPM"])
@pytest.mark.parametrize("n", [4, 8, 32])
def test_reference_plans_equal_host_plans(algo, n):
    from repro.core import grid, plan

    g = grid(n)
    for src, dests in _instances(n, 300, n * 7 + len(algo)):
        assert ref_plan.plan(algo, n, src, dests) == check.plan_triples(
            plan(algo, g, src, dests)), (algo, src, dests)


def test_plans_differing_counts_each_wrong_answer():
    from repro.core import grid, plan

    g = grid(8)
    answered = [(s, d, plan("DPM", g, s, d)) for s, d in _instances(8, 50, 1)]
    assert check.plans_differing("DPM", 8, answered) == 0
    answered[3] = (answered[3][0], answered[3][1], None)
    p = plan("MU", g, *answered[7][:2])
    answered[7] = (answered[7][0], answered[7][1], p)
    assert check.plans_differing("DPM", 8, answered) == 2


@pytest.mark.parametrize("algo,rate", [("DPM", 0.06), ("MP", 0.04),
                                       ("MU", 0.08)])
def test_reference_simulator_equals_wormhole_sim(algo, rate):
    from repro.core import grid, plan
    from repro.noc import NoCConfig, WormholeSim

    n, cycles = 4, 120
    cfg = NoCConfig(n=n, dest_range=(3, 8), multicast_fraction=0.3)
    reqs = noc_synthetic.requests(n, rate, cycles, 99, 0.3, (3, 8))
    sim = WormholeSim(cfg, measure_window=(20, cycles))
    for t, s, d in reqs:
        sim.add_plan(plan(algo, grid(n), s, d), t)
    st = sim.run(cycles + 600)
    ref = wormhole.simulate(
        n, [(t, ref_plan.plan(algo, n, s, d)) for t, s, d in reqs],
        vcs=2, depth=4, flits=4, cycles=cycles + 600, window=(20, cycles))
    for k in ("flit_link_traversals", "buffer_writes", "buffer_reads",
              "arbitrations", "ni_flits", "packets_created",
              "packets_finished", "cycles"):
        assert ref[k] == getattr(st, k), k
    assert ref["latencies"] == sorted(st.latencies)
    assert ref["delivered"] == {p.pid: set(p.delivery_times)
                                for p in sim.packets}
