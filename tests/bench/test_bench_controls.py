"""Each cell's control reads ``correct`` false at test size: the numbers
compared can tell the control from the reference, and the harness, with
the control in the program's place, says so."""
from __future__ import annotations

import random

import pytest

from bench.ref import planner as ref
from bench.ref import wormhole


@pytest.mark.parametrize("n", [8, 32])
def test_dpm_without_dual_path_pricing_differs(n):
    rng = random.Random(n)
    g = ref.Mesh(n)
    nodes = [(x, y) for y in range(n) for x in range(n)]
    bad = 0
    for _ in range(200):
        src = rng.choice(nodes)
        dests = sorted(rng.sample([d for d in nodes if d != src],
                                  rng.randint(2, 16)))
        bad += ref.plan_dpm(g, src, dests, dual_path=False) != ref.plan(
            "DPM", n, src, dests)
    assert bad > 50


def test_simulator_control_changes_the_conserved_counts():
    from bench.gen import noc_synthetic

    n = 4
    reqs = noc_synthetic.requests(n, 0.05, 80, 5, 0.5, (3, 8))
    kw = dict(vcs=2, depth=4, flits=4, cycles=800, window=(0, 80))
    g = ref.Mesh(n)
    want = wormhole.simulate(
        n, [(t, ref.plan("DPM", n, s, d)) for t, s, d in reqs], **kw)
    got = wormhole.simulate(
        n, [(t, ref.plan_dpm(g, s, sorted(d), dual_path=False))
            for t, s, d in reqs], **kw)
    assert want["packets_finished"] == want["packets_created"]
    assert got["delivered"] != want["delivered"]


@pytest.mark.parametrize("cell", ["plan.mesh8x8.stream", "plan.mesh32x32.bulk",
                                  "xsim.mesh8x8.fig6"])
def test_control_in_the_programs_place_reads_incorrect(cell, small_run):
    from repro.core import arena_clear

    from bench.controls import no_dual_path

    arena_clear()
    with no_dual_path():
        line = small_run(cell)
    arena_clear()
    assert not line["correct"], line["checks"]
    assert line["checks"][next(iter(line["checks"]))]["value"] > 0
