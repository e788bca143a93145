"""A run whose timed path is broken underneath reads ``correct`` false.

Each test drives the rest of a run, at test size on the CPU with the
harness's look for a chip skipped, once sound and once with a fault
planted in the program where it produces its answers.
"""
from __future__ import annotations

import pytest


def _alter_decoded_plans(monkeypatch):
    """Every plan the batched planner decodes loses its last worm."""
    from repro.core.batch_planner import BatchPlanner

    decode = BatchPlanner._decode

    def altered(self, *a, **kw):
        p = decode(self, *a, **kw)
        p.paths = p.paths[:-1]
        return p

    monkeypatch.setattr(BatchPlanner, "_decode", altered)


@pytest.mark.parametrize("cell", ["plan.mesh8x8.stream", "plan.mesh32x32.bulk"])
def test_plan_cells_catch_an_altered_answer(cell, small_run, monkeypatch):
    from repro.core import arena_clear

    arena_clear()
    sound = small_run(cell)
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    arena_clear()
    _alter_decoded_plans(monkeypatch)
    broken = small_run(cell)
    arena_clear()
    assert not broken["correct"]
    assert broken["checks"]["plans_differing"]["value"] > 0


def test_xsim_cell_catches_an_altered_counter(small_run, monkeypatch):
    import repro.noc
    from repro.noc.xsim.run import CTR

    sound = small_run("xsim.mesh8x8.fig6")
    assert sound["correct"], sound["checks"]
    real = repro.noc.xsimulate

    def altered(*a, **kw):
        res = real(*a, **kw)
        res.ctr = res.ctr.copy()
        res.ctr[:, CTR.index("flit_link_traversals")] += 1
        return res

    monkeypatch.setattr(repro.noc, "xsimulate", altered)
    broken = small_run("xsim.mesh8x8.fig6")
    assert not broken["correct"]
    assert broken["checks"]["pairs_differing"]["value"] > 0


def test_xsim_cell_catches_an_altered_delivery(small_run, monkeypatch):
    import repro.noc

    real = repro.noc.xsimulate

    def altered(*a, **kw):
        res = real(*a, **kw)
        res.dtime = res.dtime.copy()
        res.dtime[:, 0, 0] = -1  # the first packet never reaches its first node
        return res

    monkeypatch.setattr(repro.noc, "xsimulate", altered)
    broken = small_run("xsim.mesh8x8.fig6")
    assert not broken["correct"]


def test_xsim_cell_catches_a_latency_past_its_band(small_run, monkeypatch):
    import repro.noc

    real = repro.noc.xsimulate

    def altered(*a, **kw):
        res = real(*a, **kw)
        res.dtime = res.dtime.copy()
        res.dtime[res.dtime >= 0] += 40  # every delivery 40 cycles later
        return res

    monkeypatch.setattr(repro.noc, "xsimulate", altered)
    broken = small_run("xsim.mesh8x8.fig6")
    assert not broken["correct"]
    assert broken["checks"]["pairs_differing"]["value"] == 0
    assert broken["checks"]["latency_gap"]["value"] > 0.1
