"""The degraded-mesh cell, ``plan.mesh8x8.degraded``, at sizes a CPU
holds: its configuration, its reference against the program, the control
that shows its comparison can fail, a cut-down run of the cell sound and
broken, and its per-layer readers."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench import check, harness
from bench.gen import plan_groups
from bench.ref import planner as healthy
from bench.ref import planner_faults as ref

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELL = "plan.mesh8x8.degraded"
CONFIG = json.loads(
    (ROOT / "bench" / "configs" / "mesh8x8-tableI-12faults.json").read_text())
BROKEN = [tuple(map(tuple, link)) for link in CONFIG["broken_links"]]
RANGES = [[2, 5], [4, 8], [7, 10], [10, 16]]


def _program_plans(broken, count, seed):
    from repro.core import faulty, grid, plan

    g = faulty(grid(8), broken)
    return [(src, dests, plan("DPM", g, src, dests))
            for src, dests in plan_groups.distinct(8, seed, count, RANGES)]


def test_config_is_the_ladders_connected_twelve_link_rung():
    from benchmarks.fault_resilience import _connected_fault_ladder
    from repro.core import batch_support, faulty, grid
    from repro.core.routefn import components

    assert CONFIG["n"] == 8 and len(BROKEN) == 12
    rung = _connected_fault_ladder(grid(8), [12], seed=7)[12]
    assert sorted(BROKEN) == sorted(rung)
    g = faulty(grid(8), BROKEN)
    assert not components(g).any()
    assert batch_support(g).ok


def test_reference_equals_the_program_on_the_config_fault_set():
    answered = _program_plans(BROKEN, 200, seed=2**33 + 11)
    g = ref.FaultyMesh(8, BROKEN)
    for src, dests, p in answered:
        assert ref.plan(g, src, dests) == check.plan_triples(p), (src, dests)


def test_reference_without_faults_is_the_healthy_reference():
    g = ref.FaultyMesh(8, [])
    for src, dests in plan_groups.distinct(8, 2**33 + 12, 200, RANGES):
        assert ref.plan(g, src, dests) == healthy.plan("DPM", 8, src, dests)


def test_healthy_reference_as_control_reads_plans_differing():
    """The healthy mesh's plans in the reference's place: the comparison
    the cell decides ``correct`` by tells them apart."""
    from bench.entries import plan_bulk_degraded as entry

    answered = _program_plans(BROKEN, 200, seed=2**33 + 13)
    assert entry.plans_differing(ref.FaultyMesh(8, BROKEN), answered) == 0
    assert entry.plans_differing(ref.FaultyMesh(8, []), answered) > 150


def _small_run(seed=2**33 + 5):
    import jax

    c = harness.cell(CELL)
    c.traffic.update({"batch": 64, "batches": 2, "check_sample": 128})
    return harness.execute(c, seed, 0.4, False, jax.devices()[:1])


def test_cell_is_correct_and_catches_an_altered_answer(monkeypatch):
    from repro.core import arena_clear
    from repro.core.batch_planner import BatchPlanner

    arena_clear()
    sound = _small_run()
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    arena_clear()
    decode = BatchPlanner._decode

    def altered(self, *a, **kw):
        p = decode(self, *a, **kw)
        p.paths = p.paths[:-1]
        return p

    monkeypatch.setattr(BatchPlanner, "_decode", altered)
    broken = _small_run()
    arena_clear()
    assert not broken["correct"]
    assert broken["checks"]["plans_differing"]["value"] > 0


def test_cell_exits_where_the_program_plans_the_fabric_on_the_host(
        monkeypatch):
    """A program whose batch planner refuses the degraded fabric would run
    no operation on the device: the cell ends non-zero, naming why."""
    import jax

    import repro.core
    from repro.core.batch_planner import _Support

    class HostOnly:
        support = _Support(False, "degraded topology (broken links)")

    monkeypatch.setattr(repro.core, "planner_for", lambda *a, **kw: HostOnly)
    c = harness.cell(CELL)
    with pytest.raises(SystemExit, match="broken links"):
        harness.execute(c, 2**33 + 6, 0.4, False, jax.devices()[:1])


@pytest.mark.parametrize("counters,want", [
    ({"batched_plans": 4096, "host_plans": 0, "segment_s": 0.2048},
     {"degraded.segment_us_per_plan": 50.0, "degraded.host_plan_share": 0.0}),
    # a program without the segmenting decode plans all on the host
    ({"batched_plans": 0, "host_plans": 4096},
     {"degraded.segment_us_per_plan": None,
      "degraded.host_plan_share": 100.0}),
])
def test_counter_readers(counters, want):
    c = harness.cell(CELL)
    run = harness.Run(c, 30.0, counters, None, {}, 1)
    for name, value in want.items():
        reader = harness.load_module(
            ROOT / "bench" / "layer_metrics" / f"{name}.py", name)
        got = reader.read(run)
        assert got is None if value is None else got == pytest.approx(value)
    for name in ("degraded.merge_ms", "degraded.device_idle_share"):
        reader = harness.load_module(
            ROOT / "bench" / "layer_metrics" / f"{name}.py", name)
        assert reader.read(run) is None  # no trace, nothing to read
