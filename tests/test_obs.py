"""Host observability of the planning path (``repro.obs``): the time
counters of ``ArenaInfo`` and ``PlanServer.stats``, the collector clock,
the program's spans in a real profiler trace, and the module names that
device-trace readers find the merge and the cycle scan by."""
from __future__ import annotations

import gc
import glob
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import arena_clear, faulty, grid, plan_cache_clear
from repro.serve import PlanServer

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:  # the trace reduction lives in bench/
    sys.path.insert(0, str(ROOT))

TIME_FIELDS = ("plan_s", "lookup_s", "dispatch_s", "sync_s", "decode_s",
               "host_plan_s")
# a fault outside the 4x4's source rows: degraded plans are segmented
DEGRADED = faulty(grid(4), (((0, 0), (1, 0)),))


@pytest.fixture()
def _fresh_arena():
    plan_cache_clear()
    arena_clear()
    yield
    plan_cache_clear()
    arena_clear()


def _requests(k: int, shift: int = 0):
    return [((i % 4, (i + shift) % 4),
             [((i + 1 + shift) % 4, 3), (3, (i + 2) % 4)]) for i in range(k)]


def _serve(topo, reqs, algo="DPM"):
    with PlanServer(topo, algo, max_batch=8, max_wait_s=0.002) as ps:
        for f in [ps.submit(src, dests) for src, dests in reqs]:
            f.result(timeout=60)
    return ps


@pytest.mark.parametrize("healthy", [True, False], ids=["device", "host"])
def test_time_counters_fill_and_stay_ordered(healthy, _fresh_arena):
    """The host case: the energy objective, outside the device gate."""
    algo = "DPM" if healthy else "DPM-E"
    ps = _serve(grid(4), _requests(12), algo)
    st = ps.stats
    assert st["requests"] == 12
    assert 0.0 < st["queue_wait_max_s"] <= st["queue_wait_s"]
    first = ps.info()
    second = _serve(grid(4), _requests(12, shift=1), algo).info()
    for info in (first, second):
        assert all(getattr(info, f) >= 0.0 for f in TIME_FIELDS)
        parts = (info.lookup_s + info.dispatch_s + info.sync_s
                 + info.decode_s + info.host_plan_s)
        assert parts <= info.plan_s
    for f in TIME_FIELDS:
        assert getattr(second, f) >= getattr(first, f), f
    assert first.plan_s > 0 and first.lookup_s > 0
    if healthy:
        assert first.dispatch_s > 0 and first.sync_s > 0
        assert first.decode_s > 0 and first.host_plan_s == 0
    else:
        assert first.host_plan_s > 0 and first.decode_s == 0


def test_close_without_drain_cancels_stamped_requests(_fresh_arena):
    ps = PlanServer(grid(4), "DPM", max_batch=1, max_wait_s=0.0)
    futs = [ps.submit(src, dests) for src, dests in _requests(64)]
    ps.prefetch(_requests(8, shift=2))
    ps.close(drain=False)
    assert not ps._thread.is_alive()
    assert all(f.done() for f in futs)
    assert any(f.cancelled() for f in futs)
    assert ps.queue_depth == 0


def test_gc_clock_counts_a_forced_collection_and_installs_once():
    obs.install_gc_clock()
    obs.install_gc_clock()
    assert sum(cb is obs._CLOCK for cb in gc.callbacks) == 1
    before = obs.snapshot()
    gc.collect()
    after = obs.snapshot()
    assert after.collections >= before.collections + 1
    assert after.full_collections >= before.full_collections + 1
    assert after.pause_s > before.pause_s


def _host_spans(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host") for line in plane.lines
            for e in line.events if e.name.startswith("repro.")]


@pytest.mark.parametrize("python_tracer", [1, 0], ids=["python", "host"])
def test_profiler_trace_holds_the_program_spans(python_tracer, tmp_path,
                                                 _fresh_arena):
    """The spans are host trace events: they need no Python tracer, whose
    per-call recording slows host planning."""
    obs.install_gc_clock()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = python_tracer
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve(grid(4), _requests(12))
        _serve(DEGRADED, _requests(4))
        _serve(grid(4), _requests(4), "DPM-E")
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    names = set(_host_spans(str(tmp_path)))
    assert names >= {
        "repro.planserve.wait", "repro.planserve.batch",
        "repro.planserve.resolve", "repro.planner.lookup",
        "repro.planner.dispatch", "repro.planner.sync",
        "repro.planner.decode", "repro.planner.host_plan", "repro.gc",
        "repro.planner.segment", "repro.planner.tables",
    }


def test_idle_gap_takes_the_innermost_program_span():
    """A gap inside a collection, while the server waits, all inside an
    outer span of the caller: the collection names it."""
    from bench.trace import Reduced

    ex = {"devices": {"0": {"XLA Ops": [["fusion.1", 0, 10],
                                        ["fusion.2", 110, 10]]}},
          "host_spans": [["bench.generate", 0, 1000],
                         ["repro.planserve.wait", 5, 200],
                         ["repro.gc", 20, 80]]}
    r = Reduced(ex, [0], 1e-6)
    assert r.idle_gaps(1) == [["repro.gc", 100e-9]]


def test_segment_counters_count_what_segmentation_changed(_fresh_arena):
    """On a degraded fabric the decode segments device plans: the
    counters hold the plans segmentation changed, the worms it added and
    its seconds, a part of the decode's."""
    from repro.core import (bulk_plan, plan_dpm, planner_for,
                            segment_plan_for_faults)

    reqs = _requests(12) + _requests(12, shift=1)
    plans = bulk_plan(DEGRADED, reqs)
    raw = {(src, tuple(sorted(d))): plan_dpm(DEGRADED, src, d)
           for src, d in reqs}
    seg = {k: segment_plan_for_faults(p, DEGRADED) for k, p in raw.items()}
    info = planner_for(DEGRADED, "DPM").info()
    assert info.batched_plans == len(raw)
    assert 0 < info.segmented_plans == sum(
        seg[k] is not p for k, p in raw.items())
    assert info.relay_worms == sum(
        len(seg[k].paths) - len(p.paths) for k, p in raw.items())
    assert 0.0 < info.segment_s <= info.decode_s
    assert [p.paths for p in plans] == [
        seg[(src, tuple(sorted(d)))].paths for src, d in reqs]
    bulk_plan(grid(4), reqs)
    h = planner_for(grid(4), "DPM").info()
    assert (h.segment_s, h.segmented_plans, h.relay_worms) == (0.0, 0, 0)


def _hlo_module_name(lowered) -> str:
    return re.match(r"HloModule (\S+?),", lowered.as_text(dialect="hlo"))[1]


@pytest.mark.parametrize("k", [16, 32])
def test_merge_module_keeps_the_name_trace_readers_match(k):
    """The merge lowers into the one module at any slot width."""
    from repro.kernels.dpm_cost.ops import dpm_plan_exact

    B, NN = 4, 64
    i32, f32 = jnp.int32, jnp.float32
    shapes = [((B, k), i32), ((B,), i32), ((NN, NN), i32), ((NN,), i32),
              ((NN, NN), i32), ((NN, NN), f32), ((NN, NN), f32),
              ((NN, NN), f32)]
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    lowered = dpm_plan_exact.lower(*args, np_=8, overhead=0.0)
    assert "dpm_plan_exact" in _hlo_module_name(lowered)


def test_degraded_merge_keeps_the_module_name():
    """With the chain-pass tables of a degraded fabric, too."""
    from repro.kernels.dpm_cost.ops import dpm_plan_exact

    B, NN, k = 4, 64, 16
    i32, f32 = jnp.int32, jnp.float32
    shapes = [((B, k), i32), ((B,), i32), ((NN, NN), i32), ((NN,), i32),
              ((NN, NN), i32), ((NN, NN), f32), ((NN, NN), f32),
              ((NN, NN), f32), ((NN, NN, 2), i32), ((NN, NN, 2), i32)]
    args = [jax.ShapeDtypeStruct(s, d) for s, d in shapes]
    lowered = dpm_plan_exact.lower(*args, np_=8, overhead=0.0)
    assert "dpm_plan_exact" in _hlo_module_name(lowered)


def test_cycle_scan_module_keeps_the_name_trace_readers_match():
    from repro.noc import NoCConfig, synthetic_workload
    from repro.noc.xsim.compile import compile_workload, stack_traffic
    from repro.noc.xsim.run import _run_batch

    cfg = NoCConfig(n=4, dest_range=(2, 4), warmup=0, drain_grace=20)
    wl = synthetic_workload(cfg, 0.02, 10, seed=0)
    ref, stacked = stack_traffic([compile_workload(cfg, wl, "DPM")])
    shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
              for k, v in stacked.items()}
    lowered = _run_batch.lower(
        shapes, T=wl.horizon + cfg.drain_grace,
        F=max(cfg.flits_per_packet, int(stacked["flits"].max())),
        V=cfg.vcs_per_class, BD=cfg.buffer_depth, L=ref.num_links,
        NN=ref.num_nodes, ND=int(stacked["dslot"].max()) + 1,
        kind=ref.kind, n=ref.n, m=ref.m, params=ref.params, backend="ref",
        epoch_len=cfg.epoch_len,
    )
    assert "_run_batch" in _hlo_module_name(lowered)
