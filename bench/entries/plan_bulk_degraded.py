"""Bulk planning on a degraded mesh: a closed loop of ``bulk_plan`` calls
with both plan caches cold.

The configuration's ``broken_links`` are dead; every route detours round
them and every plan is cut into label-monotone worms. Each call plans one
batch of ``batch`` distinct instances after clearing the planner's arena
and the host planner's memo (``repro.core.plan_cache_clear``), so every
instance is planned again, wherever the program plans it. The set-up
builds ``batches`` batches from the seed and plans each once; the window
cycles through them until ``--seconds`` have passed, ending with its last
call.

The cell measures planning on the device. A program whose batch planner
refuses the fabric (``support.ok`` False: every plan would go to host
``plan()``, and no operation would run on the device) cannot run it: the
entry exits non-zero, naming the refusal, before it builds anything else.

End-to-end: ``plans_per_s``, every plan returned over the whole window.
Correct: a seeded sample of the window's plans equals the plans of
``bench/ref/planner_faults.py``.
"""
from __future__ import annotations

import gc
import time

from bench import check
from bench.harness import Outcome
from bench.ref import planner_faults as ref


def plans_differing(g: ref.FaultyMesh, answered: list) -> int:
    """How many ``(src, dests, plan)`` answers differ from the reference
    plan of their instance on the degraded mesh ``g`` (a missing plan
    differs)."""
    bad = 0
    for src, dests, p in answered:
        if (p is None or tuple(p.src) != tuple(src)
                or [tuple(d) for d in p.dests] != sorted(map(tuple, dests))
                or check.plan_triples(p) != ref.plan(g, src, dests)):
            bad += 1
    return bad


def run(ctx) -> Outcome:
    from repro import obs
    from repro.core import (bulk_plan, faulty, grid, plan_cache_clear,
                            planner_for)

    cfg, tr = ctx.config, ctx.traffic
    n, algo = cfg["n"], cfg["algorithm"]
    broken = [tuple(map(tuple, link)) for link in cfg["broken_links"]]
    g = faulty(grid(n), broken)
    pl = planner_for(g, algo)
    ctx.log(f"bench: planner device path {pl.support}")
    if not pl.support.ok:
        raise SystemExit(f"bench: the batch planner refuses this fabric "
                         f"({pl.support.reason}); the cell measures planning "
                         f"on the device")
    gen = ctx.cell.generator()
    size = tr["batch"]
    batches = [gen.distinct(n, ctx.seed, size, tr["dest_ranges"],
                            first=2 * size * i) for i in range(tr["batches"])]

    def call(batch):
        pl.clear()
        plan_cache_clear()
        return bulk_plan(g, batch, algo)

    for b in batches:
        call(b)
    gc.collect()  # every window starts from a collected heap
    before, gc0 = pl.info(), obs.snapshot()

    t0 = ctx.open_window()
    calls = plans = 0
    last = {}
    while True:
        i = calls % len(batches)
        with ctx.span("bench.bulk_plan"):
            out = call(batches[i])
        calls += 1
        plans += len(out)
        last[i] = out
        t1 = time.perf_counter()
        if t1 - t0 >= ctx.seconds:
            break
    ctx.close_window(t1)
    after, gc1 = pl.info(), obs.snapshot()

    answered = [(src, dests, p) for i, out in last.items()
                for (src, dests), p in zip(batches[i], out)]
    picked = check.sample(ctx.seed, answered, tr["check_sample"])
    bad = plans_differing(ref.FaultyMesh(n, broken), picked)
    ctx.log(f"bench: {calls} calls, {plans} plans; {len(picked)} compared")
    ctx.log(f"bench: collector in the window: "
            f"{gc1.pause_s - gc0.pause_s!r} s paused, "
            f"{gc1.collections - gc0.collections} collections, "
            f"{gc1.full_collections - gc0.full_collections} full")
    counters = {
        "n": n, "batch": size, "calls": calls,
        "dispatches": after.dispatches - before.dispatches,
        "batched_plans": after.batched_plans - before.batched_plans,
        "host_plans": after.host_plans - before.host_plans,
    }
    # counters of the segmenting decode, where the program has them
    for name in ("segment_s", "segmented_plans", "relay_worms"):
        if hasattr(after, name):
            counters[name] = getattr(after, name) - getattr(before, name)
    return Outcome(
        attempted=plans, failed=0,
        metrics={"plans_per_s": plans / (t1 - t0)}, counters=counters,
        checks=[("plans_differing", bad, 0)],
    )
