"""Bulk planning: a closed loop of ``bulk_plan`` calls on a cold arena.

A collective library or a simulator's lowering plans whole batches of
distinct (source, destination set) instances at once. Each call here
plans one batch of ``batch`` distinct instances with the planner's arena
cleared first, so every instance goes through the device merge and the
host decode. The set-up builds ``batches`` such batches from the seed and
plans each once (tables, compilation, decode memos); the window cycles
through them until ``--seconds`` have passed, ending with its last call.

End-to-end: ``plans_per_s``, every plan returned over the whole window.
Correct: a seeded sample of the window's plans equals the reference.
"""
from __future__ import annotations

import time

from bench import check
from bench.harness import Outcome


def run(ctx) -> Outcome:
    from repro.core import bulk_plan, grid, planner_for

    cfg, tr = ctx.config, ctx.traffic
    n, algo = cfg["n"], cfg["algorithm"]
    g = grid(n)
    gen = ctx.cell.generator()
    size = tr["batch"]
    batches = [gen.distinct(n, ctx.seed, size, tr["dest_ranges"],
                            first=2 * size * i) for i in range(tr["batches"])]
    pl = planner_for(g, algo)
    ctx.log(f"bench: planner device path {pl.support}")
    for b in batches:
        pl.clear()
        bulk_plan(g, b, algo)
    before = pl.info()

    t0 = ctx.open_window()
    calls = plans = 0
    last = {}
    while True:
        i = calls % len(batches)
        pl.clear()
        with ctx.span("bench.bulk_plan"):
            out = bulk_plan(g, batches[i], algo)
        calls += 1
        plans += len(out)
        last[i] = out
        t1 = time.perf_counter()
        if t1 - t0 >= ctx.seconds:
            break
    ctx.close_window(t1)
    after = pl.info()

    answered = [(src, dests, p) for i, out in last.items()
                for (src, dests), p in zip(batches[i], out)]
    picked = check.sample(ctx.seed, answered, tr["check_sample"])
    bad = check.plans_differing(algo, n, picked)
    ctx.log(f"bench: {calls} calls, {plans} plans; {len(picked)} compared")
    counters = {
        "n": n, "batch": size, "calls": calls,
        "dispatches": after.dispatches - before.dispatches,
        "batched_plans": after.batched_plans - before.batched_plans,
        "host_plans": after.host_plans - before.host_plans,
    }
    return Outcome(
        attempted=plans, failed=0,
        metrics={"plans_per_s": plans / (t1 - t0)}, counters=counters,
        checks=[("plans_differing", bad, 0)],
    )
