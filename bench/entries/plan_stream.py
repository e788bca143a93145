"""Plan serving: an open loop of requests into one ``PlanServer``.

Fabric controllers and coherence engines ask for plans for multicast
groups that repeat with skew. Requests arrive as a Poisson process at
``rate_per_s``; each one's group is drawn Zipf(``zipf_s``) from a pool of
``pool`` groups. Set-up warms every padded batch shape the server can
dispatch (powers of two up to ``max_batch``) on groups outside the pool,
then fills the arena from ``fill`` draws of the same distribution and runs
``server_warm`` of them through a server. The window submits each request
at its due time; a request's latency runs from its due time to its future
resolving, so a late generator or a stall counts against it.

End-to-end: ``plan_p99_ms`` over every request due in the window; a
request that fails or is not answered within ``timeout_s`` counts as
``timeout_s``. Correct: a seeded sample of the window's answers equals
the reference.

The client keeps as little as it can on the heap that the garbage
collector walks: groups as tuples of ints (untracked once collected),
times and outcomes in numpy arrays, and only the sampled answers, whose
indices are drawn before the window. The window's garbage collections and
the objects the collector tracks are logged: full collections stall the
generator and the server alike.
"""
from __future__ import annotations

import functools
import gc
import math
import threading
import time

import numpy as np

from bench import check
from bench.harness import Outcome


def pct(values, q: float) -> float:
    """The ``q`` quantile of ``values``: the least value with at least a
    share ``q`` of them at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    return float(v[max(0, math.ceil(q * len(v)) - 1)])


def run(ctx) -> Outcome:
    from repro.core import grid, planner_for
    from repro.serve import PlanServer

    cfg, tr = ctx.config, ctx.traffic
    n, algo = cfg["n"], cfg["algorithm"]
    g = grid(n)
    gen = ctx.cell.generator()
    ranges = tr["dest_ranges"]
    pool, seed = tr["pool"], ctx.seed
    rate = tr["rate_per_s"]
    count = int(rate * ctx.seconds * 1.2) + 64
    times = gen.poisson_times(seed, rate, count)
    count = int((times < ctx.seconds).sum())
    ranks = gen.zipf_ranks(seed, pool, tr["zipf_s"], count + tr["fill"])
    memo = {}

    def grp(r):
        v = memo.get(r)
        if v is None:
            src, dests = gen.group(n, seed, int(r), ranges)
            v = memo[r] = (src, tuple(dests))
        return v

    stream = [grp(r) for r in ranks[tr["fill"]:]]
    pl = planner_for(g, algo)
    ctx.log(f"bench: planner device path {pl.support}")
    size = 1
    while size <= tr["max_batch"]:
        pl.plan_many(gen.distinct(n, seed, size, ranges, first=pool + size))
        size *= 2
    pl.clear()
    pl.plan_many([grp(r) for r in ranks[:tr["fill"]]])
    ps = PlanServer(g, algo, max_batch=tr["max_batch"],
                    max_wait_s=tr["max_wait_s"], planner=pl)
    warm = [ps.submit(*grp(r)) for r in ranks[:tr["server_warm"]]]
    for f in warm:
        f.result(timeout=tr["timeout_s"])
    del warm
    info0, stats0 = pl.info(), dict(ps.stats)

    # the client keeps no future: each one's callback records when it
    # resolved, whether it answered, and the answer only if it is sampled
    picked = check.sample_indices(seed, count, tr["check_sample"])
    keep = set(picked)
    done = np.zeros(count)
    ok = np.zeros(count, dtype=bool)
    late = np.zeros(count)
    answers = {}
    resolved = threading.Semaphore(0)

    def finished(i, f):
        done[i] = time.perf_counter()
        if not f.cancelled() and f.exception() is None:
            ok[i] = True
            if i in keep:
                answers[i] = f.result()
        resolved.release()

    pauses = []  # (generation, seconds) of each garbage collection
    started = []

    def on_gc(phase, info):
        if phase == "start":
            started.append(time.perf_counter())
        elif started:
            pauses.append((info["generation"], time.perf_counter() - started.pop()))

    gc.collect()
    tracked_open = len(gc.get_objects())
    gc.callbacks.append(on_gc)
    t0 = ctx.open_window()
    due_s = t0 + times[:count]
    due = due_s.tolist()
    i = 0
    with ctx.span("bench.generate"):
        while i < count:
            now = time.perf_counter()
            if due[i] > now:
                time.sleep(min(due[i] - now, 0.001))
                continue
            while i < count and due[i] <= now:
                src, dests = stream[i]
                late[i] = now - due[i]
                ps.submit(src, dests).add_done_callback(
                    functools.partial(finished, i))
                i += 1
    t_end = t0 + ctx.seconds
    deadline = time.perf_counter() + tr["timeout_s"]
    for _ in range(count):
        if not resolved.acquire(timeout=max(0.0, deadline - time.perf_counter())):
            break
    ctx.close_window(max(t_end, float(done.max())))
    gc.callbacks.remove(on_gc)
    tracked_close = len(gc.get_objects())
    info1, stats1 = pl.info(), dict(ps.stats)
    ps.close(drain=False)
    failed = int(count - ok.sum())  # failed or not answered in time
    lat = np.where(ok, done - due_s, tr["timeout_s"])
    fifth = max(1, count // 5)
    p99 = pct(lat, 0.99)
    ctx.log(f"bench: {count} requests, generator late p50 "
            f"{pct(late, 0.5) * 1e3!r} ms p99 {pct(late, 0.99) * 1e3!r} ms "
            f"max {float(late.max()) * 1e3!r} ms")
    full = [t for g_, t in pauses if g_ == 2]
    ctx.log(f"bench: {len(pauses)} garbage collections in the window, "
            f"{len(full)} of the oldest generation, longest "
            f"{max((t for _, t in pauses), default=0.0) * 1e3!r} ms; "
            f"objects tracked {tracked_open} at the window's open, "
            f"{tracked_close} at its close; {len(answers)} answers kept, "
            f"{len(memo)} groups")
    bad = check.plans_differing(
        algo, n, [(*stream[k], answers.get(k)) for k in picked])
    ctx.log(f"bench: {len(picked)} answers compared with the reference")
    counters = {
        "requests": stats1["requests"] - stats0["requests"],
        "batches": stats1["batches"] - stats0["batches"],
        "hits": info1.hits - info0.hits,
        "misses": info1.misses - info0.misses,
        "dispatches": info1.dispatches - info0.dispatches,
        "generator_late_max_s": float(late.max()),
        "gc_full_collections": len(full),
        "gc_longest_s": max((t for _, t in pauses), default=0.0),
        "p50_ms": pct(lat, 0.5) * 1e3,
        "p99_first_fifth_ms": pct(lat[:fifth], 0.99) * 1e3,
        "p99_last_fifth_ms": pct(lat[-fifth:], 0.99) * 1e3,
    }
    return Outcome(
        attempted=count, failed=failed,
        metrics={"plan_p99_ms": p99 * 1e3}, counters=counters,
        checks=[("plans_differing", bad, 0)],
    )
