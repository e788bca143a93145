"""Design-space sweep: one ``xsimulate`` of the paper's fig6 grid per call.

An architect sweeps routing algorithms against injection rates. Each call
simulates every (rate, algorithm) pair of the traffic file in one batched
``xsimulate``, after clearing the plan arena and the plan cache, so every
call plans and lowers its traffic afresh. The traffic is drawn once from
the seed (one workload per rate) and replayed by every call: the engine's
static shapes follow the batch's data, so new traffic each call would
compile each call. Set-up makes the first call (compilation, tables);
the window repeats the call until ``--seconds`` have passed, ending with
its last call.

End-to-end: ``sim_flit_hops_per_s``, the simulated flit-link traversals of
every call in the window over the window. Correct: two (rate, algorithm)
pairs drawn from the seed, one of them DPM's below its saturation, are run
through the benchmark's reference simulator on reference plans: conserved
counts equal, mean latencies within the band xsim states for itself.
"""
from __future__ import annotations

import random
import time

import numpy as np

from bench.harness import Outcome


def workloads(ctx) -> list:
    """``[(rate, [(cycle, src, dests), ...]), ...]`` from the seed."""
    tr, gen = ctx.traffic, ctx.cell.generator()
    return [
        (rate, gen.requests(ctx.config["n"], rate, tr["injection_cycles"],
                            (ctx.seed << 4) + i, tr["multicast"],
                            tr["dest_range"]))
        for i, rate in enumerate(tr["rates"])
    ]


def run(ctx) -> Outcome:
    from repro.core import arena_clear, plan_cache_clear
    from repro.noc import NoCConfig, xsimulate
    from repro.noc.traffic import Request, Workload
    from repro.noc.xsim.run import CTR

    cfg, tr = ctx.config, ctx.traffic
    noc = NoCConfig(
        n=cfg["n"], vcs_per_class=cfg["vcs_per_class"],
        buffer_depth=cfg["buffer_depth"],
        flits_per_packet=cfg["flits_per_packet"],
        multicast_fraction=tr["multicast"], dest_range=tuple(tr["dest_range"]),
        warmup=tr["warmup"], drain_grace=tr["drain_grace"],
    )
    raw = workloads(ctx)
    wls = [Workload(f"uniform-{rate}", [Request(t, s, d) for t, s, d in reqs],
                    tr["injection_cycles"]) for rate, reqs in raw]
    algos = tuple(tr["algorithms"])
    hop = CTR.index("flit_link_traversals")

    def call():
        arena_clear()
        plan_cache_clear()
        with ctx.span("bench.xsimulate"):
            return xsimulate(noc, wls, algos)

    res = call()
    ctx.log(f"bench: xsim backend {res.backend} on {res.devices}, "
            f"{res.cycles} cycles, {len(wls) * len(algos)} pairs")

    t0 = ctx.open_window()
    calls = hops = 0
    while True:
        res = call()
        calls += 1
        hops += int(res.ctr[:, hop].sum())
        t1 = time.perf_counter()
        if t1 - t0 >= ctx.seconds:
            break
    ctx.close_window(t1)

    picked = pick(ctx.seed, tr["rates"], algos, tr["dpm_checked_up_to_rate"])
    checks = compare(ctx, noc, res, raw, algos, picked)
    counters = {
        "calls": calls, "cycles": res.cycles, "batch": len(wls) * len(algos),
        "links": int(res.lutil.shape[-1]), "nodes": cfg["n"] ** 2,
        "vcs": 2 * cfg["vcs_per_class"], "depth": cfg["buffer_depth"],
        "flits": cfg["flits_per_packet"],
    }
    return Outcome(
        attempted=calls, failed=0,
        metrics={"sim_flit_hops_per_s": hops / (t1 - t0)},
        counters=counters, checks=checks,
    )


def pick(seed: int, rates: list, algos: tuple, below: float) -> list:
    """The pairs compared: DPM, the paper's algorithm, at a rate drawn from
    the seed among those up to ``below`` (where it drains, so that every
    conserved count is compared), and one other algorithm at another rate
    drawn from all."""
    rng = random.Random((seed << 8) + 11)
    dpm = algos.index("DPM")
    other = rng.choice([a for a in range(len(algos)) if a != dpm])
    w1 = rng.choice([w for w, r in enumerate(rates) if r <= below])
    w2 = rng.choice([w for w in range(len(rates)) if w != w1])
    return [(w1, dpm), (w2, other)]


def compare(ctx, noc, res, raw, algos, picked) -> list:
    """Each picked pair against the reference simulator. Where the
    reference drains, the conserved counts must be equal: every packet's
    delivered set, the per-link flit counts, the flit-link traversals and
    the packets made and finished; and where both drain, the mean latency
    of the measured packets may part from the reference's by at most
    ``latency_band`` of it, the band xsim states for itself (the engines
    order arbitration differently). Where the reference does not drain (a
    saturated point), xsim must not drain either."""
    from bench.ref import planner as rp
    from bench.ref import wormhole as rw

    n = ctx.config["n"]
    differing = drained_apart = 0
    gap = 0.0
    for w, a in picked:
        t = time.perf_counter()
        ref = rw.simulate(
            n, [(c, rp.plan(algos[a], n, s, d)) for c, s, d in raw[w][1]],
            vcs=noc.vcs_per_class, depth=noc.buffer_depth,
            flits=noc.flits_per_packet,
            cycles=ctx.traffic["injection_cycles"] + noc.drain_grace,
            window=(noc.warmup, ctx.traffic["injection_cycles"]),
        )
        st = res.stats(w, a)
        ref_drained = ref["packets_finished"] == ref["packets_created"]
        drained = st.packets_finished == st.packets_created
        if drained != ref_drained:
            drained_apart += 1
        if ref_drained:
            sets = {p: {(i % n, i // n) for i in v}
                    for p, v in res.delivered_sets(w, a).items()}
            util = res.link_utilization(w, a)
            links = {}
            for lid in np.flatnonzero(util):
                u, d = divmod(int(lid), 4)
                (x, y), (dx, dy) = (u % n, u // n), ((1, 0), (-1, 0), (0, 1),
                                                       (0, -1))[d]
                links[((x, y), (x + dx, y + dy))] = int(util[lid])
            same = (sets == ref["delivered"] and links == ref["link_flits"]
                    and st.flit_link_traversals == ref["flit_link_traversals"]
                    and st.packets_created == ref["packets_created"]
                    and st.packets_finished == ref["packets_finished"])
            differing += not same
        rl = ref["latencies"]
        mean = sum(rl) / max(1, len(rl))
        if ref_drained and drained:
            gap = max(gap, abs(st.avg_latency - mean) / mean)
        ctx.log(f"bench: pair rate {raw[w][0]} {algos[a]}: reference "
                f"{'drained' if ref_drained else 'saturated'} in "
                f"{time.perf_counter() - t:.1f} s; mean latency xsim "
                f"{st.avg_latency!r} reference {mean!r}")
    return [("pairs_differing", differing, 0),
            ("pairs_drained_apart", drained_apart, 0),
            ("latency_gap", gap, ctx.traffic["latency_band"])]
