"""xsim vs WormholeSim: wall-clock + fig6-style batched latency curves.

Protocol (all knobs through ``NoCConfig`` — satellite of ISSUE 3):

* a saturation-regime fig6-style sweep — 10 injection rates x 4 algorithms
  (MU/MP/NMP/DPM) on the paper's 8x8 mesh at the heaviest destination range
  (10-16) — run twice: sequentially through the event-ordered Python
  ``WormholeSim`` (one ``simulate`` per point) and as batched ``xsimulate``
  dispatches (the whole grid in one vmapped/pmapped scan).
* the planner cache is pre-warmed untimed for both engines (planning is
  shared infrastructure); the xsim timing *includes* host lowering, XLA
  compilation and the device run — everything a user pays.
* cross-validation gate: on small mesh/torus workloads, per-packet delivery
  sets must be identical and average latency within 10% (the xsim fidelity
  contract, also pinned by tests/test_xsim.py).
* contention-aware DPM (ROADMAP item): the saturated tail of the same grid
  re-run with DPM planning under the "contention" cost model — central
  mesh links priced up, steering merges toward the edge — against plain
  hop-count DPM, with a gate that the two latency curves actually diverge
  at saturation (plans must differ AND latency must move; at low load the
  two are intentionally near-identical).

* a scale section (ISSUE 6 tentpole gate): 32x32 meshes (16x16 in quick
  mode) through the fused packed-plane cycle engine, batching a (fault
  rung x injection rate x algorithm x seed) grid — the fault axis runs a
  healthy mesh and a clustered *router* failure (``core.router_failure``)
  on the outer loop (fault sets change the plans, so they can't share one
  compiled batch), while rate x algo x seed ride the vmapped/pmap-sharded
  batch axis of one ``xsimulate`` call per rung. Reports sustained
  packet-hops/second against the pre-PR committed baseline (see
  ``_COMMITTED_BASELINE``) and writes the repo-root ``BENCH_xsim.json``
  perf-trajectory artifact.

The committed artifact (results/xsim_sweep.json) records curves from both
engines, the wall-clock breakdown, measured speedup, parity results, and the
host parallelism available — the batch axis shards across forced host CPU
devices, so the speedup scales with cores (this container has very few; see
the artifact's "env" block).
"""
from __future__ import annotations

import json
import os
import pathlib
import time

CACHE = pathlib.Path(__file__).parent / "results" / "xsim_sweep.json"
BENCH = pathlib.Path(__file__).parent.parent / "BENCH_xsim.json"

# The perf gate's reference point: the last xsim_sweep.json committed before
# the fused packed-plane engine landed (slot-pool engine, this 8x8 sweep
# protocol). Its sustained wall-clock is recorded in that artifact; the hop
# total is the sweep's conserved flit_link_traversals sum, which is plan-
# determined and engine-independent (delivery-set parity pins it), so it
# reproduces exactly by re-counting the same workload grid. Measured on 2
# forced host CPU devices — note the per-core scaling when comparing.
_COMMITTED_BASELINE = {
    "hops": 4_384_342,
    "sustained_wall_s": 31.67,
    "hops_per_s": 138_438,
    "cpu_devices": 2,
}


def _force_host_devices() -> None:
    """Shard the batched scan across host cores (one forced CPU device per
    core). Only possible before the jax backend initializes, only on a run
    held to the CPU (``JAX_PLATFORMS=cpu``; on an accelerator the sweep
    runs on its devices), and only done when this suite runs — never as an
    import side effect, so other benchmark suites keep their default
    single-device topology."""
    if "XLA_FLAGS" in os.environ:
        return
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        return
    try:
        from jax._src import xla_bridge

        if xla_bridge._backends:  # backend already up: too late, no-op
            return
    except Exception:
        return
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={os.cpu_count() or 1}"
    )

PARITY_CASES = [
    ("mesh-unicast", dict(n=4, multicast_fraction=0.0), 0.05, 100, "MU"),
    ("mesh-multicast", dict(n=5, multicast_fraction=0.5,
                            dest_range=(3, 6)), 0.04, 150, "DPM"),
    ("torus-multicast", dict(n=4, topology="torus",
                             dest_range=(2, 5)), 0.06, 150, "DPM"),
]


def _parity_case(name, cfg_kw, rate, cycles, algo):
    from repro.core import plan
    from repro.core.topology import make_topology
    from repro.noc import NoCConfig, WormholeSim, synthetic_workload, xsimulate

    cfg = NoCConfig(warmup=0, drain_grace=800, **cfg_kw)
    wl = synthetic_workload(cfg, rate, cycles, seed=2)
    res = xsimulate(cfg, [wl], (algo,))
    g = make_topology(cfg.topology, cfg.n, cfg.m)
    sim = WormholeSim(cfg, measure_window=(0, wl.horizon))
    for r in wl.requests:
        sim.add_plan(plan(algo, g, r.src, r.dests), r.time)
    pst = sim.run(wl.horizon + cfg.drain_grace)
    psets = {pk.pid: {g.idx(c) for c in pk.delivery_times}
             for pk in sim.packets}
    xlat = float(res.avg_latency(0, 0))
    dev = abs(xlat - pst.avg_latency) / max(1e-9, pst.avg_latency)
    return {
        "case": name,
        "delivery_sets_equal": bool(psets == res.delivered_sets(0, 0)),
        "latency_py": round(pst.avg_latency, 3),
        "latency_xsim": round(xlat, 3),
        "latency_rel_dev": round(dev, 4),
        "within_10pct": bool(dev <= 0.10),
    }


def _drop_node(wl, dead):
    """Filter a workload for a failed router: it can neither source nor
    sink packets (every incident link is down)."""
    from dataclasses import replace

    from repro.noc.traffic import Workload

    reqs = []
    for r in wl.requests:
        if r.src == dead:
            continue
        dests = [d for d in r.dests if d != dead]
        if dests:
            reqs.append(replace(r, dests=dests))
    return Workload(name=f"{wl.name}-minus-{dead}", requests=reqs,
                    horizon=wl.horizon)


def _scale_section(quick: bool):
    """32x32 (16x16 quick) batched sweep over (fault x rate x algo x seed).

    One ``xsimulate`` call per fault rung carries the full rate x algo x
    seed grid on the vmapped (and, with >1 host device, pmap-sharded)
    batch axis. Returns the artifact block + CSV rows; asserts the ISSUE 6
    perf gate (>= 5x the committed baseline's sustained packet-hops/s) in
    full mode.
    """
    import jax

    from repro.core import plan, router_failure
    from repro.core.topology import make_topology
    from repro.noc import NoCConfig, synthetic_workload, xsimulate
    from repro.noc.xsim.run import CTR

    n = 16 if quick else 32
    cycles = 300 if quick else 1000
    rates = [0.05] if quick else [0.04, 0.06]
    seeds = [0] if quick else [0, 1]
    algos = ("DPM",) if quick else ("DPM", "MP")
    flit_i = CTR.index("flit_link_traversals")
    base = make_topology("mesh", n, None)
    dead = (n // 2, n // 2)
    rungs = [("healthy", ()), ("router_failure", router_failure(base, dead))]

    per_rung = {}
    total_hops, total_sustained = 0, 0.0
    for rname, broken in rungs:
        cfg = NoCConfig(n=n, dest_range=(4, 8), warmup=100,
                        drain_grace=400, broken_links=broken)
        topo = make_topology(cfg.topology, cfg.n, cfg.m, cfg.broken_links)
        wls = []
        for rate in rates:
            for seed in seeds:
                wl = synthetic_workload(cfg, rate, cycles, seed=seed)
                wls.append(_drop_node(wl, dead) if broken else wl)
        for wl in wls:  # planner cache warm-up, untimed (shared infra)
            for req in wl.requests:
                for a in algos:
                    plan(a, topo, req.src, req.dests)
        t0 = time.monotonic()
        res = xsimulate(cfg, wls, algos)
        t_cold = time.monotonic() - t0
        t0 = time.monotonic()
        res = xsimulate(cfg, wls, algos)
        t_sus = time.monotonic() - t0
        hops = int(res.ctr[:, flit_i].sum())
        assert 0 < res.slots_hwm() <= res.slots
        total_hops += hops
        total_sustained += t_sus
        per_rung[rname] = {
            "batch_points": len(wls) * len(algos),
            "broken_links": len(broken),
            "cycles_simulated": res.cycles,
            "hops": hops,
            "cold_s": round(t_cold, 2),
            "sustained_s": round(t_sus, 2),
            "hops_per_s_sustained": int(hops / max(1e-9, t_sus)),
            "worm_pool_capacity": res.slots,
            "worm_pool_hwm": res.slots_hwm(),
            "avg_latency_rate0": {
                a: round(float(res.avg_latency(0, i)), 2)
                for i, a in enumerate(res.algos)
            },
        }
    hops_per_s = total_hops / max(1e-9, total_sustained)
    speedup = hops_per_s / _COMMITTED_BASELINE["hops_per_s"]
    devices = jax.local_device_count()
    block = {
        "mesh": f"{n}x{n}", "cycles": cycles, "rates": rates,
        "seeds": seeds, "algos": list(algos),
        "axes": "fault rung (outer) x rate x algo x seed (batched)",
        "per_rung": per_rung,
        "sustained_hops_per_s": int(hops_per_s),
        "committed_baseline": _COMMITTED_BASELINE,
        "speedup_vs_committed_sustained": round(speedup, 2),
        "scaling_note": (
            "the committed baseline ran with "
            f"{_COMMITTED_BASELINE['cpu_devices']} forced host CPU devices; "
            f"this run had {devices} (see env) — the batch axis pmap-shards "
            "across devices, so per-core the fused-engine gain is ~2x the "
            "reported ratio when devices=1. Sustained includes host "
            "lowering + the device scan; the device scan alone runs "
            "~1.6us/cycle/1024-node-mesh-instance (flat in pool size: "
            "state is router-centric, not worm-centric)"
        ),
    }
    if not quick:
        assert speedup >= 5.0, (
            f"fused-engine perf gate: {hops_per_s:,.0f} hops/s is only "
            f"{speedup:.2f}x the committed baseline "
            f"{_COMMITTED_BASELINE['hops_per_s']:,} hops/s"
        )
    rows = [
        (f"xsim_sweep/scale_{n}x{n}/{rname}", r["sustained_s"] * 1e6,
         f"points={r['batch_points']};hops={r['hops']};"
         f"hops_per_s={r['hops_per_s_sustained']};hwm={r['worm_pool_hwm']}")
        for rname, r in per_rung.items()
    ]
    rows.append((
        f"xsim_sweep/scale_{n}x{n}/gate", 0.0,
        f"sustained_hops_per_s={int(hops_per_s)};"
        f"speedup_vs_committed=x{speedup:.2f};devices={devices}",
    ))
    return block, rows


def run(quick: bool = False, algos=None):
    _force_host_devices()
    import jax

    from repro.core import plan
    from repro.core.topology import make_topology
    from repro.noc import NoCConfig, simulate, synthetic_workload, xsimulate

    from .noc_common import resolve_algos

    # registry figure set + DPM-E: the sweep doubles as the demonstration
    # that a cost-model variant rides the batched engine unmodified
    algos = tuple(
        resolve_algos(algos) + ([] if algos is not None else ["DPM-E"])
    )
    cycles = 250 if quick else 600
    rates = (
        [0.06, 0.10, 0.14]
        if quick
        else [0.05, 0.06, 0.07, 0.08, 0.09, 0.10, 0.11, 0.12, 0.13, 0.14]
    )
    cfg = NoCConfig(dest_range=(10, 16), warmup=100, drain_grace=400)
    wls = [synthetic_workload(cfg, r, cycles, seed=3) for r in rates]

    # planner cache warmup — shared infrastructure, untimed for both engines
    g = make_topology(cfg.topology, cfg.n, cfg.m)
    for wl in wls:
        for r in wl.requests:
            for a in algos:
                plan(a, g, r.src, r.dests)

    # --- sequential Python WormholeSim baseline -------------------------
    py_curves: dict[str, list] = {a: [] for a in algos}
    t0 = time.monotonic()
    for rate, wl in zip(rates, wls):
        for algo in algos:
            st = simulate(cfg, wl, algo)
            py_curves[algo].append((rate, round(st.avg_latency, 2)))
    t_py = time.monotonic() - t0

    # --- batched xsim: the whole grid through one engine ----------------
    t0 = time.monotonic()
    res = xsimulate(cfg, wls, algos)
    x_curves = {
        algo: [(rates[w], round(float(res.avg_latency(w, a)), 2))
               for w in range(len(rates))]
        for a, algo in enumerate(algos)
    }
    t_x_cold = time.monotonic() - t0
    # sustained: same shapes, XLA executable cached — the marginal cost of
    # the next sweep in a design-space-exploration campaign
    t0 = time.monotonic()
    xsimulate(cfg, wls, algos)
    t_x = time.monotonic() - t0
    from repro.noc.xsim.run import CTR

    hops_8x8 = int(res.ctr[:, CTR.index("flit_link_traversals")].sum())

    # --- contention-aware DPM at saturation (ROADMAP item) --------------
    # the heaviest rates of the same grid, DPM planned under "contention"
    # (mesh bisection links cost more) vs the plain hop objective; needs
    # the plain-DPM curve as baseline, so it only runs when DPM is in the
    # sweep set (an --algos override may exclude it)
    contention = None
    sat_rates = rates[-3:]
    sat_wls = wls[-3:]
    if "DPM" in algos:
        for wl in sat_wls:  # warm the contention plans untimed, like the rest
            for r in wl.requests:
                plan("DPM", g, r.src, r.dests, cost_model="contention")
        res_c = xsimulate(cfg, sat_wls, ("DPM",), cost_model="contention")
        dpm_plain = dict(x_curves["DPM"])
        curve_contention = [
            (sat_rates[w], round(float(res_c.avg_latency(w, 0)), 2))
            for w in range(len(sat_rates))
        ]
        plans_differ = sum(
            1
            for wl in sat_wls
            for r in wl.requests
            if [p.hops for p in plan("DPM", g, r.src, r.dests).paths]
            != [p.hops for p in
                plan("DPM", g, r.src, r.dests, cost_model="contention").paths]
        )
        rel_div = [
            abs(lat - dpm_plain[rate]) / max(1e-9, dpm_plain[rate])
            for rate, lat in curve_contention
        ]
        contention = {
            "rates": sat_rates,
            "dpm_plain": [(r, dpm_plain[r]) for r in sat_rates],
            "dpm_contention": curve_contention,
            "plans_differ": plans_differ,
            "max_rel_divergence": round(max(rel_div), 4),
            "diverges_at_saturation": bool(
                plans_differ > 0 and max(rel_div) > 0.01
            ),
        }
        assert contention["diverges_at_saturation"], (
            "contention-priced DPM is indistinguishable from plain DPM at "
            f"saturation: {contention}"
        )

    # --- scale section: fused engine at 32x32 (fault x rate x algo x seed)
    scale, scale_rows = _scale_section(quick)

    parity = [_parity_case(*case) for case in PARITY_CASES]
    speedup = t_py / max(1e-9, t_x)
    speedup_cold = t_py / max(1e-9, t_x_cold)

    data = {
        "sweep": {
            "mesh": "8x8", "dest_range": [10, 16], "cycles": cycles,
            "warmup": cfg.warmup, "drain_grace": cfg.drain_grace,
            "rates": rates, "algos": list(algos),
            "points": len(rates) * len(algos),
        },
        "wall_clock_s": {
            "python_wormhole_sequential": round(t_py, 2),
            "xsim_batched_cold": round(t_x_cold, 2),
            "xsim_batched_sustained": round(t_x, 2),
            "xsim_note": "cold includes host lowering + XLA compile + device"
                         " run; sustained reuses the cached executable (the"
                         " marginal sweep cost); planner cache pre-warmed"
                         " untimed for both engines",
        },
        "speedup": round(speedup, 2),
        "speedup_cold": round(speedup_cold, 2),
        "speedup_note": (
            "measured on this container — see env.cpu_count. The fused "
            "packed-plane engine is dense-arbitration-bound on XLA:CPU "
            "(per-cycle cost is set by the router geometry, flat in the "
            "in-flight worm pool) and shards the sweep axis across host "
            "devices via pmap, so the speedup scales with available cores "
            "while the Python baseline is inherently single-core; the "
            "Pallas chunked-kernel backend does not lower for TPU yet, so "
            "the ref scan is the engine on every platform"
        ),
        "env": {
            "cpu_count": os.cpu_count(),
            "jax_devices": jax.local_device_count(),
            "backend": jax.default_backend(),
        },
        "xsim": {"slots": res.slots, "slots_hwm": res.slots_hwm(),
                 "cycles_simulated": res.cycles,
                 "hops_8x8_sweep": hops_8x8},
        "curves": {"python": py_curves, "xsim": x_curves},
        "contention_dpm": contention,
        "scale": scale,
        "cross_validation": parity,
    }
    CACHE.parent.mkdir(parents=True, exist_ok=True)
    CACHE.write_text(json.dumps(data, indent=1))
    # repo-root perf-trajectory artifact (ISSUE 6 satellite): the headline
    # sustained-throughput numbers a future session compares against
    BENCH.write_text(json.dumps({
        "suite": "benchmarks.xsim_sweep",
        "quick": quick,
        "grid_8x8": {
            "sustained_hops_per_s": int(hops_8x8 / max(1e-9, t_x)),
            "speedup_vs_host_sim_sustained": round(speedup, 2),
            "speedup_vs_host_sim_cold": round(speedup_cold, 2),
        },
        "scale_grid": {
            "mesh": scale["mesh"],
            "sustained_hops_per_s": scale["sustained_hops_per_s"],
            "speedup_vs_committed_sustained":
                scale["speedup_vs_committed_sustained"],
            "committed_baseline": scale["committed_baseline"],
            "scaling_note": scale["scaling_note"],
        },
        "env": data["env"],
    }, indent=1))

    rows = [
        ("xsim_sweep/python_sequential", t_py * 1e6,
         f"points={len(rates) * len(algos)}"),
        ("xsim_sweep/xsim_batched", t_x * 1e6,
         f"slots={res.slots};devices={jax.local_device_count()}"),
        ("xsim_sweep/speedup", 0.0,
         f"sustained=x{speedup:.1f};cold=x{speedup_cold:.1f}"),
        *scale_rows,
    ]
    for p in parity:
        rows.append((
            f"xsim_sweep/parity/{p['case']}", 0.0,
            f"sets_equal={p['delivery_sets_equal']};"
            f"latency_dev={p['latency_rel_dev']:.4f}",
        ))
    for algo in algos:
        curve = ";".join(f"{r}:{lat}" for r, lat in x_curves[algo])
        rows.append((f"xsim_sweep/curve/{algo}", 0.0, curve))
    if contention is not None:
        rows.append((
            "xsim_sweep/contention_dpm", 0.0,
            ";".join(f"{r}:{lat}" for r, lat in curve_contention)
            + f";plans_differ={plans_differ}"
            + f";max_rel_div={contention['max_rel_divergence']}"
            + f";diverges={contention['diverges_at_saturation']}",
        ))
    return rows
