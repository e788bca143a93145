"""Chip smoke test: the served paths once each on a TPU, checked against
the repo's references.

    python chip_smoke.py [--seed N]    # one chip: planner + xsim phases
    python chip_smoke.py --four-chips  # four chips: DPM collectives and
                                       # sharded xsim, nothing else

One process drives every phase and starts no child (a chip belongs to one
process). The script stops at the first failed check. It exits non-zero,
without the ``ok`` line, when JAX's first device is not a TPU, and when the
repo's ``src/`` is not next to it. All data is made from ``--seed``.

Phases (one chip):

* planner — 8x8 and 32x32 meshes, DPM under the hop objective, fanout
  8-24: a cold-arena ``bulk_plan`` of distinct instances, compared with
  host ``plan()`` (every plan at 8x8, a seeded sample at 32x32); the
  planner's ``ArenaInfo`` must show every miss planned on the device and
  none on the host; then a ``PlanServer`` stream whose futures must all
  resolve to the ``bulk_plan`` result.
* xsim — an 8x8 DPM workload equal to ``WormholeSim`` in per-packet
  delivery sets and per-link flit counts; a saturated 16x16 DPM batch
  (3 rates x 2 seeds) that must drain and equal the same ``xsimulate``
  call placed on the CPU device.

Phase (four chips, only with ``--four-chips``): the DPM all-to-all against
``lax.all_to_all``, the DPM DP broadcast, one Moonlight MoE layer at its
published width through ``moe_apply_ep`` on a (1, 4) mesh against
``moe_apply_dense`` on one chip (tokens sharded over the batch, then over
the sequence), and a pmap-sharded xsim batch against one run per workload.

The printed wall times are smoke timings (cold = first call, compile
included; warm = the same call again), not benchmark results. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import random
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

FANOUT = (8, 24)  # destinations per multicast in the planner phase
PLANNER_FABRICS = (
    # (mesh side, bulk instances, host-checked sample or None = all,
    #  PlanServer stream)
    (8, 4096, None, 1024),
    (32, 1024, 128, 1024),
)
XSIM_PARITY = dict(n=8, rate=0.03, cycles=200)
XSIM_SAT = dict(n=16, rates=(0.03, 0.04, 0.05), cycles=300, drain=2000)
A2A_CHUNK_BYTES = 4 << 20  # per (src, dst) chunk
MOE_ARCH = "moonshot-v1-16b-a3b"  # published width, one MoE layer
MOE_TOKENS = ((4, 2048), (1, 8192))  # (B, S): B tiles the 4 token shards;
                                     # B=1 shards the sequence instead
MOE_CAPACITY_FACTOR = 4.0  # 4x the mean expert load: no drops
MOE_REL_TOL = 1e-5  # relative to max |y|; v5e readings ~5e-7 (f32 sum order)


def log(msg: str) -> None:
    print(msg, flush=True)


def timed(label: str, fn):
    t0 = time.monotonic()
    out = fn()
    log(f"  {label}: {time.monotonic() - t0:.3f} s (smoke timing)")
    return out


def device_gate(count: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(
            f"chip_smoke: JAX found no TPU (first device platform "
            f"{d.platform!r}); nothing was run"
        )
    log(f"device: kind={d.device_kind} count={len(devs)} "
        f"jax={jax.__version__} host_cores={os.cpu_count()}")
    if len(devs) < count:
        sys.exit(f"chip_smoke: needs {count} chips, found {len(devs)}")
    return devs


def _instances(g, count: int, rng: random.Random, seen: set) -> list:
    """``count`` (src, dests) instances not in ``seen`` (which grows)."""
    nodes = g.nodes()
    out = []
    while len(out) < count:
        src = rng.choice(nodes)
        k = rng.randint(FANOUT[0], min(FANOUT[1], len(nodes) - 1))
        dests = tuple(sorted(rng.sample([x for x in nodes if x != src], k)))
        if (src, dests) in seen:
            continue
        seen.add((src, dests))
        out.append((src, list(dests)))
    return out


# ------------------------------------------------------------------ planner
def planner_fabric(n: int, count: int, sample, n_stream: int,
                   rng: random.Random) -> None:
    from repro.core import arena_clear, bulk_plan, grid, plan, planner_for
    from repro.serve import PlanServer

    g = grid(n)
    arena_clear()
    pl = timed(f"{n}x{n} planner tables (host)",
               lambda: planner_for(g, "DPM"))
    assert pl.support.ok, f"{n}x{n} not on the device path: {pl.support}"
    seen: set = set()
    reqs = _instances(g, count, rng, seen)
    plans = timed(f"{n}x{n} bulk_plan {count} cold arena, cold compile",
                  lambda: bulk_plan(g, reqs, "DPM"))
    info = pl.info()
    assert info.misses == count and info.batched_plans == count, info
    assert info.host_plans == 0, f"host fallback ran: {info}"
    idx = range(count) if sample is None else rng.sample(range(count), sample)
    bad = [i for i in idx if plans[i] != plan("DPM", g, *reqs[i])]
    assert not bad, f"{len(bad)} device plans differ from host plan()"
    log(f"  {n}x{n}: {len(idx)} device plans == host plan(); {info}")
    pl.clear()
    warm = timed(f"{n}x{n} bulk_plan {count} cold arena, warm compile",
                 lambda: bulk_plan(g, reqs, "DPM"))
    assert warm == plans

    stream = _instances(g, n_stream, rng, seen)

    def serve():
        with PlanServer(g, "DPM") as ps:
            futs = [ps.submit(src, dests) for src, dests in stream]
            return [f.result(timeout=600) for f in futs], dict(ps.stats)

    got, stats = timed(f"{n}x{n} PlanServer {n_stream} futures", serve)
    assert len(got) == n_stream and all(p is not None for p in got)
    pl.clear()
    ref = bulk_plan(g, stream, "DPM")  # planned again on the device
    assert got == ref, "PlanServer results differ from bulk_plan"
    info = pl.info()
    assert info.host_plans == 0, f"host fallback ran: {info}"
    log(f"  {n}x{n}: PlanServer {n_stream} futures == bulk_plan "
        f"({stats['batches']} batches); {info}")


def planner_phase(seed: int) -> None:
    log("phase planner")
    rng = random.Random(seed)
    for n, count, sample, n_stream in PLANNER_FABRICS:
        planner_fabric(n, count, sample, n_stream, rng)


# --------------------------------------------------------------------- xsim
def _host_run(cfg, wl):
    from repro.core import plan
    from repro.noc import WormholeSim

    g = cfg.make_topology()
    sim = WormholeSim(cfg, measure_window=(0, wl.horizon))
    for r in wl.requests:
        sim.add_plan(plan("DPM", g, r.src, r.dests), r.time)
    st = sim.run(wl.horizon + cfg.drain_grace)
    sets = {pk.pid: {g.idx(c) for c in pk.delivery_times}
            for pk in sim.packets}
    return st, sets


def _ran_on(res, platform: str, count: int = 1) -> None:
    assert len(res.devices) == count and all(
        d.startswith(platform + ":") for d in res.devices
    ), f"xsim ran on {res.devices}, expected {count} {platform} device(s)"


def xsim_phase(seed: int) -> None:
    import jax
    import numpy as np

    from repro.noc import NoCConfig, synthetic_workload, xsimulate

    log("phase xsim")
    p = XSIM_PARITY
    cfg = NoCConfig(n=p["n"], warmup=0, drain_grace=800,
                    multicast_fraction=0.4, dest_range=(3, 6))
    wl = synthetic_workload(cfg, p["rate"], p["cycles"], seed=seed)
    res = timed(f"{p['n']}x{p['n']} xsimulate cold",
                lambda: xsimulate(cfg, [wl], ("DPM",)))
    timed(f"{p['n']}x{p['n']} xsimulate warm",
          lambda: xsimulate(cfg, [wl], ("DPM",)))
    _ran_on(res, "tpu")
    st, sets = timed(f"{p['n']}x{p['n']} WormholeSim (host)",
                     lambda: _host_run(cfg, wl))
    assert res.delivered_sets(0, 0) == sets, "delivery sets differ"
    assert np.array_equal(
        res.link_utilization(0, 0), st.telemetry.link_flits
    ), "per-link flit counts differ from WormholeSim"
    log(f"  {p['n']}x{p['n']}: {len(sets)} packets, delivery sets and "
        f"per-link flits == WormholeSim; backend={res.backend} "
        f"devices={res.devices}")

    s = XSIM_SAT
    cfg = NoCConfig(n=s["n"], dest_range=(10, 16), warmup=100,
                    drain_grace=s["drain"])
    wls = [
        synthetic_workload(cfg, r, s["cycles"], seed=seed + k)
        for r in s["rates"] for k in range(2)
    ]
    res = timed(f"{s['n']}x{s['n']} xsimulate {len(wls)} saturated cold",
                lambda: xsimulate(cfg, wls, ("DPM",)))
    timed(f"{s['n']}x{s['n']} xsimulate {len(wls)} saturated warm",
          lambda: xsimulate(cfg, wls, ("DPM",)))
    _ran_on(res, "tpu")
    for w in range(len(wls)):
        assert res.all_drained(w, 0), f"workload {w} did not drain"
    with jax.default_device(jax.devices("cpu")[0]):
        cpu = timed(f"{s['n']}x{s['n']} same xsimulate on the CPU device",
                    lambda: xsimulate(cfg, wls, ("DPM",)))
    _ran_on(cpu, "cpu")
    for name in ("ctr", "lutil", "crel", "rconf", "dtime"):
        assert np.array_equal(getattr(res, name), getattr(cpu, name)), (
            f"{name} differs between the TPU and the CPU device"
        )
    lat = [round(res.avg_latency(w, 0), 2) for w in range(len(wls))]
    log(f"  {s['n']}x{s['n']}: {len(wls)} workloads drained; counters, link "
        f"planes and delivery times == CPU device; avg latency {lat} "
        f"cycles; backend={res.backend}")


# ---------------------------------------------------------- four chips only
def four_chip_phase(seed: int) -> None:
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.configs import ARCHS
    from repro.dist.ep import moe_apply_ep
    from repro.dist.multicast import (
        alltoall_schedule,
        apply_alltoall_schedule,
        apply_schedule,
        dp_broadcast_schedule,
    )
    from repro.models.moe import moe_apply_dense, moe_init
    from repro.noc import NoCConfig, synthetic_workload, xsimulate

    log("phase four-chips")
    n = 4
    devs = jax.devices()[:n]
    mesh = jax.make_mesh((n,), ("x",), devices=devs)

    def on_four(a, what):
        k = len(a.sharding.device_set)
        assert k == n, f"{what} sits on {k} device(s), expected {n}"

    # EP all-to-all: DPM ppermute rounds vs XLA's all_to_all
    chunk = A2A_CHUNK_BYTES // 4
    x = jax.jit(
        lambda key: jax.random.normal(key, (n * n, chunk), jnp.float32),
        out_shardings=NamedSharding(mesh, P("x")),
    )(jax.random.PRNGKey(seed))
    sched = alltoall_schedule(n, "DPM")

    def a2a(fn):
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
            check_vma=False,
        ))

    dpm = a2a(lambda xl: apply_alltoall_schedule(xl, sched, "x"))
    xla = a2a(lambda xl: jax.lax.all_to_all(xl, "x", 0, 0, tiled=True))
    a = timed("all-to-all DPM schedule cold", lambda: dpm(x).block_until_ready())
    timed("all-to-all DPM schedule warm", lambda: dpm(x).block_until_ready())
    b = timed("all-to-all lax cold", lambda: xla(x).block_until_ready())
    timed("all-to-all lax warm", lambda: xla(x).block_until_ready())
    on_four(a, "DPM all-to-all")
    on_four(b, "lax all-to-all")
    assert np.array_equal(np.asarray(a), np.asarray(b)), "all-to-all differs"
    log(f"  all-to-all: DPM ({sched.num_rounds} rounds) == lax.all_to_all at "
        f"{A2A_CHUNK_BYTES} B per (src, dst) chunk")

    # DP broadcast: rank 0's payload lands on every rank
    bsched = dp_broadcast_schedule(n, "DPM")
    payload = (jnp.arange(n, dtype=jnp.float32)[:, None] * 100.0
               + jnp.arange(1024, dtype=jnp.float32)[None, :])
    payload = jax.device_put(payload, NamedSharding(mesh, P("x")))
    out = jax.jit(jax.shard_map(
        lambda xl: apply_schedule(xl, bsched, "x"), mesh=mesh,
        in_specs=P("x"), out_specs=P("x"), check_vma=False,
    ))(payload)
    on_four(out, "broadcast")
    got = np.asarray(out)
    assert all(np.array_equal(got[r], got[0]) for r in range(n))
    assert np.array_equal(got[0], np.arange(1024, dtype=np.float32))
    log(f"  dp broadcast: rank 0's payload on all {n} ranks "
        f"({bsched.num_rounds} rounds)")

    # EP MoE over a (data, model) = (1, 4) mesh vs the dense path on one
    # chip: one MoE layer at the published width, f32 at full matmul
    # precision, with a capacity that drops no token on either path (a drop
    # would differ by O(1), far outside the tolerance)
    emesh = jax.make_mesh((1, n), ("data", "model"), devices=devs)
    cfg = ARCHS[MOE_ARCH]
    cfg = cfg.scaled(moe=dataclasses.replace(
        cfg.moe, capacity_factor=MOE_CAPACITY_FACTOR))
    p = jax.jit(lambda k: moe_init(k, cfg)[0])(jax.random.PRNGKey(seed))
    dense = jax.jit(lambda p, x: moe_apply_dense(p, x, cfg)[0])
    ep = jax.jit(lambda p, x: moe_apply_ep(p, x, cfg, emesh)[0])
    for k, (B, S) in enumerate(MOE_TOKENS):
        tok = jax.random.normal(jax.random.PRNGKey(seed + 1 + k),
                                (B, S, cfg.d_model))
        with jax.default_matmul_precision("highest"):
            y_dense = timed(f"MoE dense B={B} S={S} one chip cold",
                            lambda: dense(p, tok).block_until_ready())
            y_ep = timed(f"MoE EP B={B} S={S} four chips cold",
                         lambda: ep(p, tok).block_until_ready())
            timed(f"MoE EP B={B} S={S} four chips warm",
                  lambda: ep(p, tok).block_until_ready())
        on_four(y_ep, "EP MoE output")
        assert not y_ep.sharding.is_fully_replicated, y_ep.sharding
        scale = float(jnp.max(jnp.abs(y_dense)))
        err = float(jnp.max(jnp.abs(y_ep - y_dense)))
        log(f"  MoE B={B} S={S}: tokens on {y_ep.sharding.spec}; max |y| "
            f"{scale!r}, max abs diff EP vs dense {err!r}")
        assert np.isfinite(scale) and scale > 0
        assert err <= MOE_REL_TOL * scale, (
            f"EP MoE differs from dense by {err} (> {MOE_REL_TOL} x {scale})"
        )
    log(f"  moe_apply_ep == moe_apply_dense ({MOE_ARCH}, d_model "
        f"{cfg.d_model}, {cfg.moe.n_experts} experts top-{cfg.moe.top_k}) "
        f"on a (1, {n}) mesh within {MOE_REL_TOL} x max |y|")

    # xsim: one batch of 4 workloads pmap-sharded vs one workload per call
    cfg = NoCConfig(n=8, dest_range=(4, 8), warmup=100, drain_grace=800)
    wls = [synthetic_workload(cfg, 0.04, 200, seed=seed + k) for k in range(n)]
    res = timed("xsim 4-workload batch over 4 chips",
                lambda: xsimulate(cfg, wls, ("DPM",)))
    _ran_on(res, "tpu", n)
    for w, wl in enumerate(wls):
        one = xsimulate(cfg, [wl], ("DPM",))
        _ran_on(one, "tpu")
        assert np.array_equal(res.ctr[w], one.ctr[0]), f"ctr {w} differs"
        assert np.array_equal(
            res.link_utilization(w, 0), one.link_utilization(0, 0)
        ), f"link planes {w} differ"
        assert res.delivered_sets(w, 0) == one.delivered_sets(0, 0)
        assert res.latencies(w, 0) == one.latencies(0, 0)
    log(f"  sharded xsim over {res.devices} == {n} single-workload runs")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip collectives phase")
    args = ap.parse_args()

    devs = device_gate(4 if args.four_chips else 1)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    cache = pathlib.Path(enable_compile_cache())
    held = sum(f.stat().st_size for f in cache.rglob("*") if f.is_file())
    set_by = ("JAX_COMPILATION_CACHE_DIR"
              if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "checkout")
    log(f"compile cache: {cache} (set by {set_by}), {held} B on arrival")
    t0 = time.monotonic()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        planner_phase(args.seed)
        xsim_phase(args.seed)
    log(f"all phases passed in {time.monotonic() - t0:.1f} s (smoke timing)")
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs),
    }}), flush=True)


if __name__ == "__main__":
    main()
