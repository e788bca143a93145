"""Batched device planning + plan arena (core/batch_planner, ISSUE 10).

The load-bearing property is **bit-identity**: every plan the batched
planner returns equals host ``plan()`` field for field — across algorithms
(DPM / DPM-E), cost models (hops / weighted), every registered topology
kind, and on degraded meshes (detoured routes, segmented worms) on the
device path. Plus: canonical
dest-set interning shared with the plan cache, arena LRU hit/miss/eviction
attribution mirroring ``plan_cache_info()``, and the consumer wiring
(simulator bulk admission, dist schedule builder).
"""
from __future__ import annotations

import random

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    BatchPlanner,
    DisconnectedError,
    arena_clear,
    arena_info,
    batch_support,
    bulk_plan,
    canonical_dests,
    chiplet,
    faulty,
    grid,
    label_chain_matrices,
    label_chain_passes,
    mesh3d,
    plan,
    plan_cache_clear,
    plan_cache_info,
    planner_for,
    registered_topology_kinds,
    router_failure,
    torus,
    torus3d,
)
import repro.core.batch_planner as bpm


@pytest.fixture(autouse=True)
def _fresh_caches():
    plan_cache_clear()
    arena_clear()
    yield
    plan_cache_clear()
    arena_clear()


def _requests(g, n, seed, kmax=8):
    nodes = g.nodes()
    rng = random.Random(seed)
    out, seen = [], set()
    while len(out) < n:
        src = rng.choice(nodes)
        k = rng.randint(2, min(kmax, len(nodes) - 1))
        dests = tuple(
            sorted(rng.sample([x for x in nodes if x != src], k))
        )
        if (src, dests) in seen:
            continue
        seen.add((src, dests))
        out.append((src, list(dests)))
    return out


# the 2-D kinds and the chiplet package share one jit specialization
# (NN=16, np_=8); the 3-D kinds exercise the 26-wedge candidate table and
# heterogeneous z-links
FABRICS = {
    "mesh": grid(4),
    "torus": torus(4, 4),
    "mesh3d": mesh3d(3, 3, 3, z_weight=2.0),
    "torus3d": torus3d(3, 3, 2),
    "chiplet": chiplet(4),
}


def test_fabric_fixtures_cover_every_registered_kind():
    """If a new topology kind registers, this file must grow a fabric for
    it — the bit-identity sweep below is only as wide as this dict."""
    assert set(FABRICS) == set(registered_topology_kinds())


# ---------------------------------------------------------------------------
# Bit-identity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind", sorted(FABRICS))
@pytest.mark.parametrize("algo,cm", [("DPM", "hops"), ("DPM-E", "weighted")])
def test_batched_plans_bit_identical_all_kinds(kind, algo, cm):
    g = FABRICS[kind]
    bp = BatchPlanner(g, algo, cm)
    assert bp.support.ok, bp.support.reason
    reqs = _requests(g, 10, seed=sum(map(ord, kind + algo + cm)))
    got = bp.plan_many(reqs)
    for (src, dests), pb in zip(reqs, got):
        assert pb == plan(algo, g, src, dests, cost_model=cm)
    assert bp.info().batched_plans == len(reqs)
    assert bp.info().array_decoded == len(reqs)
    assert bp.info().host_plans == 0


@pytest.mark.parametrize("algo,cm", [("DPM", "weighted"), ("DPM-E", "hops")])
def test_batched_plans_bit_identical_remaining_combos(algo, cm):
    """The algorithm x cost-model combinations the kind sweep skips."""
    g = FABRICS["mesh"]
    bp = BatchPlanner(g, algo, cm)
    assert bp.support.ok, bp.support.reason
    reqs = _requests(g, 10, seed=7)
    for (src, dests), pb in zip(reqs, bp.plan_many(reqs)):
        assert pb == plan(algo, g, src, dests, cost_model=cm)


@pytest.mark.parametrize("fabric", [grid(16), torus(16, 16)],
                         ids=["mesh16", "torus16"])
def test_large_hop_fabrics_plan_on_device(fabric):
    """A label-monotone C_p chain crosses at most NN - 1 links, so the f32
    exactness bound admits hop-priced fabrics well past 8x8."""
    bp = BatchPlanner(fabric, "DPM", "hops")
    assert bp.support.ok, bp.support.reason
    reqs = _requests(fabric, 12, seed=16, kmax=24)
    for (src, dests), pb in zip(reqs, bp.plan_many(reqs)):
        assert pb == plan("DPM", fabric, src, dests)
    assert bp.info().host_plans == 0


def test_dyadic_grain_is_the_least_power_of_two():
    assert bpm._dyadic_grain([1.0, 62.0], [0.0]) == 1
    assert bpm._dyadic_grain([0.5, 0.125]) == 8
    assert bpm._dyadic_grain([1 / 256]) == 256
    assert bpm._dyadic_grain([1 / 512]) is None
    assert bpm._dyadic_grain([0.1]) is None
    assert bpm._dyadic_grain([float("inf")]) is None


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10**9))
def test_batched_plan_bit_identical_property(seed):
    """Property form: random (src, dest-set) instances on the shared mesh
    fabric, one at a time through the arena, always equal host plan()."""
    g = FABRICS["mesh"]
    bp = planner_for(g, "DPM")
    (src, dests), = _requests(g, 1, seed)
    assert bp.plan_one(src, dests) == plan("DPM", g, src, dests)


def test_degraded_fabric_falls_back_to_host():
    """Broken links alone no longer send a fabric to the host; an
    objective outside the gate (energy) still does, degraded or not, with
    the same plans."""
    g = faulty(grid(4), (((0, 0), (1, 0)),))
    assert batch_support(g).ok
    sup = batch_support(g, "DPM-E")
    assert not sup.ok and "dyadic" in sup.reason
    bp = BatchPlanner(g, "DPM-E")
    reqs = _requests(g, 6, seed=3)
    got = bp.plan_many(reqs)
    for (src, dests), pb in zip(reqs, got):
        assert pb == plan("DPM-E", g, src, dests)
    info = bp.info()
    assert info.host_plans == len(reqs)
    assert info.batched_plans == 0 and info.dispatches == 0


def test_energy_objective_is_gated_off_device():
    """The energy model's pJ constants are not dyadic rationals — the
    f32-exactness gate must reject it (DPM-E then host-plans)."""
    sup = batch_support(grid(4), "DPM-E")  # default model: energy
    assert not sup.ok and "dyadic" in sup.reason


def test_non_dpm_algorithms_have_no_device_twin():
    sup = batch_support(grid(4), "MU")
    assert not sup.ok and "device twin" in sup.reason


# ---------------------------------------------------------------------------
# Canonical dest-set interning (shared helper)
# ---------------------------------------------------------------------------
def test_canonical_dests_sorts_dedups_and_normalizes():
    assert canonical_dests([(2, 1), (0, 3), (2, 1)]) == ((0, 3), (2, 1))
    assert canonical_dests([[2, 1], (0, 3)]) == ((0, 3), (2, 1))  # lists ok
    assert canonical_dests([]) == ()


def test_permuted_dests_share_one_plan_cache_entry():
    g = grid(4)
    dests = [(1, 2), (3, 0), (2, 3)]
    p1 = plan("DPM", g, (0, 0), dests)
    p2 = plan("DPM", g, (0, 0), list(reversed(dests)))
    p3 = plan("DPM", g, (0, 0), dests + [dests[0]])  # duplicate entry
    assert p1 is p2 is p3  # literally the same cached object
    info = plan_cache_info()
    assert info.misses == 1 and info.hits == 2


def test_permuted_dests_share_one_arena_entry():
    g = grid(4)
    bp = BatchPlanner(g, "DPM")
    dests = [(1, 2), (3, 0), (2, 3)]
    a, b = bp.plan_many(
        [((0, 0), dests), ((0, 0), list(reversed(dests)))]
    )
    assert a is b
    info = bp.info()
    # second request deduped against the first inside one plan_many call
    assert info.misses == 2 and info.currsize == 1
    c = bp.plan_one((0, 0), dests + [dests[-1]])
    assert c is a
    assert bp.info().hits == 1


# ---------------------------------------------------------------------------
# Arena LRU accounting (mirrors plan_cache_info semantics)
# ---------------------------------------------------------------------------
def test_arena_lru_hit_miss_eviction_attribution():
    g = grid(4)
    bp = BatchPlanner(g, "DPM", maxsize=4)
    reqs = _requests(g, 6, seed=11)
    bp.plan_many(reqs)
    info = bp.info()
    assert info.misses == 6 and info.evictions == 2 and info.currsize == 4
    # the two oldest were evicted: re-planning them misses again; the
    # newest still hits and refreshes its LRU slot
    bp.plan_many([reqs[-1]])
    assert bp.info().hits == 1
    bp.plan_many([reqs[0]])
    assert bp.info().misses == 7


def test_arena_info_aggregates_by_algo_and_cost_model():
    g = grid(4)
    reqs = _requests(g, 4, seed=5)
    bulk_plan(g, reqs, "DPM")
    bulk_plan(g, reqs, "DPM", cost_model="weighted")
    bulk_plan(g, reqs[:2], "DPM")  # hits on the first planner
    info = arena_info()
    assert info.hits == 2 and info.misses == 8
    assert info.by_key[("DPM", "hops")]["misses"] == 4
    assert info.by_key[("DPM", "hops")]["hits"] == 2
    assert info.by_key[("DPM", "weighted")]["misses"] == 4
    arena_clear()
    assert arena_info().misses == 0 and arena_info().currsize == 0


def test_planner_for_shares_one_arena_per_config():
    g = grid(4)
    assert planner_for(g, "DPM") is planner_for(g, "DPM")
    assert planner_for(g, "DPM") is not planner_for(g, "DPM", "weighted")


def test_bulk_plan_empty_and_order_preserving():
    g = grid(4)
    assert bulk_plan(g, []) == []
    reqs = _requests(g, 5, seed=9)
    plans = bulk_plan(g, reqs)
    for (src, dests), p in zip(reqs, plans):
        assert p.src == src and set(p.dests) == set(dests)


# ---------------------------------------------------------------------------
# Consumer wiring: simulator driver + dist schedule builder
# ---------------------------------------------------------------------------
def test_simulator_bulk_admission_matches_per_request(monkeypatch):
    from repro.noc.config import NoCConfig
    from repro.noc.simulator import WormholeSim
    from repro.noc.traffic import Request

    cfg = NoCConfig(n=4, m=4)
    reqs = [
        Request(0, (0, 0), [(3, 3), (1, 2)]),
        Request(1, (2, 2), [(0, 3)], flits=3),
        Request(3, (0, 0), [(1, 2), (3, 3)]),  # permuted duplicate
    ]
    sim_a = WormholeSim(cfg)
    sim_a.add_requests("DPM", reqs)
    assert planner_for(grid(4), "DPM").info().batched_plans > 0
    sim_b = WormholeSim(cfg)
    for r in reqs:
        sim_b.add_request("DPM", r.src, r.dests, r.time, flits=r.flits)
    sa = sim_a.run(300, drain=True)
    sb = sim_b.run(300, drain=True)
    assert sa.packets_finished == sb.packets_finished
    assert sa.flit_link_traversals == sb.flit_link_traversals


def test_dist_schedule_builder_uses_arena_and_matches_host(monkeypatch):
    from repro.dist.multicast import schedule_multicasts

    t = torus(4, 4)
    reqs = [((0, 0), [(2, 2), (1, 3)]), ((3, 3), [(0, 1), (2, 0)])]
    sched = schedule_multicasts(t, reqs)
    assert planner_for(t, "DPM").info().batched_plans > 0
    # force the host path (support gate off) and require identical rounds
    arena_clear()
    monkeypatch.setattr(
        bpm, "batch_support",
        lambda *a, **k: bpm._Support(False, "forced by test"),
    )
    sched_host = schedule_multicasts(t, reqs)
    assert planner_for(t, "DPM").info().host_plans > 0
    assert sched.rounds == sched_host.rounds
    assert sched.hops == sched_host.hops


def test_device_planning_inside_a_jit_trace():
    """EP MoE builds its all-to-all schedule while the caller's jit
    traces: the device planner still runs eagerly and returns plans, and
    its cached device tables are arrays, not the trace's tracers."""
    import jax

    from repro.dist.multicast import schedule_multicasts

    t = torus(4, 4)
    reqs = [((0, 0), [(2, 2), (1, 3)]), ((3, 3), [(0, 1), (2, 0)])]
    bp = BatchPlanner(t, "DPM")
    got = {}

    @jax.jit
    def step(x):
        got["plans"] = bp.plan_many(reqs)
        got["sched"] = schedule_multicasts(t, reqs)
        return x + 1

    assert int(step(0)) == 1
    assert got["plans"] == [plan("DPM", t, s, d) for s, d in reqs]
    assert bp.info().batched_plans == len(reqs) and bp.info().host_plans == 0
    assert bp.plan_many(_requests(t, 4, seed=3))  # tables usable after
    arena_clear()
    assert got["sched"].rounds == schedule_multicasts(t, reqs).rounds


def test_xsim_compile_bulk_plans_through_arena():
    from repro.noc.config import NoCConfig
    from repro.noc.traffic import Request, Workload
    from repro.noc.xsim.compile import compile_workload

    cfg = NoCConfig(n=4, m=4)
    wl = Workload(
        "t",
        [Request(0, (0, 0), [(3, 3)]), Request(1, (2, 2), [(0, 3), (1, 0)])],
        1,
    )
    ct = compile_workload(cfg, wl, "DPM")
    assert ct.num_packets >= 2
    assert planner_for(grid(4), "DPM").info().batched_plans > 0


def test_registry_change_clears_arenas():
    from repro.core import temporary_algorithm, plan_dpm

    g = grid(4)
    bulk_plan(g, _requests(g, 3, seed=2))
    assert arena_info().misses == 3
    with temporary_algorithm(plan_dpm, name="DPM-tmp"):
        pass  # registration mutates the registry -> arenas must drop
    assert arena_info().misses == 0


@pytest.mark.parametrize("n", [1, 7, 512, 513])
def test_batch_padding_and_multi_chunk_batches(n):
    """One request, a few (padded to a power of two), exactly one
    ``DISPATCH_CHUNK`` and one past it (a second, padded chunk): every
    plan of every chunk equals host ``plan()``."""
    g = grid(4)
    bp = BatchPlanner(g, "DPM")
    reqs = _requests(g, n, seed=22 + n, kmax=6)
    got = bp.plan_many(reqs)
    assert len(got) == n
    assert bp.info().dispatches == -(-n // bpm.DISPATCH_CHUNK)
    assert bp.info().array_decoded == n
    for (src, dests), pb in zip(reqs, got):
        assert pb == plan("DPM", g, src, dests)


@pytest.mark.parametrize("enabled", [True, False], ids=["on", "off"])
@pytest.mark.parametrize("fail", [False, True], ids=["ok", "raises"])
def test_collector_paused_while_decoding_then_restored(monkeypatch,
                                                        enabled, fail):
    """The decode runs with the cyclic collector off; afterwards it is in
    the state the caller left it, also when the decode raises."""
    import gc

    g = grid(4)
    bp = BatchPlanner(g, "DPM")
    seen = []
    real = BatchPlanner._decode

    def spy(self, *a):
        seen.append(gc.isenabled())
        if fail:
            raise RuntimeError("decode failed")
        return real(self, *a)

    monkeypatch.setattr(BatchPlanner, "_decode", spy)
    was = gc.isenabled()
    (gc.enable if enabled else gc.disable)()
    try:
        reqs = _requests(g, 6, seed=23)
        if fail:
            with pytest.raises(RuntimeError, match="decode failed"):
                bp.plan_many(reqs)
        else:
            got = bp.plan_many(reqs)
            assert got == [plan("DPM", g, s, d) for s, d in reqs]
        assert gc.isenabled() is enabled
    finally:
        (gc.enable if was else gc.disable)()
    assert seen and not any(seen)


# ---------------------------------------------------------------------------
# Destination-slot packing (kernels.dpm_cost.ops.dpm_plan_exact)
# ---------------------------------------------------------------------------
# 64-node fabrics: fanout 16 packs into MIN_SLOTS slots, 17 into twice
# that; the 3-D kinds carry the 26-wedge candidate table
SLOT_FABRICS = {
    "mesh": grid(8),
    "mesh3d": mesh3d(4, 4, 4, z_weight=2.0),
    "torus3d": torus3d(4, 4, 4),
    "chiplet": chiplet(8),
}


def _fanout_requests(g, n, fanout, seed):
    nodes = g.nodes()
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        src = rng.choice(nodes)
        dests = rng.sample([x for x in nodes if x != src], fanout)
        out.append((src, sorted(dests)))
    return out


def _merge(bp, reqs, k, bp_rows):
    """``dpm_plan_exact``'s outputs for ``reqs`` packed as the planner
    packs them, into ``k`` slots and ``bp_rows`` rows (pads all -1)."""
    import numpy as np

    from repro.kernels.dpm_cost.ops import dpm_plan_exact

    g, t = bp.topo, bp._tables()
    dests = np.full((bp_rows, k), -1, np.int32)
    sidx = np.zeros(bp_rows, np.int32)
    for b, (src, ds) in enumerate(reqs):
        sidx[b] = g.idx(src)
        dests[b, : len(ds)] = [g.idx(d) for d in ds]
    out = dpm_plan_exact(
        dests, sidx, t.memb_d, t.labels_d, t.dist_d, t.wuni_d, t.wh_d,
        t.wl_d, t.ph_d, t.pl_d, np_=bp.np_, overhead=t.overhead,
    )
    return [np.asarray(x) for x in out]


@pytest.mark.parametrize("fanout", [16, 17])
@pytest.mark.parametrize("kind", sorted(SLOT_FABRICS))
@pytest.mark.parametrize("algo,cm", [("DPM", "hops"), ("DPM-E", "weighted")])
def test_slot_pricing_bit_identical_at_any_width(algo, cm, kind, fanout):
    """Slot pricing == host ``plan()``, on either side of the MIN_SLOTS
    step (fanout 16 packs into 16 slots, 17 into 32), and the same
    instances packed into 64 slots price the same. Five instances pad to
    a chunk of eight whose three pad rows are all -1 and choose nothing."""
    g = SLOT_FABRICS[kind]
    bp = BatchPlanner(g, algo, cm)
    assert bp.support.ok, bp.support.reason
    reqs = _fanout_requests(g, 5, fanout, seed=sum(map(ord, kind)) + fanout)
    got = bp.plan_many(reqs)
    for (src, dests), pb in zip(reqs, got):
        assert pb == plan(algo, g, src, dests, cost_model=cm)
    assert bp.info().dispatches == 1

    k = bpm.MIN_SLOTS if fanout <= bpm.MIN_SLOTS else 2 * bpm.MIN_SLOTS
    wide = _merge(bp, reqs, 4 * bpm.MIN_SLOTS, 8)
    for a, b in zip(_merge(bp, reqs, k, 8), wide):
        assert (a == b).all()
    chosen, order, reps, modes, costs = wide
    assert not chosen[5:].any() and (reps[5:] == -1).all()
    assert (costs[5:] == 0).all() and modes[5:].all()


def test_plan_many_packs_each_chunk_to_its_own_width(monkeypatch):
    """One ``plan_many`` over two chunks, of fanouts 16 and 17: two
    dispatches, at 16 and 32 slots, both equal to host ``plan()``."""
    from repro.kernels.dpm_cost import ops

    widths = []
    real = ops.dpm_plan_exact

    def spy(dests, *a, **kw):
        widths.append(dests.shape[1])
        return real(dests, *a, **kw)

    monkeypatch.setattr(bpm, "DISPATCH_CHUNK", 4)
    monkeypatch.setattr(ops, "dpm_plan_exact", spy)
    g = SLOT_FABRICS["mesh"]
    bp = BatchPlanner(g, "DPM")
    reqs = (_fanout_requests(g, 4, 16, seed=1)
            + _fanout_requests(g, 4, 17, seed=2))
    before = bp.info()
    got = bp.plan_many(reqs)
    assert bp.info().dispatches - before.dispatches == 2
    assert widths == [16, 32]
    for (src, dests), pb in zip(reqs, got):
        assert pb == plan("DPM", g, src, dests)


@pytest.mark.parametrize("k", [16, 32])
def test_source_among_dests_is_already_delivered(k):
    """A destination list that holds the source plans as the host does:
    the source is in no partition, at either slot width."""
    g = SLOT_FABRICS["mesh"]
    bp = BatchPlanner(g, "DPM")
    reqs = [(src, sorted(set(dests) | {src}))
            for src, dests in _fanout_requests(g, 3, k - 2, seed=k)]
    for (src, dests), pb in zip(reqs, bp.plan_many(reqs)):
        assert pb == plan("DPM", g, src, dests)


def _has_shape(g, src, dests, shape):
    """Whether host Algorithm 1 gives (src, dests) a partition of
    ``shape``: a lone member, an MU-mode partition of several, a DP
    partition with members on both label sides of its representative
    (a sibling worm), a head S -> R that passes destinations it does not
    deliver (another partition's: the representative is its partition's
    nearest member, so a shortest head passes none of its own), or the
    source among the destinations."""
    from repro.core.partition import dpm_partition
    from repro.core.routing import xy_route

    if shape == "source_in_dests":
        return src in dests
    for p in dpm_partition(g, src, dests).partitions:
        if not p.dests:
            continue
        if shape == "singleton" and len(p.dests) == 1:
            return True
        if shape == "mu" and p.mode == "MU" and len(p.dests) > 1:
            return True
        lr = g.label(*p.rep)
        sides = {g.label(*d) > lr for d in p.dests if d != p.rep}
        if shape == "sibling" and p.mode == "DP" and len(sides) == 2:
            return True
        if shape == "head_passes" and set(
                xy_route(g, src, p.rep)[1:-1]) & set(dests):
            return True
    return False


# (shape, mesh side, fanouts); the 32x32 case is the bulk cell's fabric
SHAPES = [("singleton", 8, (1, 5)), ("mu", 8, (2, 8)),
          ("sibling", 8, (4, 16)), ("source_in_dests", 8, (2, 16)),
          ("head_passes", 8, (10, 16)), ("head_passes", 32, (10, 16))]


@pytest.mark.parametrize("shape,n,fanouts", SHAPES,
                         ids=[f"{s}-{n}x{n}" for s, n, _ in SHAPES])
def test_array_decode_bit_identical_by_partition_shape(shape, n, fanouts):
    """The chunk decode on instances that each hold one partition shape
    of the host emitter: every plan equals host ``plan()``, all of them
    decoded by the array path."""
    g = grid(n)
    nodes = g.nodes()
    rng = random.Random(sum(map(ord, shape)) + n)
    reqs = []
    for _ in range(20_000):
        src = rng.choice(nodes)
        dests = rng.sample([u for u in nodes if u != src],
                           rng.randint(*fanouts))
        if shape == "source_in_dests":
            dests.append(src)
        if _has_shape(g, src, dests, shape):
            reqs.append((src, sorted(dests)))
            if len(reqs) == 24:
                break
    assert len(reqs) == 24
    bp = BatchPlanner(g, "DPM")
    for (src, dests), pb in zip(reqs, bp.plan_many(reqs)):
        assert pb == plan("DPM", g, src, dests)
    info = bp.info()
    assert info.array_decoded == info.batched_plans == len(reqs)


def test_array_decode_counts_every_device_plan(monkeypatch):
    """On a healthy mesh every plan planned on the device is decoded by
    the array path, whose chains skip nothing (no pass tables); a host-
    planned objective decodes nothing there."""
    calls = []
    monkeypatch.setattr(BatchPlanner, "_chain_visits",
                        lambda self, *a: calls.append(a))
    g = grid(8)
    bp = BatchPlanner(g, "DPM")
    assert bp._tables().ph is None
    reqs = _requests(g, 40, seed=8, kmax=16)
    bp.plan_many(reqs)
    info = bp.info()
    assert info.array_decoded == info.batched_plans == len(reqs)
    assert not calls
    host = BatchPlanner(g, "DPM-E")
    host.plan_many(reqs[:4])
    assert host.info().array_decoded == 0 and host.info().host_plans == 4


# ---------------------------------------------------------------------------
# Degraded meshes on the device path
# ---------------------------------------------------------------------------
def _connected_faults(g, count, seed):
    """``count`` distinct links drawn uniformly from ``Random(seed)``,
    each draw kept only if the mesh stays connected."""
    from repro.core.routefn import components

    rng = random.Random(seed)
    links = sorted({tuple(sorted((u, v)))
                    for u in g.nodes() for v in g.neighbors(*u)})
    chosen: list = []
    while len(chosen) < count:
        cand = rng.choice(links)
        if cand not in chosen and not components(
                faulty(g, chosen + [cand])).any():
            chosen.append(cand)
    return chosen


# a connected 4x4 mesh keeps 15 of its 24 links, so it holds at most 9
# broken; (n, broken links) or (n, "router") for a failed router at (2, 3)
DEGRADED = [(4, 1), (4, 4), (6, 1), (6, 4), (6, 12), (6, 20), (8, 1),
            (8, 4), (8, 12), (8, 20), (8, "router")]


def _degraded(n, faults):
    g = grid(n)
    if faults == "router":
        return faulty(g, router_failure(g, (2, 3))), [(2, 3)]
    return faulty(g, _connected_faults(g, faults, seed=100 * n + faults)), []


@pytest.mark.parametrize("n,faults", DEGRADED,
                         ids=[f"{n}x{n}-{f}" for n, f in DEGRADED])
def test_degraded_mesh_plans_on_device_bit_identical(n, faults):
    """Seeded connected fault sets (and one failed router, kept out of
    the requests): every ``bulk_plan`` plan equals host ``plan()`` worm
    for worm, all planned on the device."""
    g, dead = _degraded(n, faults)
    assert batch_support(g).ok
    nodes = [u for u in g.nodes() if u not in dead]
    rng = random.Random(n * 31 + len(dead))
    reqs = []
    for _ in range(48):
        src = rng.choice(nodes)
        k = rng.randint(1, min(16, len(nodes) - 1))
        reqs.append((src, sorted(rng.sample(
            [u for u in nodes if u != src], k))))
    got = bulk_plan(g, reqs)
    for (src, dests), pb in zip(reqs, got):
        assert pb == plan("DPM", g, src, dests)
    info = planner_for(g, "DPM").info()
    assert info.host_plans == 0 and info.batched_plans == len(reqs)
    assert info.array_decoded == len(reqs)


def test_degraded_fabric_decodes_on_the_array_path(monkeypatch):
    """A degraded fabric whose label routes pass later chain members
    (pass tables present) takes the array decode too: its chains skip the
    members an earlier route passed, and every plan equals host
    ``plan()``."""
    g, _ = _degraded(8, 12)
    bp = BatchPlanner(g, "DPM")
    assert bp._tables().ph is not None
    skipped = []
    real = BatchPlanner._chain_visits

    def spy(self, *a):
        go = real(self, *a)
        skipped.append(int((~go).sum()))
        return go

    monkeypatch.setattr(BatchPlanner, "_chain_visits", spy)
    reqs = _requests(g, 256, seed=812, kmax=16)
    for (src, dests), pb in zip(reqs, bp.plan_many(reqs)):
        assert pb == plan("DPM", g, src, dests)
    info = bp.info()
    assert info.array_decoded == info.batched_plans == len(reqs)
    assert info.host_plans == 0
    assert sum(skipped) > 0


def test_unreachable_destination_raises_through_both_paths():
    g = grid(6)
    g = faulty(g, router_failure(g, (3, 3)))
    for src, dests in [((0, 0), [(3, 3), (5, 5)]), ((3, 3), [(0, 0)])]:
        with pytest.raises(DisconnectedError):
            plan("DPM", g, src, dests)
        with pytest.raises(DisconnectedError):
            bulk_plan(g, [((1, 1), [(2, 2)]), (src, dests)])
    # the planner stays usable for the nodes that remain
    (pb,) = bulk_plan(g, [((0, 0), [(5, 5), (1, 4)])])
    assert pb == plan("DPM", g, (0, 0), [(5, 5), (1, 4)])


@pytest.mark.parametrize("n,faults", [(6, 12), (8, "router")])
def test_degraded_chain_matrices_match_label_walks(n, faults):
    """Every pairwise chain price, and every node a label route passes
    past its target's label, is what a walk of ``provider.label_step``
    gives."""
    from repro.core.routefn import components, provider_for

    g, _ = _degraded(n, faults)
    wh, wl = label_chain_matrices(g)
    ph, pl = label_chain_passes(g)
    comp, step = components(g), provider_for(g).label_step
    passed_some = False
    for u in g.nodes():
        for v in g.nodes():
            iu, iv = g.idx(u), g.idx(v)
            if u == v or comp[iu] != comp[iv]:
                continue
            high = g.label(*v) > g.label(*u)
            walk = [u]
            while walk[-1] != v:
                walk.append(step(g, walk[-1], v, high))
            assert (wh if high else wl)[iu, iv] == len(walk) - 1
            lv = g.label(*v)
            beyond = {g.idx(w) for w in walk
                      if (g.label(*w) > lv if high else g.label(*w) < lv)}
            words = (ph if high else pl)[iu, iv].view("uint32")
            got = {32 * k + b for k, x in enumerate(words) for b in range(32)
                   if int(x) >> b & 1}
            assert got == beyond, (u, v, high)
            passed_some |= bool(beyond)
    assert passed_some
    assert label_chain_passes(grid(n)) is None


# ---------------------------------------------------------------------------
# The device merge against Definitions 1-3, fabric by fabric
# ---------------------------------------------------------------------------
# every registered kind at several sizes and aspect ratios, healthy and
# degraded: (topology, failed routers kept out of the requests)
MERGE_FABRICS = {
    "mesh4x4": lambda: (grid(4), []),
    "mesh6x6": lambda: (grid(6), []),
    "mesh8x8": lambda: (grid(8), []),
    "mesh16x16": lambda: (grid(16), []),
    "mesh8x4": lambda: (grid(8, 4), []),
    "torus5x5": lambda: (torus(5, 5), []),
    "torus8x8": lambda: (torus(8, 8), []),
    "torus6x4": lambda: (torus(6, 4), []),
    "mesh3d": lambda: (mesh3d(3, 3, 3, z_weight=2.0), []),
    "torus3d": lambda: (torus3d(3, 3, 2), []),
    "chiplet": lambda: (chiplet(4), []),
    "degraded8x8-12": lambda: _degraded(8, 12),
    "degraded8x8-router": lambda: _degraded(8, "router"),
}


def _merge_requests(g, dead, count, seed):
    """``count`` instances of fanout 1 to MIN_SLOTS over the live nodes."""
    rng = random.Random(seed)
    nodes = [u for u in g.nodes() if u not in dead]
    reqs = []
    for _ in range(count):
        src = rng.choice(nodes)
        k = rng.randint(1, min(bpm.MIN_SLOTS, len(nodes) - 1))
        reqs.append((src, sorted(rng.sample(
            [u for u in nodes if u != src], k))))
    return reqs


@pytest.mark.parametrize("cm", ["hops", "weighted"])
@pytest.mark.parametrize("fabric", sorted(MERGE_FABRICS))
def test_device_candidates_match_definitions(fabric, cm):
    """The device tables and the merge's per-candidate outputs follow the
    host's Definitions 1-2 on every fabric kind, healthy and degraded,
    under hop and weighted prices: provider-route distances and prices
    (all pairs, on fabrics of up to 64 nodes), wedge membership, and for
    every candidate its representative, MU/DP mode and cost."""
    import numpy as np

    from repro.core import candidate_cost, get_cost_model, route_cost_matrices
    from repro.core.partition import basic_partitions
    from repro.core.routefn import components, provider_for

    g, dead = MERGE_FABRICS[fabric]()
    model = get_cost_model(cm)
    if g.num_nodes <= 64:
        dist, w_uni, _ = route_cost_matrices(g, model)
        comp = components(g)
        for u in g.nodes():
            for v in g.nodes():
                iu, iv = g.idx(u), g.idx(v)
                if u == v:
                    continue
                if comp[iu] != comp[iv]:
                    assert dist[iu, iv] == -1 and np.isinf(w_uni[iu, iv])
                    continue
                route = provider_for(g).unicast(g, u, v)
                assert dist[iu, iv] == g.distance(u, v) == len(route) - 1
                assert w_uni[iu, iv] == model.route_cost(g, route)
    bp = BatchPlanner(g, "DPM", cm)
    assert bp.support.ok, bp.support.reason
    memb = bp._tables().memb
    reqs = _merge_requests(g, dead, 16, seed=sum(map(ord, fabric)))
    _, _, reps, modes, costs = _merge(bp, reqs, bpm.MIN_SLOTS, 16)
    for b, (src, dests) in enumerate(reqs):
        parts = basic_partitions(src, dests, g)
        for d in dests:
            assert parts[memb[g.idx(src)][g.idx(d)]].count(d) == 1
        for ci, ids in enumerate(bp._cands):
            union = [d for i in ids for d in parts[i]]
            if not union:
                assert reps[b, ci] == -1
                continue
            cc = candidate_cost(g, src, ids, union, model)
            assert reps[b, ci] == g.idx(cc.rep)
            assert bool(modes[b, ci]) == (cc.mode == "MU")
            assert costs[b, ci] == cc.cost(True)


def _host_merge(costs, reps, cands, np_):
    """Algorithm 1's merge loop as ``dpm_partition`` runs it, over one
    priced candidate row: the candidates it merges, in pick order (max
    saving, then fewer partitions, then smaller index), and the non-empty
    singles left over."""
    savings = {
        ci: max(0.0, sum(costs[i] for i in ids) - costs[ci])
        for ci, ids in enumerate(cands) if len(ids) > 1 and reps[ci] >= 0
    }
    picks: list[int] = []
    covered: set[int] = set()
    while True:
        best, best_a = None, 0
        for ci, a in savings.items():
            if a > 0 and (best is None or a > best_a or (
                    a == best_a and (len(cands[ci]), cands[ci])
                    < (len(cands[best]), cands[best]))):
                best, best_a = ci, a
        if best is None:
            break
        picks.append(best)
        covered |= set(cands[best])
        for ci in savings:
            if covered & set(cands[ci]):
                savings[ci] = 0
    return picks, [i for i in range(np_) if i not in covered and reps[i] >= 0]


# the energy model prices in floats, where near-tied savings are what a
# scalar priority encoding would mis-rank; its instances are those of
# the float tie-break check of the 8x8 merge
COVER_CASES = sorted(MERGE_FABRICS) + ["energy-mesh8x8"]


@pytest.mark.parametrize("case", COVER_CASES)
def test_device_merge_chooses_a_disjoint_cover(case):
    """The merge chooses candidates that are disjoint, non-empty and cover
    every non-empty basic partition, in the order the host's loop picks
    them over the same prices: merges by round, then the leftover singles.
    Under the exact price models that is ``dpm_partition``'s own trace."""
    import numpy as np

    from repro.core.partition import basic_partitions, dpm_partition
    from repro.kernels.dpm_cost.ops import NO_ORDER

    if case == "energy-mesh8x8":
        g, dead, models = grid(8), [], ["energy"]
        rng = random.Random(17)
        reqs = []
        for _ in range(40):
            picks = rng.sample(g.nodes(), rng.randint(1, 16) + 1)
            reqs.append((picks[0], sorted(picks[1:])))
    else:
        (g, dead), models = MERGE_FABRICS[case](), ["hops", "weighted"]
        reqs = _merge_requests(g, dead, 16, seed=sum(map(ord, case)) + 1)
    for cm in models:
        bp = BatchPlanner(g, "DPM", cm)
        cands, np_ = bp._cands, bp.np_
        chosen, order, reps, _, costs = _merge(bp, reqs, bpm.MIN_SLOTS,
                                               len(reqs))
        for b, (src, dests) in enumerate(reqs):
            parts = basic_partitions(src, dests, g)
            picked = np.flatnonzero(chosen[b])
            assert (reps[b, picked] >= 0).all()
            covered = [i for ci in picked for i in cands[ci]]
            assert len(covered) == len(set(covered)), (cm, b)
            assert set(covered) >= {i for i in range(np_) if parts[i]}
            rounds = sorted((int(order[b, ci]), ci) for ci in picked
                            if order[b, ci] != NO_ORDER)
            assert [r for r, _ in rounds] == list(range(len(rounds)))
            merges = [ci for _, ci in rounds]
            leftover = sorted(set(picked) - set(merges))
            assert (merges, leftover) == _host_merge(
                costs[b], reps[b], cands, np_), (cm, b)
            if cm != "energy":
                host = dpm_partition(g, src, dests, cost_model=cm)
                assert [cands[ci] for ci in merges] == [
                    ids for ids, _ in host.savings_trace]
                assert [cands[ci] for ci in merges + leftover] == [
                    p.ids for p in host.partitions]
