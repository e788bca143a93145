"""Topology-layer tests: Torus geometry/routing, planners on the torus,
and the repro.dist.multicast scheduler."""
import random

import pytest

from repro.core import (
    PLANNERS,
    MeshGrid,
    grid,
    make_topology,
    plan,
    ring_delta,
    torus,
    xy_route,
)
from repro.core.partition import basic_partitions
from repro.dist.multicast import (
    Torus,
    dp_broadcast_schedule,
    plan_torus_multicast,
    schedule_multicasts,
)

T8 = torus(8)
G8 = grid(8)


def _nodes(t):
    return [(x, y) for x in range(t.n) for y in range(t.rows)]


def _instances(t, count, kmax, seed):
    rng = random.Random(seed)
    nodes = _nodes(t)
    for _ in range(count):
        picks = rng.sample(nodes, rng.randint(3, kmax + 1))
        yield picks[0], picks[1:]


# ---------------------------------------------------------------- geometry
@pytest.mark.parametrize("dims", [(8, 8), (5, 7), (16, 16), (8, 1)])
def test_torus_delta_is_shortest_wrap(dims):
    """Wrap legs are valid displacements and never longer than non-wrap."""
    t = torus(*dims)
    rng = random.Random(1)
    nodes = _nodes(t)
    for _ in range(300):
        a, b = rng.choice(nodes), rng.choice(nodes)
        dx, dy = t.delta(a, b)
        assert (a[0] + dx) % t.n == b[0] and (a[1] + dy) % t.rows == b[1]
        assert abs(dx) <= t.n // 2 and abs(dy) <= t.rows // 2
        assert t.distance(a, b) <= MeshGrid.manhattan(a, b)
        assert t.distance(a, b) == t.distance(b, a)


def test_ring_delta_matches_kernel_convention():
    """Half-way ties break negative, exactly like the wrap=True kernel."""
    for size in (2, 4, 8, 16):
        assert ring_delta(size // 2, size) == -size // 2
    for size in (1, 2, 3, 5, 8):
        for d in range(-size, size + 1):
            r = ring_delta(d, size)
            assert (d - r) % size == 0 if size > 1 else r == 0


@pytest.mark.parametrize("dims", [(8, 8), (6, 4), (3, 3)])
def test_torus_xy_route_shortest_and_adjacent(dims):
    t = torus(*dims)
    rng = random.Random(2)
    nodes = _nodes(t)
    for _ in range(200):
        a, b = rng.choice(nodes), rng.choice(nodes)
        path = xy_route(t, a, b)
        assert path[0] == a and path[-1] == b
        assert len(path) - 1 == t.distance(a, b)
        for u, v in zip(path, path[1:]):
            assert v in t.neighbors(*u)


def test_torus_neighbors_degree_and_ring_degeneration():
    for (x, y) in _nodes(torus(8)):
        assert len(torus(8).neighbors(x, y)) == 4
    ring = torus(8, 1)
    assert ring.neighbors(0, 0) == [(1, 0), (7, 0)]
    assert ring.distance((0, 0), (7, 0)) == 1


def test_basic_partitions_wedges_on_torus():
    """Partition membership is the sign pattern of the shortest delta."""
    src = (0, 0)
    dests = [d for d in _nodes(T8) if d != src]
    parts = basic_partitions(src, dests, T8)
    flat = [d for p in parts for d in p]
    assert sorted(flat) == sorted(dests)  # disjoint exact cover
    for i, p in enumerate(parts):
        for d in p:
            dx, dy = T8.delta(src, d)
            expect = [
                dx > 0 and dy > 0, dx == 0 and dy > 0, dx < 0 and dy > 0,
                dx < 0 and dy == 0, dx < 0 and dy < 0, dx == 0 and dy < 0,
                dx > 0 and dy < 0, dx > 0 and dy == 0,
            ]
            assert expect[i]
    # (7, 0) is one wrap hop left of the source: P3, not P7
    assert (7, 0) in parts[3]


# ---------------------------------------------------------------- planners
@pytest.mark.parametrize("algo", list(PLANNERS))
def test_planners_cover_on_torus(algo):
    for src, dests in _instances(T8, 40, 12, seed=len(algo)):
        p = plan(algo, T8, src, dests)
        assert p.check_covers(), (algo, src, dests)
        for path in p.paths:  # hop-adjacency under torus links
            for a, b in zip(path.hops, path.hops[1:]):
                assert b in T8.neighbors(*a)


def test_torus_dpm_beats_mesh_dpm_on_wrapped_sets():
    """Wraparound shortcuts must pay off: clearly on an edge-hugging set,
    and in aggregate over random instances (per-instance the greedy
    heuristic may occasionally flip)."""
    src, dests = (0, 0), [(7, 0), (0, 7), (7, 7), (6, 1), (1, 6)]
    assert plan("DPM", T8, src, dests).total_hops < plan("DPM", G8, src, dests).total_hops
    tot_t = tot_m = 0
    for src, dests in _instances(T8, 100, 10, seed=3):
        tot_t += plan("DPM", T8, src, dests).total_hops
        tot_m += plan("DPM", G8, src, dests).total_hops
    assert tot_t <= tot_m


def test_planner_cache_normalized_and_topology_keyed():
    assert grid(8) is grid(8, 8)
    assert torus(8) is torus(8, 8)
    assert make_topology("torus", 8).kind == "torus"
    src, dests = (0, 0), [(7, 0)]
    pm = plan("MU", grid(8), src, dests)
    pt = plan("MU", torus(8), src, dests)
    assert pm.paths[0].hop_count == 7
    assert pt.paths[0].hop_count == 1  # no mesh/torus cache collision
    assert plan("MU", grid(8, 8), src, dests) is pm  # one entry per geometry


# ---------------------------------------------------------------- dist layer
def test_plan_torus_multicast_covers():
    t = Torus(16, 16)
    for src, dests in _instances(t, 25, 10, seed=7):
        assert plan_torus_multicast(t, src, dests).check_covers()


def test_schedule_multicasts_delivers_all_with_causality():
    t = Torus(16, 16)
    rng = random.Random(9)
    nodes = _nodes(t)
    reqs = []
    for _ in range(8):
        picks = rng.sample(nodes, rng.randint(4, 9))
        reqs.append((picks[0], picks[1:]))
    sched = schedule_multicasts(t, reqs)
    have = [{t.idx(s)} for s, _ in reqs]
    for rnd, rr in zip(sched.rounds, sched.round_reqs):
        senders = [s for s, _ in rnd]
        receivers = [d for _, d in rnd]
        # one ppermute per round: unique senders, unique receivers
        assert len(set(senders)) == len(senders)
        assert len(set(receivers)) == len(receivers)
        # store-and-forward causality per request
        for (s, d), rid in zip(rnd, rr):
            assert s in have[rid]
        for (s, d), rid in zip(rnd, rr):
            have[rid].add(d)
    for rid, (src, dests) in enumerate(reqs):
        assert {t.idx(d) for d in dests} <= have[rid]


@pytest.mark.parametrize("algo", ["MU", "DP", "DPM"])
def test_dp_broadcast_schedule_reaches_all_ranks(algo):
    for nr in (2, 4, 8, 16):
        sched = dp_broadcast_schedule(nr, algo)
        have = {0}
        for rnd in sched.rounds:
            assert all(s in have for s, _ in rnd)
            have |= {d for _, d in rnd}
        assert have == set(range(nr))


def test_dpm_ring_broadcast_beats_mu_rounds_and_hops():
    mu = dp_broadcast_schedule(16, "MU")
    dpm = dp_broadcast_schedule(16, "DPM")
    assert dpm.num_rounds < mu.num_rounds  # two relay chains vs serial sends
    assert dpm.total_hops < mu.total_hops
    c_mu, c_dpm = mu.cost(2**20), dpm.cost(2**20)
    assert c_dpm["time_us"] < c_mu["time_us"]
    assert c_dpm["link_bytes"] < c_mu["link_bytes"]


# ---------------------------------------------------------------- simulator
def test_wormhole_sim_on_torus_dpm_beats_mu():
    from repro.noc import NoCConfig, WormholeSim

    cfg = NoCConfig(topology="torus")
    src, dests = (0, 0), [(7, 7), (7, 0), (0, 7), (6, 6), (1, 7)]
    flits = {}
    for algo in ("MU", "DPM"):
        sim = WormholeSim(cfg)
        sim.add_plan(plan(algo, torus(8), src, dests), 0)
        st = sim.run(5000)
        assert st.packets_created == st.packets_finished
        flits[algo] = st.flit_link_traversals
    assert flits["DPM"] < flits["MU"]


def test_torus_workload_drains():
    from repro.noc import NoCConfig, simulate, synthetic_workload

    cfg = NoCConfig(topology="torus")
    wl = synthetic_workload(cfg, 0.02, 300, seed=2)
    st = simulate(cfg, wl, "DPM")
    assert st.packets_created == st.packets_finished


# ------------------------------------------- conformance (all registered kinds)
# Property suite over every registered topology kind: any new kind must add a
# representative fabric here, and the coverage test fails until it does. Uses
# hypothesis (or the conftest shim) with integer seeds only — the shim's
# @given wrapper takes no pytest-injected parameters, so kinds are looped
# inside each property body.
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core.topo3d import chiplet, mesh3d, torus3d  # noqa: E402
from repro.core.topology import registered_topology_kinds  # noqa: E402

FABRICS = {
    "mesh": grid(5, 4),
    "torus": torus(5, 4),
    "mesh3d": mesh3d(3, 4, 2),
    "torus3d": torus3d(3, 4, 3),
    "chiplet": chiplet(8, 8, 2, 2),
}
_SEED = st.integers(min_value=0, max_value=2**31 - 1)


def test_conformance_fabrics_cover_all_registered_kinds():
    assert set(FABRICS) == set(registered_topology_kinds())
    for kind, g in FABRICS.items():
        assert g.kind == kind


@given(_SEED)
@settings(max_examples=60)
def test_conformance_label_unlabel_roundtrip(seed):
    for g in FABRICS.values():
        lab = seed % g.num_nodes
        c = g.unlabel(lab)
        assert g.label(*c) == lab
        i = (seed * 7919 + 13) % g.num_nodes
        assert g.idx(g.from_idx(i)) == i


@given(_SEED)
@settings(max_examples=60)
def test_conformance_snake_successor_is_neighbor(seed):
    """The label order is a Hamiltonian path — consecutive labels are
    physically adjacent, which is what makes label-monotone dual-path
    routing deadlock-free on every fabric."""
    for g in FABRICS.values():
        lab = seed % (g.num_nodes - 1)
        u, v = g.unlabel(lab), g.unlabel(lab + 1)
        assert v in g.neighbors(*u)


@given(_SEED, _SEED)
@settings(max_examples=60)
def test_conformance_delta_matches_distance(s1, s2):
    for g in FABRICS.values():
        a, b = g.unlabel(s1 % g.num_nodes), g.unlabel(s2 % g.num_nodes)
        dv = g.delta(a, b)
        # the signed displacement lands on b (modulo wrap)
        assert g.normalize(*(c + d for c, d in zip(a, dv))) == b
        l1 = sum(abs(d) for d in dv)
        if g.kind == "chiplet":
            # sparse NoI crossings: BFS distance prices the geometric
            # displacement or more, never less
            assert g.distance(a, b) >= l1
        else:
            assert g.distance(a, b) == l1
        assert g.distance(a, b) == g.distance(b, a)
        assert (g.distance(a, b) == 0) == (a == b)


@given(_SEED)
@settings(max_examples=60)
def test_conformance_neighbors_symmetric(seed):
    for g in FABRICS.values():
        u = g.unlabel(seed % g.num_nodes)
        ns = g.neighbors(*u)
        assert len(ns) == len(set(ns)) and u not in ns
        for v in ns:
            assert u in g.neighbors(*v)
            assert g.distance(u, v) == 1


@given(_SEED, _SEED)
@settings(max_examples=60)
def test_conformance_normalize_idempotent(s1, s2):
    for g in FABRICS.values():
        c = g.unlabel(s1 % g.num_nodes)
        assert g.normalize(*c) == c  # in-range coords are fixed points
        off = (s2 % 7 - 3, (s2 // 7) % 7 - 3) + (0,) * (len(c) - 2)
        w = g.normalize(*(x + k for x, k in zip(c, off)))
        assert g.normalize(*w) == w
