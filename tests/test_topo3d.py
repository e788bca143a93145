"""3-D mesh/torus and chiplet-package topology tests (DESIGN.md §11).

Covers the full thread: geometry registration, planner coverage on the 26
3-D wedges and the sparse chiplet link set, weighted heterogeneous links
changing DPM merge choices, fault detours, host-vs-xsim delivery-set
equality, telemetry conservation, and the dist-layer scheduler on 3-D /
chiplet fabrics.
"""
import random

import numpy as np
import pytest

from repro.core import (
    PLANNERS,
    WeightedLinkCost,
    make_topology,
    plan,
)
from repro.core.partition import (
    basic_partitions,
    candidate_ids_for,
    dpm_partition,
    wedge_patterns,
)
from repro.core.routefn import faulty, provider_for, route_cost_matrices
from repro.core.topo3d import chiplet, mesh3d, torus3d
from repro.core.topology import register_topology, registered_topology_kinds
from repro.noc import NoCConfig, WormholeSim, synthetic_workload, xsimulate
from repro.noc.telemetry import link_coords, link_index

M333 = mesh3d(3, 3, 3)
T333 = torus3d(3, 3, 3)
CP = chiplet(8, 8, 2, 2)  # 2x2 chiplets of 4x4 routers

GRACE = 800


def _instances(g, count, kmax, seed):
    rng = random.Random(seed)
    nodes = g.nodes()
    for _ in range(count):
        picks = rng.sample(nodes, rng.randint(3, kmax + 1))
        yield picks[0], picks[1:]


# ------------------------------------------------------------ registration
def test_registered_kinds_include_topo3d():
    kinds = registered_topology_kinds()
    for k in ("mesh", "torus", "mesh3d", "torus3d", "chiplet"):
        assert k in kinds


def test_make_topology_unknown_kind_lists_registered():
    with pytest.raises(ValueError, match="unknown topology kind 'hypercube'"):
        make_topology("hypercube", 4)
    with pytest.raises(ValueError, match="chiplet.*mesh3d.*torus3d"):
        make_topology("hypercube", 4)


def test_register_topology_duplicate_raises():
    with pytest.raises(ValueError, match="already registered"):
        register_topology("mesh3d", mesh3d)


def test_factories_interned_and_cache_keyed():
    assert mesh3d(3, 3, 3) is make_topology("mesh3d", 3, 3, params=(3,))
    assert torus3d(3, 3, 3) is make_topology("torus3d", 3, 3, params=(3,))
    assert chiplet(8, 8, 2, 2) is make_topology("chiplet", 8, 8, params=(2, 2))
    # distinct weight classes are distinct planner-cache keys
    assert mesh3d(3, 3, 3, z_weight=2.0) is not mesh3d(3, 3, 3)
    assert mesh3d(3, 3, 3, z_weight=2.0).params == (3, 2.0)


def test_topology_protocol_invariants():
    for g in (M333, T333, CP):
        assert g.num_nodes == g.rows * g.n
        for i in (0, 1, g.num_nodes // 2, g.num_nodes - 1):
            assert g.idx(g.from_idx(i)) == i
        # directed-link id space: ports per router, dir_delta inverts
        for u in g.nodes():
            for v in g.neighbors(*u):
                d = g.direction(u, v)
                assert 0 <= d < g.ports
                dd = g.dir_delta(d)
                assert g.normalize(*(c + e for c, e in zip(u, dd))) == v


# ------------------------------------------------------------ partitions
def test_wedge_patterns_3d_extend_ring2():
    p2 = wedge_patterns(2)
    p3 = wedge_patterns(3)
    assert len(p2) == 8 and len(p3) == 26
    # dz=0 block keeps the 2-D ring order so flat sets partition identically
    assert [p[:2] for p in p3[:8]] == list(p2)
    assert p3[16] == (0, 0, 1) and p3[25] == (0, 0, -1)
    assert len(candidate_ids_for(26)) == 78


def test_basic_partitions_3d_sign_patterns():
    src = (1, 1, 1)
    dests = [d for d in M333.nodes() if d != src]
    parts = basic_partitions(src, dests, M333)
    assert len(parts) == 26
    flat = [d for p in parts for d in p]
    assert sorted(flat) == sorted(dests)  # disjoint exact cover
    pats = wedge_patterns(3)
    for i, p in enumerate(parts):
        for d in p:
            dv = M333.delta(src, d)
            assert tuple((x > 0) - (x < 0) for x in dv) == pats[i]


# ------------------------------------------------------------ planning
@pytest.mark.parametrize("g", [M333, T333, CP], ids=["mesh3d", "torus3d", "chiplet"])
@pytest.mark.parametrize("algo", list(PLANNERS))
def test_planners_cover_on_new_topologies(g, algo):
    for src, dests in _instances(g, 15, 8, seed=len(algo)):
        p = plan(algo, g, src, dests)
        assert p.check_covers(), (g.kind, algo, src, dests)
        for path in p.paths:
            for a, b in zip(path.hops, path.hops[1:]):
                assert b in g.neighbors(*a)


def test_chiplet_plans_label_monotone_per_worm():
    """On the chiplet package every worm is label-monotone: BFS routes are
    auto-segmented at direction reversals (needs_bfs_routes), so the
    dual-path VC-class deadlock argument applies per worm. (Healthy 2-D/3-D
    dimension-ordered worms are only per-hop classed, not globally
    monotone.)"""
    g = CP
    for src, dests in _instances(g, 20, 8, seed=11):
        p = plan("DPM", g, src, dests)
        for path in p.paths:
            labs = [g.label(*h) for h in path.hops]
            assert all(b > a for a, b in zip(labs, labs[1:])) or all(
                b < a for a, b in zip(labs, labs[1:])
            ), (g.kind, path.hops)


def test_weighted_z_links_change_dpm_merges():
    """Pricing TSV z-links makes the weighted objective prefer merges that
    stay in-plane: plans must differ from uniform-cost plans somewhere, and
    the weighted objective must price the weighted plan no worse."""
    cheap = mesh3d(4, 4, 4)  # z_weight 1.0
    dear = mesh3d(4, 4, 4, z_weight=4.0)
    wcost = WeightedLinkCost()
    diffs = 0
    for src, dests in _instances(dear, 40, 10, seed=5):
        r_u = dpm_partition(dear, src, list(dests))
        r_w = dpm_partition(dear, src, list(dests), cost_model=wcost)
        ids_u = sorted(p.ids for p in r_u.partitions)
        ids_w = sorted(p.ids for p in r_w.partitions)
        if ids_u != ids_w:
            diffs += 1
        # uniform fabric: the weighted model degenerates to hop counting
        r_c = dpm_partition(cheap, src, list(dests), cost_model=wcost)
        r_h = dpm_partition(cheap, src, list(dests))
        assert sorted(p.ids for p in r_c.partitions) == sorted(
            p.ids for p in r_h.partitions
        )
    assert diffs > 0, "z_weight=4.0 never changed a merge choice"


def test_weighted_noi_links_change_chiplet_dpm_merges():
    dear = chiplet(8, 8, 2, 2, noi_weight=6.0)
    wcost = WeightedLinkCost()
    diffs = 0
    for src, dests in _instances(dear, 40, 10, seed=6):
        r_u = dpm_partition(dear, src, list(dests))
        r_w = dpm_partition(dear, src, list(dests), cost_model=wcost)
        if sorted(p.ids for p in r_u.partitions) != sorted(
            p.ids for p in r_w.partitions
        ):
            diffs += 1
    assert diffs > 0, "noi_weight=6.0 never changed a merge choice"


def test_route_cost_matrices_price_heterogeneous_links():
    g = mesh3d(3, 3, 3, z_weight=2.5)
    dist, weight, _ = route_cost_matrices(g, WeightedLinkCost())
    a, b = g.idx((0, 0, 0)), g.idx((0, 0, 1))  # one z-hop
    c = g.idx((1, 0, 0))  # one x-hop
    assert dist[a, b] == 1 and weight[a, b] == 2.5
    assert dist[a, c] == 1 and weight[a, c] == 1.0
    # the default (hop-count) model ignores link weights entirely
    _, w_hops, _ = route_cost_matrices(g)
    assert w_hops[a, b] == 1.0


# ------------------------------------------------------------ faults
def test_fault_detour_on_mesh3d():
    broken = (((1, 1, 0), (1, 1, 1)),)
    g = faulty(M333, broken)
    p = plan("DPM", g, (1, 1, 0), [(1, 1, 1), (1, 1, 2), (0, 0, 2)])
    assert p.check_covers()
    for path in p.paths:
        for a, b in zip(path.hops, path.hops[1:]):
            assert not g.is_broken(a, b)


def test_fault_detour_on_chiplet_noi():
    # break one of the two east-west interposer crossings
    broken = (((3, 0), (4, 0)),)
    g = faulty(CP, broken)
    p = plan("DPM", g, (0, 0), [(7, 0), (7, 7), (4, 3)])
    assert p.check_covers()
    for path in p.paths:
        for a, b in zip(path.hops, path.hops[1:]):
            assert not g.is_broken(a, b)


def test_provider_dispatch_for_new_topologies():
    # chiplet needs BFS routes; 3-D meshes route dimension-ordered
    assert provider_for(CP).__class__.__name__ == "BFSRouteProvider"
    assert provider_for(M333).__class__.__name__ == "MinimalRouteProvider"
    assert provider_for(faulty(M333, (((0, 0, 0), (1, 0, 0)),))
                        ).__class__.__name__ == "FaultAwareProvider"


# ------------------------------------------------------ host sim vs xsim
CASES = [
    ("mesh3d-DPM",
     NoCConfig(n=3, m=3, topology="mesh3d", topology_params=(3,),
               dest_range=(2, 5)), 0.03, 100, 1, "DPM"),
    ("mesh3d-MU",
     NoCConfig(n=3, m=3, topology="mesh3d", topology_params=(3,),
               dest_range=(2, 5)), 0.03, 100, 1, "MU"),
    ("torus3d-DPM",
     NoCConfig(n=3, m=3, topology="torus3d", topology_params=(3,),
               dest_range=(2, 5)), 0.03, 100, 2, "DPM"),
    ("mesh3d-weighted-z-DPM",
     NoCConfig(n=3, m=3, topology="mesh3d", topology_params=(3, 2.0),
               dest_range=(2, 5)), 0.03, 100, 4, "DPM"),
    ("chiplet-DPM",
     NoCConfig(n=8, m=8, topology="chiplet", topology_params=(2, 2),
               dest_range=(2, 5)), 0.02, 100, 3, "DPM"),
    ("chiplet-MP",
     NoCConfig(n=8, m=8, topology="chiplet", topology_params=(2, 2),
               dest_range=(2, 5)), 0.02, 100, 3, "MP"),
]


@pytest.mark.parametrize("case", CASES, ids=[c[0] for c in CASES])
def test_xsim_matches_wormhole_on_new_topologies(case):
    _, cfg, rate, cycles, seed, algo = case
    wl = synthetic_workload(cfg, rate, cycles, seed=seed)
    res = xsimulate(cfg, [wl], (algo,), warmup=0, drain_grace=GRACE)
    g = cfg.make_topology()
    sim = WormholeSim(cfg, measure_window=(0, wl.horizon))
    for r in wl.requests:
        sim.add_request(algo, r.src, r.dests, r.time)
    pst = sim.run(wl.horizon + GRACE, drain=True)
    psets = {
        pk.pid: {g.idx(c) for c in pk.delivery_times} for pk in sim.packets
    }
    xst = res.stats(0, 0)
    # both engines fully drain (covered-and-drained acceptance)
    assert res.all_drained(0, 0)
    assert pst.packets_finished == pst.packets_created
    # identical per-packet delivery sets (the hard contract)
    assert res.delivered_sets(0, 0) == psets
    assert xst.flit_link_traversals == pst.flit_link_traversals
    assert xst.packets_created == pst.packets_created


def test_xsim_heatmap_shape_tracks_ports():
    cfg = NoCConfig(n=3, m=3, topology="mesh3d", topology_params=(3,),
                    dest_range=(2, 4))
    wl = synthetic_workload(cfg, 0.02, 60, seed=0)
    res = xsimulate(cfg, [wl], ("DPM",), warmup=0, drain_grace=GRACE)
    hm = res.link_heatmap(0, 0)
    assert hm.shape == (9, 3, 6)  # rows = m*d, 6 ports in 3-D
    assert hm.sum() == res.stats(0, 0).flit_link_traversals


# ------------------------------------------------------------ telemetry
@pytest.mark.parametrize("cfg", [
    NoCConfig(n=3, m=3, topology="mesh3d", topology_params=(3,),
              dest_range=(2, 4)),
    NoCConfig(n=8, m=8, topology="chiplet", topology_params=(2, 2),
              dest_range=(2, 4)),
], ids=["mesh3d", "chiplet"])
def test_telemetry_conservation_on_new_topologies(cfg):
    """Structured telemetry views must equal the flat conserved counters on
    the new port/link id spaces (satellite of DESIGN.md §10)."""
    wl = synthetic_workload(cfg, 0.03, 120, seed=7)
    sim = WormholeSim(cfg, measure_window=(0, wl.horizon))
    for r in wl.requests:
        sim.add_request("DPM", r.src, r.dests, r.time)
    st = sim.run(wl.horizon + GRACE, drain=True)
    tel = st.telemetry
    g = cfg.make_topology()
    ports = g.ports
    assert tel.link_flits.shape == (g.num_nodes * ports,)
    assert int(tel.link_flits.sum()) == st.flit_link_traversals
    assert np.array_equal(tel.heatmap(g).reshape(-1), tel.link_flits)
    # link_coords round-trips every used link id through the topology
    for lid in np.flatnonzero(tel.link_flits):
        u, v = link_coords(g, int(lid))
        assert v in g.neighbors(*u)
        assert link_index(g, u, v) == int(lid)


# ------------------------------------------------------------ dist layer
@pytest.mark.parametrize("g", [T333, CP], ids=["torus3d", "chiplet"])
def test_schedule_multicasts_on_new_fabrics(g):
    from repro.dist.multicast import schedule_multicasts

    rng = random.Random(9)
    nodes = g.nodes()
    reqs = []
    for _ in range(6):
        picks = rng.sample(nodes, rng.randint(4, 9))
        reqs.append((picks[0], picks[1:]))
    sched = schedule_multicasts(g, reqs)
    have = [{g.idx(s)} for s, _ in reqs]
    for rnd, rr in zip(sched.rounds, sched.round_reqs):
        senders = [s for s, _ in rnd]
        receivers = [d for _, d in rnd]
        assert len(set(senders)) == len(senders)
        assert len(set(receivers)) == len(receivers)
        for (s, d), rid in zip(rnd, rr):
            assert s in have[rid]
        for (s, d), rid in zip(rnd, rr):
            have[rid].add(d)
    for rid, (src, dests) in enumerate(reqs):
        assert {g.idx(d) for d in dests} <= have[rid]
