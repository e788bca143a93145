"""Device-side batched planning + the canonical plan arena (DESIGN.md §12).

``plan()`` is a host-side Python loop behind an LRU — fine for one multicast
at a time, not for serving-scale request streams where planning itself is
the hot path. This module plans *batches*: pack B (src, dest-set) instances
into ``(B, K)`` destination slots (node indices, -1 pads), run Algorithm 1
for all of them in one jitted dispatch (``kernels.dpm_cost.dpm_plan_exact``
— full Definition 2, C_t and C_p, MU/DP modes, greedy pick order, priced
over each instance's K slots rather than every node of the fabric), and
decode the resulting partition tensors into ``MulticastPlan``s only for
arena misses.

The correctness contract is **bit-identity with the host planner**: every
decoded plan equals ``plan(algo, topo, src, dests, cost_model=...)`` field
for field. Three things make that hold:

* the decode step reimplements the host emitter
  (``planner._emit_dpm_partition``) with array operations over a whole
  dispatched chunk: from the device-chosen partitions, representatives,
  modes and pick order it lays out every worm's route segments, expands
  them from a pool of memoized routes and takes deliveries as first
  visits along the expanded worm, as ``_deliveries_on`` does;
* a label-chain decomposition prices C_p exactly on device: a label-ordered
  chain is the concatenation of pairwise label routes between consecutive
  members, skipping a member an earlier route already passed through. On a
  healthy fabric the dual-path rule never passes a pending member early,
  so C_p reduces to a prefix scan over pairwise price matrices, over the
  label-sorted destination slots; on a degraded one a detour's BFS hops
  may overshoot the target's label, and the device walks the slots in
  label order with the nodes each pairwise route passes
  (``label_chain_passes``);
* ``batch_support`` gates batching on *exactness*: every price must be a
  dyadic rational (multiple of 1/q, q a power of two <= 256) small enough
  that float32 sums stay exact, and the cost model must price routes
  edge-additively.

Degraded fabrics plan on the device like healthy ones, and their decoded
plans are segmented into label-monotone worms as ``plan()`` segments them.
Anything outside the gate — non-dyadic objectives (energy), unregistered
algorithms/models, oversized fabrics — falls back to the host ``plan()``
transparently; the arena caches either way.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import threading
import time
from collections import OrderedDict
from typing import NamedTuple

import numpy as np

from ..obs import install_gc_clock, span
from .algo import (
    get_algorithm,
    get_cost_model,
    is_registered_algorithm,
    is_registered_cost_model,
    on_registry_change,
)
from .grid import Coord, MeshGrid
from .partition import candidate_ids_for, wedge_patterns
from .planner import (
    MulticastPlan,
    PacketPath,
    canonical_dests,
    plan,
    plan_dpm,
    plan_dpm_e,
)
from .routefn import components, provider_for, route_cost_matrices
from .routing import label_route

# Dense lowering is O(NN^2) host work (once per topology/model, cached);
# cap it so a misconfigured huge fabric degrades to host planning instead
# of stalling on table construction.
MAX_ARENA_NODES = 1024
DEFAULT_ARENA_SIZE = 65_536
# Device dispatch granularity: misses are planned in fixed-size chunks so
# every batch size ≥ CHUNK reuses one compiled shape (smaller batches pad
# to the next power of two — a handful of specializations total), and so
# on multi-core hosts the decode of chunk k overlaps the asynchronously
# dispatched device compute of chunk k+1.
DISPATCH_CHUNK = 512
# Destination slots per instance: the chunk's largest destination set,
# rounded up to a power of two and at least MIN_SLOTS, so the paper's
# fanouts (up to 16) share one compiled shape per padded batch size.
MIN_SLOTS = 16

# Exactness gate: prices must be multiples of 1/q for a power of two
# q <= SCALE, and bounded so that any candidate-cost sum stays inside
# float32's exact-integer range (2^24 in units of 1/q). 1/256 covers every
# shipped dyadic model (hops, weighted with dyadic link weights, contention
# on power-of-two extents); integral prices (hops) take q = 1.
_SCALE = 256
_EXACT_LIMIT = float(2**24)


# The cyclic collector is held off while a call decodes (``_plan_batch``).
# A decode allocates a few hundred thousand containers that all survive
# (the plans), so left alone the collector ran full collections every
# call, walking the whole heap each time and freeing nothing: some 40 %
# of a degraded 8x8 bulk window. Paused, the young plans are collected
# once after the call. Reference counting still frees garbage meanwhile.
# The pause nests across threads and restores the state it found.
_PAUSE_LOCK = threading.Lock()
_pauses = 0
_resume = False


@contextlib.contextmanager
def _collector_paused():
    global _pauses, _resume
    with _PAUSE_LOCK:
        if _pauses == 0:
            _resume = gc.isenabled()
            gc.disable()
        _pauses += 1
    try:
        yield
    finally:
        with _PAUSE_LOCK:
            _pauses -= 1
            if _pauses == 0 and _resume:
                gc.enable()


class _Support(NamedTuple):
    ok: bool
    reason: str


class ArenaInfo(NamedTuple):
    """Per-planner arena stats: lookup hits/misses, LRU bounds/evictions,
    *planning attribution* — how many misses were planned on device
    (``batched_plans``, in ``dispatches`` jitted batches) vs on the host
    fallback path (``host_plans``) — and host seconds: ``plan_s`` inside
    ``plan_many`` in all, of which ``lookup_s`` on arena lookups,
    ``dispatch_s`` packing and enqueuing device merges, ``sync_s`` blocked
    on their outputs, ``decode_s`` decoding them and ``host_plan_s`` in
    host ``plan()``. On fabrics whose plans are segmented into
    label-monotone worms (degraded and BFS-routed ones), ``segment_s`` is
    the part of ``decode_s`` spent segmenting device plans,
    ``segmented_plans`` counts the plans segmentation changed and
    ``relay_worms`` the worms it added. ``array_decoded`` counts the
    plans decoded by the chunk array decode (``BatchPlanner._decode_chunk``),
    every plan planned on the device."""

    hits: int
    misses: int
    maxsize: int
    currsize: int
    evictions: int
    batched_plans: int
    host_plans: int
    dispatches: int
    plan_s: float
    lookup_s: float
    dispatch_s: float
    sync_s: float
    decode_s: float
    host_plan_s: float
    segment_s: float
    segmented_plans: int
    relay_worms: int
    array_decoded: int


class ArenaCacheInfo(NamedTuple):
    """Aggregate arena stats across all live planners, mirroring
    ``planner.PlanCacheInfo``: ``by_key`` maps ``(algo, cost-model)`` to
    its hit/miss/eviction counters (cost-insensitive algorithms key with
    ``cm = ""``, as in the plan cache)."""

    hits: int
    misses: int
    maxsize: int
    currsize: int
    by_key: dict[tuple[str, str], dict[str, int]]


# ---------------------------------------------------------------------------
# Dense host tables (cached per topology / cost model)
# ---------------------------------------------------------------------------
def partition_membership(g, srcs) -> np.ndarray:
    """(len(srcs), NN) int32 wedge id of every node w.r.t. each source.

    Entry ``[p, v]`` is the basic-partition index of node ``v`` under
    source ``srcs[p]`` (``core.partition.wedge_patterns`` order over sign
    patterns of ``Topology.delta``), or -1 at the source itself.
    """
    nodes = g.nodes()
    ndim = len(nodes[0])
    index = {p: i for i, p in enumerate(wedge_patterns(ndim))}
    out = np.full((len(srcs), g.num_nodes), -1, np.int32)
    for pi, src in enumerate(srcs):
        for v in nodes:
            dv = g.delta(src, v)
            sign = tuple((x > 0) - (x < 0) for x in dv)
            out[pi, g.idx(v)] = index.get(sign, -1)
    return out


def snake_labels(g) -> np.ndarray:
    """(NN,) int32 boustrophedon label per node, ``Topology.idx`` order."""
    return np.array([g.label(*c) for c in g.nodes()], np.int32)


@functools.lru_cache(maxsize=256)
def membership_table(topo: MeshGrid) -> np.ndarray:
    """(NN, NN) int32 wedge id of node ``v`` w.r.t. source ``u`` for every
    pair — the all-sources ``partition_membership`` table, built once per
    topology so batch packing is a row gather instead of per-request host
    geometry."""
    return partition_membership(topo, topo.nodes())


@functools.lru_cache(maxsize=256)
def _label_chain_matrices_cached(topo: MeshGrid, cm) -> tuple:
    NN = topo.num_nodes
    nodes = topo.nodes()
    idx = topo.idx
    provider = provider_for(topo)
    comp = components(topo)
    # only a fault-aware label step (a BFS hop) can pass a label beyond
    # its target; the minimal rule never does
    detours = bool(getattr(topo, "faults", ()))
    wh = np.zeros((NN, NN), np.float32)
    wl = np.zeros((NN, NN), np.float32)
    passes: dict[tuple, int] = {}
    labels = {u: topo.label(*u) for u in nodes}
    # Per target, one label_step call per node a walk visits plus memoized
    # chain resolution: cost[u] = link_cost(u, step(u)) + cost[step(u)] —
    # O(NN) per target instead of re-walking every route (shared
    # suffixes). beyond[u] is the bitmask (over node indices) of the nodes
    # the route u -> v visits past v's label.
    for v in nodes:
        iv, lv = idx(v), labels[v]
        for high, w in ((True, wh), (False, wl)):
            nxt: dict[Coord, Coord] = {}
            cost: dict[Coord, float] = {v: 0.0}
            beyond: dict[Coord, int] = {v: 0}
            for u in nodes:
                if (u == v or (labels[u] < lv) != high
                        or comp[idx(u)] != comp[iv]):
                    continue
                stack = []
                cur = u
                while cur not in cost:
                    stack.append(cur)
                    if cur not in nxt:
                        nxt[cur] = provider.label_step(topo, cur, v, high)
                    cur = nxt[cur]
                c, m = cost[cur], beyond[cur]
                for s in reversed(stack):
                    t = nxt[s]
                    c = cm.link_cost(topo, s, t) + c
                    if detours and (labels[t] > lv if high else labels[t] < lv):
                        m |= 1 << idx(t)
                    cost[s], beyond[s] = c, m
                w[idx(u), iv] = cost[u]
                if beyond[u]:
                    passes[(high, idx(u), iv)] = beyond[u]
    if not passes:
        return wh, wl, None
    words = -(-NN // 32)
    ph = np.zeros((NN, NN, words), np.uint32)
    pl = np.zeros((NN, NN, words), np.uint32)
    for (high, iu, iv), m in passes.items():
        for k in range(words):
            (ph if high else pl)[iu, iv, k] = (m >> (32 * k)) & 0xFFFFFFFF
    return wh, wl, (ph.view(np.int32), pl.view(np.int32))


def label_chain_matrices(topo: MeshGrid, cost_model=None):
    """Dense pairwise label-route prices: ``wh[u, v]`` is the cost of the
    HIGH-subnetwork label route u -> v (defined for label(v) > label(u)),
    ``wl`` the LOW mirror — the tensors ``dpm_plan_exact``'s C_p chain
    scan gathers from. Cached per (topology, model) instance pair."""
    return _label_chain_matrices_cached(topo, get_cost_model(cost_model))[:2]


def label_chain_passes(topo: MeshGrid, cost_model=None):
    """``(ph, pl)``, ``(NN, NN, ceil(NN / 32))`` int32 bitmasks over node
    indices: bit ``w`` of ``ph[u, v]`` is set when the HIGH label route
    u -> v passes node ``w`` with a label above v's (``pl`` the LOW
    mirror, below). A chain passing a later member that way delivers it
    early and skips its own segment to it (``routing.path_multicast``).
    None when no route passes its target's label: always on a healthy
    fabric, whose label rule never moves beyond its target."""
    return _label_chain_matrices_cached(topo, get_cost_model(cost_model))[2]


def _dyadic_grain(*arrays) -> int | None:
    """The least power of two ``q <= _SCALE`` such that every value is a
    multiple of ``1/q`` (``None`` if there is none): sums of such values
    are exact in float32 while they stay below 2^24 / q (see the exactness
    gate in batch_support)."""
    vals = np.concatenate(
        [np.asarray(a, np.float64).reshape(-1) for a in arrays]
    )
    if not np.all(np.isfinite(vals)):
        return None
    q = 1
    while q <= _SCALE:
        scaled = vals * q
        if np.all(scaled == np.round(scaled)):
            return q
        q *= 2
    return None


def batch_support(topo: MeshGrid, algo="DPM", cost_model=None) -> _Support:
    """Can (topo, algo, cost_model) plan on the batched device path with
    the bit-identity guarantee? Returns (ok, reason) — the reason names the
    first failed gate, and callers fall back to host ``plan()`` on any.
    Broken links fail no gate: every fault pattern prices exactly."""
    a = get_algorithm(algo)
    if getattr(a, "_fn", None) not in (plan_dpm, plan_dpm_e):
        return _Support(False, f"algorithm {a.name!r} has no device twin")
    if not is_registered_algorithm(a):
        return _Support(False, f"algorithm {a.name!r} not registered")
    cm = get_cost_model(
        cost_model if cost_model is not None else a.default_cost_model
    )
    if not is_registered_cost_model(cm):
        return _Support(False, f"cost model {cm.name!r} not registered")
    # degraded fabrics pass: detours are in the price tables, and a label
    # route that passes a later chain member is in label_chain_passes
    if topo.num_nodes > MAX_ARENA_NODES:
        return _Support(
            False,
            f"{topo.num_nodes} nodes > MAX_ARENA_NODES ({MAX_ARENA_NODES})",
        )
    dist, w_uni, overhead = route_cost_matrices(topo, cm)
    from ..kernels.dpm_cost.ops import BIG

    if int(dist.max(initial=0)) * BIG + topo.num_nodes >= 2**31:
        return _Support(False, "route distances overflow the int32 rep key")
    wh, wl = label_chain_matrices(topo, cm)
    # pairs with no route (a failed router) price +inf and are never read
    w_uni = w_uni[np.isfinite(w_uni)]
    q = _dyadic_grain(w_uni, wh, wl, [overhead])
    if q is None:
        return _Support(
            False, f"cost model {cm.name!r} prices are not dyadic (f32-exact)"
        )
    # Largest value any candidate sum reaches: C_t is at most NN unicast
    # prices plus overheads; a C_p chain is at most NN pairwise label
    # routes, each priced at most max(wh, wl) (on a healthy fabric a chain
    # is label-monotone, at most NN - 1 links); the greedy merge adds the
    # costs of disjoint singles (C_t over disjoint members) plus their
    # source legs. 4 NN (max price + overhead + 1) covers all.
    top = max(w_uni.max(initial=0), wh.max(initial=0), wl.max(initial=0))
    bound = q * (4.0 * topo.num_nodes * (top + overhead + 1.0))
    if bound >= _EXACT_LIMIT:
        return _Support(False, "cost magnitudes exceed the f32-exact range")
    # edge-additivity spot check: the chain decomposition (and the per-edge
    # matrix build) assumes route_cost == sum of link_cost over the route
    nodes = topo.nodes()
    comp = components(topo)
    for v in nodes[:: max(1, len(nodes) // 8)]:
        if v == nodes[0] or comp[topo.idx(v)] != comp[0]:
            continue
        route = provider_for(topo).unicast(topo, nodes[0], v)
        edge_sum = sum(
            cm.link_cost(topo, x, y) for x, y in zip(route, route[1:])
        )
        if abs(cm.route_cost(topo, route) - edge_sum) > 1e-9:
            return _Support(
                False, f"cost model {cm.name!r} is not edge-additive"
            )
    return _Support(True, "")


# ---------------------------------------------------------------------------
# The batched planner + arena
# ---------------------------------------------------------------------------
class _Tables(NamedTuple):
    memb: np.ndarray  # int8 membership table (decode: each member's wedge)
    labels: np.ndarray  # snake labels (decode: chain order and sides)
    ph: np.ndarray | None  # label_chain_passes (decode: chain skips)
    pl: np.ndarray | None
    memb_d: object  # device copies (jax arrays)
    labels_d: object
    dist_d: object
    wuni_d: object
    wh_d: object
    wl_d: object
    ph_d: object  # label_chain_passes on the device, or None
    pl_d: object
    overhead: float


# Route kinds in a decoded worm: the unicast route, or the HIGH / LOW
# label route of a dual-path chain.
_UNI, _HIGH, _LOW = 0, 1, 2


class _RoutePool:
    """Every route the decode has expanded, as node indices in one flat
    array: route ``r = kind * NN^2 + a * NN + b`` (``_UNI`` the unicast
    route a -> b, ``_HIGH`` / ``_LOW`` the label route) is
    ``nodes[s : s + n]`` for ``s, n = span[r]``, both endpoints included
    (``s`` -1 until the route is needed). Filled lazily, each route the
    first time a decode needs it, through the topology's route provider;
    bounded by the 3 NN^2 node pairs."""

    def __init__(self, topo: MeshGrid):
        NN = topo.num_nodes
        self.topo = topo
        self.span = np.full((3 * NN * NN, 2), -1, np.int32)
        self.nodes = np.zeros(1 << 12, np.int32)
        self.size = 0

    def lookup(self, rid: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        sp = self.span[rid]
        missing = sp[:, 0] < 0
        if missing.any():
            self._fill(np.unique(rid[missing]))
            sp = self.span[rid]
        return sp[:, 0], sp[:, 1]

    def _fill(self, rids: np.ndarray) -> None:
        g = self.topo
        NN = g.num_nodes
        node = g.from_idx
        unicast = provider_for(g).unicast
        routes = []
        for r in rids.tolist():
            kind, pair = divmod(r, NN * NN)
            u, v = node(pair // NN), node(pair % NN)
            hops = (unicast(g, u, v) if kind == _UNI
                    else label_route(g, u, v, kind == _HIGH))
            routes.append([g.idx(c) for c in hops])
        lens = np.array([len(x) for x in routes], np.int32)
        end = self.size + int(lens.sum())
        if end > len(self.nodes):
            grown = np.zeros(max(end, 2 * len(self.nodes)), np.int32)
            grown[: self.size] = self.nodes[: self.size]
            self.nodes = grown
        self.nodes[self.size : end] = np.concatenate(routes)
        self.span[rids, 0] = self.size + np.cumsum(lens) - lens
        self.span[rids, 1] = lens
        self.size = end


def _rank_in_runs(keys: np.ndarray) -> np.ndarray:
    """Position of each element of an ascending key array within its run
    of equal keys."""
    return np.arange(len(keys)) - np.searchsorted(keys, keys)


class BatchPlanner:
    """Batched DPM planner over one (topology, algorithm, cost model) with
    a bounded LRU arena of decoded ``MulticastPlan``s.

    ``plan_many(requests)`` is the entry point: arena lookups first
    (canonical keys — permuted duplicate requests hit one entry), then one
    jitted ``dpm_plan_exact`` dispatch over all unique misses, then host
    decode of the partition tensors. When ``support.ok`` is False every
    miss plans through host ``plan()`` instead (same results, same arena).
    On a degraded fabric a request whose destinations the source cannot
    reach raises ``DisconnectedError``, as ``plan()`` does. Thread-safe:
    the plan server and direct callers may share an instance.
    """

    def __init__(self, topo: MeshGrid, algo="DPM", cost_model=None,
                 maxsize: int = DEFAULT_ARENA_SIZE):
        self.topo = topo
        self._algo = get_algorithm(algo)
        self._cm = get_cost_model(
            cost_model if cost_model is not None else
            self._algo.default_cost_model
        )
        self.maxsize = maxsize
        self.np_ = len(wedge_patterns(len(topo.from_idx(0))))
        self._cands = candidate_ids_for(self.np_)
        with span("repro.planner.tables"):
            self.support = batch_support(topo, self._algo, self._cm)
        # plan() segments plans on these fabrics; so does the decode
        self._segments = bool(getattr(topo, "faults", ())) or getattr(
            topo, "needs_bfs_routes", False
        )
        comp = components(topo)
        self._comp = comp.tolist() if comp.max(initial=0) > 0 else None
        self._arena: "OrderedDict[tuple, MulticastPlan]" = OrderedDict()
        self._lock = threading.Lock()
        self._tables_cached: _Tables | None = None
        self._routes: _RoutePool | None = None  # made by the first decode
        # decode constants: node index -> Coord, and per candidate the
        # wedges it holds and each wedge's place in its union
        self._coords = np.empty(topo.num_nodes, object)
        for i, c in enumerate(topo.nodes()):
            self._coords[i] = c
        width = max(map(len, self._cands))
        self._cand_wedges = np.array(  # padded by repeating the first
            [ids + ids[:1] * (width - len(ids)) for ids in self._cands])
        self._wedge_pos = np.zeros((len(self._cands), self.np_), np.int64)
        for ci, ids in enumerate(self._cands):
            self._wedge_pos[ci, list(ids)] = range(len(ids))
        self._member_at = np.zeros(0, np.int32)  # decode scratch, all -1
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._batched = 0
        self._host = 0
        self._dispatches = 0
        self._plan_s = self._lookup_s = self._dispatch_s = 0.0
        self._sync_s = self._decode_s = self._host_plan_s = 0.0
        self._segment_s = 0.0
        self._segmented = self._relays = self._array_decoded = 0
        install_gc_clock()

    # ------------------------------------------------------------- public
    def plan_many(self, requests) -> list[MulticastPlan]:
        """Plan ``[(src, dests), ...]``; returns plans in request order,
        each bit-identical to ``plan(algo, topo, src, dests, cost_model)``."""
        with self._lock:
            t0 = time.perf_counter()
            try:
                return self._plan_many_locked(list(requests))
            finally:
                self._plan_s += time.perf_counter() - t0

    def plan_one(self, src: Coord, dests) -> MulticastPlan:
        return self.plan_many([(src, dests)])[0]

    def info(self) -> ArenaInfo:
        return ArenaInfo(
            self._hits, self._misses, self.maxsize, len(self._arena),
            self._evictions, self._batched, self._host, self._dispatches,
            self._plan_s, self._lookup_s, self._dispatch_s, self._sync_s,
            self._decode_s, self._host_plan_s, self._segment_s,
            self._segmented, self._relays, self._array_decoded,
        )

    def clear(self) -> None:
        with self._lock:
            self._arena.clear()

    # ------------------------------------------------------------ internal
    def _plan_many_locked(self, requests) -> list[MulticastPlan]:
        t0 = time.perf_counter()
        with span("repro.planner.lookup"):
            keys = [
                (tuple(src), canonical_dests(dests)) for src, dests in requests
            ]
            out: list[MulticastPlan | None] = [None] * len(keys)
            missing: list[tuple] = []
            first_at: dict[tuple, int] = {}
            for i, key in enumerate(keys):
                hit = self._arena.get(key)
                if hit is not None:
                    self._arena.move_to_end(key)
                    self._hits += 1
                    out[i] = hit
                else:
                    self._misses += 1
                    if key not in first_at:
                        first_at[key] = len(missing)
                        missing.append(key)
        self._lookup_s += time.perf_counter() - t0
        if missing:
            if self.support.ok:
                if self._comp is not None:
                    self._check_reachable(missing)
                plans = self._plan_batch(missing)
                self._batched += len(missing)
            else:
                t0 = time.perf_counter()
                with span("repro.planner.host_plan"):
                    plans = [
                        plan(self._algo, self.topo, src, list(dests),
                             cost_model=self._cm)
                        for src, dests in missing
                    ]
                self._host_plan_s += time.perf_counter() - t0
                self._host += len(missing)
            for key, p in zip(missing, plans):
                self._arena[key] = p
                while len(self._arena) > self.maxsize:
                    self._arena.popitem(last=False)
                    self._evictions += 1
            for i, key in enumerate(keys):
                if out[i] is None:
                    out[i] = plans[first_at[key]]
        return out  # type: ignore[return-value]

    def _check_reachable(self, keys: list[tuple]) -> None:
        """Raise host ``plan()``'s ``DisconnectedError`` for the first
        request whose source cannot reach all of its destinations."""
        comp, idx = self._comp, self.topo.idx
        for src, dests in keys:
            c = comp[idx(src)]
            if any(comp[idx(d)] != c for d in dests):
                plan(self._algo, self.topo, src, list(dests),
                     cost_model=self._cm)

    def _tables(self) -> _Tables:
        if self._tables_cached is None:
            import jax.numpy as jnp

            with span("repro.planner.tables"):
                dist, w_uni, overhead = route_cost_matrices(
                    self.topo, self._cm)
                wh, wl = label_chain_matrices(self.topo, self._cm)
                passes = label_chain_passes(self.topo, self._cm)
                ph, pl = (None, None) if passes is None else passes
                labels = snake_labels(self.topo)
                memb = membership_table(self.topo)
                self._tables_cached = _Tables(
                    memb.astype(np.int8),  # wedge ids < 27: a quarter the bytes
                    labels,
                    ph,
                    pl,
                    jnp.asarray(memb),
                    jnp.asarray(labels),
                    jnp.asarray(dist),
                    jnp.asarray(w_uni),
                    jnp.asarray(wh),
                    jnp.asarray(wl),
                    None if ph is None else jnp.asarray(ph),
                    None if pl is None else jnp.asarray(pl),
                    float(overhead),
                )
        return self._tables_cached

    def _dispatch(self, keys: list[tuple], k: int):
        """One jitted ``dpm_plan_exact`` call over ≤ DISPATCH_CHUNK keys,
        padded to a power of two, each packed into ``k`` destination slots.
        Returns the device arrays *without* synchronizing — JAX dispatch is
        asynchronous, so the caller can keep issuing chunks (and decoding
        earlier ones) while XLA computes this one in its own threadpool —
        with the packed ``(dests, sidx)`` the decode reads."""
        import jax.numpy as jnp

        from ..kernels.dpm_cost.ops import dpm_plan_exact

        t = self._tables()
        idx = self.topo.idx
        Bp = 1 << max(0, len(keys) - 1).bit_length()
        dests = np.full((Bp, k), -1, np.int32)
        sidx = np.zeros(Bp, np.int32)
        for b, (src, ds) in enumerate(keys):
            sidx[b] = idx(src)
            dests[b, : len(ds)] = [idx(d) for d in ds]
        out = dpm_plan_exact(
            jnp.asarray(dests),
            jnp.asarray(sidx),
            t.memb_d,
            t.labels_d,
            t.dist_d,
            t.wuni_d,
            t.wh_d,
            t.wl_d,
            t.ph_d,
            t.pl_d,
            np_=self.np_,
            overhead=t.overhead,
        )
        return out, dests, sidx

    def _plan_batch(self, keys: list[tuple]) -> list[MulticastPlan]:
        # Issue every chunk's device work first (async dispatch), then
        # decode in order — chunk k's host decode overlaps chunk k+1's
        # device compute where cores allow, so the pipeline costs
        # ~max(device, decode) instead of their sum.
        import jax

        chunks = [
            keys[i : i + DISPATCH_CHUNK]
            for i in range(0, len(keys), DISPATCH_CHUNK)
        ]
        t0 = time.perf_counter()
        ks = [
            max(MIN_SLOTS,
                1 << (max(len(ds) for _, ds in ck) - 1).bit_length())
            for ck in chunks
        ]
        # run eagerly even while a caller's jit traces (EP MoE builds its
        # all-to-all schedule inside the jitted step): plans are host data
        with span("repro.planner.dispatch", k=max(ks)), \
                jax.ensure_compile_time_eval():
            outs = [self._dispatch(ck, k) for ck, k in zip(chunks, ks)]
        t1 = time.perf_counter()
        self._dispatch_s += t1 - t0
        self._dispatches += len(chunks)
        plans: list[MulticastPlan] = []
        with _collector_paused():  # the decoded plans all survive
            for ck, (out, dests, sidx) in zip(chunks, outs):
                # one bulk device->host sync per chunk
                with span("repro.planner.sync"):
                    host = [np.asarray(x) for x in out[:4]]
                t2 = time.perf_counter()
                with span("repro.planner.decode"):
                    plans.extend(self._decode_chunk(ck, *host, dests, sidx))
                t3 = time.perf_counter()
                self._sync_s += t2 - t1
                self._decode_s += t3 - t2
                t1 = t3
        return plans

    def _decode_chunk(self, keys, chosen, order, reps, modes, dests, sidx):
        """One dispatched chunk's partition tensors -> its
        ``MulticastPlan``s, by array operations over the whole chunk. This
        reimplements the host emitter (``planner._emit_dpm_partition``,
        which ``plan_dpm`` runs) step by step; ``tests/test_batch_planner.py``
        pins the two equal, worm for worm:

        * a destination's final partition is the picked candidate whose
          wedges hold it (the source is in no wedge: already delivered);
        * partitions emit by greedy pick round, then leftover singles by
          candidate index (``NO_ORDER`` sorts after every round); members
          keep the union order, wedge by wedge in the candidate, each
          wedge in destination order;
        * a DP partition of two or more members emits the worm S -> R
          continued by the chain into its larger label side (HIGH on a
          tie), then a sibling re-injected at R with the other side's
          chain; a chain visits its side in label order, one label route
          to each member, skipping a member an earlier route of the chain
          passed (only on a degraded fabric, ``_chain_visits``);
        * any other partition emits the worm S -> R, then a unicast worm
          from R to each member the head did not pass, in union order;
        * a worm is its routes joined end to end (``_RoutePool``), and
          delivers each member it serves at its first visit, as
          ``_deliveries_on`` does.

        On a segmenting fabric the kept worms are then cut at their label
        reversals (``_segment_worms``). Python then touches each plan once,
        to slice its worms out of the chunk's flat hop and delivery lists
        (``_decode``)."""
        B = len(keys)
        t = self._tables()
        NN = self.topo.num_nodes
        NC = chosen.shape[1]
        self._array_decoded += B
        # partitions in emission order: the picked nonempty candidates by
        # (plan, pick round, candidate index)
        ub, uc = np.nonzero(chosen[:B] & (reps[:B] >= 0))
        o = np.argsort((ub << 40) + order[ub, uc].astype(np.int64) * NC + uc)
        pb, pci = ub[o], uc[o]
        P = len(pb)
        if P == 0:  # nothing to deliver but the source itself
            return [self._decode(src, ds, []) for src, ds in keys]
        prep = reps[pb, pci].astype(np.int64)
        # members: the destination slots in a wedge, each in the picked
        # candidate that holds its wedge (picked candidates are disjoint)
        held = np.zeros((B, self.np_), np.int64)
        held[pb[:, None], self._cand_wedges[pci]] = np.arange(P)[:, None]
        dests = dests[:B]
        wedge = t.memb[sidx[:B, None], np.maximum(dests, 0)]
        mb, mk = np.nonzero((dests >= 0) & (wedge >= 0))
        mv = dests[mb, mk].astype(np.int64)
        mw = wedge[mb, mk]
        mg = held[mb, mw]
        lab = t.labels
        side = np.sign(lab[mv] - lab[prep[mg]])  # 0 at the representative
        cnt = np.bincount(mg, minlength=P)
        nh = np.bincount(mg, side > 0, minlength=P)
        nl = np.bincount(mg, side < 0, minlength=P)
        mu = modes[pb, pci] | (cnt < 2)
        first = np.where(nh >= nl, 1, -1)
        sibling = ~mu & (np.where(first > 0, nl, nh) > 0)
        # worms: a DP partition's head and sibling; an MU partition's head
        # and a unicast worm per other member (dropped below where the
        # head passes the member)
        nworm = np.where(mu, cnt, 1 + sibling)
        wstart = np.cumsum(nworm) - nworm
        W = int(nworm.sum())
        wg = np.repeat(np.arange(P), nworm)
        wsub = np.arange(W) - wstart[wg]
        wside = np.where((wsub > 0) & ~mu[wg], -first[wg], 0)
        wmember = np.full(W, -1, np.int64)
        kid = np.nonzero(mu[mg] & (side != 0))[0]
        kid = kid[np.argsort(
            (mg[kid] * self.np_ + self._wedge_pos[pci[mg[kid]], mw[kid]])
            * dests.shape[1] + mk[kid])]
        kid_worm = wstart[mg[kid]] + 1 + _rank_in_runs(mg[kid])
        wmember[kid_worm] = kid
        # chains: DP members off the representative, by (partition, side,
        # label in the side's direction)
        cm = np.nonzero(~mu[mg] & (side != 0))[0]
        up = side[cm] > 0
        cm = cm[np.argsort((4 * mg[cm] + 2 * up) * NN
                           + np.where(up, lab[mv[cm]], -lab[mv[cm]]))]
        up = side[cm] > 0
        chain = 2 * mg[cm] + up
        if t.ph is not None and len(cm):
            go = self._chain_visits(chain, mv[cm], up, prep[mg[cm]])
            cm, chain, up = cm[go], chain[go], up[go]
        cpos = _rank_in_runs(chain)
        cv = mv[cm]
        on_head = side[cm] == first[mg[cm]]
        # route segments: (worm, place in worm, from, to, kind)
        seg = [np.concatenate(x) for x in zip(
            (wstart, np.zeros(P, np.int64), sidx[pb].astype(np.int64), prep,
             np.full(P, _UNI)),
            (wstart[mg[cm]] + ~on_head, cpos + on_head,
             np.where(cpos > 0, np.concatenate((cv[:1], cv[:-1])),
                      prep[mg[cm]]), cv,
             np.where(up, _HIGH, _LOW)),
            (kid_worm, np.zeros(len(kid), np.int64), prep[mg[kid]], mv[kid],
             np.full(len(kid), _UNI)),
        )]
        nseg = np.bincount(seg[0], minlength=W)
        sstart = np.cumsum(nseg) - nseg
        at = sstart[seg[0]] + seg[1]
        place, src_n, dst_n, kind = (np.empty_like(x) for x in seg[1:])
        for out_, x in zip((place, src_n, dst_n, kind), seg[1:]):
            out_[at] = x
        # expand: each worm's first route whole, later ones past their
        # first node (the previous route's last)
        if self._routes is None:
            self._routes = _RoutePool(self.topo)
        st, ln = self._routes.lookup((kind * NN + src_n) * NN + dst_n)
        later = place > 0
        st, ln = st + later, ln - later
        off = np.cumsum(ln) - ln
        hops = self._routes.nodes[np.repeat(st - off, ln)
                                  + np.arange(int(ln.sum()))]
        wlen = np.add.reduceat(ln, sstart)
        woff = np.cumsum(wlen) - wlen
        # deliveries: first visits of the members each worm serves — the
        # head all of its partition, a sibling its side, a unicast worm
        # its member. ``_member_at`` maps (plan, node) to the member.
        wb = pb[wg]
        cell = mb * NN + mv
        if len(self._member_at) < B * NN:
            self._member_at = np.full(B * NN, -1, np.int32)
        self._member_at[cell] = np.arange(len(mv))
        m = self._member_at[np.repeat(wb * NN, wlen) + hops]
        self._member_at[cell] = -1
        hp = np.nonzero(m >= 0)[0]
        m = m[hp]
        hw = np.searchsorted(woff, hp, side="right") - 1
        ws, wm = wside[hw], wmember[hw]
        ok = ((mg[m] == wg[hw]) & ((ws == 0) | (side[m] == ws))
              & ((wm < 0) | (m == wm)))
        hp, hw, m = hp[ok], hw[ok], m[ok]
        _, firsts = np.unique(hw * NN + hops[hp], return_index=True)
        firsts.sort()
        dpos, dw = hp[firsts], hw[firsts]
        dcnt = np.bincount(dw, minlength=W)
        doff = np.cumsum(dcnt) - dcnt
        # an MU partition's unicast worms go only to the members its head
        # did not pass
        head = (wsub[hw] == 0) & mu[wg[hw]]
        passed = np.zeros(len(mv), bool)
        passed[m[head]] = True
        keep = wmember < 0
        keep[kid_worm] = ~passed[kid]
        kept = np.nonzero(keep)[0]
        per_plan = np.bincount(wb[kept], minlength=B)
        local = np.cumsum(keep) - 1 - (np.cumsum(per_plan) - per_plan)[wb]
        parent = np.where(wsub > 0, local[wstart[wg]], -1)[kept]
        if self._segments:
            (hops, a, b, dnode, c, d, parent,
             per_plan) = self._segment_worms(hops, woff, wlen, dpos, dw, keep,
                                             wb, parent, B)
        else:
            a, b = woff[kept], (woff + wlen)[kept]
            dnode, c, d = hops[dpos], doff[kept], (doff + dcnt)[kept]
        # Python: one slice of the flat hop and delivery lists per worm
        H = self._coords[hops].tolist()
        D = self._coords[dnode].tolist()
        worms = [
            PacketPath(H[a:b], D[c:d], None if p < 0 else p)
            for a, b, c, d, p in zip(a.tolist(), b.tolist(), c.tolist(),
                                     d.tolist(), parent.tolist())
        ]
        out, lo = [], 0
        for (src, ds), hi in zip(keys, np.cumsum(per_plan).tolist()):
            out.append(self._decode(src, ds, worms[lo:hi]))
            lo = hi
        return out

    def _segment_worms(self, hops, woff, wlen, dpos, dw, keep, wb, parent,
                       B):
        """Cut the chunk's kept worms into label-monotone segments, as
        ``planner.segment_plan_for_faults`` cuts every plan ``plan()``
        returns on a degraded or BFS-routed fabric, with array operations
        over the whole chunk; ``tests/test_batch_planner.py`` pins the two
        equal, worm for worm:

        * a worm ends a run where the label direction of its next hop
          reverses (a step to a higher label is up, any other down); the
          next run starts at that node, so consecutive segments share it;
        * a delivery goes to the segment with ``s < pos <= e``, a shared
          node's to the earlier one (a worm never delivers its own first
          node, so ``pos`` is the first visit past it);
        * a later segment's parent is the segment before it; a child
          worm's first segment's parent is the segment of its parent worm
          that holds the parent's first visit (past its first node) of the
          child's first node, its partition's representative;
        * a worm of one node is one segment, its deliveries kept.

        Returns the segments end to end, in the plans' worm order: their
        hops, start and end in them, delivered nodes with the start and
        end of each segment's, parents (plan-local, -1 none), and the
        number of segments of each plan."""
        t0 = time.perf_counter()
        with span("repro.planner.segment"):
            kept = np.nonzero(keep)[0]
            K = len(kept)
            wk = wb[kept]
            kl = wlen[kept]
            ko = np.cumsum(kl) - kl
            n = int(kl.sum())
            shift = np.zeros(len(wlen), np.int64)
            shift[kept] = woff[kept] - ko
            hops = hops[np.repeat(shift[kept], kl) + np.arange(n)]
            lab = self._tables().labels[hops]
            head = np.zeros(n, bool)
            head[ko] = True
            cut = np.zeros(n, bool)
            up = lab[1:] > lab[:-1]
            cut[1:-1] = up[1:] != up[:-1]
            cut &= ~head
            cut[ko + kl - 1] = False
            # a cut node ends one segment and starts the next: laid out
            # twice, the second copy a segment's first node
            twice = 1 + cut
            at = np.cumsum(twice) - twice
            starts = np.zeros(n + int(cut.sum()), bool)
            starts[at[head]] = True
            starts[at[cut] + 1] = True
            # each hop's segment, a cut node's the earlier of its two
            seg_of = (np.cumsum(starts) - 1)[at]
            sstart = np.nonzero(starts)[0]
            S = len(sstart)
            fs = seg_of[ko]
            nseg = seg_of[ko + kl - 1] - fs + 1
            splan = np.repeat(wk, nseg)
            per_plan = np.bincount(splan, minlength=B)
            # parents: the segment before, or for a child worm's first
            # segment the parent worm's at its first visit of the child's
            # first node
            sparent = np.arange(S) - 1
            child = np.nonzero(parent >= 0)[0]
            nk = np.bincount(wk, minlength=B)
            pk = (np.cumsum(nk) - nk)[wk[child]] + parent[child]
            tgt = np.full(K, -1, np.int64)
            tgt[pk] = hops[ko[child]]
            hit = np.nonzero((hops == np.repeat(tgt, kl)) & ~head)[0]
            hk = np.searchsorted(ko, hit, side="right") - 1
            new = np.ones(len(hit), bool)
            new[1:] = hk[1:] != hk[:-1]
            visit = np.zeros(K, np.int64)
            visit[hk[new]] = hit[new]
            sparent[fs] = -1
            sparent[fs[child]] = seg_of[visit[pk]]
            sparent = np.where(
                sparent >= 0,
                sparent - (np.cumsum(per_plan) - per_plan)[splan], -1)
            # deliveries of the kept worms, by segment
            dk = keep[dw]
            dp = dpos[dk] - shift[dw[dk]]
            dcnt = np.bincount(seg_of[dp], minlength=S)
            doff = np.cumsum(dcnt) - dcnt
            self._relays += S - K
            self._segmented += int(np.count_nonzero(
                np.bincount(wk, nseg - 1, minlength=B)))
            out = (np.repeat(hops, twice), sstart,
                   np.append(sstart[1:], len(starts)), hops[dp], doff,
                   doff + dcnt, sparent, per_plan)
        self._segment_s += time.perf_counter() - t0
        return out

    def _chain_visits(self, chain, node, high, rep) -> np.ndarray:
        """Which members of the label-sorted chains (``chain`` ids, runs in
        visit order) the chain routes to: a member an earlier route of
        its chain passed is delivered there and skipped, as
        ``routing.path_multicast`` skips it. ``label_chain_passes`` holds
        the nodes each pairwise route passes beyond its target; a loop
        over the chain positions, each step over every chain at once."""
        t = self._tables()
        new = np.ones(len(chain), bool)
        new[1:] = chain[1:] != chain[:-1]
        cid = np.cumsum(new) - 1
        pos = _rank_in_runs(chain)
        C, L = int(cid[-1]) + 1, int(pos.max()) + 1
        nodes = np.full((C, L), -1, np.int64)
        nodes[cid, pos] = node
        hi = np.zeros(C, bool)
        hi[cid] = high
        cur = np.zeros(C, np.int64)
        cur[cid[new]] = rep[new]
        passed = np.zeros((C, L), bool)
        for j in range(L):
            tj = nodes[:, j]
            go = (tj >= 0) & ~passed[:, j]
            r = np.nonzero(go)[0]
            later = nodes[r, j + 1:]
            if later.size:
                a, b, w = cur[r, None], tj[r, None], np.maximum(later, 0)
                word = np.where(hi[r, None], t.ph[a, b, w >> 5],
                                t.pl[a, b, w >> 5])
                passed[r, j + 1:] |= (((word >> (w & 31)) & 1) == 1) & (
                    later >= 0)
            cur = np.where(go, tj, cur)
        return ~passed[cid, pos]

    def _decode(self, src, dests, paths) -> MulticastPlan:
        """One plan of a decoded chunk around its worms: the decode's only
        per-plan step."""
        return MulticastPlan(self._algo.name, src, list(dests), paths)


# ---------------------------------------------------------------------------
# Module-level planner registry (the bulk-planning backend consumers use)
# ---------------------------------------------------------------------------
_PLANNERS: "OrderedDict[tuple, BatchPlanner]" = OrderedDict()
_MAX_PLANNERS = 64
_PLANNERS_LOCK = threading.Lock()


def planner_for(topo: MeshGrid, algo="DPM", cost_model=None,
                maxsize: int = DEFAULT_ARENA_SIZE) -> BatchPlanner:
    """The shared ``BatchPlanner`` for (topo, algo, cost-model) — one arena
    per combination, so every consumer (simulator drivers, xsim compile,
    dist schedule builders, trace replay, the plan server) reuses plans the
    others already decoded."""
    a = get_algorithm(algo)
    cm = get_cost_model(
        cost_model if cost_model is not None else a.default_cost_model
    )
    key = (topo, a.name, cm.name if a.cost_sensitive else "")
    with _PLANNERS_LOCK:
        pl = _PLANNERS.get(key)
        if pl is not None:
            _PLANNERS.move_to_end(key)
            return pl
        pl = BatchPlanner(topo, a, cm, maxsize=maxsize)
        _PLANNERS[key] = pl
        while len(_PLANNERS) > _MAX_PLANNERS:
            _PLANNERS.popitem(last=False)
        return pl


def bulk_plan(topo: MeshGrid, requests, algo="DPM",
              cost_model=None) -> list[MulticastPlan]:
    """Plan a request list ``[(src, dests), ...]`` through the shared plan
    arena: one jitted device dispatch for all arena misses where the
    batched path is supported, host ``plan()`` otherwise. Always returns
    plans bit-identical to per-request ``plan()`` calls, in request order.

    This is the bulk-planning backend ``WormholeSim.add_requests``,
    ``xsim.compile_workload``, ``dist.schedule_multicasts`` and the trace
    replay drivers route through.
    """
    requests = list(requests)
    if not requests:
        return []
    a = get_algorithm(algo)
    cm = get_cost_model(
        cost_model if cost_model is not None else a.default_cost_model
    )
    if not is_registered_algorithm(a) or (
        a.cost_sensitive and not is_registered_cost_model(cm)
    ):
        # unregistered instances cannot key an arena (the name would not
        # resolve back); plan uncached exactly as plan() itself would
        return [
            plan(a, topo, src, list(dests), cost_model=cm)
            for src, dests in requests
        ]
    return planner_for(topo, a, cm).plan_many(requests)


def arena_info() -> ArenaCacheInfo:
    """Aggregate stats over every live arena, shaped like
    ``planner.plan_cache_info()`` (hits/misses/maxsize/currsize + per-
    (algo, cost-model) attribution)."""
    hits = misses = maxsize = currsize = 0
    by_key: dict[tuple[str, str], dict[str, int]] = {}
    with _PLANNERS_LOCK:
        items = list(_PLANNERS.items())
    for (_, algo, cmk), pl in items:
        i = pl.info()
        hits += i.hits
        misses += i.misses
        maxsize += i.maxsize
        currsize += i.currsize
        st = by_key.setdefault(
            (algo, cmk), {"hits": 0, "misses": 0, "evictions": 0}
        )
        st["hits"] += i.hits
        st["misses"] += i.misses
        st["evictions"] += i.evictions
    return ArenaCacheInfo(hits, misses, maxsize, currsize, by_key)


def arena_clear() -> None:
    """Drop every planner (and its arena). Also the registry-mutation hook:
    arenas key plans by algorithm/cost-model *name*, so a re-registered
    name must not serve stale plans — same contract as the plan cache."""
    with _PLANNERS_LOCK:
        _PLANNERS.clear()


on_registry_change(arena_clear)
