"""Plain multicast planners on a healthy 2-D mesh under hop counting.

The benchmark's own reference for the paper's MU, MP, NMP and DPM
(arXiv:2108.00566, Algorithm 1 with Definitions 1-3), written from the
paper's rules with nothing taken from the program under test. A plan is
a list of ``(hops, deliveries, parent)`` triples: ``hops`` the node
sequence of one worm, ``deliveries`` the nodes that absorb a copy, in
path order, and ``parent`` the index of the worm whose arrival at
``hops[0]`` releases this one (DPM's re-injection at the representative),
or None.

Conventions that fix a plan exactly (they are the paper's, with its
tie-breaks made explicit): destination sets are planned sorted; labels are
the boustrophedon snake; XY routes move along x first; the label routing
function steps to the in-bounds neighbour (+x, -x, +y, -y order) with the
largest label not above the target (high channel) or the smallest not
below it (low channel); DPM's representative is the nearest destination,
ties to the smaller label; merge savings tie to fewer, then lower-indexed,
partitions; MU mode wins a tie with dual-path.
"""
from __future__ import annotations

# wedge order P0..P7, counter-clockwise from the upper-right quadrant
RING = ((1, 1), (0, 1), (-1, 1), (-1, 0), (-1, -1), (0, -1), (1, -1), (1, 0))
CANDIDATES = tuple(
    [(i,) for i in range(8)]
    + [(i, (i + 1) % 8) for i in range(8)]
    + [(i, (i + 1) % 8, (i + 2) % 8) for i in range(8)]
)


class Mesh:
    """An ``n`` x ``n`` mesh: labels, distances and the routing functions."""

    def __init__(self, n: int):
        self.n = n

    def label(self, c) -> int:
        x, y = c
        return y * self.n + (x if y % 2 == 0 else self.n - x - 1)

    @staticmethod
    def dist(a, b) -> int:
        return abs(a[0] - b[0]) + abs(a[1] - b[1])

    def neighbours(self, c):
        x, y = c
        for dx, dy in ((1, 0), (-1, 0), (0, 1), (0, -1)):
            if 0 <= x + dx < self.n and 0 <= y + dy < self.n:
                yield (x + dx, y + dy)

    @staticmethod
    def xy(a, b) -> list:
        path = [tuple(a)]
        x, y = a
        while x != b[0]:
            x += 1 if b[0] > x else -1
            path.append((x, y))
        while y != b[1]:
            y += 1 if b[1] > y else -1
            path.append((x, y))
        return path

    def step(self, cur, target, high: bool):
        lt = self.label(target)
        best = None
        for v in self.neighbours(cur):
            lv = self.label(v)
            if high and lv <= lt and (best is None or lv > best[0]):
                best = (lv, v)
            if not high and lv >= lt and (best is None or lv < best[0]):
                best = (lv, v)
        return best[1]

    def chain(self, src, dests, high: bool) -> list:
        """Path-based multicast: visit ``dests`` in label order, a
        destination passed on the way counting as visited."""
        pending = sorted((d for d in dests if d != src), key=self.label,
                         reverse=not high)
        path = [src]
        while pending:
            cur = self.step(path[-1], pending[0], high)
            path.append(cur)
            pending = [d for d in pending if d != cur]
        return path

    def tour(self, src, dests) -> list:
        """NMP's tour: XY legs to the nearest remaining destination (ties
        to the smaller row-major index)."""
        path, cur = [src], src
        pending = [d for d in dests if d != src]
        while pending:
            nxt = min(pending, key=lambda d: (self.dist(cur, d),
                                              d[1] * self.n + d[0]))
            leg = self.xy(cur, nxt)
            path.extend(leg[1:])
            cur = nxt
            entered = set(leg[1:])
            pending = [d for d in pending if d not in entered]
        return path


def _on(path, dests) -> list:
    seen, out = set(), []
    for c in path:
        if c in dests and c not in seen:
            seen.add(c)
            out.append(c)
    return out


def _worm(path, dests, parent=None):
    return (tuple(path), tuple(_on(path, set(dests))), parent)


def _groups(g: Mesh, src, dests):
    ls, sx = g.label(src), src[0]
    hi = [d for d in dests if g.label(d) > ls]
    lo = [d for d in dests if g.label(d) < ls]
    return (([d for d in hi if d[0] < sx], True),
            ([d for d in hi if d[0] >= sx], True),
            ([d for d in lo if d[0] < sx], False),
            ([d for d in lo if d[0] >= sx], False))


def plan_mu(g: Mesh, src, dests) -> list:
    return [(tuple(g.xy(src, d)), (d,), None) for d in dests]


def plan_mp(g: Mesh, src, dests) -> list:
    return [_worm(g.chain(src, grp, high), grp)
            for grp, high in _groups(g, src, dests) if grp]


def plan_nmp(g: Mesh, src, dests) -> list:
    return [_worm(g.tour(src, grp), grp)
            for grp, _ in _groups(g, src, dests) if grp]


def _dual_path_cost(g: Mesh, rep, rest) -> int:
    lr = g.label(rep)
    cost = 0
    for side, high in (([d for d in rest if g.label(d) > lr], True),
                       ([d for d in rest if g.label(d) < lr], False)):
        if side:
            cost += len(g.chain(rep, side, high)) - 1
    return cost


def dpm_partitions(g: Mesh, src, dests, dual_path: bool = True) -> list:
    """Algorithm 1: ``[(members, representative, mode), ...]`` in the
    order the partitions are emitted. ``dual_path=False`` prices C_t
    alone (every partition in MU mode): the benchmark's control, a
    shortcut that breaks the guarantee of Algorithm 1's plans."""
    parts = [[] for _ in RING]
    for d in dests:
        sign = ((d[0] > src[0]) - (d[0] < src[0]),
                (d[1] > src[1]) - (d[1] < src[1]))
        if sign != (0, 0):
            parts[RING.index(sign)].append(d)
    cand = {}
    for ids in CANDIDATES:
        members = [d for i in ids for d in parts[i]]
        if not members:
            cand[ids] = (members, None, "MU", 0)
            continue
        rep = min(members, key=lambda d: (g.dist(src, d), g.label(d)))
        rest = [d for d in members if d != rep]
        c_t = sum(g.dist(rep, d) for d in rest)
        c_p = _dual_path_cost(g, rep, rest) if dual_path else c_t + 1
        cost = min(c_t, c_p) + g.dist(src, rep)
        cand[ids] = (members, rep, "MU" if c_t <= c_p else "DP", cost)
    saving = {
        ids: max(0, sum(cand[(i,)][3] for i in ids) - cand[ids][3])
        for ids in CANDIDATES if len(ids) > 1 and cand[ids][0]
    }
    chosen = []
    while True:
        best = None
        for ids, a in saving.items():
            if a > 0 and (best is None or a > best[0] or (
                    a == best[0] and (len(ids), ids) < (len(best[1]), best[1]))):
                best = (a, ids)
        if best is None:
            break
        chosen.append(best[1])
        for ids in saving:
            if set(ids) & set(best[1]):
                saving[ids] = 0
    covered = {i for ids in chosen for i in ids}
    chosen += [(i,) for i in range(8) if i not in covered and parts[i]]
    return [cand[ids][:3] for ids in chosen]


def plan_dpm(g: Mesh, src, dests, dual_path: bool = True) -> list:
    worms = []
    for members, rep, mode in dpm_partitions(g, src, dests, dual_path):
        if not members:
            continue
        head = g.xy(src, rep)
        rest = [d for d in members if d != rep]
        if mode == "DP" and rest:
            lr = g.label(rep)
            hi = [d for d in rest if g.label(d) > lr]
            lo = [d for d in rest if g.label(d) < lr]
            (first, fh), (second, sh) = (
                ((hi, True), (lo, False)) if len(hi) >= len(lo)
                else ((lo, False), (hi, True)))
            tail = g.chain(rep, first, fh) if first else [rep]
            parent = len(worms)
            worms.append(_worm(head + tail[1:], members))
            if second:
                worms.append(_worm(g.chain(rep, second, sh), second, parent))
        else:
            parent = len(worms)
            worms.append(_worm(head, members))
            got = set(worms[-1][1])
            worms += [(tuple(g.xy(rep, d)), (d,), parent)
                      for d in rest if d not in got]
    return worms


PLANNERS = {"MU": plan_mu, "MP": plan_mp, "NMP": plan_nmp, "DPM": plan_dpm}


def plan(algo: str, n: int, src, dests) -> list:
    """The reference plan of one instance (destinations sorted, unique)."""
    dests = sorted({tuple(d) for d in dests})
    return PLANNERS[algo](Mesh(n), tuple(src), dests)
