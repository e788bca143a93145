"""DPM under hop counting on a 2-D mesh with broken links.

The benchmark's own reference for the degraded-mesh cells: the paper's
Algorithm 1 (arXiv:2108.00566, Definitions 1-3) run over routes that
detour around permanently broken links, and its worms cut into
label-monotone segments, written from the rules below with nothing taken
from the program under test. Algorithm 1, its tie-breaks and the
emission of worms are ``bench/ref/planner.py``'s: ``FaultyMesh`` replaces
the three routing functions that planner calls (``dist``, ``xy``,
``step``), so the same code plans over the degraded routes.

Rules on the degraded mesh (a broken link is dead in both directions):

* distance is the BFS hop count over live links; a destination the
  source cannot reach raises ``KeyError``;
* unicast (the S->R leg and every multiple-unicast worm) is the XY route
  when it crosses no broken link, else a BFS shortest path, walked back
  from the destination: among the neighbours one hop nearer the source,
  the one whose CRC-32 of ``repr((flow, node, neighbour))`` is least,
  with ``flow`` the CRC-32 of ``repr((src, dst))``;
* the label step takes, among live neighbours no farther (in BFS hops)
  from the target, the one that advances the label furthest without
  passing the target's label (high channel; the mirror for low); if
  there is none, it takes the first neighbour (+x, -x, +y, -y) one BFS
  hop nearer the target;
* a chain visits its members in label order, and a member passed on the
  way counts as visited (the healthy reference's rule, unchanged);
* every worm of the plan is then cut at each reversal of label
  direction into maximal label-monotone segments, so that each crosses
  links of one virtual-channel class; a segment after the first is the
  child of the one before it, the first keeps the original parent (the
  segment of the parent that first enters its start node), and each
  delivery stays with the segment that enters it.

A mesh with no broken link is planned by the healthy rules alone.
Departures from the paper, which plans on a healthy mesh: distances,
representatives, C_t, C_p and the S->R leg are all priced over the
detoured routes; and the label rule may leave the monotone label order
(a BFS hop), which is why worms are segmented. The tie-break among equal
BFS paths spreads flows over them; a first-predecessor rule would give
other, equally short, detours.
"""
from __future__ import annotations

import zlib
from collections import deque

from bench.ref import planner as healthy


class FaultyMesh(healthy.Mesh):
    """An ``n`` x ``n`` mesh whose ``broken`` links carry nothing."""

    def __init__(self, n: int, broken):
        super().__init__(n)
        self.broken = {frozenset(map(tuple, link)) for link in broken}
        self._hops: dict = {}

    def neighbours(self, c):
        for v in super().neighbours(c):
            if frozenset((tuple(c), v)) not in self.broken:
                yield v

    def hops_from(self, a) -> dict:
        """BFS hop count from ``a`` to every node it reaches."""
        out = self._hops.get(a)
        if out is None:
            out, q = {a: 0}, deque([a])
            while q:
                u = q.popleft()
                for v in self.neighbours(u):
                    if v not in out:
                        out[v] = out[u] + 1
                        q.append(v)
            self._hops[a] = out
        return out

    def dist(self, a, b) -> int:
        return self.hops_from(tuple(a))[tuple(b)]

    def xy(self, a, b) -> list:
        a, b = tuple(a), tuple(b)
        path = healthy.Mesh.xy(a, b)
        if all(frozenset(h) not in self.broken for h in zip(path, path[1:])):
            return path
        near = self.hops_from(a)
        flow = zlib.crc32(repr((a, b)).encode())
        back = [b]
        while back[-1] != a:
            u = back[-1]
            back.append(min(
                (v for v in self.neighbours(u) if near.get(v) == near[u] - 1),
                key=lambda v: zlib.crc32(repr((flow, u, v)).encode()),
            ))
        return back[::-1]

    def step(self, cur, target, high: bool):
        far = self.hops_from(tuple(target))
        lc, lt, dc = self.label(cur), self.label(target), far[tuple(cur)]
        best = None
        for v in self.neighbours(cur):
            lv = self.label(v)
            if far[v] > dc:
                continue
            if high and lc < lv <= lt and (best is None or lv > best[0]):
                best = (lv, v)
            if not high and lt <= lv < lc and (best is None or lv < best[0]):
                best = (lv, v)
        if best is not None:
            return best[1]
        return next(v for v in self.neighbours(cur) if far[v] == dc - 1)


def _runs(g: FaultyMesh, hops) -> list:
    """Maximal label-monotone runs of a hop sequence, as inclusive index
    ranges sharing their boundary nodes."""
    runs, start, up = [], 0, None
    for i in range(1, len(hops)):
        rising = g.label(hops[i]) > g.label(hops[i - 1])
        if up is not None and rising != up:
            runs.append((start, i - 1))
            start = i - 1
        up = rising
    return runs + [(start, len(hops) - 1)]


def segment(g: FaultyMesh, worms: list) -> list:
    """Cut every worm into label-monotone segments (module docstring)."""
    runs = [_runs(g, hops) for hops, _, _ in worms]
    first, at = [], 0
    for r in runs:
        first.append(at)
        at += len(r)

    def entering(w: int, node) -> int:
        hops = worms[w][0]
        pos = hops.index(node, 1)
        return first[w] + next(j for j, (s, e) in enumerate(runs[w])
                               if s < pos <= e)

    out = []
    for w, (hops, dl, parent) in enumerate(worms):
        head = None if parent is None else entering(parent, hops[0])
        if len(hops) == 1:
            out.append((hops, dl, head))
            continue
        pos = sorted((hops.index(d, 1), d) for d in dl)
        for j, (s, e) in enumerate(runs[w]):
            out.append((tuple(hops[s:e + 1]),
                        tuple(d for p, d in pos if s < p <= e),
                        head if j == 0 else first[w] + j - 1))
    return out


def plan(g: FaultyMesh, src, dests) -> list:
    """The reference DPM plan of one instance (destinations sorted,
    unique) on the degraded mesh ``g``. A mesh with no broken link is the
    healthy mesh, planned by the healthy rules and not segmented."""
    dests = sorted({tuple(d) for d in dests})
    if not g.broken:
        return healthy.plan_dpm(healthy.Mesh(g.n), tuple(src), dests)
    return segment(g, healthy.plan_dpm(g, tuple(src), dests))
