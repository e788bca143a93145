"""Plain flit-level wormhole simulator: the benchmark's reference for xsim.

The paper's router (arXiv:2108.00566, Table I), cycle by cycle, written
for clarity over speed: a worm with route ``[n0 .. nk]`` moves its flits
through stages, stage ``i`` being the input FIFO at ``n(i+1)`` fed by link
``(n(i), n(i+1))``. Each directed link has ``vcs`` high-channel and ``vcs``
low-channel virtual channels of ``depth`` flits; a hop takes the high class
iff the snake label rises on it. The header takes a free VC of its class,
the body follows on it, and the tail frees it. One flit crosses a link per
cycle, the oldest enqueue time winning (then packet id, then flit index);
one flit ejects per node per cycle. A destination receives its copy when
the tail reaches it. A child worm (DPM's re-injection at its
representative) is released, on the node's relay lane, the cycle after its
parent's header first reaches that node.

Input is reference plans (``bench.ref.planner``); output is the conserved
counts the simulated hardware would show.
"""
from __future__ import annotations

from collections import deque


def simulate(n: int, requests, *, vcs: int, depth: int, flits: int,
             cycles: int, window: tuple[int, int]) -> dict:
    """``requests`` is ``[(time, plan), ...]``; runs at most ``cycles``
    cycles, stopping early once every worm has finished."""
    lab = lambda c: c[1] * n + (c[0] if c[1] % 2 == 0 else n - c[0] - 1)
    pk = []  # [hops, deliveries, t_enq, parent_pid]
    for t, plan in requests:
        ids = []  # worm index in the plan -> packet id (a 1-node worm has none)
        for hops, dl, parent in plan:
            ids.append(None if len(hops) == 1 else len(pk))
            if len(hops) > 1:
                pk.append([hops, set(dl), t, None if parent is None
                           else ids[parent]])
    P = len(pk)
    sent = [0] * P
    head_stage = [-1] * P
    vc_held = [dict() for _ in range(P)]
    header_at = [dict() for _ in range(P)]
    delivered = [dict() for _ in range(P)]
    done = [False] * P
    pending = set(range(P))
    active = set()
    fifos: dict = {}
    owner: dict = {}
    lanes: dict = {}
    link_flits: dict = {}
    st = dict(flit_link_traversals=0, buffer_writes=0, buffer_reads=0,
              arbitrations=0, ni_flits=0, packets_created=0,
              packets_finished=0)
    lat = []

    def fifo(link):
        f = fifos.get(link)
        if f is None:
            f = fifos[link] = [deque() for _ in range(2 * vcs)]
        return f

    now = 0
    while now < cycles:
        for pid in sorted(pending):
            hops, _, t, parent = pk[pid]
            if t > now:
                continue
            if parent is not None:
                h = header_at[parent].get(hops[0])
                if h is None or h >= now:
                    continue
            lanes.setdefault((hops[0], parent is not None), deque()).append(pid)
            st["packets_created"] += 1
            pending.discard(pid)
            active.add(pid)
        cand: dict = {}
        for q in lanes.values():
            if q and sent[q[0]] < flits:
                pid = q[0]
                link = (pk[pid][0][0], pk[pid][0][1])
                cand.setdefault(link, []).append((pk[pid][2], pid, sent[pid], -1))
        for link, f in fifos.items():
            for q in f:
                if q:
                    pid, fid, stage = q[0]
                    hops = pk[pid][0]
                    if stage + 1 < len(hops) - 1:
                        nxt = (hops[stage + 1], hops[stage + 2])
                        cand.setdefault(nxt, []).append(
                            (pk[pid][2], pid, fid, stage))
        for link, reqs in cand.items():
            reqs.sort()
            st["arbitrations"] += len(reqs)
            f = fifo(link)
            high = lab(link[1]) > lab(link[0])
            for _, pid, fid, frm in reqs:
                hops = pk[pid][0]
                to = frm + 1
                if fid == 0:
                    lo = 0 if high else vcs
                    vc = next((i for i in range(lo, lo + vcs)
                               if (link, i) not in owner), None)
                    if vc is None:
                        continue
                    owner[(link, vc)] = pid
                    vc_held[pid][to] = vc
                    head_stage[pid] = to
                else:
                    vc = vc_held[pid].get(to)
                    if vc is None or len(f[vc]) >= depth:
                        continue
                if frm == -1:
                    sent[pid] += 1
                    st["ni_flits"] += 1
                    if sent[pid] == flits:
                        lanes[(hops[0], pk[pid][3] is not None)].popleft()
                else:
                    prev = (hops[frm], hops[frm + 1])
                    svc = vc_held[pid][frm]
                    fifo(prev)[svc].popleft()
                    st["buffer_reads"] += 1
                    if fid == flits - 1:
                        owner.pop((prev, svc), None)
                        del vc_held[pid][frm]
                f[vc].append((pid, fid, to))
                st["buffer_writes"] += 1
                st["flit_link_traversals"] += 1
                link_flits[link] = link_flits.get(link, 0) + 1
                node = hops[to + 1]
                if fid == 0 and node not in header_at[pid]:
                    header_at[pid][node] = now
                if (fid == flits - 1 and node in pk[pid][1]
                        and node not in delivered[pid]):
                    delivered[pid][node] = now
                    if window[0] <= pk[pid][2] < window[1]:
                        lat.append(now - pk[pid][2])
                break
        ej: dict = {}
        for link, f in fifos.items():
            for vc, q in enumerate(f):
                if q:
                    pid, fid, stage = q[0]
                    if stage + 1 == len(pk[pid][0]) - 1:
                        ej.setdefault(link[1], []).append(
                            (pk[pid][2], pid, fid, stage, link, vc))
        for reqs in ej.values():
            _, pid, fid, stage, link, vc = min(reqs)
            fifos[link][vc].popleft()
            st["buffer_reads"] += 1
            st["ni_flits"] += 1
            if fid == flits - 1:
                owner.pop((link, vc), None)
                vc_held[pid].pop(stage, None)
                if (not vc_held[pid] and sent[pid] >= flits
                        and head_stage[pid] == len(pk[pid][0]) - 2
                        and not done[pid]):
                    done[pid] = True
                    active.discard(pid)
                    st["packets_finished"] += 1
        now += 1
        if not pending and not active:
            break
    st["cycles"] = now
    st["latencies"] = sorted(lat)
    st["delivered"] = {pid: set(d) for pid, d in enumerate(delivered)}
    st["link_flits"] = link_flits
    return st
