"""Reference (jnp) fused wormhole cycle over packed router-centric planes.

This is the single source of truth for one simulated NoC cycle — the Pallas
kernel in ``noc_cycle.py`` runs the *same* ``cycle_core`` inside its inner
``fori_loop``, so the two backends are bit-identical by construction.

State layout (DESIGN.md §8). Instead of the old per-worm slot pool
(``SlotState``: ``sfpos[K, F]`` + two segmented-min scatter rounds per
cycle), state lives where the hardware keeps it — in the routers:

* ``fowner[L, W]``  packet id owning VC FIFO ``(link, vc)`` (-1 free);
                    ``W = 2V`` VCs per directed link, vcs ``[0, V)`` are
                    class HIGH(0), ``[V, 2V)`` class LOW(1).
* ``fstage[L, W]``  int16 — the owner's route stage this FIFO serves.
* ``fhead[L, W]``   int8 — flit id of the FIFO's front (FIFOs hold the
                    contiguous flit run ``[fhead, fhead + fcount)``).
* ``fcount[L, W]``  int8 — flits resident (0 while the run is in transit).
* ``lpid/lsent/lptr[2NN]`` NI lane fronts: current injecting packet, flits
                    already injected, and the root-lane static-order cursor.
* ``crtime[C]``     cycle each DPM child becomes releasable (-1 pending) —
                    set by the parent header's arrival event on the child's
                    ``watch_link``; ``ctaken`` marks consumed children.
* ``inflight/ctr``  scalar counters (same event semantics as the host sim).

Why this layout is fast *and* fuses: every flit that can move is the front
of exactly one FIFO (or NI lane), and the flits competing for node ``v``'s
output links all sit in ``v``'s input FIFOs — a static ``node_ports[NN,
4W+2]`` table. Both arbitration rounds therefore reduce to a dense masked
min over that table, and winner masks map *back* to FIFO planes through the
static ``cand_node``/``cand_port`` inverse — gathers only, no scatters, no
segmented-min, no slot allocation (capacity is structural: a worm holds a
VC or a lane front). The only scatter left in the whole engine is the
(L,)-sized delivery-time recording, which the Pallas backend moves out of
the kernel entirely via a packed per-cycle arrival-event row.

Decision rules are the host simulator's, unchanged from the old engine:
admissibility from start-of-cycle state, (enqueue, pid, fid) age keys, one
winner per directed link, ejection arbitrated per node on post-move state,
a freed VC re-allocable the next cycle. One fidelity *upgrade* over the
old engine: same-lane DPM children now inject in dynamic parent-arrival
order — ``(crtime, pid)`` priority over the per-node ``chl`` candidate
table — exactly the host sim's release-order queue, instead of the old
static (enqueue, pid) approximation (DESIGN.md §5/§8).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

# Large sentinel: above every real key, far from int32 overflow when compared.
# A plain Python int so kernels can close over it without a captured constant.
NOC_INF = 2**30

# counter indices (named after the SimStats fields they feed; slots_hwm is
# xsim-only: the in-flight-worm high-water mark)
CTR = (
    "flit_link_traversals", "buffer_writes", "buffer_reads",
    "xbar_traversals", "arbitrations", "ni_flits", "packets_finished",
    "slots_hwm",
)
_I = {name: i for i, name in enumerate(CTR)}

# table fields cycle_core reads (the kernel passes them as explicit refs)
TABLE_FIELDS = (
    "enqueue", "lane", "num_stages", "flits", "link", "vcls", "lane_seq",
    "chl", "child_pid", "child_parent", "child_rs", "child_enq", "watch_link",
)


class CycleState(NamedTuple):
    fowner: jax.Array  # (L, W) int32
    fstage: jax.Array  # (L, W) int16
    fhead: jax.Array  # (L, W) int8
    fcount: jax.Array  # (L, W) int8
    fdvc: jax.Array  # (L, W) int8 — downstream VC the front worm's header
    #                  allocated at its next link (valid once fhead > 0)
    freq: jax.Array  # (L, W) int32 — the owner's next-hop link (-1 = this
    #                  FIFO serves the final stage), cached at header arrival
    fkey: jax.Array  # (L, W) int32 — owner's age-key base (enq*P+pid)*F
    fcls: jax.Array  # (L, W) int8 — owner's VC class at the next hop
    ffin: jax.Array  # (L, W) bool — FIFO serves the owner's final stage
    fnf: jax.Array  # (L, W) int8 — owner's worm length (per-packet flits),
    #                  cached at header arrival like fkey/ffin
    lpid: jax.Array  # (2NN,) int32
    lsent: jax.Array  # (2NN,) int8
    lptr: jax.Array  # (2NN,) int32
    ldvc: jax.Array  # (2NN,) int8 — lane-front worm's VC at its first link
    crtime: jax.Array  # (C,) int32, -1 = not yet releasable
    ctaken: jax.Array  # (C,) bool — consumed by its lane front
    lutil: jax.Array  # (E, L) int32 — per-epoch per-link flit traversals
    #                  (telemetry; epoch = min(t // EPL, E-1), DESIGN.md §10)
    rconf: jax.Array  # (E, NN) int32 — per-epoch per-router arbitration
    #                  conflicts (losing requests across the 4 output links)
    inflight: jax.Array  # () int32 — worms between lane-front and finish
    ctr: jax.Array  # (len(CTR),) int32


def init_planes(L: int, W: int, NN: int, C: int, E: int = 1) -> CycleState:
    return CycleState(
        fowner=jnp.full((L, W), -1, jnp.int32),
        fstage=jnp.zeros((L, W), jnp.int16),
        fhead=jnp.zeros((L, W), jnp.int8),
        fcount=jnp.zeros((L, W), jnp.int8),
        fdvc=jnp.zeros((L, W), jnp.int8),
        freq=jnp.full((L, W), -1, jnp.int32),
        fkey=jnp.zeros((L, W), jnp.int32),
        fcls=jnp.zeros((L, W), jnp.int8),
        ffin=jnp.zeros((L, W), bool),
        fnf=jnp.ones((L, W), jnp.int8),
        lpid=jnp.full((2 * NN,), -1, jnp.int32),
        lsent=jnp.zeros((2 * NN,), jnp.int8),
        lptr=jnp.zeros((2 * NN,), jnp.int32),
        ldvc=jnp.zeros((2 * NN,), jnp.int8),
        crtime=jnp.full((C,), -1, jnp.int32),
        ctaken=jnp.zeros((C,), bool),
        lutil=jnp.zeros((E, L), jnp.int32),
        rconf=jnp.zeros((E, NN), jnp.int32),
        inflight=jnp.zeros((), jnp.int32),
        ctr=jnp.zeros((len(CTR),), jnp.int32),
    )


def cycle_core(state: CycleState, tb: dict, t: jax.Array, geom: dict, *,
               F: int, V: int, BD: int, L: int, NN: int,
               EPL: int = 1 << 30):
    """One wormhole cycle. Pure jnp, no scatters — runs under lax.scan (ref
    backend) and inside the Pallas kernel's fori_loop unchanged.

    ``tb`` holds the compiled-traffic tables (traced), ``geom`` the static
    numpy router geometry from ``compile.geometry_tables``. Returns the new
    state plus the per-link arrival events ``(aval, apid, astage, afid)``
    the caller turns into delivery times (the one scatter, kept outside).
    """
    (fowner, fstage, fhead, fcount, fdvc, freq, fkey, fcls, ffin, fnf, lpid,
     lsent, lptr, ldvc, crtime, ctaken, lutil, rconf, inflight, ctr) = state
    enqueue = tb["enqueue"]
    ns = tb["num_stages"]
    flits_t = tb["flits"]
    link_t = tb["link"]
    vcls_t = tb["vcls"]
    lane_seq = tb["lane_seq"]
    chl = tb["chl"]
    child_pid = tb["child_pid"]
    P, S = link_t.shape
    Q = lane_seq.shape[1]
    C = crtime.shape[0]
    W = 2 * V
    LW = L * W
    INF = jnp.int32(NOC_INF)
    node_ports = geom["node_ports"]  # (NN, 4W+2) static
    cand_node = geom["cand_node"]  # (CAND+1,) static
    cand_port = geom["cand_port"]
    crow_ids = jnp.arange(C, dtype=jnp.int32)

    # ---- 1. NI lane refill ------------------------------------------------
    # root lanes (even): static (enqueue, pid) cursor; child lanes (odd):
    # dynamic (release-cycle, pid) priority — the host sim's queue order
    cand_root = jnp.take_along_axis(
        lane_seq, jnp.clip(lptr, 0, Q - 1)[:, None], axis=1
    )[:, 0]
    root_ok = (
        (lptr < Q) & (cand_root >= 0)
        & (enqueue[jnp.clip(cand_root, 0, P - 1)] <= t)
    )
    released = (crtime >= 0) & (crtime <= t) & ~ctaken
    ckey = jnp.where(released, crtime * C + crow_ids, INF)
    ktab = jnp.where(
        chl >= 0, ckey[jnp.clip(chl, 0, C - 1)], INF
    )  # (NN, QC)
    cargm = jnp.argmin(ktab, axis=1).astype(jnp.int32)
    child_ok = jnp.min(ktab, axis=1) < INF
    crow = jnp.take_along_axis(chl, cargm[:, None], axis=1)[:, 0]  # (NN,)
    cpid = child_pid[jnp.clip(crow, 0, C - 1)]
    lane_cand = jnp.stack(
        [cand_root.reshape(NN, 2)[:, 0], cpid], axis=1
    ).reshape(2 * NN)
    lane_ok = jnp.stack(
        [root_ok.reshape(NN, 2)[:, 0], child_ok], axis=1
    ).reshape(2 * NN)
    need = (lpid < 0) | (
        lsent.astype(jnp.int32) >= flits_t[jnp.clip(lpid, 0, P - 1)]
    )
    got = need & lane_ok
    lpid = jnp.where(got, lane_cand, jnp.where(need, -1, lpid))
    lsent = jnp.where(got, jnp.int8(0), lsent)
    is_root_lane = (jnp.arange(2 * NN) % 2) == 0
    lptr = lptr + (got & is_root_lane)
    got_child = got.reshape(NN, 2)[:, 1]  # (NN,)
    cnode = tb["lane"][jnp.clip(child_pid, 0, P - 1)] // 2  # (C,)
    ctaken = ctaken | (got_child[cnode] & (crow[cnode] == crow_ids))
    inflight = inflight + jnp.sum(got, dtype=jnp.int32)
    ctr = ctr.at[_I["slots_hwm"]].max(inflight)

    # ---- 2. link-round candidates (start-of-cycle admissibility) ----------
    # the per-worm route lookups (next link / VC class / age-key base /
    # final-stage flag) were cached into planes at header arrival, so this
    # phase reads no (P, S) table — at scale those random gathers into the
    # multi-MB compiled tables dominate the cycle
    fp = jnp.clip(fowner, 0, P - 1)
    occ = (fowner >= 0) & (fcount > 0)  # front flit present
    fs32 = fstage.astype(jnp.int32)
    fh32 = fhead.astype(jnp.int32)
    to_f = fs32 + 1
    req_f = jnp.where(occ, freq, -1)  # (L, W); freq = -1 at final stage
    req_fc = jnp.clip(req_f, 0, L - 1)
    key_f = fkey + fh32
    cls_f = fcls.astype(jnp.int32)
    is_hdr_f = fh32 == 0
    freev = fowner < 0  # (L, W) start-of-cycle free VCs
    free_cls = jnp.stack(
        [freev[:, :V].any(axis=1), freev[:, V:].any(axis=1)], axis=1
    )  # (L, 2)
    # first free VC per (link, class) — headers claim the lowest free one
    hvc_cls = jnp.stack(
        [jnp.argmax(freev[:, :V], axis=1),
         V + jnp.argmax(freev[:, V:], axis=1)], axis=1
    ).astype(jnp.int32)  # (L, 2)
    hdr_ok_f = free_cls[req_fc, cls_f]
    hvc_f = hvc_cls[req_fc, cls_f]
    # body flits advance into the FIFO their header allocated at `to` —
    # recorded in ``fdvc`` the cycle the header won (a worm allocates one
    # FIFO per stage, so this equals the old owner/stage search)
    dv_f = fdvc.astype(jnp.int32)
    if BD >= F:
        body_ok_f = True  # a FIFO holds one worm: credit cannot run out
    else:
        body_ok_f = fcount[req_fc, dv_f].astype(jnp.int32) < BD
    adm_f = (req_f >= 0) & jnp.where(is_hdr_f, hdr_ok_f, body_ok_f)
    tvc_f = jnp.where(is_hdr_f, hvc_f, dv_f)

    # NI lane candidates: the front worm's next flit targets stage 0
    lp = jnp.clip(lpid, 0, P - 1)
    ls32 = lsent.astype(jnp.int32)
    lvalid = (lpid >= 0) & (ls32 < flits_t[lp])
    req_l = jnp.where(lvalid, link_t[lp, 0], -1)  # (2NN,)
    req_lc = jnp.clip(req_l, 0, L - 1)
    key_l = (enqueue[lp] * P + lp) * F + ls32
    cls_l = vcls_t[lp, 0]
    is_hdr_l = ls32 == 0
    hdr_ok_l = free_cls[req_lc, cls_l]
    hvc_l = hvc_cls[req_lc, cls_l]
    dv_l = ldvc.astype(jnp.int32)
    if BD >= F:
        body_ok_l = True
    else:
        body_ok_l = fcount[req_lc, dv_l].astype(jnp.int32) < BD
    adm_l = lvalid & jnp.where(is_hdr_l, hdr_ok_l, body_ok_l)
    tvc_l = jnp.where(is_hdr_l, hvc_l, dv_l)

    # flatten candidates: FIFOs, lanes, one trailing dummy (pad target)
    pad1 = lambda v, fill: jnp.concatenate(
        [v, jnp.full((1,), fill, v.dtype)]
    )
    req = pad1(jnp.concatenate([req_f.reshape(LW), req_l]), -1)
    key = pad1(jnp.concatenate([key_f.reshape(LW), key_l]), NOC_INF)
    adm = pad1(jnp.concatenate([adm_f.reshape(LW), adm_l]), False)
    pid_c = pad1(jnp.concatenate([fp.reshape(LW), lp]), 0)
    to_c = pad1(
        jnp.concatenate([to_f.reshape(LW), jnp.zeros_like(req_l)]), 0
    )
    fid_c = pad1(jnp.concatenate([fh32.reshape(LW), ls32]), 0)
    tvc_c = pad1(jnp.concatenate([tvc_f.reshape(LW), tvc_l]), 0)

    # ---- 3. link arbitration: dense masked min over each node's ports -----
    req_np = req[node_ports]  # (NN, PORTS)
    key_np = key[node_ports]
    adm_np = adm[node_ports]
    D = L // NN  # output ports per router (4 in 2-D, 6 in 3-D)
    out_link = (
        jnp.arange(NN, dtype=jnp.int32)[:, None] * D
        + jnp.arange(D, dtype=jnp.int32)[None, :]
    )  # (NN, D) == link-id layout
    m = adm_np[:, None, :] & (req_np[:, None, :] == out_link[:, :, None])
    kk = jnp.where(m, key_np[:, None, :], INF)  # (NN, D, PORTS)
    wport = jnp.argmin(kk, axis=2).astype(jnp.int32)
    aval = (
        jnp.take_along_axis(kk, wport[:, :, None], axis=2)[:, :, 0] < INF
    ).reshape(L)  # winner per link
    rows = jnp.arange(NN)[:, None]
    # winner candidate id per link, then (L,)-sized attribute gathers
    wcand = jnp.asarray(node_ports)[rows, wport].reshape(L)
    apid = pid_c[wcand]
    astage = to_c[wcand]
    afid = fid_c[wcand]
    avc = tvc_c[wcand]
    from_lane = (wport >= D * W).reshape(L) & aval
    # map winners back to candidates through the static inverse (gather)
    won = (
        adm & (req >= 0)
        & aval[jnp.clip(req, 0, L - 1)]
        & (wport.reshape(L)[jnp.clip(req, 0, L - 1)] == cand_port)
    )
    won_f = won[:LW].reshape(L, W)
    won_l = won[LW:LW + 2 * NN]

    # ---- 4. apply moves ---------------------------------------------------
    # a winning header pins the VC it was granted for its body flits
    fdvc = jnp.where(won_f & is_hdr_f, tvc_f.astype(jnp.int8), fdvc)
    ldvc = jnp.where(won_l & is_hdr_l, tvc_l.astype(jnp.int8), ldvc)
    dep_tail = won_f & (fhead == fnf - 1)
    fhead = fhead + won_f.astype(jnp.int8)
    fcount = fcount - won_f.astype(jnp.int8)
    fowner = jnp.where(dep_tail, -1, fowner)
    lsent = lsent + won_l.astype(jnp.int8)
    arr1h = aval[:, None] & (avc[:, None] == jnp.arange(W))  # (L, W)
    hdr1h = arr1h & (afid[:, None] == 0)
    fowner = jnp.where(hdr1h, apid[:, None], fowner)
    fstage = jnp.where(hdr1h, astage[:, None].astype(jnp.int16), fstage)
    fhead = jnp.where(hdr1h, jnp.int8(0), fhead)
    fcount = fcount + arr1h.astype(jnp.int8)
    # cache the arriving worm's route lookups in the FIFO planes — (L,)
    # gathers once per arrival replace (L, W) gathers every cycle
    a_ns = ns[apid]  # (L,)
    nxt = astage + 1
    nxtc = jnp.clip(nxt, 0, S - 1)
    a_req = jnp.where(nxt < a_ns, link_t[apid, nxtc], -1)
    a_cls = vcls_t[apid, nxtc]
    a_key = (enqueue[apid] * P + apid) * F
    a_fin = astage == a_ns - 1
    a_nf = flits_t[apid]  # (L,) — the arriving worm's length
    freq = jnp.where(hdr1h, a_req[:, None], freq)
    fkey = jnp.where(hdr1h, a_key[:, None], fkey)
    fcls = jnp.where(hdr1h, a_cls.astype(jnp.int8)[:, None], fcls)
    ffin = jnp.where(hdr1h, a_fin[:, None], ffin)
    fnf = jnp.where(hdr1h, a_nf.astype(jnp.int8)[:, None], fnf)

    # ---- 5. ejection (per node, post-move state) --------------------------
    ecand_f = (fowner >= 0) & (fcount > 0) & ffin
    ekey_f = fkey + fhead.astype(jnp.int32)
    ecand = pad1(
        jnp.concatenate([ecand_f.reshape(LW), jnp.zeros_like(req_l, bool)]),
        False,
    )
    ekey = pad1(
        jnp.concatenate([ekey_f.reshape(LW), jnp.zeros_like(req_l)]),
        NOC_INF,
    )
    ek_np = jnp.where(ecand[node_ports], ekey[node_ports], INF)
    eport = jnp.argmin(ek_np, axis=1).astype(jnp.int32)  # (NN,)
    ewin_n = jnp.min(ek_np, axis=1) < INF
    ewon = ecand & ewin_n[cand_node] & (eport[cand_node] == cand_port)
    ewon_f = ewon[:LW].reshape(L, W)
    etail = ewon_f & (fhead == fnf - 1)
    fhead = fhead + ewon_f.astype(jnp.int8)
    fcount = fcount - ewon_f.astype(jnp.int8)
    fowner = jnp.where(etail, -1, fowner)

    # ---- 6. DPM child release: watch the parent header's arrival ----------
    wlc = jnp.clip(tb["watch_link"], 0, L - 1)
    hit = (
        aval[wlc] & (apid[wlc] == tb["child_parent"])
        & (astage[wlc] == tb["child_rs"]) & (afid[wlc] == 0)
    )
    crtime = jnp.where(
        (crtime < 0) & hit, jnp.maximum(t + 1, tb["child_enq"]), crtime
    )

    # ---- 7. counters (same events the host sim counts) --------------------
    n_moves = jnp.sum(aval, dtype=jnp.int32)
    n_inj = jnp.sum(from_lane, dtype=jnp.int32)
    n_ej = jnp.sum(ewon_f, dtype=jnp.int32)
    finished = jnp.sum(etail, dtype=jnp.int32)
    inflight = inflight - finished
    zero = jnp.zeros((), jnp.int32)
    ctr = ctr + jnp.stack([
        n_moves, n_moves, n_moves - n_inj + n_ej, n_moves,
        jnp.sum(req >= 0, dtype=jnp.int32), n_inj + n_ej, finished, zero,
    ])

    # ---- 8. telemetry planes (epoch-bucketed, DESIGN.md §10) --------------
    # one-hot epoch accumulate (no dynamic scatter — Mosaic-safe and
    # bit-identical across backends). lutil decomposes flit_link_traversals
    # per directed link; rconf counts losing requests per router — the same
    # candidate sets the arbitrations counter tallies, minus the winners.
    E = lutil.shape[0]
    eh = (
        jnp.arange(E, dtype=jnp.int32) == jnp.minimum(t // EPL, E - 1)
    ).astype(jnp.int32)  # (E,)
    lutil = lutil + eh[:, None] * aval.astype(jnp.int32)[None, :]
    nreq = jnp.sum(
        (req_np[:, None, :] == out_link[:, :, None]).astype(jnp.int32),
        axis=2,
    )  # (NN, D) requests per output link, admissible or not (host parity)
    conf_n = jnp.sum(jnp.maximum(nreq - 1, 0), axis=1)  # (NN,)
    rconf = rconf + eh[:, None] * conf_n[None, :]

    state = CycleState(fowner, fstage, fhead, fcount, fdvc, freq, fkey,
                       fcls, ffin, fnf, lpid, lsent, lptr, ldvc, crtime,
                       ctaken, lutil, rconf, inflight, ctr)
    return state, (aval, apid, astage, afid)
