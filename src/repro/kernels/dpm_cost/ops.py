"""The device merge: Algorithm 1 batched, on the device, for the planner.

``dpm_plan_exact`` prices every DPM candidate of a batch of multicast
instances under the full Definition 2 objective (C_t and C_p, MU/DP mode)
and runs the greedy merge, returning everything ``core.batch_planner``
needs to decode each ``MulticastPlan`` bit-identically to host ``plan()``.
The geometry enters as host-built tables (``core.batch_planner``), so one
program serves every registered topology, healthy or degraded.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...core.partition import candidate_ids_for

# Scale of the Definition 1 representative key ``dist * BIG + label``:
# ``core.batch_planner.batch_support`` gates on ``max dist * BIG + NN``
# staying inside int32, so it must read this same constant.
BIG = 1 << 20


@functools.lru_cache(maxsize=None)
def _cand_bits(np_: int) -> np.ndarray:
    """candidate -> bitmask over the ``np_`` basic partitions (np_ <= 30).

    numpy (not jnp) so the cached constant never captures a jit tracer.
    """
    return np.array(
        [sum(1 << i for i in ids) for ids in candidate_ids_for(np_)],
        dtype=np.int32,
    )


# order sentinel: "never picked by the merge loop" (leftover singles sort
# after every real pick round; see _greedy_merge_ordered)
NO_ORDER = jnp.int32(2**30)


def _greedy_merge_ordered(costs, reps, np_: int):
    """Algorithm 1's greedy merge over a priced candidate table, with the
    *pick order*: ``(chosen, order)``.

    ``costs`` are float32 and savings stay in that dtype; the host
    tie-break is reproduced exactly. ``np_`` is the basic-partition count
    (8 wedges in 2-D, 26 in 3-D); the candidate axis is ``3 * np_``
    (singles + consecutive pairs + triples, ``core.partition.
    candidate_ids_for`` order).

    ``order[p, ci]`` is the merge round (0-based) at which candidate ``ci``
    won, or ``NO_ORDER`` for unpicked candidates and leftover singles. The
    host planner emits partitions in greedy pick order followed by leftover
    singles in ascending index — an ordering that determines path/parent
    indices inside the final ``MulticastPlan`` — so the batched decoder
    (``core.batch_planner``) needs the rounds, not just the winning set,
    to reproduce host plans bit-identically.
    """
    cands = candidate_ids_for(np_)
    NC = len(cands)
    cand_bits = jnp.asarray(_cand_bits(np_))
    P = costs.shape[0]
    nonempty = reps >= 0  # (P, NC)

    split_cost = jnp.zeros_like(costs)
    for ci, ids in enumerate(cands):
        if len(ids) == 1:
            continue
        sc = sum(costs[:, i] for i in ids)
        split_cost = split_cost.at[:, ci].set(sc)
    saving0 = jnp.where(
        (jnp.arange(NC) >= np_)[None, :] & nonempty,
        jnp.maximum(0, split_cost - costs),
        0,
    )

    # host tie-break (dpm_partition): max saving, then fewer merged
    # partitions, then smaller candidate index — resolved as a two-step
    # argmax/argmin so exact-tie semantics survive float32 savings (a
    # scalar "saving * K - adj" encoding would mis-rank near-ties under
    # the energy/contention objectives)
    prio_adj = (
        jnp.array([len(ids) for ids in cands], jnp.int32) * 128
        + jnp.arange(NC, dtype=jnp.int32)
    )

    def step(state, rnd):
        saving, covered, chosen, order = state
        overlap = (cand_bits[None, :] & covered[:, None]) != 0
        s = jnp.where(overlap, 0, saving)
        smax = jnp.max(s, axis=1, keepdims=True)
        is_best = (s == smax) & (s > 0)
        best = jnp.argmin(
            jnp.where(is_best, prio_adj[None, :], jnp.int32(2**30)), axis=1
        )
        has = smax[:, 0] > 0
        bbits = cand_bits[best]
        covered = jnp.where(has, covered | bbits, covered)
        rows = jnp.arange(P)
        chosen = chosen.at[rows, best].set(chosen[rows, best] | has)
        order = order.at[rows, best].set(
            jnp.where(has, jnp.minimum(order[rows, best], rnd), order[rows, best])
        )
        return (s, covered, chosen, order), None

    chosen0 = jnp.zeros((P, NC), bool)
    covered0 = jnp.zeros((P,), jnp.int32)
    order0 = jnp.full((P, NC), NO_ORDER, jnp.int32)
    # every winning merge covers >= 2 uncovered partitions, so np_ // 2
    # rounds always reach the fixed point
    (saving, covered, chosen, order), _ = jax.lax.scan(
        step, (saving0, covered0, chosen0, order0),
        jnp.arange(np_ // 2, dtype=jnp.int32),
    )
    single_bit = 1 << jnp.arange(np_, dtype=jnp.int32)
    leftover = nonempty[:, :np_] & (
        (covered[:, None] & single_bit[None, :]) == 0
    )
    chosen = chosen.at[:, :np_].set(chosen[:, :np_] | leftover)
    return chosen, order


def _pick(onehot, x):
    """``x`` at the one slot ``onehot`` marks along its last axis, as a
    where-sum: one term plus zeros, so exact in any dtype."""
    return jnp.sum(jnp.where(onehot, x, jnp.zeros((), x.dtype)), axis=-1)


@functools.partial(jax.jit, static_argnames=("np_", "overhead"))
def dpm_plan_exact(
    dests: jax.Array,  # (B, K) int32 destination node indices, -1 pads
    src_idx: jax.Array,  # (B,) int32 Topology.idx of each source
    memb: jax.Array,  # (NN, NN) int32 wedge membership (membership_table)
    labels: jax.Array,  # (NN,) int32 snake labels
    dist: jax.Array,  # (NN, NN) provider-route hop counts
    w_uni: jax.Array,  # (NN, NN) unicast-route prices (C_t terms)
    w_high: jax.Array,  # (NN, NN) HIGH-subnetwork label-route prices
    w_low: jax.Array,  # (NN, NN) LOW-subnetwork label-route prices
    pass_high: jax.Array | None = None,  # (NN, NN, W) int32 bitmasks
    pass_low: jax.Array | None = None,  # (label_chain_passes), or None
    *,
    np_: int,
    overhead: float = 0.0,
):
    """Algorithm 1 batched with the *full* Definition 2 objective.

    Evaluates both C_t and C_p per candidate, plus the source leg S -> R,
    and records the MU/DP mode choice and the greedy pick order,
    everything the host decode needs to
    rebuild each ``MulticastPlan`` bit-identically (``core.batch_planner``;
    exactness conditions in ``batch_support`` there). Each instance is its
    source and up to K destination slots. Returns ``(chosen, order, reps,
    mode_mu, costs)``, all ``(B, 3 * np_)`` over the ``candidate_ids_for``
    axis.

    Candidates are priced over the slots, not the fabric's nodes: the
    slots are sorted by snake label (pads last), and every price a
    candidate needs is a pair of destinations (or the source and one),
    read once per instance into ``(B, K, K)`` tables from contiguous
    ``(B * K, NN)`` row gathers. A column of those rows is picked by a
    where-sum over the NN nodes, on the TPU several times faster than
    element gathers; the work grows with ``K * K * NN`` per instance.

    ``pass_high`` / ``pass_low`` (``core.batch_planner.label_chain_passes``)
    are given on a degraded fabric, where a detoured label route may pass
    a later chain member: C_p is then priced by a walk over the slots in
    label order that skips members already passed. Without them (a
    healthy fabric) the program is the prefix scan alone.
    """
    K = dests.shape[1]
    NN = labels.shape[0]
    dist = dist.astype(jnp.int32)
    pos = jnp.arange(K, dtype=jnp.int32)
    lab = jnp.take(labels, jnp.clip(dests, 0))
    lab = jnp.where(dests >= 0, lab, jnp.int32(2**31 - 1))
    lab, node = jax.lax.sort((lab, dests), dimension=1, num_keys=1)
    valid = node >= 0
    node = jnp.clip(node, 0)
    col = node[:, :, None] == jnp.arange(NN, dtype=node.dtype)

    def at_slots(table):  # (NN, NN) -> (B, K): table[source, slot]
        return _pick(col, jnp.take(table, src_idx, axis=0)[:, None])

    def pairs(table):  # (NN, NN) -> (B, K, K): table[slot i, slot j]
        rows = jnp.take(table.astype(jnp.float32), node, axis=0)
        return _pick(col[:, None], rows[:, :, None])

    dsrc, part = at_slots(dist), at_slots(memb)
    w_src = at_slots(w_uni.astype(jnp.float32))
    wu, wh, wl = pairs(w_uni), pairs(w_high), pairs(w_low)
    # membership: candidate ci holds slot k iff k's wedge bit is in ci's
    # mask; the source (membership -1) and pads are in no candidate
    bits = jnp.asarray(_cand_bits(np_))[:, None, None]
    sel = (valid & (part >= 0))[None] & (
        ((bits >> jnp.clip(part, 0)[None]) & 1) == 1
    )  # (NC, B, K)
    any_sel = sel.any(2)
    # Definition 1 representative: min (dist-to-src, label); labels are
    # unique, so the minimum is one slot
    key = jnp.where(sel, (dsrc * BIG + lab)[None], jnp.int32(2**30))
    r = jnp.argmin(key, axis=2).astype(jnp.int32)  # (NC, B)
    at_r = r[..., None] == pos  # (NC, B, K)
    rep = _pick(at_r, node[None])
    rep_lab = _pick(at_r, lab[None])
    # C_t: one unicast worm from the representative per member
    w_rep = _pick(at_r[:, :, None, :], jnp.swapaxes(wu, 1, 2)[None])
    cnt = jnp.sum(sel.astype(jnp.float32), 2)
    cost_mu = jnp.sum(jnp.where(sel, w_rep, 0.0), 2)
    cost_mu = cost_mu + jnp.maximum(cnt - 1.0, 0.0) * float(overhead)
    # C_p: a label-ordered chain is the concatenation of pairwise label
    # routes between consecutive members. On a healthy fabric the label
    # rule only ever moves through labels at or below (above, descending)
    # the current target, so no pending member is passed early, and each
    # side prices as a prefix scan over the label-sorted slots: a
    # member's predecessor is the previous member on its side, or the
    # representative. A detour may pass a later member, which the chain
    # then skips: the walk below.
    act_h = sel & (lab[None] > rep_lab[..., None])
    act_l = sel & (lab[None] < rep_lab[..., None])
    if pass_high is None:
        run_h = jax.lax.cummax(jnp.where(act_h, pos, -1), axis=2)
        prev_h = jnp.concatenate(
            [jnp.full(run_h.shape[:2] + (1,), -1, jnp.int32),
             run_h[..., :-1]],
            axis=2,
        )
        run_l = jax.lax.cummin(jnp.where(act_l, pos, K), axis=2,
                               reverse=True)
        prev_l = jnp.concatenate(
            [run_l[..., 1:], jnp.full(run_l.shape[:2] + (1,), K, jnp.int32)],
            axis=2,
        )
        prev_h = jnp.where(prev_h >= 0, prev_h, r[..., None])
        prev_l = jnp.where(prev_l < K, prev_l, r[..., None])

        def side(prev, act, w):  # sum over members of w[prev slot, slot]
            step = _pick(prev[..., None] == pos, jnp.swapaxes(w, 1, 2)[None])
            return jnp.sum(jnp.where(act, step, 0.0), 2), act.any(2)

        hi, any_h = side(prev_h, act_h, wh)
        lo, any_l = side(prev_l, act_l, wl)
    else:
        def passes(table):  # (NN, NN, W) -> (B, K, K, K)
            # [b, i, k, j]: the label route slot i -> slot k passes slot j
            words = jnp.take(
                table.reshape(NN * NN, -1),
                node[:, :, None] * NN + node[:, None, :], axis=0,
            )  # (B, K, K, W)
            at_w = (node // 32)[..., None] == jnp.arange(table.shape[2])
            word = _pick(at_w[:, None, None], words[:, :, :, None])
            bit = (word >> (node % 32)[:, None, None]) & 1
            return (bit == 1) & valid[:, None, None]

        def walk(act, w, ps, reverse):
            # the chain visits its members in label order from the
            # representative, skipping a member an earlier route passed
            def step(carry, x):
                cur, seen, cost = carry
                act_k, w_k, ps_k, k = x
                at_cur = cur[..., None] == pos  # (NC, B, K)
                go = act_k & ~jnp.any(seen & (pos == k), axis=2)
                cost = cost + jnp.where(go, _pick(at_cur, w_k[None]), 0.0)
                passed = jnp.any(at_cur[..., None] & ps_k[None], axis=2)
                seen = seen | (go[..., None] & passed)
                return (jnp.where(go, k, cur), seen, cost), None

            xs = (jnp.moveaxis(act, 2, 0), jnp.moveaxis(w, 2, 0),
                  jnp.moveaxis(ps, 2, 0), pos)
            init = (r, jnp.zeros_like(act), jnp.zeros(r.shape, jnp.float32))
            (_, _, cost), _ = jax.lax.scan(step, init, xs, reverse=reverse)
            return cost, act.any(2)

        hi, any_h = walk(act_h, wh, passes(pass_high), reverse=False)
        lo, any_l = walk(act_l, wl, passes(pass_low), reverse=True)
    cost_dp = hi + lo + (
        any_h.astype(jnp.float32) + any_l.astype(jnp.float32)
    ) * float(overhead)
    # ties prefer MU (the paper: D_H/D_L computation is then skipped)
    mode_mu = cost_mu <= cost_dp
    cost = jnp.minimum(cost_mu, cost_dp)
    cost = cost + _pick(at_r, w_src[None])
    costs = jnp.where(any_sel, cost, 0.0).T
    reps = jnp.where(any_sel, rep, -1).T
    modes = (mode_mu | ~any_sel).T
    chosen, order = _greedy_merge_ordered(costs, reps, np_)
    return chosen, order, reps, modes, costs
