"""Expert-parallel MoE over shard_map, dispatched through DPM schedules.

``moe_apply_ep`` is the explicit-collective twin of
``repro.models.moe.moe_apply_dense``: experts shard over the ``model``
mesh axis, tokens over ``(data..., model)``, and the dispatch/combine
exchange runs as the ppermute rounds of ``repro.dist.multicast.
alltoall_schedule`` — DPM partition merging plans every (src, dst) token
chunk's route on the rank ring, instead of a bare ``lax.all_to_all``
(DESIGN.md §4).

Numerics: routing, dispatch ranking, and the per-row expert SwiGLU reuse
the dense path's helpers, so with a no-drop capacity factor the EP output
equals the dense output modulo f32 reduction order (tests/dist_checks.py
pins 2e-5).  The aux load-balance loss is the pmean of the per-shard
losses — an unbiased estimate of the dense aux, not bit-equal.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.config import ArchConfig, MoEConfig
from ..models.moe import (
    capacity,
    dispatch_indices,
    expert_ffn,
    moe_apply_dense,
    route,
)
from .multicast import alltoall_schedule, apply_alltoall_schedule

EP_AXIS = "model"
_EXPERT_LEAVES = ("wi", "wg", "wo")


def _param_specs(p) -> dict:
    """shard_map in_specs for the MoE param dict: stacked expert weights
    shard their leading experts axis over the EP axis, the router and
    shared experts replicate."""
    return {
        k: (P(EP_AXIS) if k in _EXPERT_LEAVES else jax.tree.map(lambda _: P(), v))
        for k, v in p.items()
    }


def moe_apply_ep(
    p,
    x: jax.Array,
    cfg: ArchConfig,
    mesh,
    data_axes: tuple[str, ...] | None = None,
    algo: str = "DPM",
):
    """Expert-parallel MoE FFN.  x: (B, S, d) -> (y, aux_loss).

    Tokens shard over ``(*data_axes, EP_AXIS)`` along the batch axis, or
    along the sequence axis when the shards do not tile the batch (a long
    prefill of few sequences), so the output stays distributed.  Each
    shard flattens and routes its tokens locally, packs one
    (E_loc, cap, d) chunk per expert shard, and the chunks ride the DPM
    all-to-all schedule out and back.  Falls back to the dense path when
    the mesh or shapes don't divide (single EP rank, ragged experts, or
    token shards that tile neither B nor S).
    """
    m: MoEConfig = cfg.moe
    B, S, d = x.shape
    if data_axes is None:
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    sizes = dict(mesh.shape)
    n_ep = sizes.get(EP_AXIS, 1)
    n_data = math.prod(sizes[a] for a in data_axes) if data_axes else 1
    mesh_axes = (*data_axes, EP_AXIS)
    n_tok = n_data * n_ep
    if n_ep <= 1 or m.n_experts % n_ep:
        return moe_apply_dense(p, x, cfg)
    if B % n_tok == 0:
        tok_spec = P(mesh_axes)
    elif S % n_tok == 0:
        tok_spec = P(None, mesh_axes)
    else:
        return moe_apply_dense(p, x, cfg)

    e_loc = m.n_experts // n_ep
    t_loc = B * S // n_tok
    cap = capacity(m, t_loc)
    sched = alltoall_schedule(n_ep, algo)

    def local(p_l, x_l):
        # x_l: (B or B/n_tok, S/n_tok or S, d) local tokens, t_loc in all;
        # expert leaves of p_l: (e_loc, ...)
        xt = x_l.reshape(t_loc, d)
        ids, w, aux = route(p_l, xt, m)
        slot, keep = dispatch_indices(ids, m, cap)
        xt_rep = jnp.repeat(xt, m.top_k, axis=0)
        buf = jnp.zeros((m.n_experts * cap, d), xt.dtype)
        buf = buf.at[slot].add(jnp.where(keep[:, None], xt_rep, 0))
        # dispatch: chunk j goes to expert shard j over the DPM schedule
        chunks = buf.reshape(n_ep, e_loc * cap, d)
        recv = apply_alltoall_schedule(chunks, sched, EP_AXIS)
        xe = (
            recv.reshape(n_ep, e_loc, cap, d)
            .transpose(1, 0, 2, 3)
            .reshape(e_loc, n_ep * cap, d)
        )
        ye = expert_ffn({k: p_l[k] for k in _EXPERT_LEAVES}, xe)
        # combine: same schedule back (all-to-all is its own inverse here)
        back = (
            ye.reshape(e_loc, n_ep, cap, d)
            .transpose(1, 0, 2, 3)
            .reshape(n_ep, e_loc * cap, d)
        )
        outb = apply_alltoall_schedule(back, sched, EP_AXIS)
        gathered = outb.reshape(m.n_experts * cap, d)[slot]
        gathered = jnp.where(keep[:, None], gathered, 0)
        y = (
            gathered.reshape(t_loc, m.top_k, d) * w[..., None].astype(xt.dtype)
        ).sum(1)
        if m.n_shared:
            h = xt @ p_l["shared_wi"].astype(xt.dtype)
            g = xt @ p_l["shared_wg"].astype(xt.dtype)
            y = y + (jax.nn.silu(g) * h) @ p_l["shared_wo"].astype(xt.dtype)
        return y.reshape(x_l.shape), jax.lax.pmean(aux, mesh_axes)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(_param_specs(p), tok_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )(p, x)
