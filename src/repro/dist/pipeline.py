"""GPipe-style microbatched pipeline parallelism over a mesh axis.

``pipeline_apply`` schedules M microbatches across the S stages of a
``pipe`` mesh axis: at step t stage s runs microbatch ``t - s``, stage
outputs hand off to the next stage with a single ``jax.lax.ppermute``
shift per step, and the last stage's results are returned from the
drain.  The whole thing is a static Python loop of ``M + S - 1`` steps
inside one shard_map, so it traces once, scans each stage's stacked
layer weights, and is differentiable end-to-end (ppermute transposes to
the reverse shift; the warmup/drain bubbles contribute zeros).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(layer_fn, stage_params, x: jax.Array, mesh, axis: str = "pipe"):
    """Run ``layer_fn`` layers, partitioned into pipeline stages.

    layer_fn: (layer_params, h) -> h, one layer.
    stage_params: pytree with leading dims (S, L_per_stage, ...) — stage-
        major stacked layer weights; sharded over ``axis``.
    x: (M, microbatch...) — M microbatches, replicated.
    Returns (M, microbatch...): every microbatch through all S*L layers.
    """
    n_stages = dict(mesh.shape)[axis]
    n_micro = x.shape[0]
    for leaf in jax.tree.leaves(stage_params):
        if leaf.shape[0] != n_stages:
            # shard_map would accept any divisible leading dim and the
            # per-stage [0] slice would then silently drop layers
            raise ValueError(
                f"stage_params leading dim {leaf.shape[0]} != "
                f"{n_stages} pipeline stages on axis {axis!r}"
            )

    def local(sp, xl):
        sp = jax.tree.map(lambda a: a[0], sp)  # (L_per_stage, ...) this stage
        stage = jax.lax.axis_index(axis)
        first, last = stage == 0, stage == n_stages - 1
        shift = [(i, i + 1) for i in range(n_stages - 1)]

        def run_stage(h):
            def body(h, lp):
                return layer_fn(lp, h), None

            h, _ = jax.lax.scan(body, h, sp)
            return h

        state = jnp.zeros_like(xl[0])
        outs = jnp.zeros_like(xl)
        for t in range(n_micro + n_stages - 1):
            inject = xl[t] if t < n_micro else jnp.zeros_like(xl[0])
            state = jnp.where(first, inject, state)
            y = run_stage(state)
            if t >= n_stages - 1:
                outs = outs.at[t - n_stages + 1].set(
                    jnp.where(last, y, jnp.zeros_like(y))
                )
            state = jax.lax.ppermute(y, axis, perm=shift)
        # only the last stage wrote non-zeros; psum replicates the result
        return jax.lax.psum(outs, axis)

    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
