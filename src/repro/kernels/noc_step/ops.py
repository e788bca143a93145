"""Backend dispatch + winner derivation for xsim's arbitration rounds.

``arbitrate`` turns a (mask, key, resource-id) candidate set into the winner
mask of one arbitration round: per resource, the admissible candidate with
the smallest age key wins (keys are unique, so at most one winner per
resource). The segmented-min reduction runs either through the Pallas kernel
(``noc_step.py``, interpret mode for validation) or the jnp oracle
(``ref.py`` — the default, where it lowers to a native scatter-min).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .noc_step import NOC_INF, segmented_min
from .ref import segmented_min_ref


def resolve_backend(backend: str | None) -> str:
    """``None``/"auto" -> "ref" on every platform.

    The fused ``noc_cycle`` Pallas kernel does not lower through Mosaic yet
    (its gathers are refused), so the compiled ``lax.scan`` of the same
    ``cycle_core`` is the engine on TPU as on CPU; ``"pallas"`` stays
    callable explicitly."""
    if backend in (None, "auto"):
        return "ref"
    if backend not in ("ref", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown noc_step backend: {backend!r}")
    return backend


# Below this many (candidate x segment) cells the broadcast-compare min-
# reduction beats XLA:CPU's serialized scatter-min (measured ~2.5x on the
# ejection round); above it the scatter wins on memory traffic.
_DENSE_CELLS = 65536


def segmin(
    keys: jax.Array,  # (...,) int32; NOC_INF = no candidate
    segs: jax.Array,  # (...,) int32 resource ids in [0, num_segments)
    num_segments: int,
    backend: str = "ref",
) -> jax.Array:
    """Per-resource minimum key, (num_segments,); NOC_INF where empty."""
    flat_k = keys.reshape(-1).astype(jnp.int32)
    flat_s = segs.reshape(-1).astype(jnp.int32)
    if backend == "ref":
        if flat_k.shape[0] * num_segments <= _DENSE_CELLS:
            hit = flat_s[:, None] == jnp.arange(num_segments)[None, :]
            return jnp.min(
                jnp.where(hit, flat_k[:, None], NOC_INF), axis=0
            ).astype(jnp.int32)
        return segmented_min_ref(flat_k, flat_s, num_segments)
    return segmented_min(
        flat_k, flat_s, num_segments,
        interpret=(backend == "pallas_interpret"),
    )


def arbitrate(
    adm: jax.Array,  # (...,) bool — admissible candidates
    keys: jax.Array,  # (...,) int32 age keys, unique among admissible
    segs: jax.Array,  # (...,) int32 resource ids in [0, num_segments)
    num_segments: int,
    backend: str = "ref",
) -> jax.Array:
    """Winner mask, same shape as ``adm`` (one winner max per resource)."""
    mkeys = jnp.where(adm, keys, NOC_INF).astype(jnp.int32)
    seg_min = segmin(mkeys, segs, num_segments, backend=backend)
    won = mkeys == seg_min[jnp.clip(segs, 0, num_segments - 1)]
    return adm & won & (mkeys < NOC_INF)
