"""Backend dispatch for the fused wormhole cycle.

``run_cycles`` advances the packed-plane engine ``T`` cycles and returns
the simulation outputs (``dtime``, counters, released-children mask):

* ``ref`` — one ``lax.scan`` of ``ref.cycle_core`` with the (L,)-sized
  delivery scatter inline. The default on every platform, and the engine
  that compiles for TPU: XLA fuses the dense cycle, and per-cycle state
  stays on chip inside the scan.
* ``pallas`` / ``pallas_interpret`` — chunks of ``chunk`` cycles per fused
  kernel launch (``noc_cycle.make_chunk_runner``); state planes round-trip
  HBM only at chunk boundaries, and the packed arrival-event logs are
  decoded into ``dtime`` between launches. ``pallas_interpret`` is the
  CPU-validation flavor (bit-identical to ``ref`` — CI enforces it).
  Compiled ``pallas`` does not lower for TPU yet: Mosaic refuses the
  gathers of ``cycle_core`` (the first is ``take_along_axis`` in
  ``ref.py``).

Backend names resolve through ``resolve_backend`` (``None``/``"auto"``
picks ``ref`` everywhere), so the whole xsim stack shares one switch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .noc_cycle import make_chunk_runner
from .ref import CTR, TABLE_FIELDS, CycleState, cycle_core, init_planes

__all__ = [
    "CTR", "CycleState", "init_planes", "resolve_backend", "run_cycles",
]


def resolve_backend(backend: str | None) -> str:
    """``None``/"auto" -> "ref" on every platform.

    The fused ``noc_cycle`` Pallas kernel does not lower through Mosaic yet
    (its gathers are refused), so the compiled ``lax.scan`` of the same
    ``cycle_core`` is the engine on TPU as on CPU; ``"pallas"`` stays
    callable explicitly."""
    if backend in (None, "auto"):
        return "ref"
    if backend not in ("ref", "pallas", "pallas_interpret"):
        raise ValueError(f"unknown xsim backend: {backend!r}")
    return backend


def run_cycles(tr: dict, geom: dict, *, T: int, F: int, V: int, BD: int,
               L: int, NN: int, ND: int, backend: str,
               chunk: int = 32, epoch_len: int | None = None) -> dict:
    """Run ``T`` cycles over one compiled-traffic tensor dict ``tr``.

    Returns ``{"dtime": (ND + 1,), "ctr": (len(CTR),), "crel": (C,),
    "lutil": (E, L), "rconf": (E, NN)}`` — ``dtime`` is the *flat*
    delivery-time array indexed by the compiler's ``dslot`` table (slot
    ``ND`` is the discard slot); the runner rebuilds the (P, S) view.
    Carrying only the sparse delivery slots through the scan keeps the
    per-cycle state small — the dense (P, S) plane would dominate the
    carry at scale. ``lutil``/``rconf`` are the telemetry planes
    (per-epoch per-link flit traversals / per-router arbitration
    conflicts) bucketed on ``cycle // epoch_len`` with ``E =
    ceil(T / epoch_len)`` (``epoch_len=None``: one epoch spanning the
    run). vmap/pmap-safe: fixed shapes, no host callbacks, all backends.
    """
    P, S = tr["link"].shape
    C = tr["child_parent"].shape[0]
    W = 2 * V
    # int32 headroom for the packed keys/events (compile.py guards the
    # (enqueue, pid, fid) age keys separately)
    assert (T + 2) * max(C, 1) < 2**31, "child release keys exceed int32"
    assert P * S * 4 + 1 < 2**31, "arrival events exceed int32"
    if "flits" not in tr:  # legacy/minimal table dicts: uniform worm length
        tr = dict(tr)
        tr["flits"] = jnp.full((P,), F, jnp.int32)
    tb = {f: jnp.asarray(tr[f]) for f in TABLE_FIELDS}
    dslot = jnp.asarray(tr["dslot"], jnp.int32)
    EPL = T if epoch_len is None else int(epoch_len)
    EPL = max(EPL, 1)
    E = max(1, -(-T // EPL))
    planes0 = init_planes(L, W, NN, C, E)
    dtime0 = jnp.full((ND + 1,), -1, jnp.int32)
    params = dict(F=F, V=V, BD=BD, L=L, NN=NN, EPL=EPL)

    def record(dtime, aval, apid, astage, tail, t):
        """The engine's one scatter: tail arrivals at delivery stages."""
        sc = jnp.clip(astage, 0, S - 1)
        ds = dslot[jnp.clip(apid, 0, P - 1), sc]  # -1 = not a delivery
        hit = aval & tail & (ds >= 0)
        return dtime.at[jnp.where(hit, ds, ND)].set(t, mode="drop")

    if backend == "ref":
        def body(carry, t):
            planes, dtime = carry
            planes, (aval, apid, astage, afid) = cycle_core(
                planes, tb, t, geom, **params
            )
            tail = afid == tb["flits"][jnp.clip(apid, 0, P - 1)] - 1
            return (planes, record(dtime, aval, apid, astage, tail, t)), None

        (planes, dtime), _ = jax.lax.scan(
            body, (planes0, dtime0), jnp.arange(T, dtype=jnp.int32)
        )
    else:
        interpret = backend == "pallas_interpret"

        def apply_events(dtime, ev, t0):
            Tc = ev.shape[0]
            flat = ev.reshape(-1)
            code = jnp.maximum(flat - 1, 0)
            tail = (code % 4) >= 2
            ps = code // 4
            stage, pid = ps % S, ps // S
            aval = flat > 0
            times = t0 + jnp.repeat(jnp.arange(Tc, dtype=jnp.int32), L)
            return record(dtime, aval, pid, stage, tail, times)

        carry = (planes0, dtime0)
        full, rem = divmod(T, chunk)
        if full:
            runner = make_chunk_runner(
                geom, S=S, Tc=chunk, interpret=interpret, **params
            )

            def body(carry, i):
                planes, dtime = carry
                t0 = i * chunk
                planes, ev = runner(planes, tb, t0)
                return (planes, apply_events(dtime, ev, t0)), None

            carry, _ = jax.lax.scan(
                body, carry, jnp.arange(full, dtype=jnp.int32)
            )
        if rem:
            runner = make_chunk_runner(
                geom, S=S, Tc=rem, interpret=interpret, **params
            )
            planes, ev = runner(carry[0], tb, full * chunk)
            carry = (planes, apply_events(carry[1], ev, full * chunk))
        planes, dtime = carry

    crel = (planes.crtime >= 0) & (planes.crtime < T)
    return {
        "dtime": dtime, "ctr": planes.ctr, "crel": crel,
        "lutil": planes.lutil, "rconf": planes.rconf,
    }
