"""Fused Pallas wormhole-cycle kernel: a chunk of cycles in one launch.

One ``pallas_call`` advances the simulator ``Tc`` cycles: every state plane
is loaded from its ref once, carried through an in-kernel ``fori_loop`` as
VMEM-resident values (never round-tripping per cycle), and stored back once
at the chunk boundary. The loop body is ``ref.cycle_core`` — the exact jnp
function the reference backend scans — so the two paths are bit-identical
by construction; this file only adds the ref plumbing and the packed
arrival-event log.

Delivery times are the one non-dense update in the engine, so they stay
out of the kernel: each cycle writes one packed int32 row ``ev[t, link] =
1 + (pid * S + stage) * 4 + is_tail * 2 + is_header`` (0 = no arrival; at
most one flit arrives per directed link per cycle), and the host-side
wrapper in ``ops.py`` turns the chunk's log into ``dtime`` scatters between
kernel launches.

The static router geometry (``node_ports`` and friends) and the compiled-
traffic tables are explicit kernel operands (``pallas_call`` kernels may
not capture array constants), so the whole runner stays vmap/pmap-able
over the sweep batch axis. The kernel runs under ``interpret=True`` (the
validation path CI exercises). It does not compile for TPU yet: Mosaic
refuses the gathers of ``cycle_core``, so ``resolve_backend`` never picks
it and the ``ref`` scan is the on-chip engine.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import CTR, TABLE_FIELDS, CycleState, cycle_core

_NPLANES = len(CycleState._fields)
_GEOM_FIELDS = ("node_ports", "cand_node", "cand_port")


def make_chunk_runner(geom: dict, *, F: int, V: int, BD: int, L: int,
                      NN: int, S: int, Tc: int, interpret: bool,
                      EPL: int = 1 << 30):
    """Build ``run(planes, tb, t0) -> (planes', ev[Tc, L])`` for one chunk
    length. ``t0`` is the absolute cycle of the chunk's first iteration."""
    params = dict(F=F, V=V, BD=BD, L=L, NN=NN, EPL=EPL)

    n_in = _NPLANES + len(TABLE_FIELDS) + len(_GEOM_FIELDS) + 1

    def kernel(*refs):
        plane_refs = refs[:_NPLANES]
        table_refs = refs[_NPLANES:_NPLANES + len(TABLE_FIELDS)]
        geom_refs = refs[_NPLANES + len(TABLE_FIELDS):n_in - 1]
        t0_ref = refs[n_in - 1]
        out_refs = refs[n_in:-1]
        ev_ref = refs[-1]
        tb = {f: r[...] for f, r in zip(TABLE_FIELDS, table_refs)}
        gm = {f: r[...] for f, r in zip(_GEOM_FIELDS, geom_refs)}
        planes = [r[...] for r in plane_refs]
        planes[-2] = planes[-2][0]  # inflight rides as (1,) around the call
        state = CycleState(*planes)
        t0 = t0_ref[0]

        def body(i, st):
            st, (aval, apid, astage, afid) = cycle_core(
                st, tb, t0 + i, gm, **params
            )
            # tail bit is per-packet: a trace worm may be shorter/longer
            # than the config default (heterogeneous payloads)
            nf = tb["flits"][jnp.clip(apid, 0, tb["flits"].shape[0] - 1)]
            ev = jnp.where(
                aval,
                1 + ((apid * S + astage) * 4
                     + (afid == nf - 1).astype(jnp.int32) * 2
                     + (afid == 0).astype(jnp.int32)),
                0,
            )
            ev_ref[pl.dslice(i, 1), :] = ev[None, :]
            return st

        out = jax.lax.fori_loop(0, Tc, body, state)
        for r, v in zip(out_refs, out):
            r[...] = v if v.ndim else v[None]

    def run(planes: CycleState, tb: dict, t0) -> tuple[CycleState, jax.Array]:
        flat = [
            p if p.ndim else p[None]  # scalar inflight -> (1,)
            for p in planes
        ]
        tables = [tb[f] for f in TABLE_FIELDS]
        gtabs = [jnp.asarray(geom[f]) for f in _GEOM_FIELDS]
        t0a = jnp.asarray(t0, jnp.int32)[None]
        out_shape = [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in flat]
        out_shape.append(jax.ShapeDtypeStruct((Tc, L), jnp.int32))
        outs = pl.pallas_call(
            kernel,
            out_shape=out_shape,
            interpret=interpret,
        )(*flat, *tables, *gtabs, t0a)
        ev = outs[-1]
        new = list(outs[:-1])
        new[-2] = new[-2][0]  # (1,) -> scalar inflight
        return CycleState(*new), ev

    return run
