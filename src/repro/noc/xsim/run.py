"""Batch runner: compile -> one jitted, vmapped fused-cycle run -> SimStats.

``xsimulate(cfg, workloads, algos)`` lowers every (workload, algorithm) pair
with the compiler, pads the batch to one common (P, S) shape, and runs the
whole grid through a single ``jax.vmap``-ed dispatch of the fused cycle
engine (``kernels.noc_cycle``) — seeds, injection rates, and routing
algorithms all ride the batch axis, and multi-device hosts additionally
pmap-shard it.

The cycle count is fixed (``max horizon + drain_grace``): scans cannot exit
early, so unlike the host sim there is no drain-and-stop — saturation points
cost the same as idle ones, which is exactly why the batched sweep wins.

There is no slot pool anymore: the packed router-centric state is sized by
the network itself (every in-flight worm holds a VC FIFO or an NI lane
front), so per-cycle cost is bounded by ``L * 2V + 2 * NN`` regardless of
injection rate or backlog, and the old overflow/regrow loop is gone.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..config import NoCConfig
from ..simulator import SimStats
from ..traffic import Workload
from ...core.algo import available_algorithms, get_algorithm
from ...core.topology import make_topology
from ...kernels.noc_cycle import CTR, run_cycles
from ...obs import span
from .compile import (
    CompiledTraffic,
    compile_workload,
    geometry_tables,
    stack_traffic,
)


def _run_one(tr: dict, T: int, F: int, V: int, BD: int, L: int, NN: int,
             ND: int, kind: str, n: int, m: int, params: tuple, backend: str,
             epoch_len: int | None = None):
    geom = geometry_tables(kind, n, m, params, V)
    return run_cycles(
        tr, geom, T=T, F=F, V=V, BD=BD, L=L, NN=NN, ND=ND, backend=backend,
        epoch_len=epoch_len,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "T", "F", "V", "BD", "L", "NN", "ND", "kind", "n", "m", "params",
        "backend", "epoch_len",
    ),
)
def _run_batch(stacked: dict, T: int, F: int, V: int, BD: int, L: int,
               NN: int, ND: int, kind: str, n: int, m: int, params: tuple,
               backend: str, epoch_len: int):
    fn = functools.partial(
        _run_one, T=T, F=F, V=V, BD=BD, L=L, NN=NN, ND=ND, kind=kind, n=n,
        m=m, params=params, backend=backend, epoch_len=epoch_len,
    )
    return jax.vmap(fn)(stacked)


def _devices_of(x: jax.Array) -> tuple[str, ...]:
    return tuple(
        f"{d.platform}:{d.id}"
        for d in sorted(x.sharding.device_set, key=lambda d: d.id)
    )


def _run_sharded(stacked: dict, **kw):
    """vmap the batch axis; additionally pmap-shard it across the local
    devices when more than one is available (the chips of a multi-chip
    host, or CPU devices forced with --xla_force_host_platform_device_count)
    and it divides evenly. Returns ``(out, devices)``: the devices that
    hold the results."""
    B = stacked["link"].shape[0]
    D = jax.local_device_count()
    while D > 1 and B % D:
        D -= 1
    if D <= 1:
        out = _run_batch(stacked, **kw)
        return out, _devices_of(out["ctr"])
    fn = jax.pmap(
        jax.vmap(functools.partial(_run_one, **kw)), axis_name="shard"
    )
    shaped = {
        k: jnp.reshape(v, (D, B // D) + v.shape[1:])
        for k, v in stacked.items()
    }
    out = fn(shaped)
    devices = _devices_of(out["ctr"])
    return (
        {k: jnp.reshape(v, (B,) + v.shape[2:]) for k, v in out.items()},
        devices,
    )


@dataclass
class XSimResults:
    """Batched results over a (workloads x algos) grid.

    ``b = w * len(algos) + a`` indexes the flat batch axis. ``stats(w, a)``
    adapts one cell to the host simulator's ``SimStats`` (same counter
    semantics; ``cycles`` is the fixed scan length, so compare dynamic
    *energy* across simulators, not per-cycle power).
    """

    cfg: NoCConfig
    algos: tuple[str, ...]
    horizons: np.ndarray  # (W,) int
    warmup: int
    cycles: int  # scan length T
    slots: int  # structural worm capacity 2*V*L + 2*NN (informational)
    traffic: dict  # stacked compile tensors, numpy, leading axis B
    dtime: np.ndarray  # (B, P, S) int32
    ctr: np.ndarray  # (B, len(CTR)) int32
    crel: np.ndarray  # (B, C) bool
    epoch_len: int = 0  # telemetry bucket width (cycles)
    lutil: np.ndarray | None = None  # (B, E, L) per-epoch link flits
    rconf: np.ndarray | None = None  # (B, E, NN) per-epoch router conflicts
    backend: str = "ref"  # the cycle engine that ran (resolve_backend)
    devices: tuple[str, ...] = ()  # "platform:id" of each device that ran it

    def _b(self, w: int, a: int) -> int:
        return w * len(self.algos) + a

    def latencies(self, w: int, a: int) -> list[int]:
        """Per-delivery latencies of measured packets (warmup window)."""
        b = self._b(w, a)
        enq = self.traffic["enqueue"][b]
        measured = (
            self.traffic["valid"][b]
            & (enq >= self.warmup)
            & (enq < self.horizons[w])
        )
        hit = (
            self.traffic["deliver"][b]
            & (self.dtime[b] >= 0)
            & measured[:, None]
        )
        return (self.dtime[b] - enq[:, None])[hit].tolist()

    def avg_latency(self, w: int, a: int) -> float:
        lats = self.latencies(w, a)
        return sum(lats) / max(1, len(lats))

    def avg_latency_matrix(self) -> np.ndarray:
        W = len(self.horizons)
        return np.array(
            [[self.avg_latency(w, a) for a in range(len(self.algos))]
             for w in range(W)]
        )

    def delivered_sets(self, w: int, a: int) -> dict[int, set[int]]:
        """pid -> set of delivered node indices (for host-sim parity)."""
        b = self._b(w, a)
        hit = self.traffic["deliver"][b] & (self.dtime[b] >= 0)
        node = self.traffic["node"][b]
        out: dict[int, set[int]] = {}
        for p in np.flatnonzero(self.traffic["valid"][b]):
            out[int(p)] = {int(n) for n in node[p][hit[p]]}
        return out

    def packets_created(self, w: int, a: int) -> int:
        """Packets that entered an NI lane queue (host-sim semantics: every
        root whose enqueue time fell inside the run, plus released children).
        """
        b = self._b(w, a)
        tr = self.traffic
        roots = (
            tr["valid"][b] & (tr["parent"][b] < 0)
            & (tr["enqueue"][b] < self.cycles)
        )
        return int(roots.sum()) + int(self.crel[b].sum())

    def all_drained(self, w: int, a: int) -> bool:
        st = self.stats(w, a)
        return st.packets_finished == st.packets_created

    def slots_hwm(self) -> int:
        """Max in-flight worms across the batch (diagnostic: how much of the
        structural ``slots`` capacity the sweep actually used)."""
        return int(self.ctr[:, CTR.index("slots_hwm")].max())

    def link_utilization(self, w: int, a: int,
                         epoch: int | None = None) -> np.ndarray:
        """(L,) per-directed-link flit traversals for one grid cell — the
        conserved-event decomposition of ``flit_link_traversals``, exactly
        matching the host sim's ``Telemetry.link_flits`` when delivery sets
        match. ``epoch`` selects one ``epoch_len``-cycle bucket; default
        sums the run."""
        planes = self.lutil[self._b(w, a)]
        return planes.sum(axis=0) if epoch is None else planes[epoch]

    def router_conflicts(self, w: int, a: int,
                         epoch: int | None = None) -> np.ndarray:
        """(NN,) per-router losing arbitration requests (see ``lutil``
        semantics for the ``epoch`` argument)."""
        planes = self.rconf[self._b(w, a)]
        return planes.sum(axis=0) if epoch is None else planes[epoch]

    def link_heatmap(self, w: int, a: int) -> np.ndarray:
        """(rows, n, ports) per-node outgoing-link flit counts (rendering)."""
        util = self.link_utilization(w, a)
        rows = self.cfg.rows
        ports = util.shape[-1] // (rows * self.cfg.n)
        return util.reshape(rows, self.cfg.n, ports)

    def stats(self, w: int, a: int) -> SimStats:
        b = self._b(w, a)
        st = SimStats(latencies=sorted(self.latencies(w, a)))
        for i, name in enumerate(CTR):
            if hasattr(st, name):  # slots_hwm is xsim-only
                setattr(st, name, int(self.ctr[b, i]))
        st.packets_created = self.packets_created(w, a)
        st.cycles = self.cycles
        return st


def _capacity(cfg: NoCConfig, num_nodes: int, num_links: int) -> int:
    """Structural in-flight worm bound: every in-network worm holds >= 1 VC
    FIFO, plus one possible lane front per lane."""
    return 2 * cfg.vcs_per_class * num_links + 2 * num_nodes


def xsimulate(
    cfg: NoCConfig,
    workloads: list[Workload],
    algos: tuple | None = None,
    *,
    cost_model=None,
    warmup: int | None = None,
    drain_grace: int | None = None,
    backend: str | None = None,
    slots: int | None = None,
    pad_packets: int | None = None,
    pad_stages: int | None = None,
    epoch_len: int | None = None,
    broken_links_per_workload: list | None = None,
) -> XSimResults:
    """Simulate every (workload, algo) pair in one vmapped device dispatch.

    ``algos`` entries resolve through the routing-algorithm registry (names
    or ``RoutingAlgorithm`` instances); the default is every registered
    algorithm that supports the configured topology. ``cost_model``
    optionally overrides the planning objective for the whole grid.
    ``backend`` (or ``cfg.xsim_backend``) selects the cycle engine; see
    ``step.py``. ``slots`` is accepted for backwards compatibility and
    ignored — the packed-plane engine has no slot pool to size.
    ``epoch_len`` (default ``cfg.epoch_len``) buckets the telemetry planes.
    ``broken_links_per_workload`` overrides ``cfg.broken_links`` per
    workload (entries may be None = use the config's set) — routes are
    planned on each workload's degraded topology at compile time while the
    whole grid still runs as one batch (the engine itself is
    fault-agnostic; trace replay uses this for mid-run link failures).
    """
    del slots  # legacy slot-pool hint: capacity is structural now
    topo = make_topology(
        cfg.topology, cfg.n, cfg.m, cfg.broken_links, cfg.topology_params
    )
    if algos is None:
        algos = tuple(available_algorithms(topo))
    resolved = [get_algorithm(a) for a in algos]
    warmup = cfg.warmup if warmup is None else warmup
    drain_grace = cfg.drain_grace if drain_grace is None else drain_grace
    epoch_len = cfg.epoch_len if epoch_len is None else int(epoch_len)
    if broken_links_per_workload is not None and len(
        broken_links_per_workload
    ) != len(workloads):
        raise ValueError(
            "broken_links_per_workload needs one entry per workload "
            f"({len(broken_links_per_workload)} != {len(workloads)})"
        )
    from ...kernels.noc_cycle import resolve_backend

    backend = resolve_backend(
        cfg.xsim_backend if backend is None else backend
    )
    traffics: list[CompiledTraffic] = []
    with span("repro.xsim.lower"):
        for wi, wl in enumerate(workloads):
            wcfg = cfg
            if broken_links_per_workload is not None:
                faults = broken_links_per_workload[wi]
                if faults is not None:
                    wcfg = dataclasses.replace(
                        cfg, broken_links=tuple(faults)
                    )
            for algo in resolved:
                traffics.append(
                    compile_workload(
                        wcfg, wl, algo,
                        pad_packets=pad_packets, pad_stages=pad_stages,
                        cost_model=cost_model,
                    )
                )
        ref, stacked = stack_traffic(traffics)
    T = max(wl.horizon for wl in workloads) + drain_grace
    ND = int(stacked["dslot"].max()) + 1  # flat delivery-slot space
    # the engine's static F is the largest worm in the batch: it sizes the
    # age-key multiplier and the BD>=F credit shortcut; per-packet lengths
    # ride the compiled ``flits`` table
    F = max(cfg.flits_per_packet, int(stacked["flits"].max()))
    stacked_j = {k: jnp.asarray(v) for k, v in stacked.items()}
    with span("repro.xsim.run"):
        out, devices = _run_sharded(
            stacked_j,
            T=T, F=F, V=cfg.vcs_per_class,
            BD=cfg.buffer_depth, L=ref.num_links, NN=ref.num_nodes, ND=ND,
            kind=ref.kind, n=ref.n, m=ref.m, params=ref.params,
            backend=backend, epoch_len=epoch_len,
        )
        out = jax.tree_util.tree_map(np.asarray, out)  # blocks until ready
    # scatter-compact flat delivery times -> the (B, P, S) view the results
    # object (and the parity tests) consume
    ds = stacked["dslot"]
    B = ds.shape[0]
    dtime = np.where(
        ds >= 0,
        out["dtime"][np.arange(B)[:, None, None], np.clip(ds, 0, ND)],
        -1,
    ).astype(np.int32)
    return XSimResults(
        cfg=cfg,
        algos=tuple(a.name for a in resolved),
        horizons=np.array([wl.horizon for wl in workloads]),
        warmup=warmup,
        cycles=T,
        slots=_capacity(cfg, ref.num_nodes, ref.num_links),
        traffic=stacked,
        dtime=dtime,
        ctr=out["ctr"],
        crel=out["crel"],
        epoch_len=epoch_len,
        lutil=out["lutil"],
        rconf=out["rconf"],
        backend=backend,
        devices=devices,
    )


def latency_vs_rate_batched(
    cfg: NoCConfig,
    rates: list[float],
    algos: tuple | None = None,
    cycles: int = 1500,
    seed: int = 0,
    **kw,
) -> tuple[dict[str, list[tuple[float, float]]], XSimResults]:
    """The fig6 latency-vs-injection-rate sweep as one batched call.

    Returns ``({algo: [(rate, avg_latency), ...]}, results)``. ``algos``
    defaults to every registered algorithm supporting the topology. Unlike
    the host-sim ``latency_vs_rate`` there is no early saturation cut-off:
    every (rate, algo) point costs the same inside the vmapped scan.
    """
    from ..traffic import synthetic_workload

    wls = [synthetic_workload(cfg, r, cycles, seed=seed) for r in rates]
    res = xsimulate(cfg, wls, algos, **kw)
    curves = {
        algo: [(rates[w], res.avg_latency(w, a)) for w in range(len(rates))]
        for a, algo in enumerate(res.algos)
    }
    return curves, res
