"""Ahead-of-time compiles of the served device programs for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` topology, and refuses what the chip's compiler
would refuse (an op Mosaic cannot lower, a program that does not fit). The
topology is described inside module-scoped fixtures, never at import, so
every test worker collects the same tests and only the one that runs this
file loads the TPU library. Nothing here runs a program or measures time.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """Compiles for a described chip cannot be read back from the
    persistent cache, so keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> int:
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
    return used


@pytest.mark.parametrize(
    "side,k", [(8, 16), (32, 16), (8, 32), (32, 32), (32, 64)]
)
def test_dpm_plan_exact_compiles_for_v5e(side, k, one_chip,
                                         no_persistent_cache):
    """The batched planner's dispatch at DISPATCH_CHUNK, on the 8x8 serving
    fabric and at MAX_ARENA_NODES (32x32), with the paper's fanouts (16
    destination slots) and with fanouts past 16 (32 and 64 slots)."""
    from repro.core.batch_planner import DISPATCH_CHUNK, MAX_ARENA_NODES
    from repro.kernels.dpm_cost.ops import dpm_plan_exact

    B, NN = DISPATCH_CHUNK, side * side
    assert NN <= MAX_ARENA_NODES
    i32, f32 = jnp.int32, jnp.float32
    args = (
        _spec((B, k), i32, one_chip),  # destination slots
        _spec((B,), i32, one_chip),  # sources
        _spec((NN, NN), i32, one_chip),  # wedge membership
        _spec((NN,), i32, one_chip),  # snake labels
        _spec((NN, NN), i32, one_chip),  # hop distances
        _spec((NN, NN), f32, one_chip),  # unicast prices
        _spec((NN, NN), f32, one_chip),  # HIGH label-route prices
        _spec((NN, NN), f32, one_chip),  # LOW label-route prices
    )
    compiled = dpm_plan_exact.lower(*args, np_=8, overhead=0.0).compile()
    _fits(compiled)


@pytest.mark.parametrize("side", [8, 32])
def test_degraded_dpm_plan_exact_compiles_for_v5e(side, one_chip,
                                                  no_persistent_cache):
    """The dispatch on a degraded fabric: the chain-pass bitmasks
    (``label_chain_passes``) added, at the paper's fanouts."""
    from repro.core.batch_planner import DISPATCH_CHUNK, MIN_SLOTS
    from repro.kernels.dpm_cost.ops import dpm_plan_exact

    B, NN, W = DISPATCH_CHUNK, side * side, -(-side * side // 32)
    i32, f32 = jnp.int32, jnp.float32
    args = (
        _spec((B, MIN_SLOTS), i32, one_chip),
        _spec((B,), i32, one_chip),
        _spec((NN, NN), i32, one_chip),
        _spec((NN,), i32, one_chip),
        _spec((NN, NN), i32, one_chip),
        _spec((NN, NN), f32, one_chip),
        _spec((NN, NN), f32, one_chip),
        _spec((NN, NN), f32, one_chip),
        _spec((NN, NN, W), i32, one_chip),  # HIGH chain-pass bitmasks
        _spec((NN, NN, W), i32, one_chip),  # LOW chain-pass bitmasks
    )
    compiled = dpm_plan_exact.lower(*args, np_=8, overhead=0.0).compile()
    _fits(compiled)


def test_xsim_ref_backend_compiles_for_v5e_16x16(one_chip,
                                                 no_persistent_cache):
    """xsim's batched ``lax.scan`` engine as ``xsimulate`` builds it for a
    saturated 16x16 DPM batch, compiled for the chip."""
    from repro.noc import NoCConfig, synthetic_workload
    from repro.noc.xsim.compile import compile_workload, stack_traffic
    from repro.noc.xsim.run import _run_batch

    cfg = NoCConfig(n=16, dest_range=(10, 16), warmup=0, drain_grace=100)
    wls = [synthetic_workload(cfg, 0.05, 40, seed=s) for s in (0, 1)]
    ref, stacked = stack_traffic(
        [compile_workload(cfg, wl, "DPM") for wl in wls]
    )
    shapes = {
        k: _spec(v.shape, v.dtype, one_chip) for k, v in stacked.items()
    }
    compiled = _run_batch.lower(
        shapes,
        T=max(wl.horizon for wl in wls) + cfg.drain_grace,
        F=max(cfg.flits_per_packet, int(stacked["flits"].max())),
        V=cfg.vcs_per_class, BD=cfg.buffer_depth, L=ref.num_links,
        NN=ref.num_nodes, ND=int(stacked["dslot"].max()) + 1,
        kind=ref.kind, n=ref.n, m=ref.m, params=ref.params, backend="ref",
        epoch_len=cfg.epoch_len,
    ).compile()
    _fits(compiled)
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("requested", [None, "auto"])
def test_auto_backend_never_picks_pallas(requested):
    """The fused Pallas cycle kernel does not lower through Mosaic, so the
    automatic choice is the ``ref`` scan on every platform."""
    from repro.kernels.noc_cycle import resolve_backend

    assert resolve_backend(requested) == "ref"
    assert resolve_backend("pallas") == "pallas"  # explicit stays callable
