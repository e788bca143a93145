"""Pallas kernel sweeps: shapes x dtypes vs pure-jnp oracles (interpret=True).

Per the deliverable: for each kernel, sweep shapes/dtypes and
assert_allclose against the ref.py oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd.ops import ssd_scan_pallas
from repro.kernels.ssd.ref import ssd_reference


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_SHAPES = [
    # (B, S, H, KH, D, bq, bk, window)
    (1, 128, 4, 4, 64, 64, 64, None),  # MHA
    (2, 256, 8, 2, 64, 128, 128, None),  # GQA 4:1
    (2, 256, 8, 1, 32, 64, 128, None),  # MQA
    (1, 200, 4, 2, 64, 64, 64, None),  # ragged (pad path)
    (2, 256, 4, 4, 128, 64, 64, 96),  # sliding window
    (1, 512, 2, 2, 64, 128, 256, 128),  # window, rectangular blocks
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(shape, dtype):
    B, S, H, KH, D, bq, bk, window = shape
    key = jax.random.PRNGKey(hash(shape) & 0xFFFF)
    q = jax.random.normal(key, (B, S, H, D), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KH, D), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KH, D), dtype)
    out = flash_attention(
        q, k, v, window=window, block_q=bq, block_k=bk, interpret=True
    )
    ref = attention_ref(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        window=window,
    ).transpose(0, 2, 1, 3)
    atol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=atol
    )


def test_flash_attention_q_offset_decode_chunk():
    """Chunked decode/extension: q_offset shifts the causal diagonal."""
    key = jax.random.PRNGKey(7)
    B, H, D = 1, 2, 64
    Sk, Sq, off = 256, 64, 192  # queries are positions 192..255
    q = jax.random.normal(key, (B, Sq, H, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Sk, H, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Sk, H, D))
    out = flash_attention(q, k, v, q_offset=off, block_q=64, block_k=64,
                          interpret=True)
    ref = attention_ref(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        q_offset=off,
    ).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------
SSD_SHAPES = [
    # (B, S, H, P, G, N, chunk)
    (1, 64, 2, 8, 1, 16, 16),
    (2, 128, 4, 16, 2, 8, 32),
    (2, 96, 4, 16, 2, 8, 32),  # ragged
    (1, 256, 8, 32, 1, 64, 64),  # mamba2-like ratios
]


@pytest.mark.parametrize("shape", SSD_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_sweep(shape, dtype):
    B, S, H, P, G, N, chunk = shape
    key = jax.random.PRNGKey(hash(shape) & 0xFFFF)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, S, G, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, G, N), dtype)
    y, h = ssd_scan_pallas(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, hr = ssd_reference(x, dt, A, Bm, Cm)
    atol = 5e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(yr, np.float32), atol=atol
    )
    np.testing.assert_allclose(
        np.asarray(h, np.float32), np.asarray(hr, np.float32), atol=atol
    )


# ---------------------------------------------------------------------------
# fused wormhole-cycle kernel (kernels/noc_cycle): ref <-> Pallas parity
# ---------------------------------------------------------------------------
def _cycle_fixture(cfg, rate, cycles, seed, algo):
    """Compile one workload down to the fused engine's operands."""
    from repro.kernels.noc_cycle import ref as R
    from repro.noc import synthetic_workload
    from repro.noc.xsim.compile import (
        compile_workload,
        geometry_tables,
        stack_traffic,
    )

    wl = synthetic_workload(cfg, rate, cycles, seed=seed)
    ct = compile_workload(cfg, wl, algo)
    refm, stacked = stack_traffic([ct])
    tb = {
        f: jnp.asarray(stacked[f][0]) for f in R.TABLE_FIELDS
    }
    geom = geometry_tables(
        refm.kind, refm.n, refm.m, refm.params, cfg.vcs_per_class
    )
    params = dict(
        F=cfg.flits_per_packet, V=cfg.vcs_per_class, BD=cfg.buffer_depth,
        L=refm.num_links, NN=refm.num_nodes,
    )
    C = stacked["child_parent"].shape[1]
    planes = R.init_planes(
        refm.num_links, 2 * cfg.vcs_per_class, refm.num_nodes, C
    )
    return R, tb, geom, params, planes, stacked["link"].shape[2]


CYCLE_TOPOS = [
    ("mesh", dict(n=4, dest_range=(2, 4))),
    ("torus", dict(n=4, topology="torus", dest_range=(2, 4))),
    ("degraded", dict(n=4, dest_range=(2, 4),
                      broken_links=(((1, 1), (2, 1)),))),
]


@pytest.mark.parametrize(
    "topo_kw", [c[1] for c in CYCLE_TOPOS], ids=[c[0] for c in CYCLE_TOPOS]
)
def test_noc_cycle_pallas_lockstep_state_parity(topo_kw):
    """The fused kernel must reproduce the jnp reference *per cycle*: every
    packed state plane bit-equal after each single-cycle chunk, and the
    packed arrival-event row decoding to the reference arrival tuple."""
    from repro.noc import NoCConfig
    from repro.kernels.noc_cycle.noc_cycle import make_chunk_runner

    cfg = NoCConfig(**topo_kw)
    R, tb, geom, params, planes, S = _cycle_fixture(cfg, 0.06, 30, 5, "DPM")
    F = params["F"]
    runner = jax.jit(
        make_chunk_runner(geom, S=S, Tc=1, interpret=True, **params)
    )
    step_r = jax.jit(
        lambda st, t: R.cycle_core(st, tb, t, geom, **params)
    )
    st_r, st_p = planes, planes
    for t in range(24):
        st_r, (aval, apid, astage, afid) = step_r(st_r, jnp.int32(t))
        st_p, ev = runner(st_p, tb, t)
        for name, a, b in zip(R.CycleState._fields, st_r, st_p):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=f"plane {name} @ t={t}"
            )
        ev_ref = np.where(
            np.asarray(aval),
            1 + (np.asarray(apid) * S + np.asarray(astage)) * 4
            + (np.asarray(afid) == F - 1) * 2 + (np.asarray(afid) == 0),
            0,
        )
        np.testing.assert_array_equal(np.asarray(ev[0]), ev_ref, err_msg=f"ev @ t={t}")
    assert int(st_r.ctr[0]) > 0  # traffic actually moved


@given(st.integers(0, 10**6), st.integers(0, 6), st.integers(0, 2))
@settings(max_examples=3, deadline=None)
def test_noc_cycle_pallas_chunked_parity_randomized(seed, ri, ti):
    """Property: for randomized traffic on mesh/torus/degraded, a chunked
    fused-kernel run (several cycles per launch) ends in exactly the
    reference scan's state."""
    from repro.noc import NoCConfig
    from repro.kernels.noc_cycle.noc_cycle import make_chunk_runner

    cfg = NoCConfig(**CYCLE_TOPOS[ti][1])
    rate = 0.02 + 0.01 * ri
    R, tb, geom, params, planes, S = _cycle_fixture(cfg, rate, 20, seed, "DPM")
    Tc, chunks = 8, 2

    @jax.jit
    def ref_end(planes):
        def body(st, t):
            st, _ = R.cycle_core(st, tb, t, geom, **params)
            return st, None
        st, _ = jax.lax.scan(
            body, planes, jnp.arange(Tc * chunks, dtype=jnp.int32)
        )
        return st

    st_r = ref_end(planes)
    runner = jax.jit(
        make_chunk_runner(geom, S=S, Tc=Tc, interpret=True, **params)
    )
    st_p = planes
    for c in range(chunks):
        st_p, _ = runner(st_p, tb, c * Tc)
    for name, a, b in zip(R.CycleState._fields, st_r, st_p):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"plane {name} seed={seed} rate={rate} topo={ti}",
        )
