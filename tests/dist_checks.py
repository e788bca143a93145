"""Multi-device distribution checks. Run with 8 forced host devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 python tests/dist_checks.py

Invoked as a subprocess by tests/test_dist.py so the main pytest process
keeps its single-device view.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

import dataclasses  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402


def check_ep_matches_dense():
    """shard_map EP MoE == dense MoE path on a 2x4 (data, model) mesh."""
    from repro.configs import SMOKES
    from repro.dist.ep import moe_apply_ep
    from repro.models.moe import moe_apply_dense, moe_init

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = SMOKES["moonshot-v1-16b-a3b"]
    cfg = cfg.scaled(
        moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
    )  # no drops => exact equality modulo reduction order
    p, _ = moe_init(jax.random.PRNGKey(0), cfg)
    # tokens shard over B when the 8 shards tile it, else over S
    for shape, spec in (((8, 16), P(("data", "model"))),
                        ((4, 16), P(None, ("data", "model")))):
        x = jax.random.normal(jax.random.PRNGKey(1), (*shape, cfg.d_model))
        y_dense, aux_d = moe_apply_dense(p, x, cfg)
        with jax.set_mesh(mesh):
            y_ep, aux_e = moe_apply_ep(p, x, cfg, mesh)
        assert y_ep.sharding.spec == spec, (shape, y_ep.sharding)
        np.testing.assert_allclose(
            np.asarray(y_dense), np.asarray(y_ep), atol=2e-5
        )
        print(f"ep == dense: OK (B, S) = {shape}, tokens on {spec}")


def check_dpm_broadcast():
    """DPM ppermute schedule delivers the rank-0 payload to every rank."""
    from repro.dist.multicast import apply_schedule, dp_broadcast_schedule

    mesh = jax.make_mesh((8,), ("data",))
    sched = dp_broadcast_schedule(8, "DPM")

    x = jnp.arange(8, dtype=jnp.float32) * 100.0  # rank i holds 100*i

    def fn(xl):
        return apply_schedule(xl, sched, "data")

    out = jax.shard_map(
        fn, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False,
    )(x)
    np.testing.assert_array_equal(np.asarray(out), np.zeros(8))
    print("dpm broadcast: OK (all ranks got rank-0 payload)")


def check_compressed_psum():
    """int8 RS+AG all-reduce ~= psum; error feedback shrinks the residual."""
    from repro.dist.compress import compressed_psum

    mesh = jax.make_mesh((8,), ("data",))
    g = jax.random.normal(jax.random.PRNGKey(0), (8, 1024))

    def fn(gl):
        gl = gl[0]
        err = jnp.zeros_like(gl)
        s1, e1 = compressed_psum(gl, err, "data")
        exact = jax.lax.psum(gl, "data")
        return (
            s1[None],
            exact[None],
            jnp.sum(jnp.abs(e1))[None],
        )

    s1, exact, errn = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=P("data"),
        out_specs=(P("data"), P("data"), P("data")),
        check_vma=False,
    )(g)
    rel = float(
        jnp.abs(s1 - exact).max() / jnp.abs(exact).max()
    )
    assert rel < 0.05, rel
    print(f"compressed psum: OK (rel err {rel:.4f})")


def check_pipeline_forward():
    """4-stage GPipe == sequential layer application."""
    from repro.dist.pipeline import pipeline_apply

    mesh = jax.make_mesh((4,), ("pipe",))
    L, d = 8, 16
    ws = jax.random.normal(jax.random.PRNGKey(0), (L, d, d)) * 0.1

    def layer_fn(w, x):
        return jnp.tanh(x @ w)

    x = jax.random.normal(jax.random.PRNGKey(1), (8, 4, d))  # 8 microbatches
    stage_params = ws.reshape(4, L // 4, d, d)
    out = pipeline_apply(layer_fn, stage_params, x, mesh, axis="pipe")

    ref = x
    for i in range(L):
        ref = layer_fn(ws[i], ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    print("pipeline forward: OK")

    # grads flow through the pipeline; make_mesh axes are Explicit, so the
    # eager backward pass needs the mesh as context to place the replicated
    # cotangents
    def loss(sp):
        return jnp.sum(pipeline_apply(layer_fn, sp, x, mesh, axis="pipe") ** 2)

    with jax.set_mesh(mesh):
        gr = jax.grad(loss)(stage_params)
    assert bool(jnp.isfinite(gr).all()) and float(jnp.abs(gr).max()) > 0
    print("pipeline grad: OK")


def check_zero1_shardings():
    from repro.configs import SMOKES
    from repro.dist.sharding import param_shardings, zero1_shardings
    from repro.models import RunConfig
    from repro.models.model import abstract_init

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    cfg = SMOKES["smollm-135m"]
    run = RunConfig()
    shapes, specs = abstract_init(cfg, run)
    ps = param_shardings(specs, mesh)
    zs = zero1_shardings(specs, shapes, mesh)
    n_extra = 0
    for a, b in zip(jax.tree.leaves(ps), jax.tree.leaves(zs)):
        sa = [x for x in a.spec if x is not None]
        sb = [x for x in b.spec if x is not None]
        assert set(sa) <= set(map(str, sb)) | set(sb) or len(sb) >= len(sa)
        if len(sb) > len(sa):
            n_extra += 1
    assert n_extra > 0, "zero1 must shard extra dims over data"
    print(f"zero1 shardings: OK ({n_extra} leaves gained a data shard)")


def check_ep_dispatch_uses_dpm_schedule():
    """EP dispatch is lowered through the DPM multicast schedule: the
    traced program runs ppermute rounds, not a bare all_to_all."""
    from repro.configs import SMOKES
    from repro.dist.ep import moe_apply_ep
    from repro.dist.multicast import alltoall_schedule
    from repro.models.moe import moe_init

    mesh = jax.make_mesh((2, 4), ("data", "model"))
    sched = alltoall_schedule(4, "DPM")
    pairs = sorted(pr for rnd in sched.rounds for pr in rnd)
    assert pairs == sorted(
        (i, j) for i in range(4) for j in range(4) if i != j
    ), pairs

    cfg = SMOKES["moonshot-v1-16b-a3b"]
    p, _ = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))
    jaxpr = str(
        jax.make_jaxpr(lambda q, z: moe_apply_ep(q, z, cfg, mesh)[0])(p, x)
    )
    assert "ppermute" in jaxpr, "EP dispatch must run the schedule's rounds"
    assert "all_to_all" not in jaxpr, "EP dispatch must not use bare all_to_all"
    n_perm = jaxpr.count("ppermute")
    assert n_perm >= 2 * sched.num_rounds, (n_perm, sched.num_rounds)
    print(
        f"ep dispatch schedule: OK (DPM, {sched.num_rounds} rounds, "
        f"{n_perm} ppermutes in jaxpr)"
    )


if __name__ == "__main__":
    assert jax.device_count() == 8, jax.devices()
    check_dpm_broadcast()
    check_compressed_psum()
    check_pipeline_forward()
    check_zero1_shardings()
    check_ep_matches_dense()
    check_ep_dispatch_uses_dpm_schedule()
    print("ALL DIST CHECKS PASSED")
