"""Launch-layer tests: HLO analyzer, mesh/spec builders (1-device view),
the compile-cache helper and the benchmark runner's exit code."""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo import analyze, collective_bytes


def test_analyzer_counts_scan_trip_counts():
    """cost_analysis() counts a scan body once; analyze() multiplies by the
    trip count (the whole reason the module exists)."""
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)

    def scanned(w, x):
        def body(h, wi):
            return jnp.tanh(h @ wi), None

        h, _ = jax.lax.scan(body, x, w)
        return h

    def unrolled(w, x):
        h = x
        for i in range(8):
            h = jnp.tanh(h @ w[i])
        return h

    a_scan = analyze(jax.jit(scanned).lower(w, x).compile().as_text())
    a_unrl = analyze(jax.jit(unrolled).lower(w, x).compile().as_text())
    expect = 2 * 32 * 128 * 128 * 8
    assert abs(a_scan["flops"] - a_unrl["flops"]) / a_unrl["flops"] < 0.05
    assert a_scan["flops"] >= expect
    xla = jax.jit(scanned).lower(w, x).compile().cost_analysis()
    assert xla["flops"] < expect / 4  # demonstrates the undercount


def test_analyzer_dus_inplace():
    """In-place cache update: bytes ~ update size, not buffer size."""
    buf = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    upd = jax.ShapeDtypeStruct((1, 1024), jnp.float32)

    def f(buf, upd):
        return jax.lax.dynamic_update_slice(buf, upd, (5, 0))

    a = analyze(jax.jit(f, donate_argnums=0).lower(buf, upd).compile().as_text())
    assert a["bytes"] < 1024 * 1024 * 4 / 4  # far less than the full buffer


def test_collective_bytes_on_sharded_program():
    devs = jax.device_count()
    if devs < 2:
        pytest.skip("needs >1 device (run under forced host device count)")


def test_production_mesh_requires_512_devices():
    """make_production_mesh needs the dry-run env; verify the error path."""
    from repro.launch.mesh import make_production_mesh

    if jax.device_count() >= 512:
        m = make_production_mesh()
        assert m.shape == {"data": 16, "model": 16}
    else:
        with pytest.raises(Exception):
            make_production_mesh()


def test_model_flops_accounting():
    from repro.configs import ARCHS, SHAPES
    from repro.launch.specs import model_flops, param_counts
    from repro.models import RunConfig

    run = RunConfig()
    c = param_counts(ARCHS["deepseek-v2-236b"], run)
    # active ~ 21-22B of 236B for top-6/160 + shared
    assert 15e9 < c["active"] < 35e9 < 200e9 < c["total"] < 250e9
    mf_train = model_flops(ARCHS["smollm-135m"], SHAPES["train_4k"], run)
    n = param_counts(ARCHS["smollm-135m"], run)["total"]
    assert abs(mf_train - 6 * n * 256 * 4096) / mf_train < 1e-6


def test_importing_dryrun_leaves_xla_flags_alone():
    before = os.environ.get("XLA_FLAGS")
    import repro.launch.dryrun  # noqa: F401

    assert os.environ.get("XLA_FLAGS") == before


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
print(jax.config.jax_compilation_cache_dir)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
"""


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "checkout"])
def test_compile_cache_dir(env_dir, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and is the only directory written;
    without it the cache sits at <checkout>/.jax_cache."""
    from repro.launch.compile_cache import CACHE_DIR

    root = pathlib.Path(__file__).resolve().parents[1]
    assert CACHE_DIR == root / ".jax_cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"))
    want = str(tmp_path / "cc") if env_dir else str(CACHE_DIR)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    code = _CACHE_PROBE
    if not env_dir:  # check where it points without compiling into it
        code = code.split("jax.config.update(")[0]
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300, cwd=tmp_path,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]
    if env_dir:
        assert any((tmp_path / "cc").iterdir())
        assert [p.name for p in tmp_path.iterdir()] == ["cc"]


def test_benchmark_runner_exits_nonzero_when_a_suite_raises(
    monkeypatch, capsys
):
    import benchmarks.torus_planner as suite
    from benchmarks import run as bench_run
    from repro.launch import compile_cache

    def boom(**_kw):
        raise RuntimeError("suite failed")

    monkeypatch.setattr(suite, "run", boom)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["run", "--only", "torus"])
    with pytest.raises(SystemExit) as exc:
        bench_run.main()
    assert exc.value.code not in (0, None)
    assert "torus/ERROR,0,RuntimeError:suite failed" in capsys.readouterr().out
