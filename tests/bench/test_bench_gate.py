"""The benchmark refuses to run, and prints no result, without its chips."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]


def test_device_gate_refuses_a_cpu():
    from bench import harness

    with pytest.raises(SystemExit, match="no TPU"):
        harness.device_gate(1)


@pytest.mark.parametrize("cell", ["plan.mesh8x8.stream", "plan.mesh32x32.bulk"])
def test_run_exits_nonzero_with_no_result_on_a_cpu(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(2**40 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_unknown_workload_is_refused():
    from bench import harness

    with pytest.raises(SystemExit, match="unknown workload"):
        harness.cell("no.such.cell")
