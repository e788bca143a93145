"""Shared set-up of the benchmark's tests: the checkout root on the path,
and small cells that a CPU runs in seconds."""
from __future__ import annotations

import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# test sizes of each cell: the same entry, traffic and comparisons, cut
SMALL = {
    "plan.mesh8x8.stream": {
        "traffic": {"rate_per_s": 400, "fill": 2000, "server_warm": 64,
                    "max_batch": 8, "check_sample": 300},
    },
    "plan.mesh32x32.bulk": {
        "config": {"n": 8},
        "traffic": {"batch": 64, "batches": 2, "check_sample": 128},
    },
    "xsim.mesh8x8.fig6": {
        "config": {"n": 4},
        "traffic": {"rates": [0.02, 0.04], "algorithms": ["MU", "DPM"],
                    "injection_cycles": 60, "warmup": 0, "drain_grace": 400,
                    "dest_range": [2, 4], "dpm_checked_up_to_rate": 0.04},
    },
}


# cells whose files are kept but that the manifest leaves out, with the
# workload entry that would name them there
PREPARED = {
    "xsim.mesh8x8.fig6": {"name": "xsim.mesh8x8.fig6", "config": "mesh8x8-tableI",
                          "traffic": "fig6", "chips": 1},
}


@pytest.fixture
def small_run():
    """``run(cell_name, seed) -> result line`` of a cut-down cell on the
    CPU, with the harness's look for a chip skipped."""
    import jax

    from bench import harness

    def run(name: str, seed: int = 2**33 + 5, seconds: float = 0.4):
        man = harness.manifest()
        c = (harness.build_cell(PREPARED[name], man) if name in PREPARED
             else harness.cell(name, man))
        c.config.update(SMALL[name].get("config", {}))
        c.traffic.update(SMALL[name].get("traffic", {}))
        return harness.execute(c, seed, seconds, False, jax.devices()[:1])

    return run
