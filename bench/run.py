"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix, each a file under ``bench/``. The run checks for the TPUs the
cell needs before anything else, builds every input from ``--seed``, warms
every shape, measures for ``--seconds``, checks what the timed path
produced against the benchmark's own reference, and prints one JSON object
as the last line of standard output: the end-to-end metrics with
``--trace 0``, the per-layer metrics from a profiler trace with
``--trace 1``. The numbers compared, each beside its limit, are the last
lines of standard error and the result's last key.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

T0 = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    harness.T_START = T0
    c = harness.cell(args.workload)
    devices = harness.device_gate(c.chips)
    harness.log(f"bench: {args.workload} on {len(devices)} x "
                f"{devices[0].device_kind}; compile cache "
                f"{harness.enable_compile_cache()}")
    import repro  # noqa: F401  (the system under test must be here)

    watch = harness.CompileWatch()
    line = harness.execute(c, args.seed, args.seconds, bool(args.trace),
                           devices, watch)
    for name, chk in line["checks"].items():
        harness.log(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
