"""Find the highest request rate the plan server sustains, on the chip.

    python3 bench/rate_sweep.py --workload plan.mesh8x8.stream --seed <n> \\
        --seconds <s> --rates 2000,4000,8000

Runs the cell's entry once per rate in one process (one compilation) and
prints, per rate, the p50 and p99 latency, the p99 of the first and last
fifth of the window (a backlog that grows shows as the last above the
first), how late the generator ran, and whether the answers were correct.
The cell's traffic file then takes a fixed rate below the highest one
sustained; the benchmark itself never searches.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    c = harness.cell(args.workload)
    devices = harness.device_gate(c.chips)
    harness.enable_compile_cache()
    for k, rate in enumerate(float(r) for r in args.rates.split(",")):
        c.traffic["rate_per_s"] = rate
        ctx = harness.Ctx(c, args.seed + k, args.seconds, False, devices)
        out = c.entry.run(ctx)
        print(json.dumps({"rate_per_s": rate, **out.metrics, **out.counters,
                          "failed": out.failed, "attempted": out.attempted,
                          "correct": all(v <= lim for _, v, lim in out.checks)}),
              flush=True)


if __name__ == "__main__":
    main()
