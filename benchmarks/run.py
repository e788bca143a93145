"""Benchmark harness — one module per paper table/figure + beyond-paper.

Prints ``name,us_per_call,derived`` CSV rows. ``--quick`` trims sweep sizes.
A suite that raises prints an ``<suite>/ERROR`` row, the remaining suites
still run, and the process exits non-zero.
The system's speed on the chip is measured by ``bench/`` (see PERF.md),
not by wall-time here.
"""
from __future__ import annotations

import argparse
import inspect
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated module names "
        "(fig6,fig7,fig8,partition,tpu,torus,dist,fault,trace,telemetry,"
        "topo3d)",
    )
    ap.add_argument(
        "--algos",
        default=None,
        help="comma-separated routing algorithms (validated against the "
        "repro.core.algo registry; default: each suite's registry query)",
    )
    args = ap.parse_args()

    algos = None
    if args.algos:
        from repro.core.algo import get_algorithm

        # unknown names raise here, listing what is registered
        algos = [get_algorithm(a.strip()).name for a in args.algos.split(",")]

    from . import (
        dist_collectives,
        fault_resilience,
        fig6_latency,
        fig7_power,
        fig8_traces,
        partition_quality,
        telemetry_calibration,
        topo3d_sweep,
        torus_planner,
        tpu_multicast,
        trace_replay,
    )

    suites = {
        "fig6": fig6_latency.run,
        "fig7": fig7_power.run,
        "fig8": fig8_traces.run,
        "partition": partition_quality.run,
        "tpu": tpu_multicast.run,
        "torus": torus_planner.run,
        "dist": dist_collectives.run,
        "fault": fault_resilience.run,
        "trace": trace_replay.run,
        "telemetry": telemetry_calibration.run,
        "topo3d": topo3d_sweep.run,
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    unknown = only - set(suites)
    if unknown:
        # a typo'd --only used to run nothing silently; fail loudly instead
        ap.error(
            f"unknown suite(s) {sorted(unknown)}; available: "
            f"{','.join(suites)}"
        )
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites.items():
        if name not in only:
            continue
        kwargs = {"quick": args.quick}
        if algos is not None and "algos" in inspect.signature(fn).parameters:
            kwargs["algos"] = algos
        t0 = time.monotonic()
        try:
            rows = fn(**kwargs)
        except Exception as e:  # noqa: BLE001
            print(f"{name}/ERROR,0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
            continue
        for r in rows:
            print(f"{r[0]},{r[1]:.1f},{r[2]}", flush=True)
        print(
            f"{name}/_suite_wall,{(time.monotonic() - t0) * 1e6:.0f},ok",
            flush=True,
        )
    if failed:
        sys.exit(f"suites raised: {','.join(failed)}")


if __name__ == "__main__":
    main()
