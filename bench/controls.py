"""The control of the planner cells' comparison, run through the harness.

    python3 bench/controls.py --workload <cell> --seeds 1,2,3 [--seconds 5]

The control is the benchmark's reference planner put in the program's
place, with one step taken that would tempt a later change: DPM without
its dual-path pricing, so that every partition goes out in
multiple-unicast mode. Every plan the batched planner would compute
(``BatchPlanner._plan_batch``, which serves ``bulk_plan``, the plan server
and xsim's lowering alike) is replaced by the control's plan of the same
instance. Everything else runs as ``bench/run.py`` runs it: the cell's own
sizes and load, a window of ``--seconds``, the same comparison. For each
seed it prints one JSON line with the numbers compared and ``correct``,
which has to read false.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent


@contextlib.contextmanager
def no_dual_path():
    """Within the block, the batched planner answers with the reference's
    plans of DPM without dual-path pricing."""
    from repro.core.batch_planner import BatchPlanner
    from repro.core.planner import MulticastPlan, PacketPath

    from bench.ref import planner as ref

    real = BatchPlanner._plan_batch

    def control(self, keys):
        g = ref.Mesh(self.topo.n)
        return [
            MulticastPlan(self._algo.name, tuple(src), list(dests), [
                PacketPath(list(hops), list(dl), parent)
                for hops, dl, parent in ref.plan_dpm(
                    g, tuple(src), sorted(map(tuple, dests)), dual_path=False)
            ])
            for src, dests in keys
        ]

    BatchPlanner._plan_batch = control
    try:
        yield
    finally:
        BatchPlanner._plan_batch = real


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    c = harness.cell(args.workload)
    devices = harness.device_gate(c.chips)
    harness.enable_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        with no_dual_path():
            line = harness.execute(c, seed, args.seconds, False, devices)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": line["correct"],
                          "attempted": line["attempted"],
                          "checks": line["checks"]}), flush=True)


if __name__ == "__main__":
    main()
