"""The DPM merge's share of its HBM roofline, in percent: the least bytes
a dispatch of Algorithm 1 must move (``bench.roofline``) at the chip's HBM
bandwidth, over the merge's device time per dispatch. Bytes bound it: the
merge is integer and float32 compares and sums, with no published peak."""
import math

from bench import roofline


def read(run):
    if run.trace is None:
        return None
    s, launches = run.trace.module_seconds("dpm_plan_exact")
    if not launches:
        return None
    c = run.counters
    plans_per_dispatch = c["batched_plans"] / max(1, c["dispatches"])
    batch = 1 << max(0, math.ceil(plans_per_dispatch) - 1).bit_length()
    need = roofline.dpm_merge_bytes(batch, c["n"] ** 2)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / (s / launches)
