"""The cycle scan's share of its HBM roofline, in percent: the state
planes one cycle of the batch reads and writes (``bench.roofline``) at the
chip's HBM bandwidth, over the scan's device time per cycle."""
from bench import roofline


def read(run):
    if run.trace is None:
        return None
    s, launches = run.trace.module_seconds("_run_batch")
    if not launches:
        return None
    c = run.counters
    need = roofline.xsim_cycle_bytes(c["batch"], c["links"], c["vcs"],
                                     c["depth"], c["nodes"])
    per_cycle = s / launches / c["cycles"]
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / per_cycle
