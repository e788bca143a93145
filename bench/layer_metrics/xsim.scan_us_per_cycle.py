"""Device time of the cycle scan (``_run_batch``) per simulated cycle, in
microseconds, for the whole batch of (rate, algorithm) pairs."""


def read(run):
    if run.trace is None:
        return None
    s, launches = run.trace.module_seconds("_run_batch")
    return s / launches / run.counters["cycles"] * 1e6 if launches else None
