"""Share of the traced window in which no operation ran on the device, in
percent (averaged over the chips used)."""


def read(run):
    s = run.trace.idle_share() if run.trace is not None else None
    return None if s is None else 100.0 * s
