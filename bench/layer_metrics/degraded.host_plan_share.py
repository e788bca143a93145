"""Share of the window's plans planned on the host fallback path
(``ArenaInfo`` ``host_plans`` over all plans planned), in percent."""


def read(run):
    c = run.counters
    total = c.get("host_plans", 0) + c.get("batched_plans", 0)
    return 100.0 * c["host_plans"] / total if total else None
