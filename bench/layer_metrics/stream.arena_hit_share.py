"""Share of the window's arena lookups that hit, in percent."""


def read(run):
    c = run.counters
    total = c.get("hits", 0) + c.get("misses", 0)
    return 100.0 * c["hits"] / total if total else None
