"""Mean requests per planning batch the PlanServer formed in the window."""


def read(run):
    b = run.counters.get("batches", 0)
    return run.counters["requests"] / b if b else None
