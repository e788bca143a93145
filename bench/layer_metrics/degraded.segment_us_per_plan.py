"""Host time the decode spends cutting device plans into label-monotone
worms, per plan planned on the device, in microseconds (``ArenaInfo``
``segment_s`` over ``batched_plans``, window deltas)."""


def read(run):
    c = run.counters
    if "segment_s" not in c or not c.get("batched_plans"):
        return None
    return c["segment_s"] / c["batched_plans"] * 1e6
