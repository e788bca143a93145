"""Device time of the DPM merge (``dpm_plan_exact``) per dispatch on the
degraded mesh, in ms."""


def read(run):
    if run.trace is None:
        return None
    s, launches = run.trace.module_seconds("dpm_plan_exact")
    return s / launches * 1e3 if launches else None
