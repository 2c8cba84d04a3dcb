"""Device time of the AOT decode step program per step, from the trace:
the ``XLA Modules`` events of the step program over their count."""
from chipbench import names, trace


def read(run):
    rec = run["trace"]
    if rec is None:
        return None
    s, n = trace.module_time_s(rec, names.DECODE_STEP_MODULE)
    return s / n * 1e3 if n else None
