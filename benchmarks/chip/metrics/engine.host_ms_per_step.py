"""Host time of the engine loop per step: the program's own span
``step_us`` less the blocking token fetch ``harvest_us`` and the prefill
``prefill_us``, summed over the window's steps outside the traced slice
(the profiler slows the host), over their count."""


def read(run):
    steps = [s for s in run["steps"] if not s.traced]
    if not steps:
        return None
    host = sum(s.spans["step_us"] - s.spans["harvest_us"]
               - s.spans["prefill_us"] for s in steps)
    return host / len(steps) / 1e3
