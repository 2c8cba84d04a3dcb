"""Share of the engine's step time spent in prefill (``prefill_us`` over
``step_us``, summed over the window), in percent."""


def read(run):
    step = sum(s.spans["step_us"] for s in run["steps"])
    pre = sum(s.spans["prefill_us"] for s in run["steps"])
    if step <= 0 or pre <= 0:
        return None
    return 100.0 * pre / step
