"""Whole decode step's share of the chip's bf16 peak: the model
operations of every row the window's steps dispatched (routed experts
only, attention over each row's actual context: ``flops.decode_token``)
over the steps' wall time less prefill (``step_us`` - ``prefill_us``)
times the peak, in percent; steps in the traced slice (slowed by the
profiler) are left out."""
from chipbench import flops


def read(run):
    steps = [s for s in run["steps"] if s.rows and not s.traced]
    us = sum(s.spans["step_us"] - s.spans["prefill_us"] for s in steps)
    if us <= 0:
        return None
    m = run["model"]
    ops = sum(flops.decode_token(m, c) for s in steps for c in s.ctx_list)
    return 100.0 * ops / (us * 1e-6 * run["peaks"]["bf16_flops"])
