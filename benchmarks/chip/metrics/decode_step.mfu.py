"""Whole decode step's share of the chip's bf16 peak: the model
operations of every row the window's steps dispatched (the cell's
architecture's ``decode_token``: routed experts only, attention over each
row's actual context) over the steps' wall time less prefill
(``step_us`` - ``prefill_us``) times the peak, in percent; steps in the
traced slice (slowed by the profiler) are left out."""


def read(run):
    steps = [s for s in run["steps"] if s.rows and not s.traced]
    us = sum(s.spans["step_us"] - s.spans["prefill_us"] for s in steps)
    if us <= 0:
        return None
    arch, m = run["arch"], run["model"]
    ops = sum(arch.decode_token(m, c) for s in steps for c in s.ctx_list)
    return 100.0 * ops / (us * 1e-6 * run["peaks"]["bf16_flops"])
