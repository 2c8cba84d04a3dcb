"""Whole prefill's share of the chip's bf16 peak: the model operations of
the prompts prefilled in the window (the cell's architecture's
``prefill``) over the program's ``prefill_us`` times the peak, in
percent."""


def read(run):
    us = sum(s.spans["prefill_us"] for s in run["steps"])
    lens = [n for s in run["steps"] for n in s.prefilled]
    if us <= 0 or not lens:
        return None
    ops = sum(run["arch"].prefill(run["model"], n) for n in lens)
    return 100.0 * ops / (us * 1e-6 * run["peaks"]["bf16_flops"])
