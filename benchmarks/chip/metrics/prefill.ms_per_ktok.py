"""Prefill time per thousand prompt tokens: the program's ``prefill_us``
summed over the window, over the prompt tokens whose first token came
out of those steps."""


def read(run):
    us = sum(s.spans["prefill_us"] for s in run["steps"])
    toks = sum(sum(s.prefilled) for s in run["steps"])
    if toks == 0:
        return None
    return us / 1e3 / (toks / 1e3)
