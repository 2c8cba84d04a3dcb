"""Tokens stamped inside the window, of every request, over the window."""


def read(run):
    return run["e2e"]["output_tokens_per_s"]
