"""Seconds from the start of set-up (weights, engine, AOT buckets, prefill
warm-up, the closed set's own prefills) to the window's opening."""


def read(run):
    return run["setup_s"]
