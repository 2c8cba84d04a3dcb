"""95th percentile of time to first token over every request due in the
window, from its due time, on the client's clock (``stats.py``)."""


def read(run):
    return run["e2e"].get("ttft_p95_ms")
