"""95th percentile over requests of each request's mean gap between its
tokens, on the client's clock (``stats.py``)."""


def read(run):
    return run["e2e"].get("tpot_p95_ms")
