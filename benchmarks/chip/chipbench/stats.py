"""End-to-end metric arithmetic on the client's own token stamps.

The definitions follow the program's ``serving/metrics.py`` (TTFT from
arrival to the first token, TPOT as the mean gap between a request's
tokens, every submitted request in the denominator), with the times taken
on the client side: a request is *due* at its scheduled arrival, and each
token is stamped when the ``step()`` call that made it visible returns.

Rules for requests the window cuts off (stated in ``PERF.md``):

* every request due inside the window counts in ``ttft_p95_ms``.  The
  client keeps serving after the window closes (with the traffic's tail
  still arriving) until each of them has its first token; one that still
  has none when the client gives up counts with the time it had waited by
  then, which is a lower bound, never left out;
* ``tpot_*`` take every request due inside the window that has two tokens
  or more stamped after the window opened, over those tokens (a closed
  set's sessions were admitted in set-up; their set-up tokens do not
  count);
* ``output_tokens_per_s`` counts the tokens stamped inside the window, of
  any request, over the window's length.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile, by linear interpolation between order
    statistics (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttft_s(due: float, stamps: list, give_up: float) -> float:
    return (stamps[0] if stamps else give_up) - due


def tpot_s(stamps: list) -> float | None:
    if len(stamps) < 2:
        return None
    return (stamps[-1] - stamps[0]) / (len(stamps) - 1)


def end_to_end(reqs: list, window: tuple, give_up: float) -> dict:
    """``reqs``: dicts with ``due`` (absolute), ``stamps`` (absolute token
    times) and ``in_window`` (due inside the window).  ``window`` is
    (open, close) on the same clock.  Values in ms and tokens/s."""
    w0, w1 = window
    measured = [r for r in reqs if r["in_window"]]
    ttfts = [ttft_s(r["due"], r["stamps"], give_up) for r in measured]
    tpots = [t for t in (tpot_s([x for x in r["stamps"] if x >= w0])
                         for r in measured) if t is not None]
    toks = sum(1 for r in reqs for t in r["stamps"] if w0 <= t < w1)
    out = {"output_tokens_per_s": toks / (w1 - w0),
           "requests": len(measured),
           "requests_without_first_token": sum(
               1 for r in measured if not r["stamps"]),
           "tpot_requests": len(tpots)}
    if ttfts:
        out["ttft_p50_ms"] = percentile(ttfts, 50) * 1e3
        out["ttft_p95_ms"] = percentile(ttfts, 95) * 1e3
    if tpots:
        out["tpot_p50_ms"] = percentile(tpots, 50) * 1e3
        out["tpot_p95_ms"] = percentile(tpots, 95) * 1e3
    return out
