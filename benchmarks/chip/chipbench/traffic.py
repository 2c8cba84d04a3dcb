"""Request traffic from a data file and a seed.

One general generator reads every traffic file under ``traffic/``.  The
interval shares and the log-uniform lengths inside an interval follow the
NanoCP paper's Table 1 (the same tables as the program's
``serving/workload.py``, copied here so that the yardstick does not move
when the program does).

Every seed gets the same multiset of work: prompt lengths, output lengths
and gaps between arrivals are drawn at fixed, evenly spaced quantiles of
their distributions, and the seed only permutes them (and picks the token
ids).  So two seeds differ in order, not in how much work they offer, and
runs with different seeds spread no wider than runs of one seed.

Two kinds of traffic:

* ``open_loop``: Poisson arrivals at ``rate_per_s`` (exponential gaps at
  fixed quantiles), prompt lengths from ``prompt.intervals``, each rounded
  up to the next rung of ``prompt.ladder`` (the lengths set-up warms), and
  output lengths uniform in ``output.lo``..``output.hi``.
* ``sessions``: a closed set of long sessions, all admitted before the
  window, decoding through all of it.  How many there are follows from the
  configuration's KV capacity (``session_count``).
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass

import numpy as np

# Table 1 interval shares: (lo, hi, probability)
SHAREGPT_4O = [(64, 1_000, 0.857), (1_000, 10_000, 0.107),
               (10_000, 100_000, 0.035)]
GITHUB_ISSUE = [(100_000, 500_000, 0.6506), (500_000, 1_000_000, 0.3494)]
DATASETS = {"sharegpt4o": SHAREGPT_4O, "github_issue": GITHUB_ISSUE}


@dataclass(frozen=True)
class Req:
    """One request as the client offers it."""
    due: float          # seconds after the window opens (open loop)
    prompt_len: int
    max_new_tokens: int


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    kind = spec.get("kind")
    if kind not in ("open_loop", "sessions"):
        raise ValueError(f"{path}: kind must be open_loop or sessions, "
                         f"not {kind!r}")
    return spec


def _quantiles(n: int) -> np.ndarray:
    """n evenly spaced probabilities in (0, 1): the midpoints of n strata."""
    return (np.arange(n) + 0.5) / n


def interval_table(prompt: dict) -> list:
    """[(lo, hi, p)] from ``prompt``: a named Table 1 dataset or explicit
    ``intervals``, with every ``hi`` cut at ``cap`` and shares renormalised."""
    table = (DATASETS[prompt["dataset"]] if "dataset" in prompt
             else [tuple(x) for x in prompt["intervals"]])
    cap = prompt.get("cap")
    out = []
    for lo, hi, p in table:
        if cap is not None:
            if lo >= cap:
                continue
            hi = min(hi, cap)
        out.append((float(lo), float(hi), float(p)))
    tot = sum(p for _, _, p in out)
    return [(lo, hi, p / tot) for lo, hi, p in out]


def length_quantile(table: list, u: float) -> float:
    """Inverse CDF of the interval mixture (log-uniform inside an
    interval) at probability ``u``."""
    acc = 0.0
    for lo, hi, p in table:
        if u < acc + p or (lo, hi, p) == table[-1]:
            f = min(max((u - acc) / p, 0.0), 1.0)
            return math.exp(math.log(lo) + f * (math.log(hi) - math.log(lo)))
        acc += p
    raise AssertionError("unreachable")


def round_to_ladder(n: float, ladder: list) -> int:
    for rung in ladder:
        if n <= rung:
            return int(rung)
    return int(ladder[-1])


def _mix_counts(shares: list, n: int) -> list:
    """n split by ``shares``, largest remainders first."""
    exact = [s * n for s in shares]
    counts = [int(math.floor(x)) for x in exact]
    order = sorted(range(len(shares)), key=lambda i: counts[i] - exact[i])
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def prompt_lengths(prompt: dict, n: int) -> np.ndarray:
    """n prompt lengths at fixed quantiles.  A ``mix`` holds components
    (each a prompt spec with a ``share``) that split the n requests."""
    if "mix" in prompt:
        parts = prompt["mix"]
        counts = _mix_counts([p["share"] for p in parts], n)
        raw = np.concatenate([prompt_lengths(p, c)
                              for p, c in zip(parts, counts) if c])
    else:
        table = interval_table(prompt)
        raw = [length_quantile(table, u) for u in _quantiles(n)]
    ladder = prompt.get("ladder")
    if ladder:
        return np.array([round_to_ladder(x, ladder) for x in raw], np.int64)
    return np.maximum(np.rint(raw), 1).astype(np.int64)


def output_lengths(output: dict, n: int) -> np.ndarray:
    lo, hi = int(output["lo"]), int(output["hi"])
    return np.floor(lo + _quantiles(n) * (hi - lo + 1)).astype(np.int64)


def open_loop(spec: dict, seed: int, seconds: float) -> tuple[list, int]:
    """Requests due in the window plus those due in the ``tail_s`` after
    it (they keep the load steady while the window's last requests
    finish).  Returns (requests, number due inside the window)."""
    rate = float(spec["rate_per_s"])
    n_win = max(1, int(round(rate * seconds)))
    n_tail = int(math.ceil(rate * float(spec.get("tail_s", 0.0))))
    rng = np.random.default_rng(seed)
    out = []
    t0 = 0.0
    for n, span in ((n_win, seconds), (n_tail, None)):
        if n == 0:
            continue
        gaps = -np.log1p(-_quantiles(n)) / rate          # exponential
        gaps = rng.permutation(gaps)
        if span is not None:
            # the window's gaps fill the window exactly, whatever the seed
            gaps = gaps * (span / gaps.sum())
        plen = rng.permutation(prompt_lengths(spec["prompt"], n))
        olen = rng.permutation(output_lengths(spec["output"], n))
        due = t0 + np.cumsum(gaps) - gaps[0]
        for i in range(n):
            out.append(Req(float(due[i]), int(plen[i]), int(olen[i])))
        t0 = t0 + float(gaps.sum())
    return out, n_win


def session_count(spec: dict, kv_capacity_tokens: int, slots: int,
                  page: int) -> int:
    """As many sessions as the KV pool and the decode slots hold: the
    largest K whose K lengths (at fixed quantiles), each with
    ``growth_tokens`` of room and rounded up to whole pages, fit."""
    best = 0
    for k in range(1, slots + 1):
        lens = prompt_lengths(spec["prompt"], k)
        need = sum(-(-(int(n) + int(spec["growth_tokens"])) // page) * page
                   for n in lens)
        if need <= kv_capacity_tokens:
            best = k
    if best == 0:
        raise ValueError("no session fits the KV pool")
    return best


def sessions(spec: dict, seed: int, k: int) -> list:
    rng = np.random.default_rng(seed)
    lens = rng.permutation(prompt_lengths(spec["prompt"], k))
    return [Req(0.0, int(n), int(spec["max_new_tokens"])) for n in lens]


def prompt_tokens(seed: int, reqs: list, vocab_size: int) -> list:
    """Token ids of every prompt, uniform over the vocabulary, from the
    seed (a stream of its own, so lengths and ids do not interact)."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(0, vocab_size, r.prompt_len, dtype=np.int64)
            for r in reqs]
