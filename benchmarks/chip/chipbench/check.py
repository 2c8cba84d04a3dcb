"""Decides ``correct``: the served tokens against the fp32 reference.

The reference is the cell's architecture's ``logits_and_margins``
(``archs/<name>.py``).  After the window, a sample of the served
requests, drawn from the seed and always holding the longest (in a
closed set: every session), is run once through it over prompt + served
tokens.  In each request a fixed share of the sample's positions, drawn
from the seed and always holding the last, is compared, so every live
slot is covered and a run compares as many positions whatever its
window.  At those positions the number compared is the widest gap by
which a served token's reference logit lies below the reference's best.
The engine decodes greedily, so a correct engine only ever serves a
token that the reference also ranks first or nearly so (ties broken by
rounding); a wrong page, expert, scale or token shows as a wide gap.

At a position where the reference's own expert choice is nearly tied
(its router margin, as the architecture defines it, under
``min_margin``), bf16 rounding in a correct engine can pick the other
expert and move that position's logits by O(1); such positions are left
out by that rule on the reference, not by name (``PERF.md`` gives the
readings).

The control (``control_gaps``) puts the reference itself in the
program's place at the next precision down (fp8 weights): at the same
positions, the token it ranks first is read in the fp32 reference the
same way, and with ``--control 1`` that reading decides ``correct``.
The limit lies between the two readings (``PERF.md``).
"""
from __future__ import annotations

import numpy as np


def sample(served: list, seed: int, max_requests: int,
           positions: int) -> list:
    """``served``: dicts with ``rid``, ``prompt`` (ids) and ``tokens``
    (served ids).  The longest (by prompt + tokens) first, then others in
    an order drawn from the seed, up to ``max_requests``; each gets an
    equal share of ``positions`` (at least one), drawn from the seed among
    its served tokens and always holding the last, as ``at``."""
    cands = [s for s in served if s["tokens"]]
    if not cands:
        return []
    cands.sort(key=lambda s: (-(len(s["prompt"]) + len(s["tokens"])),
                              s["rid"]))
    rng = np.random.default_rng([seed, 2])
    rest = [cands[i + 1] for i in rng.permutation(len(cands) - 1)]
    picked = ([cands[0]] + rest)[:max_requests]
    share = max(1, -(-positions // len(picked)))
    out = []
    for s in picked:
        n = len(s["tokens"])
        at = rng.permutation(n - 1)[:share - 1] if n > 1 else []
        out.append(dict(s, at=np.sort(np.append(at, n - 1)).astype(int)))
    return out


def _positions(arch, model, params, s: dict, quant=None):
    prompt, toks = list(s["prompt"]), list(s["tokens"])
    seq = prompt + toks[:-1]
    ref, margin = arch.logits_and_margins(
        model, params, seq, start=len(prompt) - 1, n=len(toks), quant=quant)
    return ref[s["at"]], margin[s["at"]]


def gaps(arch, model, params, s: dict) -> tuple[np.ndarray, np.ndarray]:
    """At each compared position: (reference best logit minus the served
    token's, the reference's router margin there)."""
    ref, margin = _positions(arch, model, params, s)
    toks = np.asarray(s["tokens"])[s["at"]]
    return ref.max(-1) - ref[np.arange(len(toks)), toks], margin


def control_gaps(arch, model, params, s: dict):
    """(program-side gaps, router margins, control gaps) at the same
    positions: the control's token is the one the fp8-weight reference
    ranks first."""
    ref, margin = _positions(arch, model, params, s)
    low, _ = _positions(arch, model, params, s, quant="fp8")
    toks = np.asarray(s["tokens"])[s["at"]]
    idx = np.arange(len(toks))
    best = ref.max(-1)
    return best - ref[idx, toks], margin, best - ref[idx, low.argmax(-1)]


def widest(gap: np.ndarray, margin: np.ndarray, min_margin: float) -> float:
    """The widest gap over the positions where the reference's routing is
    clear of a tie by ``min_margin`` (all positions of a dense model)."""
    keep = margin >= min_margin
    return float(gap[keep].max()) if keep.any() else 0.0
