"""AOT graph engine (Alg. 2): bounded family of pre-compiled executables.

CUDA-Graph capture/replay maps onto XLA AOT compilation: both demand static
shapes, both pay per-shape capture cost once, both replay with near-zero
host orchestration.  The engine keys executables by the routing-table shape
bucket (M_hat, S_hat, MB_hat, W) and pre-compiles ("captures") the family
offline; the online path is a dict lookup + execute.

A ``step_builder(key) -> (fn, arg_specs)`` callback supplies the step
function and its ShapeDtypeStruct signature for each bucket; the engine owns
lowering, compilation, the executable cache, and Table-2-style accounting
(graph count, buffer-pool bytes).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import numpy as np


def _round_pow2(x: int, lo: int = 1) -> int:
    v = lo
    while v < x:
        v *= 2
    return v


@dataclass
class AOTStats:
    captured: int = 0
    capture_seconds: float = 0.0
    lookups: int = 0
    hits: int = 0
    online_compiles: int = 0
    buffer_bytes: int = 0
    # donation accounting: a donated serve-state arg whose output buffers
    # are NOT the input buffers means XLA silently copied (copy-on-donate) —
    # the exact host/alloc overhead donation is supposed to eliminate.
    donation_checks: int = 0
    donation_reuses: int = 0
    donation_copies: int = 0
    donation_unknown: int = 0

    def as_dict(self) -> dict:
        return {k: getattr(self, k) for k in
                ("captured", "capture_seconds", "lookups", "hits",
                 "online_compiles", "buffer_bytes", "donation_checks",
                 "donation_reuses", "donation_copies", "donation_unknown")}


class AOTGraphEngine:
    """Offline capture + online replay of bucketed step executables."""

    # donation checks sampled by default: only the first WARMUP_CHECKS
    # dispatches read back buffer pointers (reading output pointers may
    # synchronize the stream)
    WARMUP_CHECKS = 8

    def __init__(self, step_builder, mb_grid=(8, 16, 32, 64, 128, 256, 512,
                                              1024, 2048, 4096, 8192),
                 audit_every_step: bool = False,
                 r_ladder: tuple | None = None,
                 key_tag: str | None = None):
        self._builder = step_builder
        self._mb_grid = mb_grid
        self._cache: dict = {}
        self.stats = AOTStats()
        # opaque suffix appended to every bucket key (e.g. the engine's
        # kv_dtype for quantized pools): variants that lower different
        # state dtypes must never share an executable.  None (the default)
        # keeps keys exactly as before — bf16 engines are unaffected.
        self.key_tag = key_tag
        # debug mode: audit donation on EVERY step instead of sampling the
        # warmup ones.  Cheap on accelerator backends where
        # ``unsafe_buffer_pointer`` is a metadata read; catches a
        # copy-on-donate regression the moment a recompile introduces it.
        self.audit_every_step = audit_every_step
        # quantisation grid for R (rotation rounds used).  None -> pow2
        # ladder capped at W-1.  Topology-aware callers pass a ladder that
        # includes ``comm.node_local_rounds(W_node)`` so a step whose
        # bindings are (or have RELAXED back to) node-local compiles exactly
        # the node-local round count instead of jumping to the cluster ring
        # (pow2 rounds 2(W_node-1) up past the node bound on most shapes).
        self.r_ladder = tuple(sorted(set(r_ladder))) if r_ladder else None

    def should_audit_donation(self) -> bool:
        """Whether the caller should capture pointers for this dispatch."""
        return (self.audit_every_step
                or self.stats.donation_checks < self.WARMUP_CHECKS)

    # ---------------- bucket resolution (Alg. 2 l.19) ----------------
    def quantise(self, M: int, S: int, MB: int, W: int,
                 R: int | None = None) -> tuple:
        """Bucket key.  ``R`` (rotation rounds actually used, from
        ``RoutingTables.R``) is quantised onto a pow2 ladder capped at the
        full ring W-1: a step whose bindings stay within a few ring
        positions compiles with that many ppermute rounds instead of the
        whole cluster ring (W < I multi-node topologies keep the ring
        cluster-wide, so this is what bounds the collectives per step).

        When ``key_tag`` is set it is appended AFTER the R component, so
        builders unpack the shape dims as ``key[:5]`` regardless of tag."""
        from .routing import _quantize_dim
        tag = () if self.key_tag is None else (self.key_tag,)
        key = (M, S, _quantize_dim(MB), W)
        if R is None:
            return key + tag
        if S == 0:
            rq = 0
        elif self.r_ladder is not None:
            r = max(R, 1)
            rq = min((g for g in self.r_ladder if g >= r), default=W - 1)
            rq = min(rq, W - 1)
        else:
            rq = min(_round_pow2(max(R, 1)), W - 1)
        return key + (rq,) + tag

    # ---------------- offline capture (Alg. 2 l.7-17) ----------------
    def capture(self, keys) -> None:
        for key in keys:
            self._compile(key)

    def _compile(self, key):
        if key in self._cache:
            return self._cache[key]
        t0 = time.perf_counter()
        fn, arg_specs = self._builder(key)
        lowered = fn.lower(*arg_specs) if not isinstance(arg_specs, dict) \
            else fn.lower(**arg_specs)
        compiled = lowered.compile()
        self.stats.capture_seconds += time.perf_counter() - t0
        self.stats.captured += 1
        self.stats.buffer_bytes += _spec_bytes(arg_specs)
        self._cache[key] = compiled
        return compiled

    # ---------------- online replay (Alg. 2 l.19-24) ----------------
    def lookup(self, M: int, S: int, MB: int, W: int, R: int | None = None):
        """Quantise-and-replay.  Pass ``R`` (``RoutingTables.R``) when the
        step builder keys on rounds used — mixing keyed and unkeyed lookups
        against one builder would fragment the cache."""
        return self.lookup_key(self.quantise(M, S, MB, W, R))

    def lookup_key(self, key: tuple):
        """Replay lookup for an already-quantised bucket key (the hot path
        quantises once and reuses the key)."""
        self.stats.lookups += 1
        if key in self._cache:
            self.stats.hits += 1
            return self._cache[key]
        self.stats.online_compiles += 1
        return self._compile(key)

    @property
    def num_graphs(self) -> int:
        return len(self._cache)

    def executable(self, key: tuple):
        """The compiled executable cached for ``key`` (no lookup counted) —
        for inspecting what was compiled, e.g. its HLO text."""
        return self._cache[key]

    def cached_keys(self) -> list:
        """The captured bucket keys (elastic-join pre-warm enumerates these
        to compile their wider-ring variants off the hot path)."""
        return list(self._cache.keys())

    # ---------------- donation accounting ----------------
    @staticmethod
    def buffer_ptrs(tree) -> list:
        """Per-leaf device buffer pointers (tuple over addressable shards);
        None where the runtime doesn't expose them."""
        out = []
        for leaf in jax.tree.leaves(tree):
            try:
                shards = getattr(leaf, "addressable_shards", None)
                if shards:
                    out.append(tuple(s.data.unsafe_buffer_pointer()
                                     for s in shards))
                else:
                    out.append((leaf.unsafe_buffer_pointer(),))
            except Exception:
                out.append(None)
        return out

    def note_donation(self, in_ptrs: list, out_tree) -> bool:
        """Record whether a donated argument's buffers were actually reused.

        ``in_ptrs``: ``buffer_ptrs`` of the donated arg captured BEFORE the
        call (donated buffers are unreadable afterwards).  Reads the output
        pointers, which may synchronize — call sparingly (warmup steps).
        Returns True when every comparable leaf was reused in place.
        """
        out_ptrs = self.buffer_ptrs(out_tree)
        self.stats.donation_checks += 1
        reused = True
        for a, b in zip(in_ptrs, out_ptrs):
            if a is None or b is None:
                self.stats.donation_unknown += 1
            elif a == b:
                self.stats.donation_reuses += 1
            else:
                self.stats.donation_copies += 1
                reused = False
        return reused


def _spec_bytes(specs) -> int:
    leaves = jax.tree.leaves(specs)
    return int(sum(np.prod(l.shape) * np.dtype(l.dtype).itemsize
                   for l in leaves if hasattr(l, "shape")))
