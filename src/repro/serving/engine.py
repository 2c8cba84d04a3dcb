"""NanoCP real-execution decode engine (§3 lifecycle, on an actual JAX mesh).

Drives the full stack end to end: ENQUEUE -> dual-balanced scheduling ->
MIGRATE/TRANSFER (prefill KV -> DCP placement) -> DISPATCH (routing-table
lowering) -> LOOKUP/REPLAY (AOT executable cache) -> the 4-phase DCP decode
step -> sampling -> finish.  Used by examples and integration tests with
tiny models on CPU host-device meshes; the same code lowers for the
production mesh in the dry-run.

Prefill executes on the reference forward path (``models.transformer``) —
the paper assumes prefill-decode disaggregation with external prefill (§3).

Decode hot path (the Alg. 2 "dict lookup + replay" contract, made real):

  * The serve state LIVES ON DEVICE for the engine's whole lifetime.  The
    AOT step executables are compiled with ``donate=True`` and the engine
    consumes the returned state, so XLA reuses the pool buffers in place
    (``AOTGraphEngine.note_donation`` audits that donation actually held).
  * Prefill KV/SSM state is written by jitted on-device scatters
    (``migrate.PrefillScatter``): page-table coordinates travel as small
    int32 tensors; all requests admitted in one step batch into one call.
  * Iterations are pipelined one step ahead: ``step`` lowers iteration t's
    routing tables while the device still computes iteration t-1, then
    harvests t-1's tokens (fetched via an async device->host copy started
    right after dispatch) and only patches the per-slot input-token row
    before dispatching t.  The host never blocks on the device except for
    that (usually already complete) token fetch.
  * Finish-by-length is known at dispatch time and applied immediately so
    the scheduler reuses pages/slots without waiting a round trip; EOS is
    only visible in sampled tokens, so an EOS request may execute one extra
    speculative iteration whose output is discarded.  With ``eos_token``
    set, the step executables carry a device-side stop-token check
    (``DecodeDims.eos``): the speculative iteration's KV append is masked
    on device (redirected to the scratch frame), so an EOS finish leaves
    exactly the KV entries of its real tokens behind.  ``pipeline=False``
    switches to the non-pipelined reference semantics (dispatch + harvest
    every step; EOS applies before the next lowering, no speculative slot).

Whisper (enc-dec) requests enter via ``add_audio_request``: prefill runs
encode + teacher-forced decode, cross-attn KV scatters into the paged DCP
pools and the decoder-prefix self-attn KV into the per-slot caches; decode
replays ``make_encdec_serve_step`` executables (cross pools read-only, no
appends).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..configs.base import ModelConfig
from ..core import dcp, migrate, routing
from ..core.aot import AOTGraphEngine
from ..core.comm import node_local_rounds, ring_round
from ..core.bucketing import CPBuckets, DEFAULT_BUCKETS, ShapeBuckets
from ..core.handoff import HandoffTask
from ..core.page_table import KVSpillError
from ..core.prefix import PrefixTrie, page_keys
from ..core.scheduler import BaseScheduler, DualBalancedScheduler
from ..core.state import ClusterState, Request
from ..kernels import quant
from ..models import encdec, transformer


@dataclass
class GenResult:
    rid: int
    prompt: list
    tokens: list = field(default_factory=list)
    # True when the request was finished early by a clean request-level OOM
    # (KV spill with no shard headroom anywhere to escalate into)
    oom: bool = False
    # failure-recovery outcome: None = never touched by an instance failure;
    # True = affected and recovered (partial-shard re-prefill — final tokens
    # match a from-scratch run); False = degraded finish (the cluster lacked
    # headroom or the arch pins unrecoverable per-slot state — the request
    # completed early with the tokens it had, never hanging)
    recovered: bool | None = None
    # admission-control outcomes: the request never ran (no tokens) — it
    # bounced off a full queue (rejected) or its TTFT deadline expired
    # while queued (shed).  Both are typed SLO violations, never a silent
    # drop.
    rejected: bool = False
    shed: bool = False


class UnsupportedDrainError(RuntimeError):
    """``drain_instance`` on an arch whose per-slot device state cannot be
    migrated with the slot (SSM recurrent state, whisper's per-slot self-attn
    caches): a graceful drain would silently corrupt the pinned state, so the
    engine refuses with a typed error instead.  ``fail_instance`` remains
    available (crash semantics: affected requests degrade cleanly)."""


@dataclass
class _Inflight:
    """One dispatched-but-unharvested decode iteration."""
    toks: object                 # [I, M] device array; async d2h copy started
    # (rid, request, instance, slot, is_last) snapshot at dispatch time —
    # immune to later rebalancing/slot reuse
    slots: list
    # rid -> frozenset of instances this iteration's computation touched for
    # the request (KV shard holders + the slot instance) at dispatch time:
    # the exact blast radius of an instance failure between dispatch and
    # harvest — entries outside it harvest normally
    holders: dict = field(default_factory=dict)
    # [I, M, V] device logits when the engine runs with keep_logits
    # (quant conformance); None on the hot path
    logits: object = None


class _Span:
    """One phase of the engine: a ``jax.profiler.TraceAnnotation`` named
    ``nanocp.<name>`` (on the profiler's clock, beside the device's ops,
    when a trace is being taken; about a microsecond when none is) and its
    own ``perf_counter`` duration added to ``engine.timings["<name>_us"]``
    (dots become underscores: ``prefill.forward`` -> ``prefill_forward_us``;
    a phase entered twice in one step sums)."""
    __slots__ = ("eng", "key", "ann", "t0")

    def __init__(self, eng, name: str, args: dict):
        self.eng = eng
        self.key = name.replace(".", "_") + "_us"
        self.ann = jax.profiler.TraceAnnotation("nanocp." + name, **args)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        us = (time.perf_counter() - self.t0) * 1e6
        self.ann.__exit__(*exc)
        timings = self.eng.timings
        timings[self.key] = timings.get(self.key, 0.0) + us
        return False


class NanoCPEngine:
    def __init__(self, cfg: ModelConfig, params, mesh, *,
                 num_instances: int, instances_per_node: int,
                 kv_capacity_tokens: int, page_size: int = 16,
                 tp: int | None = None, backend: str = "routed",
                 scheduler: BaseScheduler | None = None,
                 buckets: CPBuckets = DEFAULT_BUCKETS,
                 shape_buckets: ShapeBuckets | None = None,
                 eos_token: int | None = None,
                 max_slots_per_instance: int = 16,
                 pipeline: bool = True,
                 audit_donation_every_step: bool = False,
                 admission=None,
                 prefix_cache: bool = False,
                 prefill_cells: int = 0,
                 chunk_tokens: int | None = None,
                 kv_dtype: str = "bf16",
                 keep_logits: bool = False):
        self.cfg = cfg
        self.mesh = mesh
        self.tp = tp or mesh.shape["model"]
        self.backend = backend
        self.eos = eos_token
        # paged-KV storage precision (kernels/quant.py): "bf16" keeps
        # today's bit-exact pools; "fp8"/"int8" store quantized pages with
        # per-page scale sidecars and fuse dequant into decode attention
        quant.check_kv_dtype(kv_dtype)
        self.kv_dtype = kv_dtype
        if quant.is_quantized(kv_dtype):
            assert cfg.has_attention and not cfg.is_encoder_decoder, \
                "quantized KV pools need a decoder-side paged attention " \
                "pool (encoder-decoder and attention-free archs are bf16)"
        # debug/conformance hook: keep each step's logits on device and
        # record them per request at harvest (tolerance-gated engine-vs-
        # reference comparison for quantized pools) — off on the hot path
        self.keep_logits = keep_logits
        self.step_logits: dict = {}
        # one-step-lookahead pipeline (False = dispatch+harvest each step:
        # EOS finishes apply before the next lowering, so no speculative
        # slot-steps ever run — the non-pipelined reference semantics)
        self.pipeline = pipeline
        self.is_encdec = cfg.is_encoder_decoder
        _, _, ps = dcp.attn_tp_geometry(cfg, self.tp)
        self.cluster = ClusterState(num_instances=num_instances,
                                    instances_per_node=instances_per_node,
                                    kv_capacity_tokens=kv_capacity_tokens,
                                    page_size=page_size, kv_stripes=ps,
                                    prefill_cells=prefill_cells)
        # cross pools are read-only during decode (whisper): no KV appends —
        # and therefore no decode-time KV growth to escalate for
        self._append_tokens = cfg.has_attention and not self.is_encdec
        # per-slot device state (SSM recurrent state, whisper self-attn
        # caches) pins the slot dimension of the serve state: ONE fixed M
        # bucket and no MoE-binding rebalance
        pinned_slots = cfg.family in ("ssm", "hybrid") or self.is_encdec
        self._pinned_slots = pinned_slots
        self.scheduler = scheduler or DualBalancedScheduler(
            buckets=buckets, allow_rebalance=not pinned_slots,
            max_batch_per_instance=max_slots_per_instance,
            has_kv=cfg.has_attention,
            # keep one decode page of growth headroom on every MoE binding
            # at admission so the first appended tokens never spill
            kv_reserve=page_size if self._append_tokens else 0,
            allow_escalation=self._append_tokens)
        if admission is not None:
            # SLO-aware admission control (core.scheduler.AdmissionController)
            # attaches to whichever scheduler serves this engine — the
            # control loop (deadlines, shedding, preemption-by-relaxation)
            # lives in schedule(), not here
            self.scheduler.admission = admission
        if not self._append_tokens and \
                getattr(self.scheduler, "allow_escalation", False):
            # a caller-supplied scheduler must not escalate when decode
            # never appends KV (nothing grows; the re-shard op only covers
            # the decoder-only pool layouts)
            self.scheduler.allow_escalation = False
        # global CoW prefix cache (core.prefix): decoder-only attention
        # archs only — the suffix-only scatter and the CoW copy collective
        # both target the paged k/v pools (per-slot SSM / whisper state has
        # no sharable page identity)
        if prefix_cache:
            assert self._append_tokens, \
                "prefix_cache needs a decoder-only attention arch"
        self.prefix_trie = PrefixTrie(page_size) if prefix_cache else None
        self.scheduler.prefix_cache = self.prefix_trie
        # disaggregated prefill/decode cells (PR 9): the tail `prefill_cells`
        # instances never decode — long prompts prefill there in fixed-size
        # chunks whose KV streams into the decode cluster as each chunk
        # finishes (core.handoff drives the bookkeeping; the physical write
        # is the same donated PrefillScatter the admission path uses)
        if prefill_cells:
            assert self._append_tokens and not pinned_slots, \
                "disaggregated prefill cells need a decoder-only attention " \
                "arch (chunked KV streaming targets the paged k/v pools)"
        self.chunk_tokens = chunk_tokens or 4 * page_size
        assert self.chunk_tokens > 0 and self.chunk_tokens % page_size == 0, \
            f"chunk_tokens must be a positive page multiple " \
            f"(got {self.chunk_tokens}, page={page_size})"
        # rid -> HandoffTask for requests parked in cluster.prefilling;
        # per-cell FIFO of rids owed chunk forwards; first sampled token
        # (device scalar) stashed until handoff completes and the request
        # activates on the decode cluster
        self._handoff: dict = {}
        self._cell_queue: dict = {}
        self._first_tok: dict = {}
        self._cp_buckets = getattr(self.scheduler, "buckets", None) \
            or CPBuckets(edges=(), degrees=(1,))
        # the data plane's rotation window is the CLUSTER ring (node
        # boundaries are a link class, not a routing wall) — bindings may
        # span nodes on W < I topologies
        ring = self.cluster.window
        if shape_buckets is None and pinned_slots:
            shape_buckets = ShapeBuckets(m_buckets=(max_slots_per_instance,),
                                         window=ring)
        self.shape_buckets = shape_buckets or ShapeBuckets(window=ring)
        self.params = params
        self._dims0 = dcp.DecodeDims(
            M=max_slots_per_instance, S=0, N=1, MB=4, W=ring,
            num_frames=self.cluster.page_table.frames_per_instance + 1,
            page=page_size, data_size=num_instances, tp=self.tp,
            backend=backend,
            eos=-1 if eos_token is None else int(eos_token),
            kv_dtype=kv_dtype)
        # Decode params and the initial serve state are COMMITTED to their
        # shard_map layouts here, once: otherwise every dispatch re-shards
        # them (implicit device-to-device transfers on multi-device meshes —
        # caught by the conformance matrix's transfer-guard window) and the
        # first donation silently degrades to copy-on-donate.
        # The layout conversion runs eagerly, not under jit: leaves it passes
        # through unchanged (expert weights, embeddings, norms) stay the
        # caller's buffers instead of a second copy — at published widths
        # the experts are most of the device memory.
        from jax.sharding import NamedSharding
        if self.is_encdec:
            self.decode_params = dcp.to_encdec_decode_params(cfg, params,
                                                             self.tp)
            self.state = dcp.init_encdec_serve_state(
                cfg, self._dims0, num_instances, dtype=jnp.float32)
            pspecs = dcp.encdec_param_specs(cfg, self.decode_params)
            sspecs = dcp.encdec_state_specs(self.state)
        else:
            self.decode_params = dcp.to_decode_params(cfg, params, self.tp)
            self.state = dcp.init_serve_state(cfg, self._dims0, num_instances,
                                              dtype=jnp.float32)
            pspecs = dcp.decode_param_specs(cfg, self.decode_params)
            sspecs = dcp.serve_state_specs(cfg, self.state)
        self.decode_params = jax.device_put(
            self.decode_params,
            jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                         is_leaf=lambda x: isinstance(x, P)))
        self.state = jax.device_put(
            self.state,
            jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs,
                         is_leaf=lambda x: isinstance(x, P)))
        self._tbl_shardings: dict | None = None
        # R quantisation ladder includes the node-local bound 2(W_node-1):
        # a steady state whose bindings stay — or RELAX back to — node-local
        # compiles exactly the node-local rotation rounds, never the
        # cluster ring (the compiler-visible payoff of DCP relaxation)
        # quantized engines tag every bucket key with the kv dtype: a bf16
        # and an fp8 engine sharing a process must never share executables
        # (their serve-state signatures differ); bf16 keys stay unchanged
        self.aot = AOTGraphEngine(self._build_step,
                                  audit_every_step=audit_donation_every_step,
                                  r_ladder=self._r_ladder(
                                      ring, instances_per_node),
                                  key_tag=(kv_dtype if
                                           quant.is_quantized(kv_dtype)
                                           else None))
        self._scatter = migrate.PrefillScatter(cfg, self._dims0,
                                               num_instances)
        # live KV re-shard collective (mid-decode CP escalation / drain);
        # coords replicate over the mesh so dispatch stays implicit-free
        self._reshard = migrate.KVReshard(
            self._scatter, coord_sharding=NamedSharding(mesh, P()))
        self._arena = routing.TableArena()
        self.next_tok: dict = {}
        self.results: dict = {}
        self._prompts: dict = {}
        self._dec_prefix: dict = {}
        self.finished: list = []
        self.iterations = 0
        self._inflight: _Inflight | None = None
        self._t0 = time.monotonic()
        # the last step's phases, "<span name>_us" -> microseconds (see
        # ``span``); read by the chip benchmark's per-layer metrics
        # (benchmarks/chip/metrics), benchmarks/decode_step.py and the tests
        self.timings: dict = {}
        self.last_bucket: tuple | None = None
        # lowered rotation rounds of the last dispatched step
        # (RoutingTables.R, pre-quantisation): the relaxation cells assert
        # this returns to <= 2(W_node-1) after a cross-node retraction
        self.last_rounds_used: int = 0
        self.hot_path_stats: dict = {
            "steps": 0, "async_token_fetches": 0, "speculative_slots": 0,
            "prefill_eos_finishes": 0, "escalations": 0, "reshard_tokens": 0,
            "spill_escalations": 0, "oom_finishes": 0, "drains": 0,
            "relaxations": 0, "relax_tokens": 0, "compacts": 0,
            "failures": 0, "recovered_tokens": 0, "reprefill_tokens": 0,
            "degraded_finishes": 0, "joins": 0,
            "rejected": 0, "shed": 0, "preemptions": 0,
            # PR 8: global prefix cache + refcounted frame ownership
            "prefix_hit_tokens": 0, "prefix_inserts": 0, "forks": 0,
            # PR 9: disaggregated prefill cells + streamed KV handoff
            "staged": 0, "prefill_chunks": 0, "handoff_tokens": 0}
        self._donation_ptrs = None

    def span(self, name: str, **args) -> _Span:
        """``with self.span("lower"):`` times one phase of the engine: a
        ``nanocp.<name>`` profiler span (``args`` become its arguments)
        and ``timings["<name>_us"]``.  No flag turns it on or off: with
        the profiler stopped the span costs about a microsecond."""
        return _Span(self, name, args)

    # ------------------------------------------------------------------ #
    @staticmethod
    def _r_ladder(ring: int, node_width: int) -> tuple | None:
        """AOT quantisation grid for rounds-used: pow2 steps plus the
        node-local bound (and the full ring as the ceiling)."""
        if ring <= 1:
            return None
        lad = {1, ring - 1}
        v = 1
        while v < ring - 1:
            v *= 2
            lad.add(v)
        nl = node_local_rounds(node_width)
        if nl >= 1:
            lad.add(nl)
        return tuple(sorted(g for g in lad if 1 <= g <= ring - 1))

    def _now(self) -> float:
        return time.monotonic() - self._t0

    def expert_load(self) -> np.ndarray | None:
        """Routed assignments of live tokens that reached each held expert,
        summed over MoE layers, decode steps and instances ([held] int64);
        the decode step counts them on the device and this reads them on
        demand.  None unless the configuration holds a share of its
        experts (``cfg.holds_share``)."""
        load = self.state.get("expert_load")
        if load is None:
            return None
        return np.asarray(jax.device_get(load), np.int64).sum(axis=0)

    def add_request(self, prompt_tokens, max_new_tokens: int,
                    now: float | None = None) -> int:
        now = self._now() if now is None else now
        rid = len(self._prompts)
        self._prompts[rid] = list(map(int, prompt_tokens))
        keys = (page_keys(self._prompts[rid], self._dims0.page)
                if self.prefix_trie is not None else ())
        self.cluster.enqueue(Request(rid=rid, prompt_len=len(prompt_tokens),
                                     max_new_tokens=max_new_tokens,
                                     arrival=now, prefix_keys=keys), now)
        self.results[rid] = GenResult(rid, self._prompts[rid])
        return rid

    def add_audio_request(self, frames, dec_prefix_tokens,
                          max_new_tokens: int, now: float | None = None) -> int:
        """Whisper: enqueue an audio request.  ``frames`` [S_enc, d_model]
        stub frame embeddings (the DCP-managed cross-attn KV source),
        ``dec_prefix_tokens`` the decoder prompt."""
        assert self.is_encdec, "add_audio_request is enc-dec only"
        now = self._now() if now is None else now
        rid = len(self._prompts)
        self._prompts[rid] = np.asarray(frames, np.float32)
        self._dec_prefix[rid] = list(map(int, dec_prefix_tokens))
        self.cluster.enqueue(
            Request(rid=rid, prompt_len=len(self._prompts[rid]),
                    max_new_tokens=max_new_tokens, arrival=now,
                    dec_prefix_len=len(self._dec_prefix[rid])), now)
        self.results[rid] = GenResult(rid, self._dec_prefix[rid])
        return rid

    # ------------------------------------------------------------------ #
    def _build_step(self, key):
        M, S, MB, W, R = key[:5]   # key may carry the kv_dtype tag after R
        N = M + (W - 1) * S
        # rounds_used=R bounds the compiled ppermute rounds: node-local
        # placements on a W < I topology never pay the full cluster ring
        d = dcp.DecodeDims(M=M, S=S, N=N, MB=MB, W=W,
                           num_frames=self._dims0.num_frames,
                           page=self._dims0.page,
                           data_size=self.cluster.num_instances, tp=self.tp,
                           backend=self.backend, eos=self._dims0.eos,
                           rounds_used=R, kv_dtype=self.kv_dtype)
        I = self.cluster.num_instances
        tbl_spec = {
            "slot_rid": (I, M), "slot_token": (I, M), "slot_pos": (I, M),
            "slot_active": (I, M), "append_frame": (I, M),
            "append_off": (I, M), "q_send_idx": (I, W - 1, S),
            "q_recv_slot": (I, W - 1, S), "work_src": (I, N),
            "work_bt": (I, N, MB), "work_len": (I, N),
            "ret_send_idx": (I, W - 1, S), "merge_src": (I, M, W),
            "merge_round": (I, M, W), "merge_peer_row": (I, M, W),
        }
        tbl_sds = {k: jax.ShapeDtypeStruct(v, jnp.int32)
                   for k, v in tbl_spec.items()}
        p_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.decode_params)
        s_sds = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), self.state)
        mk = (dcp.make_encdec_serve_step if self.is_encdec
              else dcp.make_serve_step)
        fn = mk(self.cfg, d, self.mesh, p_sds, s_sds, tbl_sds, donate=True)
        return fn, (p_sds, s_sds, tbl_sds)

    # ------------------------------------------------------------------ #
    def _prefill_batch(self, reqs: list, now: float) -> None:
        """Prefill admitted requests; migrate their KV/SSM state into the
        on-device pools with ONE donated scatter per state kind.

        The prefill forward runs on device and its caches stay there — the
        only host work is assembling the small int32 coordinate tensors from
        the page table (MIGRATE + TRANSFER, §3 (2)-(3))."""
        if self.is_encdec:
            return self._prefill_batch_encdec(reqs, now)  # -> finished reqs
        pattern = self.cfg.block_pattern()
        ps = self._scatter.ps
        page = self._dims0.page
        kv_k, kv_v, kv_coords = [], [], []
        ssm_conv, ssm_h, ssm_coords = [], [], []
        firsts = []
        for req in reqs:
            # the prefill forward always runs over the FULL prompt — a
            # prefix-cache hit saves the KV WRITE (only the novel suffix
            # scatters; the attached pages already hold identical KV, since
            # equal chain keys imply an equal transcript), never the
            # correctness of the first sampled token
            hit = req.prefix_hit_tokens
            with self.span("prefill.forward", rid=req.rid,
                           tokens=req.prompt_len):
                toks = jnp.asarray(self._prompts[req.rid])[None, :]
                logits, caches = transformer.forward(
                    self.cfg, self.params, toks, collect_kv=True)
                # the FIRST generated token is sampled from the prefill
                # logits; the decode loop then extends from it.  Keep the
                # argmax on device — ONE batched readback happens after
                # every forward has been enqueued (admission-path readback)
                firsts.append(jnp.argmax(logits[0, -1]))
            ks, vs, lats, convs, hs = [], [], [], [], []
            for li, kind in enumerate(pattern):
                aux = caches[li]
                if kind["mixer"] == "attn":
                    a, b = aux["kv"]
                    if self.cfg.is_mla:
                        lats.append(jnp.concatenate([a[:, 0], b[:, 0]],
                                                    axis=-1))
                    else:
                        ks.append(a[:, 0])
                        vs.append(b[:, 0])
                else:
                    cs, hs_ = aux["ssm"]
                    convs.append(cs[:, 0])
                    hs.append(hs_[:, 0])
            if lats:
                # [nb, na, len, 1, dk] — MLA's single latent "head"
                kv_k.append(jnp.stack(lats, axis=1)[:, :, hit:][..., None, :])
                kv_coords.append(self._prompt_coords(req, hit, page, ps))
            elif ks:
                khs = self._scatter.khs
                # Hkv heads -> khs groups of kg heads (flattened last dim)
                k3 = jnp.stack(ks, axis=1)[:, :, hit:]  # [nb, na, T, Hkv, hd]
                v3 = jnp.stack(vs, axis=1)[:, :, hit:]
                kv_k.append(k3.reshape(*k3.shape[:3], khs, -1))
                kv_v.append(v3.reshape(*v3.shape[:3], khs, -1))
                kv_coords.append(self._prompt_coords(req, hit, page, ps))
            if convs:
                inst, slot = self.cluster.slot_map[req.rid]
                ssm_conv.append(jnp.stack(convs, axis=1)[:, :, None])
                ssm_h.append(jnp.stack(hs, axis=1)[:, :, None])
                ssm_coords.append([inst, slot])
        eos_done = self._record_first_tokens(reqs, firsts, now)
        with self.span("prefill.scatter"):
            if kv_k:
                k = jnp.concatenate(kv_k, axis=2)
                v = jnp.concatenate(kv_v, axis=2) if kv_v else None
                coords = np.concatenate(kv_coords, axis=1)
                self.state = self._scatter.scatter_kv(self.state, k, v,
                                                      coords)
            if ssm_conv:
                conv = jnp.concatenate(ssm_conv, axis=2)
                h = jnp.concatenate(ssm_h, axis=2)
                coords = np.asarray(ssm_coords, np.int32).T
                self.state = self._scatter.scatter_ssm(self.state, conv, h,
                                                       coords)
        self._register_prefixes(reqs)
        return self._finish_prefill_eos(eos_done, now)

    def _prompt_coords(self, req, hit: int, page: int, ps: int) -> np.ndarray:
        """Scatter coordinates for the prompt tokens the prefill must WRITE:
        all of them on a cache miss (the contiguous sorted-order layout
        ``migrate.prefill_coords`` assumes), only the novel suffix on a hit
        (the attach breaks that layout, so positions resolve through the
        page table's range map instead)."""
        if hit == 0:
            return migrate.prefill_coords(self.cluster, req.rid, page, ps)
        c3 = self.cluster.page_table.position_coords(
            req.rid, range(hit, req.prompt_len))
        return np.stack([c3[0], c3[1] % ps, c3[1] // ps,
                         c3[2]]).astype(np.int32)

    def _register_prefixes(self, reqs: list) -> None:
        """Register the admitted requests' cacheable prompt pages in the
        trie (one cache_hold per new replica) — BEFORE any prefill-EOS
        finish frees the pages, so even a one-shot request's prefix KV
        outlives it."""
        if self.prefix_trie is None:
            return
        pt = self.cluster.page_table
        for req in reqs:
            if req.prefix_keys:
                self.hot_path_stats["prefix_inserts"] += \
                    self.prefix_trie.insert(pt, req.rid, req.prefix_keys,
                                            req.prompt_len)
            self.hot_path_stats["prefix_hit_tokens"] += req.prefix_hit_tokens

    def _prefill_batch_encdec(self, reqs: list, now: float) -> None:
        """Whisper admission: encode frames, teacher-force the decoder
        prefix, scatter cross-attn KV (paged, DCP-placed) and prefix
        self-attn KV (per-slot contiguous) into the on-device pools.

        Encoder forwards BATCH over same-shape frame stacks (one ``encode``
        call per shape group, not one per request): batching is over the
        leading axis only, so each request's encoder states — and therefore
        its scatters — are bit-for-bit those of the per-request call."""
        cfg = self.cfg
        page = self._dims0.page
        khs, kg, ps = self._scatter.khs, self._scatter.kg, self._scatter.ps
        by_shape: dict = {}
        for req in reqs:
            by_shape.setdefault(self._prompts[req.rid].shape, []).append(req)
        enc_of = {}
        for grp in by_shape.values():
            stack = jnp.asarray(np.stack([self._prompts[r.rid] for r in grp]))
            enc_grp = encdec.encode(cfg, self.params, stack)
            for b, r in enumerate(grp):
                enc_of[r.rid] = enc_grp[b:b + 1]
        firsts = []
        ck, cv, c_coords = [], [], []
        sk, sv, s_coords = [], [], []
        for req in reqs:
            enc = enc_of[req.rid]
            with self.span("prefill.forward", rid=req.rid,
                           tokens=req.prompt_len):
                toks = jnp.asarray(self._dec_prefix[req.rid])[None, :]
                logits, caches = encdec.decode_forward(
                    cfg, self.params, toks, enc, collect_kv=True)
                firsts.append(jnp.argmax(logits[0, -1]))
            kc, vc = caches["cross_kv"]          # [L, 1, S_enc, Hkv, hd]
            L_, S_enc = kc.shape[0], kc.shape[2]
            ck.append(kc[:, 0].reshape(L_, S_enc, khs, -1))
            cv.append(vc[:, 0].reshape(L_, S_enc, khs, -1))
            c_coords.append(migrate.prefill_coords(
                self.cluster, req.rid, page, ps))
            ksf, vsf = caches["self_kv"]         # [L, 1, T0, Hkv, hd]
            T0 = ksf.shape[2]
            # chunk layout [p0h0..p0hK, p1h0..]: tile head groups over the
            # ps page subgroups
            sk.append(jnp.tile(ksf[:, 0].reshape(L_, T0, khs, -1),
                               (1, 1, ps, 1)))
            sv.append(jnp.tile(vsf[:, 0].reshape(L_, T0, khs, -1),
                               (1, 1, ps, 1)))
            inst, slot = self.cluster.slot_map[req.rid]
            s_coords.append(np.stack([np.full(T0, inst), np.full(T0, slot),
                                      np.arange(T0)]).astype(np.int32))
        eos_done = self._record_first_tokens(reqs, firsts, now)
        if ck:
            with self.span("prefill.scatter"):
                self.state = self._scatter.scatter_cross_kv(
                    self.state, jnp.concatenate(ck, axis=1),
                    jnp.concatenate(cv, axis=1),
                    np.concatenate(c_coords, axis=1))
                self.state = self._scatter.scatter_self_kv(
                    self.state, jnp.concatenate(sk, axis=1),
                    jnp.concatenate(sv, axis=1),
                    np.concatenate(s_coords, axis=1))
        return self._finish_prefill_eos(eos_done, now)

    def _record_first_tokens(self, reqs: list, firsts: list, now: float):
        """One batched readback of the prefill-sampled first tokens; returns
        the requests whose first token is already EOS."""
        eos_done = []
        with self.span("prefill.readback"):
            firsts = jax.device_get(firsts)
        for req, first in zip(reqs, firsts):
            first = int(first)
            self.next_tok[req.rid] = first
            self.results[req.rid].tokens.append(first)
            req.token_times.append(now)
            if self.eos is not None and first == self.eos:
                eos_done.append(req)
        return eos_done

    def _finish_prefill_eos(self, reqs: list, now: float) -> list:
        """EOS sampled straight from the prefill logits: the request is done
        before its first decode iteration — finish it now so it never
        occupies a slot (and appends zero decode KV entries).  Returns the
        finished requests so ``step`` reports them like every other finish
        path."""
        for req in reqs:
            self.cluster.finish(req, now)
            self.finished.append(req)
            self.hot_path_stats["prefill_eos_finishes"] += 1
        return reqs

    # ------------------------------------------------------------------ #
    # disaggregated prefill cells: chunked prefill + streamed KV handoff
    # ------------------------------------------------------------------ #
    def _stage_handoff(self, req: Request) -> None:
        """Open a HandoffTask for a request the scheduler staged on a
        prefill cell (``plan.staged``): the placeholder pages are already
        allocated (novel suffix on the cell, prefix-hit pages attached on
        their decode owners) — this just queues the chunk forwards."""
        cl = self.cluster
        p = next(i for i in req.kv_binding if cl.role_of(i) == "prefill")
        attach = tuple(i for i in req.kv_binding if i != p)
        hit = req.prefix_hit_tokens - req.prefix_hit_tokens % self._dims0.page
        self._handoff[req.rid] = HandoffTask(
            req.rid, req.prompt_len, hit, self.chunk_tokens,
            self._dims0.page, p, attach=attach)
        self._cell_queue.setdefault(p, deque()).append(req.rid)
        self.hot_path_stats["staged"] += 1

    def _process_prefill_chunks(self, now: float) -> list:
        """Advance every alive prefill cell by ONE chunk of its head task,
        streaming each finished chunk's KV straight into the decode cluster
        — so a 1M-token prompt never holds a cell (or the engine loop) for
        one monolithic forward, and decode admission overlaps the tail of
        prefill.

        Streaming order is position-REVERSED: ``move_pages`` re-homes the
        TAIL of the cell's placeholder fill, the chunk forward recomputes
        exactly those positions' KV (a causal prefix forward over
        ``[0, end)`` keeping rows ``[end-chunk, end)``), and the scatter
        writes STRAIGHT to the decode destination coordinates.  Placeholder
        frames on a prefill cell therefore never hold live KV — a handoff
        never copies garbage, a cell crash never loses device state, and
        the donated-scatter discipline (one batched ``scatter_kv`` per
        step) is identical to the admission path's.  The first generated
        token is sampled from the full-prompt chunk's logits and recorded
        when the handoff completes.  Returns requests finished at
        activation (prefill-EOS).  Pinned by the ``disagg`` conformance
        cells (token parity vs colocated) and ``tests/test_handoff.py``.
        """
        cl = self.cluster
        pt = cl.page_table
        pattern = self.cfg.block_pattern()
        ps = self._scatter.ps
        kv_k, kv_v, kv_coords = [], [], []
        ready = []
        for p in sorted(cl.prefill_instances()):
            if p in cl.dead_instances:
                continue
            q = self._cell_queue.get(p)
            while q and (q[0] not in cl.prefilling
                         or self._handoff.get(q[0]) is None
                         or self._handoff[q[0]].instance != p):
                q.popleft()                      # stale (crashed/re-staged)
            if not q:
                continue
            rid = q[0]
            task = self._handoff[rid]
            cands = self.scheduler.handoff_candidates(
                cl, task, task.next_chunk().tokens)
            if not cands:
                continue     # decode backpressure: no headroom, retry later
            chunk, dest = task.complete_chunk(self._cp_buckets, cands)
            # the positions about to move: the tail of the placeholder fill
            ranges = pt.request_positions(rid)[p]
            pos = [i for st, ln in ranges
                   for i in range(st, st + ln)][-chunk.tokens:]
            _, dst = pt.move_pages(rid, [(p, dest, chunk.tokens)])
            end = pos[-1] + 1
            toks = jnp.asarray(self._prompts[rid][:end])[None, :]
            logits, caches = transformer.forward(self.cfg, self.params, toks,
                                                 collect_kv=True)
            if end == task.prompt_len and rid not in self._first_tok:
                self._first_tok[rid] = jnp.argmax(logits[0, -1])
            ks, vs, lats = [], [], []
            for li, kind in enumerate(pattern):
                if kind["mixer"] != "attn":
                    continue
                a, b = caches[li]["kv"]
                if self.cfg.is_mla:
                    lats.append(jnp.concatenate([a[:, 0], b[:, 0]], axis=-1))
                else:
                    ks.append(a[:, 0])
                    vs.append(b[:, 0])
            sel = jnp.asarray(pos)
            if lats:
                kv_k.append(jnp.stack(lats, axis=1)[:, :, sel][..., None, :])
            else:
                khs = self._scatter.khs
                k3 = jnp.stack(ks, axis=1)[:, :, sel]
                v3 = jnp.stack(vs, axis=1)[:, :, sel]
                kv_k.append(k3.reshape(*k3.shape[:3], khs, -1))
                kv_v.append(v3.reshape(*v3.shape[:3], khs, -1))
            inst, frame, off = dst
            kv_coords.append(np.stack([inst, frame % ps, frame // ps,
                                       off]).astype(np.int32))
            self.hot_path_stats["prefill_chunks"] += 1
            self.hot_path_stats["handoff_tokens"] += chunk.tokens
            if task.done:
                q.popleft()
                ready.append(rid)
        if kv_k:
            k = jnp.concatenate(kv_k, axis=2)
            v = jnp.concatenate(kv_v, axis=2) if kv_v else None
            self.state = self._scatter.scatter_kv(
                self.state, k, v, np.concatenate(kv_coords, axis=1))
        return self._activate_handoffs(ready, now)

    def _activate_handoffs(self, rids: list, now: float) -> list:
        """Promote fully-streamed requests to the decode cluster: the
        binding is the MEASURED one (attach owners + lazily opened stream
        destinations — ``HandoffTask.binding``), the first token (sampled
        from the full-prompt chunk) is recorded now, and a first-token EOS
        finishes without ever occupying a decode slot."""
        if not rids:
            return []
        cl = self.cluster
        firsts, reqs = [], []
        for rid in rids:
            req = cl.prefilling[rid]
            task = self._handoff.pop(rid)
            self.scheduler.admit_handoff(cl, req, task.binding(), now)
            firsts.append(self._first_tok.pop(rid))
            reqs.append(req)
        eos_done = self._record_first_tokens(reqs, firsts, now)
        self._register_prefixes(reqs)
        return self._finish_prefill_eos(eos_done, now)

    def _restage_prefilling(self, rec, now: float) -> list:
        """PR 6 recovery for a request parked mid-handoff.  A dead prefill
        cell loses only PLACEHOLDER frames (live KV streams straight to
        decode destinations), so the crash costs exactly the unstreamed
        tail: re-stage it on a surviving cell (``restore_ranges`` re-homes
        the lost positions as fresh placeholders; the normal chunk stream
        recomputes them) — or degrade when no cell has headroom, or when a
        DECODE member holding streamed/attached pages died (the landed
        prefix is gone; typed finish, never a hang)."""
        cl = self.cluster
        pt = cl.page_table
        req = rec.req
        rid = req.rid
        task = self._handoff.get(rid)
        lost = sum(n for _, n in rec.lost)
        if task is not None and task.instance in cl.dead_instances \
                and lost > 0:
            survived = task.survived_tokens()
            cells = [c for c in cl.prefill_instances()
                     if c not in cl.dead_instances
                     and cl.kv_headroom(c) >= lost]
            if cells:
                p2 = max(cells, key=lambda s: (cl.kv_headroom(s), -s))
                pt.restore_ranges(rid, {p2: lost}, list(rec.lost))
                req.kv_binding = sorted(set(task.binding()) | {p2})
                self._handoff[rid] = HandoffTask(
                    rid, req.prompt_len, survived, self.chunk_tokens,
                    self._dims0.page, p2, attach=tuple(task.binding()))
                self._cell_queue.setdefault(p2, deque()).append(rid)
                self.results[rid].recovered = True
                self.hot_path_stats["recovered_tokens"] += survived
                self.hot_path_stats["reprefill_tokens"] += lost
                return []
        self._handoff.pop(rid, None)
        self._first_tok.pop(rid, None)
        cl.prefilling.pop(rid, None)
        pt.free_request(rid)
        self.results[rid].recovered = False
        req.status = "degraded"
        req.finish_time = now
        self.finished.append(req)
        self.hot_path_stats["degraded_finishes"] += 1
        return [req]

    # ------------------------------------------------------------------ #
    def _table_shardings_for(self, tbl) -> dict:
        """Per-field NamedShardings for the table upload (shard over `data`).

        Built once (field -> sharding depends only on the field's rank);
        uploading tables pre-sharded keeps dispatch free of the implicit
        device-to-device re-shard a default-device ``device_put`` causes."""
        if self._tbl_shardings is None:
            from dataclasses import fields
            from jax.sharding import NamedSharding
            sh = {}
            for f in fields(tbl):
                v = getattr(tbl, f.name)
                if isinstance(v, np.ndarray):
                    sh[f.name] = NamedSharding(
                        self.mesh, P("data", *([None] * (v.ndim - 1))))
            self._tbl_shardings = sh
        return self._tbl_shardings

    # ------------------------------------------------------------------ #
    def _apply_escalations(self, escalations: list) -> None:
        """Dispatch the live KV re-shard for this step's escalations.

        Page-table bookkeeping already happened (inside the scheduler); the
        device-side move rides the same dispatch stream as the decode steps:
        its input is the in-flight iteration's output state, so the gather
        reads post-append pools, and the next lowered step sees the moved
        frames.  One batched gather->scatter covers every escalated request.
        """
        if not escalations:
            return
        # page-table bookkeeping is already applied by the scheduler; if this
        # engine cannot physically move the KV, silently dropping the records
        # would desynchronize tables from pools — fail loudly instead
        assert self._append_tokens, \
            "scheduler escalated on an arch whose KV the engine cannot re-shard"
        with self.span("reshard"):
            src = np.concatenate([e.src_coords for e in escalations], axis=1)
            dst = np.concatenate([e.dst_coords for e in escalations], axis=1)
            self.state = self._reshard(self.state, src, dst)
        relaxed = [e for e in escalations
                   if getattr(e, "is_relaxation", False)]
        self.hot_path_stats["escalations"] += len(escalations) - len(relaxed)
        self.hot_path_stats["relaxations"] += len(relaxed)
        self.hot_path_stats["relax_tokens"] += sum(e.tokens_moved
                                                   for e in relaxed)
        self.hot_path_stats["reshard_tokens"] += int(src.shape[1])

    def _apply_copies(self, copies: list) -> None:
        """Apply owed data-plane KV copies ((src, dst) [3, T] coordinate
        pairs: CoW splits, hot-prefix replication) through the re-shard
        collective — gathers read pre-copy pools, so one batched call is
        safe for any mix whose sources are never also destinations."""
        if not copies:
            return
        src = np.concatenate([s for s, _ in copies], axis=1)
        dst = np.concatenate([d for _, d in copies], axis=1)
        if src.shape[1] == 0:
            return
        with self.span("reshard"):
            self.state = self._reshard(self.state, src, dst)

    def _cow_appends(self) -> None:
        """Pre-lowering CoW pass: any active request whose next decode
        append would land in a SHARED frame (a fork/prefix sibling still
        reads it) gets its partial tails split to exclusive clones first —
        ``routing.lower_plan`` appends assuming exclusive write targets.
        Raises ``KVSpillError`` into the caller's spill-retry loop when a
        clone cannot allocate."""
        pt = self.cluster.page_table
        copies = []
        for rid in sorted(self.cluster.active):
            req = self.cluster.active[rid]
            if req.moe_binding >= 0 and \
                    pt.append_needs_cow(rid, req.moe_binding):
                copies.append(pt.exclusive_tails(rid))
        self._apply_copies(copies)

    def _handle_spill(self, err: KVSpillError, now: float) -> list:
        """A decode append overran its shard at table lowering: evict cold
        prefix-cache replicas on the spilled instance first (cache-only
        frames are convenience copies — they go before ANY live request is
        escalated), then escalate the spilled request onto shards with
        headroom, or — when no shard in the node can take the KV — finish
        it with a clean request-level OOM.  Returns the requests finished
        here (empty when relief worked)."""
        if self.prefix_trie is not None:
            keep = getattr(self.cluster.active.get(err.rid), "prefix_keys",
                           ())
            if self.prefix_trie.evict(self.cluster.page_table, 1,
                                      instance=err.instance, keep=keep):
                return []            # the append can take a frame now: retry
        escs = (self.scheduler.relieve_spill(self.cluster, err.rid,
                                             err.instance)
                if hasattr(self.scheduler, "relieve_spill") else [])
        if escs:
            self._apply_escalations(escs)
            self.hot_path_stats["spill_escalations"] += len(escs)
            return []
        req = self.cluster.active.get(err.rid)
        if req is None:
            return []
        self.results[err.rid].oom = True
        self.cluster.finish(req, now)
        req.status = "oom"
        self.finished.append(req)
        self.hot_path_stats["oom_finishes"] += 1
        return [req]

    def drain_instance(self, instance: int, force: bool = False) -> list:
        """Planned drain (live migration, zero data loss): evacuate every
        request's resident KV off ``instance`` through the re-shard
        collective, mark the instance dead, and rebalance MoE bindings off
        it.  Unlike ``fail_instance`` (crash semantics: KV lost, affected
        requests re-prefill), a drained instance's requests keep decoding
        with unchanged tokens.

        ``force=True`` is the drain-DEADLINE fallback: requests whose KV
        cannot be evacuated gracefully take fail-semantics — their resident
        KV on the instance is partial-dropped and recovered (re-prefill or
        degraded finish) — so a forced drain ALWAYS completes with the
        instance empty and dead.

        Raises ``UnsupportedDrainError`` for archs whose per-slot device
        state is pinned (SSM recurrent state, whisper self-attn caches) —
        the slot cannot move without a state migration, so a graceful drain
        is impossible; the refusal leaves the cluster untouched.

        Draining a PREFILL CELL is the crash path with zero data loss by
        construction: cell frames are placeholders (streamed KV already
        lives on decode destinations), so the unstreamed tail simply
        re-stages on a surviving cell.  Pinned by tests/test_fault.py and
        the ``multinode-fault`` (`engine_fault.py`) / ``chaos``
        (``drainforce``/``refusal``) conformance cells; tokens stay equal
        through a graceful drain."""
        if self.cluster.role_of(instance) == "prefill":
            # a prefill cell's frames are PLACEHOLDERS — each chunk's pages
            # move to their decode destination BEFORE its KV is computed, so
            # there is never live device state to evacuate.  A drain is the
            # crash path with zero data loss: mark the cell dead and
            # re-stage its queued tails on surviving cells; the normal
            # chunk stream recomputes them deterministically (tokens
            # unchanged — pinned by the disagg conformance cells).
            records = self.cluster.fail_instance(instance)
            if self.prefix_trie is not None:
                self.prefix_trie.drop_instance(instance)
            self._recover(records, self._now())
            self.hot_path_stats["drains"] += 1
            return []
        if not (self._append_tokens
                and getattr(self.scheduler, "allow_rebalance", True)):
            raise UnsupportedDrainError(
                f"drain_instance({instance}): {self.cfg.family}/"
                f"{'encdec' if self.is_encdec else 'dec'} pins per-slot "
                f"device state — the MoE binding cannot move without a slot "
                f"state migration (use fail_instance for crash semantics)")
        # prefix-cache holds on the leaver are released FIRST: cache-only
        # frames free immediately (nothing worth evacuating), and frames
        # shared with live requests become exclusively theirs so the
        # evacuation moves them like any other.  Not rolled back on a
        # failed drain — losing convenience replicas is always safe.
        if self.prefix_trie is not None:
            self.prefix_trie.release_instance(self.cluster.page_table,
                                              instance)
        # dead first so the evacuation planner never picks it as a receiver;
        # rolled back if the node lacks headroom (evacuate raises with the
        # page table untouched) — a failed drain must leave the instance
        # serving, not dead-with-resident-KV
        self.cluster.dead_instances.add(instance)
        stragglers = []
        try:
            if force:
                escalations, stragglers = self.scheduler.evacuate(
                    self.cluster, instance, partial=True)
            else:
                escalations = self.scheduler.evacuate(self.cluster, instance)
        except MemoryError:
            self.cluster.dead_instances.discard(instance)
            raise
        self._apply_escalations(escalations)
        if stragglers:
            # deadline expired with KV still resident: fail-semantics for
            # the stragglers.  The in-flight iteration stays VALID (the
            # instance is healthy until we stop routing to it — this is a
            # planned drop, not a crash), so only the cluster-level partial
            # drop runs; the lost ranges re-prefill or degrade like a crash.
            records = self.cluster.fail_instance(instance)
            self._recover(records, self._now())
        self.scheduler.rebalance(self.cluster)
        self.hot_path_stats["drains"] += 1
        return escalations

    # ------------------------------------------------------------------ #
    def fail_instance(self, instance: int, now: float | None = None) -> list:
        """Abrupt instance failure (crash semantics) — safe at ANY point of
        the pipelined loop, including between dispatch and harvest.

        Three phases: (1) in-flight discard — snapshot entries whose
        computation touched the dead instance (a KV shard or the decode slot
        lived there) are voided and their dispatch-time bookkeeping rolled
        back, so a dead instance's speculative token is never applied and no
        slot double-frees; (2) cluster-level partial drop —
        ``ClusterState.fail_instance`` frees ONLY the dead instance's frames
        and reports the exact lost token ranges; (3) typed recovery per
        affected request — partial-shard re-prefill of just those ranges
        into a replacement WaterFill placement (surviving shards untouched),
        or a degraded finish when the alive cluster lacks headroom.  Never
        hangs, never leaks frames.  Returns the requests finished (degraded)
        here.  Pinned by tests/test_fault.py, the kill/join property in
        tests/test_properties.py, and the ``chaos``/``disagg`` conformance
        shards (recovered tokens == a from-scratch run; degraded tokens a
        prefix of it; prefill-cell crashes re-stage only the unstreamed
        tail)."""
        now = self._now() if now is None else now
        cl = self.cluster
        assert 0 <= instance < cl.num_instances, instance
        if instance in cl.dead_instances:
            return []
        self.hot_path_stats["failures"] += 1
        if self._inflight is not None:
            keep = []
            for ent in self._inflight.slots:
                rid, req, i, b, last = ent
                holders = self._inflight.holders.get(rid, frozenset())
                if i != instance and instance not in holders:
                    keep.append(ent)
                    continue
                # discard the speculative result: roll back the dispatch-time
                # bookkeeping (the next dispatch re-derives the same token
                # deterministically from next_tok)
                req.generated -= 1
                if last:
                    # length-finished at dispatch: pages/slot already freed —
                    # resurrect; its ENTIRE context is a lost range now, so
                    # recovery below re-prefills (or degrades) it
                    cl.finished.remove(req)
                    req.status = "running"
                    req.finish_time = -1.0
                    cl.active[rid] = req
                    if (req.moe_binding >= 0
                            and req.moe_binding != instance
                            and req.moe_binding not in cl.dead_instances):
                        cl.move_slot(rid, req.moe_binding)
                elif self._append_tokens:
                    # un-append the input token's KV entry written at this
                    # step's lowering (i is the dispatch-time MoE shard)
                    cl.page_table.pop_token(rid, i)
            self._inflight = _Inflight(self._inflight.toks, keep,
                                       self._inflight.holders)
        records = cl.fail_instance(instance)
        if self.prefix_trie is not None:
            # the replicas died with the hardware and the page table purged
            # its ledger — FORGET them without releasing (a release would
            # double-free into the instance's fresh pool)
            self.prefix_trie.drop_instance(instance)
        return self._recover(records, now)

    def _discard_inflight(self, rids: set) -> None:
        """Drop the given rids' entries from the in-flight snapshot (their
        speculative token is never applied).  Used when recovery finishes a
        request that is still in flight — its pages are freed wholesale, so
        no per-token rollback is needed, only the harvest suppression."""
        if self._inflight is None:
            return
        self._inflight = _Inflight(
            self._inflight.toks,
            [e for e in self._inflight.slots if e[0] not in rids],
            self._inflight.holders)

    def _recover(self, records: list, now: float) -> list:
        """Typed recovery for ``ClusterState.fail_instance`` records:
        partial-shard re-prefill into a replacement WaterFill placement, or
        a degraded finish.  Returns the requests finished (degraded) here."""
        cl = self.cluster
        pt = cl.page_table
        ledger = {s: pt.free_frames(s) for s in cl.alive_instances()}
        items, finished, cows = [], [], []
        for rec in records:
            req = rec.req
            rid = req.rid
            if rid in cl.prefilling:
                # parked mid-handoff on a prefill cell: re-stage the
                # unstreamed tail (or degrade) — the streamed prefix on
                # decode instances survives untouched
                finished += self._restage_prefilling(rec, now)
                continue
            if rid not in cl.active:
                continue
            resident = sum(pt.shard_tokens(rid).values())
            ranges = list(rec.lost)
            if resident == 0 and not ranges and req.length > 0:
                # nothing survived anywhere (or the request was resurrected
                # from a dispatch-time finish): the whole context is lost
                ranges = [(0, req.prompt_len + req.generated)]
            lost = sum(n for _, n in ranges)
            # full recovery = replaying lost ranges through the reference
            # forward and scattering their KV: decoder-only attention archs
            # only, and never when pinned per-slot state died with the slot
            recoverable = (self._append_tokens
                           and not (rec.slot_lost and self._pinned_slots))
            split = None
            ok = req.moe_binding >= 0 and (lost == 0 or recoverable)
            if ok and lost > 0:
                split = self.scheduler.place_recovery(cl, req, lost, ledger) \
                    if hasattr(self.scheduler, "place_recovery") else None
                ok = split is not None
            if not ok:
                # degraded finish: complete NOW with the tokens it has —
                # a failure must never hang a request or leak its frames
                self.results[rid].recovered = False
                self._discard_inflight({rid})
                cl.finish(req, now)
                req.status = "degraded"
                self.finished.append(req)
                finished.append(req)
                self.hot_path_stats["degraded_finishes"] += 1
                continue
            if lost == 0:
                continue                 # only the binding/slot was touched
            self.results[rid].recovered = True
            self.hot_path_stats["recovered_tokens"] += resident
            self.hot_path_stats["reprefill_tokens"] += lost
            # surviving shards may carry SHARED partial tails (a fork or
            # prefix sibling still reads them): split to exclusive clones
            # before restore_ranges appends into the tail slack —
            # place_recovery already priced the clone frames as pads
            cows.append(pt.exclusive_tails(rid))
            positions, coords = pt.restore_ranges(rid, split, ranges)
            req.kv_binding = sorted(set(req.kv_binding) | set(split)
                                    | {req.moe_binding})
            items.append((req, positions, coords))
        self._apply_copies(cows)
        if items:
            self._reprefill_ranges(items)
        return finished

    def _reprefill_ranges(self, items: list) -> None:
        """Partial-shard re-prefill: replay ONLY the lost token ranges of
        each recovering request through the reference forward and scatter
        their KV into the replacement placement — surviving shards are never
        read or rewritten, and the scatter is the same donated collective
        the admission path uses (one batched call for all requests)."""
        pattern = self.cfg.block_pattern()
        ps = self._scatter.ps
        kv_k, kv_v, kv_coords = [], [], []
        for req, positions, coords in items:
            # prompt + every token recorded so far covers ALL existing KV
            # positions [0, prompt+generated) at any pipeline point
            seq = self._prompts[req.rid] + self.results[req.rid].tokens
            toks = jnp.asarray(seq)[None, :]
            _, caches = transformer.forward(self.cfg, self.params, toks,
                                            collect_kv=True)
            ks, vs, lats = [], [], []
            for li, kind in enumerate(pattern):
                if kind["mixer"] != "attn":
                    continue
                a, b = caches[li]["kv"]
                if self.cfg.is_mla:
                    lats.append(jnp.concatenate([a[:, 0], b[:, 0]], axis=-1))
                else:
                    ks.append(a[:, 0])
                    vs.append(b[:, 0])
            pos = jnp.asarray(positions)
            if lats:
                kv_k.append(jnp.stack(lats, axis=1)[:, :, pos][..., None, :])
            else:
                khs = self._scatter.khs
                k3 = jnp.stack(ks, axis=1)[:, :, pos]  # [nb, na, T, Hkv, hd]
                v3 = jnp.stack(vs, axis=1)[:, :, pos]
                kv_k.append(k3.reshape(*k3.shape[:3], khs, -1))
                kv_v.append(v3.reshape(*v3.shape[:3], khs, -1))
            inst, frame, off = coords
            kv_coords.append(np.stack([inst, frame % ps, frame // ps,
                                       off]).astype(np.int32))
        k = jnp.concatenate(kv_k, axis=2)
        v = jnp.concatenate(kv_v, axis=2) if kv_v else None
        coords = np.concatenate(kv_coords, axis=1)
        self.state = self._scatter.scatter_kv(self.state, k, v, coords)

    def join_instance(self, instance: int, prewarm: bool = True) -> None:
        """Elastic scale-up: a standby/failed/drained instance (re)enters
        the zig-zag ring.  The engine's mesh is fixed at construction, so it
        joins only instances within it (``ClusterState.join_instance`` can
        also GROW host-side topologies).  The page-table join path guards
        against frame aliasing; ``relax``/consolidation then spread load
        onto the joiner naturally, and ``prewarm`` compiles the AOT buckets
        the wider ring reach makes reachable OFF the hot path — the first
        post-join step that recruits the joiner replays instead of
        compiling."""
        cl = self.cluster
        assert 0 <= instance < cl.num_instances, \
            "engine mesh is fixed: join a standby/failed instance"
        cl.join_instance(instance)
        self.hot_path_stats["joins"] += 1
        if prewarm:
            self._prewarm_join(instance)

    def _prewarm_join(self, instance: int) -> None:
        """Pre-compile the cached buckets at the ring reach the joiner adds
        (max zig-zag rounds between it and any alive peer in its window
        segment), so post-join recruitment stays a dict-lookup replay."""
        cl = self.cluster
        win = cl.window
        seg = instance // win
        need = 0
        for p in cl.alive_instances():
            if p == instance or p // win != seg:
                continue
            need = max(need, ring_round(instance - p, win),
                       ring_round(p - instance, win))
        if need <= 0:
            return
        have = set(self.aot.cached_keys())
        new_keys = []
        for key in sorted(have, key=lambda k: k[:5]):
            M, S, MB, W, R = key[:5]
            if S == 0:
                continue
            k2 = self.aot.quantise(M, S, MB, W, max(R, need))
            if k2 not in have and k2 not in new_keys:
                new_keys.append(k2)
        if new_keys:
            self.aot.capture(new_keys)

    def compact(self) -> list:
        """Planned maintenance — the relaxation twin of ``drain_instance``:
        force ONE cluster-wide relaxation pass (de-escalate every binding
        wider than its bucket degree, consolidate fragmented tail pages back
        onto the MoE-binding shards) and apply the live re-shard now.

        ``force=True`` overrides the per-request cooldown — an operator-
        initiated compaction after a drain/burst should not wait out the
        hysteresis window — but NEVER the headroom guard band: a shard near
        its low-water mark keeps its KV spread.  Requires the same
        rebalance-able attention arch as ``drain_instance`` (the re-shard
        only covers decoder-only pool layouts)."""
        assert self._append_tokens, \
            "compact needs a decoder-only attention arch"
        records = (self.scheduler.relax(self.cluster, force=True)
                   if hasattr(self.scheduler, "relax") else [])
        self._apply_escalations(records)
        self.hot_path_stats["compacts"] += 1
        return records

    def fork_request(self, parent_rid: int, max_new_tokens: int,
                     next_token: int | None = None,
                     now: float | None = None) -> int:
        """Fork an ACTIVE request mid-decode: the child attaches to the
        parent's resident KV (full frames shared by refcount — zero data
        movement; partial tails CoW-cloned so divergent appends never
        tramp each other) and decodes independently from here on.

        ``next_token`` overrides the child's PENDING token (the parent's
        last sample, not yet consumed by a forward pass) — the fork point's
        divergence, e.g. a different sampling candidate.  It replaces that
        token in the child's transcript too, so ``prompt + tokens`` is
        always the sequence the child actually processes.  Default is the
        parent's, in which case greedy decoding makes the branches
        identical.  ``max_new_tokens`` counts the child's TOTAL emitted
        tokens, inherited ones included (the parent's finish semantics).
        Decoder-only attention archs only: per-slot device state (SSM,
        whisper) has no page identity to share.  Invariant:
        ``prompt + tokens`` is the child's processed sequence exactly, and
        shared frames are never appended into without a CoW split — pinned
        by tests/test_prefix.py, the fork audits in
        tests/test_properties.py, and the ``prefix`` ``fork`` conformance
        cell (both lineages vs independent references)."""
        assert self._append_tokens and not self._pinned_slots, \
            "fork_request needs a decoder-only attention arch"
        now = self._now() if now is None else now
        if self._inflight is not None:
            # settle the pipeline: the fork must snapshot a harvested state
            # (the in-flight iteration's token is part of the lineage)
            self._harvest(now)
        cl = self.cluster
        parent = cl.active.get(parent_rid)
        assert parent is not None, f"fork of inactive request {parent_rid}"
        pt = cl.page_table
        rid = len(self._prompts)
        self._prompts[rid] = list(self._prompts[parent_rid])
        try:
            src, dst = pt.fork_request(rid, parent_rid)
        except KVSpillError as err:
            # tail clones lack a frame: cold cache replicas go first
            if self.prefix_trie is None or not self.prefix_trie.evict(
                    pt, 1, instance=err.instance):
                raise
            src, dst = pt.fork_request(rid, parent_rid)
        self._apply_copies([(src, dst)])
        B = np.bincount([r.moe_binding for r in cl.active.values()],
                        minlength=cl.num_instances)
        members = [s for s in parent.kv_binding
                   if s not in cl.dead_instances] or [parent.moe_binding]
        m = int(min(members, key=lambda s: (B[s], s)))
        child = Request(rid=rid, prompt_len=parent.prompt_len,
                        max_new_tokens=max_new_tokens, arrival=now,
                        prefix_keys=parent.prefix_keys,
                        generated=parent.generated, status="running",
                        kv_binding=sorted(set(parent.kv_binding) | {m}),
                        moe_binding=m, node=cl.node_of(m),
                        start_time=now,
                        token_times=list(parent.token_times))
        cl.active[rid] = child
        cl.assign_slot(rid, m)
        res = GenResult(rid, self._prompts[rid])
        res.tokens = list(self.results[parent_rid].tokens)
        self.results[rid] = res
        if next_token is not None:
            # the pending token's KV was never appended: overriding the
            # input must override the transcript entry it came from, or the
            # recorded lineage would claim a token the child never saw
            assert res.tokens and self.next_tok[parent_rid] == res.tokens[-1]
            res.tokens[-1] = int(next_token)
            self.next_tok[rid] = int(next_token)
        else:
            self.next_tok[rid] = self.next_tok[parent_rid]
        self.hot_path_stats["forks"] += 1
        return rid

    # ------------------------------------------------------------------ #
    def _harvest(self, now: float) -> list:
        """Materialize the in-flight iteration's tokens (async copy started
        at dispatch), record them, and apply finishes."""
        infl = self._inflight
        if infl is None:
            return []
        self._inflight = None
        with self.span("harvest"):
            toks = np.asarray(jax.device_get(infl.toks))
            logits = (None if infl.logits is None
                      else np.asarray(jax.device_get(infl.logits)))
        self.hot_path_stats["async_token_fetches"] += 1
        done = []
        with self.span("harvest.record"):
            for rid, req, i, b, last in infl.slots:
                t = int(toks[i, b])
                self.results[rid].tokens.append(t)
                self.next_tok[rid] = t
                if logits is not None:
                    self.step_logits.setdefault(rid, []).append(logits[i, b])
                req.token_times.append(now)
                if last:
                    # cluster bookkeeping already done at dispatch; stamp the
                    # actual emission time now that the token materialized
                    req.finish_time = now
                    self.finished.append(req)
                    done.append(req)
                elif self.eos is not None and t == self.eos:
                    # EOS is only visible post-readback: under the lookahead
                    # pipeline the request is already lowered into the next
                    # iteration (one speculative slot whose input is patched to
                    # the stop token so the device-side mask suppresses its KV
                    # append; output discarded at the next harvest).  A request
                    # no longer active here was OOM-finished between dispatch
                    # and harvest — already reported, don't double-finish.
                    if rid in self.cluster.active:
                        self.cluster.finish(req, now)
                        if self.pipeline:
                            self.hot_path_stats["speculative_slots"] += 1
                        self.finished.append(req)
                        done.append(req)
        return done

    # ------------------------------------------------------------------ #
    def step(self, now: float | None = None) -> list:
        """One scheduling+decode iteration, pipelined one step ahead.

        Order: advance prefill-cell chunk streams (completed handoffs
        activate BEFORE this step's schedule sees the active set) ->
        schedule (stage/admit/escalate/relax/shed/reject) -> batched
        donated prefill scatter -> lower routing tables -> harvest the
        in-flight iteration's tokens -> dispatch this iteration.

        Invariants: steady state is a dict lookup + replay — no compile,
        no implicit transfer, donation holds (``aot.stats`` audits
        ``donation_copies``; pinned by tests/test_hot_path.py and every
        conformance cell's transfer-guard window) — and a ``KVSpillError``
        at lowering is relieved (cache evict -> relieve_spill) or finished
        as a typed request-level OOM, never raised to the caller (pinned
        by the ``escalation`` ``oom`` cells).

        Returns the requests whose completion became visible during this
        call (i.e. at the harvest of the previously dispatched iteration).
        """
        self.timings = {}
        with self.span("step"):
            return self._step(self._now() if now is None else now)

    def _step(self, now: float) -> list:
        # -- disaggregated cells: advance the chunk streams FIRST, so a
        #    completed handoff activates on the decode cluster before this
        #    step's schedule/lowering sees the active set -------------------
        handoff_done = []
        if self.cluster.prefill_cells:
            with self.span("handoff"):
                handoff_done = self._process_prefill_chunks(now)

        # -- schedule + admit (prefill -> on-device KV migration) ----------
        with self.span("schedule"):
            plan = self.scheduler.schedule(self.cluster, now)
            # requests the scheduler parked on a prefill cell this step:
            # open their handoff tasks (first chunk forwards run next step)
            for req in plan.staged:
                self._stage_handoff(req)
        # mid-decode CP escalations AND relaxations decided by the
        # scheduler: dispatch the live KV re-shard FIRST so the gather reads
        # the pools before this step's admissions scatter into (possibly
        # just-freed) frames.  One batched gather->scatter covers both —
        # escalation records precede relaxation records, matching the order
        # the scheduler applied their page-table bookkeeping.
        self._apply_escalations(plan.escalations + plan.relaxations)
        # data-plane copies owed outside the escalation records (hot-prefix
        # replication, scheduler-side CoW splits): same collective, same
        # ordering argument — before this step's admissions scatter
        self._apply_copies(plan.copies)
        # typed admission-control outcomes: a rejected/shed request never
        # ran (its GenResult stays token-free), but it finishes HERE — in
        # the done list, in ``self.finished``, flagged on the result —
        # never a silent drop
        dropped = []
        for req in plan.rejected + plan.shed:
            res = self.results.get(req.rid)
            if res is not None:
                if req.status == "rejected":
                    res.rejected = True
                else:
                    res.shed = True
            req.finish_time = now
            self.finished.append(req)
            dropped.append(req)
        self.hot_path_stats["rejected"] += len(plan.rejected)
        self.hot_path_stats["shed"] += len(plan.shed)
        self.hot_path_stats["preemptions"] += plan.preemptions
        prefill_done = handoff_done + dropped
        if plan.admitted:
            with self.span("prefill"):
                prefill_done = prefill_done + (
                    self._prefill_batch(plan.admitted, now) or [])
        if not self.cluster.active:
            # drain a trailing iteration
            return prefill_done + self._harvest(now)

        # -- lower THIS iteration's tables while the device computes the
        #    previous one (routing never depends on token VALUES).  A typed
        #    KV spill surfaces HERE (pre-flight, page table untouched): the
        #    engine escalates the request onto shards with headroom — or
        #    OOM-finishes it when none exists — and retries the lowering. ---
        with self.span("lower"):
            spill_done = []
            attempts = len(self.cluster.active) + 1
            while True:
                try:
                    if self._append_tokens:
                        self._cow_appends()
                    tbl = routing.lower_plan(
                        self.cluster, plan, buckets=self.shape_buckets,
                        append_tokens=self._append_tokens,
                        next_tokens=self.next_tok, arena=self._arena)
                    break
                except KVSpillError as err:
                    attempts -= 1
                    if attempts <= 0:
                        raise
                    spill_done += self._handle_spill(err, now)
                    if not self.cluster.active:
                        return prefill_done + spill_done + self._harvest(now)
            key = self.aot.quantise(tbl.M, tbl.S, tbl.MB, tbl.W, tbl.R)
            # lower_plan already quantised MB on the same (idempotent)
            # ladder; a mismatch would mean the arena buffers no longer
            # match the AOT executable's expected shape
            assert key[2] == tbl.MB, (key, tbl.MB)
            fn = self.aot.lookup_key(key)

        # -- harvest the previous iteration (tokens usually already home) --
        # (slot snapshot only needed when a harvested EOS can leave a
        # speculative slot in THIS iteration's tables — pipelined mode only)
        slots_at_lower = ({rid: self.cluster.slot_map[rid]
                           for rid in self.cluster.active}
                          if self.eos is not None and self.pipeline else None)
        done = prefill_done + spill_done + self._harvest(now)

        # -- patch per-slot input tokens now that they are all known, and
        #    put the tables on the device -----------------------------------
        with self.span("upload"):
            for rid in self.cluster.active:
                i, b = self.cluster.slot_map[rid]
                tbl.slot_token[i, b] = self.next_tok[rid]
            if slots_at_lower is not None:
                # EOS finishes discovered at this harvest are already
                # lowered into THIS iteration (the one speculative
                # slot-step): feed the stop token as their input so the
                # device-side check masks the KV append and the sampled
                # output
                for req in done:
                    loc = slots_at_lower.get(req.rid)
                    if loc is not None:
                        tbl.slot_token[loc[0], loc[1]] = self.eos
            tbl_dev = routing.as_device_arrays(
                tbl, self._table_shardings_for(tbl))

        # -- dispatch (async) + start the token readback copy --------------
        with self.span("dispatch"):
            check = self.aot.should_audit_donation()
            in_ptrs = self.aot.buffer_ptrs(self.state) if check else None
            self.state, toks, step_logits = fn(self.decode_params,
                                               self.state, tbl_dev)
            if not self.keep_logits:
                step_logits = None
            try:
                toks.copy_to_host_async()
            except AttributeError:
                pass

        # -- dispatch-time bookkeeping: the iteration WILL emit one token
        #    per active slot; length-based finishes are deterministic, so
        #    free their pages/slots for the next schedule immediately ------
        with self.span("bookkeep"):
            if check:
                self.aot.note_donation(in_ptrs, self.state)
            snapshot = []
            length_done = []
            holders = {}
            pt = self.cluster.page_table
            for rid in list(self.cluster.active):
                req = self.cluster.active[rid]
                i, b = self.cluster.slot_map[rid]
                req.generated += 1
                last = len(self.results[rid].tokens) + 1 >= req.max_new_tokens
                snapshot.append((rid, req, i, b, last))
                # the iteration's blast radius for this request: every
                # instance holding one of its KV shards, plus the
                # decode-slot instance — recorded BEFORE length-finishes
                # free the pages, so a failure between dispatch and harvest
                # can still identify affected rows
                holders[rid] = frozenset(
                    s for s, t in pt.shard_tokens(rid).items() if t > 0) | {i}
                if last:
                    length_done.append(req)
            for req in length_done:
                self.cluster.finish(req, now)
            self._inflight = _Inflight(toks, snapshot, holders, step_logits)
            self.iterations += 1
            self.last_bucket = key
            self.last_rounds_used = tbl.R
            self.hot_path_stats["steps"] += 1
        if not self.pipeline:
            # non-pipelined reference semantics: harvest this very iteration
            # so EOS finishes are visible before the next lowering
            done += self._harvest(now)
        return done

    def run(self, max_iters: int = 1000) -> dict:
        it = 0
        while ((self.cluster.active or self.cluster.waiting
                or self.cluster.prefilling
                or self._inflight is not None) and it < max_iters):
            self.step()
            it += 1
        return self.results
