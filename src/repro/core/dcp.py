"""DCP decode execution engine (§5): the per-iteration serve step.

Executes NanoCP's four-phase attention data path plus wide-EP MoE inside a
single ``shard_map`` over the (`data`, `model`) mesh axes (plus `pod`, over
which instances are simply more shards):

  Phase 1  Projection & Q-Routing — each MoE binding computes q for its M_hat
           local slots and emits cross-instance rows via the routing backend
           (zig-zag cluster-ring rotations, core/comm.py; node boundaries
           are a link class, not a reachability wall).
  Phase 2  Paged attention — every instance runs the paged-decode kernel over
           its N_hat work rows against its local KV pool (LSE out).
  Phase 3  Res-Routing — partial (out, lse) rows return via reverse rotations.
  Phase 4  LSE merge — the MoE binding merges <=W partials per slot
           (kernels/ref.merge_lse), then runs MoE dispatch/combine (EP over
           `data`) or the dense TP FFN, then samples the next token.

Everything is shaped by the AOT bucket (M, S, N, MB, W): the same compiled
executable replays any placement with those bounds (core/aot.py).

Within an instance, attention/FFN are TP over `model` (tp = axis size).
The KV cache is HYBRID-sharded: kv heads over khs = min(Hkv, tp) chunks and
pages striped over ps = tp/khs devices per kv head, with a subgroup
LSE-merge reassembling stripe partials (``attn_tp_geometry``).  No KV is
ever replicated — MLA's single latent head stripes across all tp devices
(TPLA-style; FlashMLA analogue with absorbed W_uk/W_uv).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..compat import shard_map as _shard_map
from ..configs.base import ModelConfig
from ..kernels import ops, quant, ref
from ..kernels.paged_attention import lane_tiles
from ..models import layers as L
from . import comm
from .moe_parallel import dense_decode_ffn, moe_decode_ffn


# --------------------------------------------------------------------------- #
# static decode dimensions (one AOT bucket x cluster geometry)
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class DecodeDims:
    M: int                 # slots / instance
    S: int                 # cross-send rows / rotation round
    N: int                 # attention work rows / instance
    MB: int                # page blocks / work row
    W: int                 # rotation window (cluster ring, ClusterState.window)
    num_frames: int        # KV pool frames / instance
    page: int = 64
    data: str = "data"     # instance mesh axis
    model: str = "model"   # TP mesh axis
    data_size: int = 16
    tp: int = 16
    backend: str = "routed"          # routed | dense (Fig. 17 baseline)
    rounds_used: int = -1            # effective W-1 rounds (-1 = all)
    MBT: int = 0                     # page blocks per work row per kv stripe
                                     # (0 -> MB; hybrid sharding)
    eos: int = -1                    # stop token id; >= 0 enables the
                                     # device-side EOS mask: a slot whose
                                     # INPUT token is eos (the one-step-late
                                     # speculative step of an EOS finish) is
                                     # treated as inactive — its KV append is
                                     # redirected to the scratch frame and
                                     # its sampled token comes back as -1
    kv_dtype: str = "bf16"           # paged-KV pool storage format
                                     # (kernels/quant.py): "bf16" keeps the
                                     # model dtype (bit-identical legacy
                                     # path); "fp8"/"int8" store quantized
                                     # pools + per-page scale sidecars

    @property
    def num_rounds(self) -> int:
        r = self.W - 1 if self.rounds_used < 0 else self.rounds_used
        return r if self.S > 0 else 0


def attn_tp_geometry(cfg: ModelConfig, tp: int):
    """Hybrid decode-KV sharding geometry for tp-way attention TP.

    Returns (hp, khs, ps):
      hp  — q heads padded to a tp multiple,
      khs — kv-head shards  = min(Hkv, tp),
      ps  — page shards     = tp / khs (each kv-head subgroup stripes its KV
            pages across ps devices; partials merge via a subgroup LSE
            all-gather).  ps=1 degenerates to plain head-TP; khs=1 (MLA's
            single latent head) stripes pages across ALL tp devices — no KV
            replication anywhere (beyond-paper memory optimisation,
            EXPERIMENTS.md §Perf).

    When tp < Hkv each shard owns a GROUP of kg = Hkv/tp kv heads
    (``kv_group_size``): the per-device sub-pool stores kg heads per token
    (last dim kg*hd) and the paged kernel's kv-head grid indexes within the
    group.  Grouping (kg>1) and page striping (ps>1) are mutually exclusive
    by construction.
    """
    if not cfg.has_attention:                  # SSM-only: no attention geometry
        return 0, 1, 1
    hp = ((cfg.num_heads + tp - 1) // tp) * tp
    hkv = 1 if cfg.is_mla else cfg.num_kv_heads
    khs = min(hkv, tp)
    assert tp % khs == 0, (hkv, tp)
    assert hkv % khs == 0, \
        f"tp={tp} < num_kv_heads={hkv} needs tp | num_kv_heads for head groups"
    return hp, khs, tp // khs


def kv_group_size(cfg: ModelConfig, tp: int) -> int:
    """kv heads co-resident on one model chunk (tp < Hkv head-grouping)."""
    if not cfg.has_attention:
        return 1
    hkv = 1 if cfg.is_mla else max(cfg.num_kv_heads, 1)
    _, khs, _ = attn_tp_geometry(cfg, tp)
    return hkv // khs


def _head_perm(hp: int, tp: int, khs: int) -> list[int]:
    """q-head order so model-chunk c = p*khs + h carries heads
    [h*G + p*hl, ...) — after the page-subgroup gather, kv-head h's G q
    heads assemble in order.  Identity when khs==tp or khs==1."""
    ps = tp // khs
    hl = hp // tp
    G = hp // khs
    perm = []
    for c in range(tp):
        p, h = c // khs, c % khs
        perm.extend(range(h * G + p * hl, h * G + (p + 1) * hl))
    return perm


def _head_tools(cfg: ModelConfig, tp: int):
    """(pad_q, pad_q_rows, tile_kv, perm) for the hybrid-sharded head layout."""
    hp, khs, ps = attn_tp_geometry(cfg, tp)
    hkv = 1 if cfg.is_mla else max(cfg.num_kv_heads, 1)
    perm = jnp.asarray(_head_perm(hp, tp, khs), jnp.int32) if hp else None

    def pad_q(w, per):
        """[..., Hq*per] -> [..., hp*per]: pad each kv group, then permute
        heads into the model-chunk order."""
        hq = cfg.num_heads
        g_in, g_out = hq // hkv, hp // hkv
        w = w.reshape(w.shape[:-1] + (hkv, g_in, per))
        pad = [(0, 0)] * (w.ndim - 3) + [(0, 0), (0, g_out - g_in), (0, 0)]
        w = jnp.pad(w, pad).reshape(w.shape[:-3] + (hp, per))
        w = jnp.take(w, perm, axis=-2)
        return w.reshape(w.shape[:-2] + (hp * per,))

    def pad_q_rows(w, per):
        """wo [Hq*per, D] -> [hp*per, D] with the same grouped pad + perm."""
        hq, D = cfg.num_heads, w.shape[-1]
        g_in, g_out = hq // hkv, hp // hkv
        w = w.reshape(hkv, g_in, per, D)
        w = jnp.pad(w, ((0, 0), (0, g_out - g_in), (0, 0), (0, 0)))
        w = jnp.take(w.reshape(hp, per, D), perm, axis=0)
        return w.reshape(hp * per, D)

    def tile_kv(w, per):
        """[..., Hkv*per] -> [..., tp*(kg*per)]: kv head layout [p0h0..p0hK,
        p1h0..] so model-chunk c = p*khs + h holds kv-head GROUP h, i.e. the
        kg = Hkv/khs heads [h*kg, (h+1)*kg) in order (kg=1 unless tp < Hkv,
        in which case ps=1 and the layout is plain grouped column TP)."""
        kg = hkv // khs
        shape = w.shape[:-1] + (khs, kg * per)
        w = w.reshape(shape)
        w = jnp.concatenate([w] * ps, axis=-2)
        return w.reshape(w.shape[:-2] + (tp * kg * per,))

    return pad_q, pad_q_rows, tile_kv, perm


# =========================================================================== #
# decode parameter layout
# =========================================================================== #
def quantize_decode_weights(dparams: dict, dtype=jnp.float8_e4m3fn) -> dict:
    """Store large decode matrices in fp8 (weight-streaming-bound decode:
    DeepSeek-V3-style fp8 serving).  Dequantisation happens at use — on TPU
    in-register before the MXU, in the CPU artifact as a convert fusion.
    Norm scales / biases / routers stay high precision."""
    skip = {"ln1", "ln2", "final_norm", "router", "q_norm", "k_norm",
            "kv_norm", "norm", "A_log", "D", "dt_bias",
            "embed", "head"}   # embeddings feed activations directly

    def q(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        if leaf.ndim >= 2 and leaf.size >= 65536 and                 not (set(names) & skip) and leaf.dtype == jnp.bfloat16:
            return leaf.astype(dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(q, dparams)


def to_decode_params(cfg: ModelConfig, params: dict, tp: int) -> dict:
    """Restructure training params for the decode step: pad q heads PER KV
    GROUP to the hybrid-sharding layout (grouped pad + chunk permutation,
    see ``attn_tp_geometry``), tile kv heads across page subgroups, split
    SSM in_proj by sharding class, reshape MLA up-projections per head.
    Pure; jit/eval_shape friendly."""
    hd = cfg.head_dim_
    hp, khs, ps = attn_tp_geometry(cfg, tp)
    pad_q, pad_q_rows, tile_kv, perm = _head_tools(cfg, tp)

    def conv_layer(lp, kind):
        out = {"ln1": lp["ln1"]}
        mx = lp["mixer"]
        if kind["mixer"] == "attn":
            if cfg.is_mla:
                dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                              cfg.v_head_dim)
                kvr = cfg.kv_lora_rank
                m = {"wkv_a": mx["wkv_a"], "kv_norm": mx["kv_norm"]}
                if cfg.q_lora_rank:
                    m["wq_a"] = mx["wq_a"]
                    m["q_norm"] = mx["q_norm"]
                    m["wq_b"] = pad_q(mx["wq_b"], dn + dr)
                else:
                    m["wq"] = pad_q(mx["wq"], dn + dr)
                wk_b = mx["wk_b"].reshape(kvr, cfg.num_heads, dn).transpose(1, 0, 2)
                wv_b = mx["wv_b"].reshape(kvr, cfg.num_heads, dv).transpose(1, 0, 2)
                padh = ((0, hp - cfg.num_heads), (0, 0), (0, 0))
                m["wk_b"] = jnp.take(jnp.pad(wk_b, padh), perm, axis=0)
                m["wv_b"] = jnp.take(jnp.pad(wv_b, padh), perm, axis=0)
                m["wo"] = pad_q_rows(mx["wo"], dv)
            else:
                m = {"wq": pad_q(mx["wq"], hd),
                     "wk": tile_kv(mx["wk"], hd),
                     "wv": tile_kv(mx["wv"], hd),
                     "wo": pad_q_rows(mx["wo"], hd)}
                if cfg.qkv_bias:
                    m["bq"] = pad_q(mx["bq"], hd)
                    m["bk"] = tile_kv(mx["bk"], hd)
                    m["bv"] = tile_kv(mx["bv"], hd)
                if cfg.qk_norm:
                    m["q_norm"] = mx["q_norm"]
                    m["k_norm"] = mx["k_norm"]
        else:  # ssm: split in_proj by sharding class
            din, ns, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads
            w = mx["in_proj"]
            m = {"wz": w[..., :din], "wx": w[..., din:2 * din],
                 "wB": w[..., 2 * din:2 * din + ns],
                 "wC": w[..., 2 * din + ns:2 * din + 2 * ns],
                 "wdt": w[..., 2 * din + 2 * ns:],
                 "conv_x": mx["conv_w"][..., :din],
                 "conv_B": mx["conv_w"][..., din:din + ns],
                 "conv_C": mx["conv_w"][..., din + ns:],
                 "convb_x": mx["conv_b"][..., :din],
                 "convb_B": mx["conv_b"][..., din:din + ns],
                 "convb_C": mx["conv_b"][..., din + ns:],
                 "A_log": mx["A_log"], "D": mx["D"],
                 "dt_bias": mx["dt_bias"], "norm": mx["norm"],
                 "out_proj": mx["out_proj"]}
        out["mixer"] = m
        if kind["ffn"] != "none":
            out["ln2"] = lp["ln2"]
            out["ffn"] = lp["ffn"]
        return out

    out = {"embed": params["embed"], "final_norm": params["final_norm"],
           "head": params["head"]}
    for key, pattern, _, _ in cfg.segments():
        out[key] = {"layers": [
            jax.vmap(lambda lp, kd=kind: conv_layer(lp, kd))(
                params[key]["layers"][i])
            for i, kind in enumerate(pattern)]}
    return out


# =========================================================================== #
# serve state (KV pools / SSM states), global [I, ...] arrays
# =========================================================================== #
def init_serve_state(cfg: ModelConfig, dims: DecodeDims, num_instances: int,
                     dtype=jnp.bfloat16) -> dict:
    """Zeroed pools; shapes are the contract for specs/dry-run."""
    I = num_instances
    nb = cfg.num_blocks
    pattern = cfg.block_pattern()
    n_attn = sum(1 for k in pattern if k["mixer"] == "attn")
    n_ssm = sum(1 for k in pattern if k["mixer"] == "ssm")
    hd = cfg.head_dim_
    state = {}
    if n_attn:
        _, khs, ps = attn_tp_geometry(cfg, dims.tp)
        kg = kv_group_size(cfg, dims.tp)
        fp = -(-(dims.num_frames - 1) // ps) + 1     # frames/stripe + scratch
        # quantized pools (dims.kv_dtype fp8/int8) store a narrow dtype plus
        # a per-page f32 scale sidecar [nb, n_attn, I, tp, F'] that travels
        # with the pools through every donated step / movement collective.
        # Scales init to 1.0 (any positive value works: a frame is always
        # refilled from offset 0 before it is read — the offset-0 rule,
        # kernels/quant.py).
        pdt = quant.kv_storage_dtype(dims.kv_dtype, dtype)
        sc_shape = (nb, n_attn, I, dims.tp, fp)
        if cfg.is_mla:
            # the latent (kv_lora_rank + rope lanes) is stored at whole
            # 128-lane tiles, its pad lanes zero and never read: at 288 or
            # 576 lanes the TPU would lay the state out frame-minor and
            # relayout it for the paged kernel, which reads page-major tiles
            lanes = lane_tiles(cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            state["kv_pool"] = jnp.zeros(
                (nb, n_attn, I, dims.tp, fp, dims.page, lanes), pdt)
            if quant.is_quantized(dims.kv_dtype):
                state["kv_scale"] = jnp.ones(sc_shape, jnp.float32)
        else:
            # last dim kg*hd: each model chunk stores its kv-head GROUP
            state["k_pool"] = jnp.zeros(
                (nb, n_attn, I, dims.tp, fp, dims.page, kg * hd), pdt)
            state["v_pool"] = jnp.zeros_like(state["k_pool"])
            if quant.is_quantized(dims.kv_dtype):
                state["k_scale"] = jnp.ones(sc_shape, jnp.float32)
                state["v_scale"] = jnp.ones(sc_shape, jnp.float32)
    if n_ssm:
        din, ns, nh = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads
        cw = cfg.ssm_conv_width
        # conv windows stay bf16 regardless of the KV pool dtype (fp8 KV is
        # an attention-cache optimisation; SSM state is precision-sensitive)
        cdt = jnp.bfloat16 if dtype == jnp.float8_e4m3fn else dtype
        state["conv_x"] = jnp.zeros((nb, n_ssm, I, dims.M, cw - 1, din), cdt)
        state["conv_B"] = jnp.zeros((nb, n_ssm, I, dims.M, cw - 1, ns), cdt)
        state["conv_C"] = jnp.zeros((nb, n_ssm, I, dims.M, cw - 1, ns), cdt)
        state["ssm_state"] = jnp.zeros((nb, n_ssm, I, dims.M, nh,
                                        cfg.ssm_head_dim, ns), jnp.float32)
    if cfg.holds_share:
        # routed assignments of live tokens that reached each held expert,
        # summed over the MoE layers and steps (``NanoCPEngine.expert_load``)
        state["expert_load"] = jnp.zeros((I, cfg.num_held_experts),
                                         jnp.int32)
    return state


# =========================================================================== #
# per-device step (runs inside shard_map)
# =========================================================================== #
def _mask_eos_slots(dims: DecodeDims, tbl: dict, tokens):
    """Device-side stop-token check (`dims.eos`).

    A slot whose input token equals the stop token can only be the
    speculative step of an EOS finish (the pipelined engine lowers iteration
    t+1 before iteration t's sampled EOS is visible on the host): clearing
    ``slot_active`` for it makes the KV append land in the scratch frame and
    the sampled token come back -1 — the EOS request finishes without a
    stray KV entry, and the mask costs one compare+and per slot, surviving
    ``donate=True`` (it rewrites no state)."""
    if dims.eos < 0:
        return tbl
    live = (tbl["slot_active"][0] != 0) & (tokens != dims.eos)
    tbl = dict(tbl)
    tbl["slot_active"] = live[None].astype(jnp.int32)
    return tbl


def _embed_lookup(embed_local, tokens, vs_local, tp_axis):
    """Vocab-sharded embedding: masked local gather + psum."""
    j = jax.lax.axis_index(tp_axis)
    local = tokens - j * vs_local
    ok = (local >= 0) & (local < vs_local)
    rows = embed_local[jnp.clip(local, 0, vs_local - 1)]
    rows = jnp.where(ok[:, None], rows, 0)
    return jax.lax.psum(rows, tp_axis)


def _sample_greedy(logits_local, vs_local, tp_axis):
    """Distributed argmax over the model-sharded vocab."""
    j = jax.lax.axis_index(tp_axis)
    loc_max = jnp.max(logits_local, axis=-1)                      # [M]
    loc_idx = jnp.argmax(logits_local, axis=-1) + j * vs_local
    allm = jax.lax.all_gather(loc_max, tp_axis, axis=0)           # [tp, M]
    alli = jax.lax.all_gather(loc_idx, tp_axis, axis=0)
    win = jnp.argmax(allm, axis=0)                                # [M]
    return jnp.take_along_axis(alli, win[None, :], axis=0)[0].astype(jnp.int32)


def _split_pages(bt, length, ps, p_j, mbt, page):
    """Stripe a row's global block table onto this device's page stripe.

    bt [N, MB] global frame ids, length [N].  Device p_j owns frames with
    f % ps == p_j at local index f // ps.  Owned pages keep position order,
    so valid tokens stay a prefix (at most the row's LAST page is partial).
    Returns (bt_local [N, mbt], len_local [N]).
    """
    if ps == 1:
        return bt, length
    N, MB = bt.shape
    pos = jnp.arange(MB)
    npages = -(-length // page)                              # [N]
    valid = pos[None, :] < npages[:, None]
    own = valid & ((bt % ps) == p_j)
    order = jnp.argsort(jnp.where(own, pos[None, :], MB + pos[None, :]),
                        axis=1)[:, :mbt]
    sel = jnp.take_along_axis(own, order, axis=1)
    bt_local = jnp.where(sel, jnp.take_along_axis(bt // ps, order, axis=1), 0)
    toks = jnp.clip(length[:, None] - pos[None, :] * page, 0, page)
    toks_sel = jnp.take_along_axis(jnp.where(own, toks, 0), order, axis=1)
    return bt_local.astype(bt.dtype), jnp.sum(toks_sel, axis=1).astype(length.dtype)


def _append_kv(dims: DecodeDims, tbl, pools, new_k, new_v, *, ps, p_j,
               base, frames):
    """KV append (write-then-attend) of this step's M tokens: the only pool
    bytes the decode step writes.

    Only the frame's stripe owner writes; everyone else (and inactive
    slots) scatters into the layer's scratch frame (its last frame, never
    handed out by the allocator).  pools = (k_pool, v_pool, k_scale,
    v_scale) as ``_dcp_attention`` takes them, the layer's frames starting
    at ``base``; a row fills the first lanes of the pool's (a padded
    latent's pad lanes stay zero)."""
    k_pool, v_pool, k_scale, v_scale = pools
    M, page = dims.M, k_pool.shape[1]
    act = tbl["slot_active"][0].astype(bool)
    af_g = tbl["append_frame"][0]
    mine = act & ((af_g % ps) == p_j) if ps > 1 else act
    af = base + jnp.where(mine, af_g // ps, frames - 1)            # [M]
    ao = jnp.where(mine, tbl["append_off"][0], jnp.arange(M) % page)
    kl = new_k.shape[-1]
    if k_scale is None:
        k_pool = k_pool.at[af, ao, :kl].set(new_k.astype(k_pool.dtype))
        if v_pool is not None:
            v_pool = v_pool.at[af, ao].set(new_v.astype(v_pool.dtype))
        return k_pool, v_pool, k_scale, v_scale
    # offset-0 rule: an append landing at page offset 0 starts a fresh
    # page, so it RESETS that page's scale to this token's amax/qmax;
    # appends at later offsets CLIP into the page's existing scale
    # (already-stored tokens are never re-scaled).  Distinct active slots
    # never share an append frame; duplicate scatter rows only hit the
    # scratch frame (garbage anyway).
    ks_eff = jnp.where(ao == 0, quant.amax_scale(new_k, dims.kv_dtype),
                       k_scale[af])
    k_pool = k_pool.at[af, ao, :kl].set(
        quant.quantize(new_k, ks_eff[:, None], dims.kv_dtype))
    k_scale = k_scale.at[af].set(ks_eff)
    if v_pool is not None:
        vs_eff = jnp.where(ao == 0, quant.amax_scale(new_v, dims.kv_dtype),
                           v_scale[af])
        v_pool = v_pool.at[af, ao].set(
            quant.quantize(new_v, vs_eff[:, None], dims.kv_dtype))
        v_scale = v_scale.at[af].set(vs_eff)
    return k_pool, v_pool, k_scale, v_scale


def _dcp_attention(cfg, dims: DecodeDims, q, k_pool, v_pool, new_k, new_v,
                   tbl, *, dk, dv, geom, k_scale=None, v_scale=None,
                   base=0, frames=None):
    """Phases 1-4 for one attention layer (per device).

    q: [M, hl, dk] local-slot queries.  k_pool/v_pool: [F', page, kg*(dk|dv)]
    — the device's hybrid-sharded sub-pool: kv-head group h_j = chunk % khs
    (kg = Hkv/khs heads per group, flattened into the last dim), page
    stripe p_j = chunk // khs (geom = (hp, khs, ps); DESIGN.md §2).  MLA's
    latent pool is stored at whole 128-lane tiles past dk.  The decode
    step passes every layer's frames as one pool: this layer's ``frames``
    (F') frames start at frame ``base``.
    new_k/new_v: [M, kg*(dk|dv)] this step's token KV for the device's kv
    heads (written at append_frame/off iff the frame's stripe is p_j), or
    new_k=None for read-only pools (whisper cross-attention).
    k_scale/v_scale: per-page dequant scales [F'] f32 iff the pool is
    quantized (dims.kv_dtype fp8/int8); appends quantize into them under
    the offset-0 rule (kernels/quant.py) and the paged kernel dequants
    with them.  MLA passes its single kv_scale as k_scale.
    Returns merged [M, hl, dv], updated (k_pool, v_pool, k_scale, v_scale).
    """
    M, S, N, W = dims.M, dims.S, dims.N, dims.W
    R = dims.num_rounds
    hp, khs, ps = geom
    hl = hp // dims.tp
    Fp = frames or k_pool.shape[0]
    page = k_pool.shape[1]
    kg = kv_group_size(cfg, dims.tp)                # kv heads per model chunk
    assert kg == 1 or ps == 1, (kg, ps)
    j = jax.lax.axis_index(dims.model)
    p_j = j // khs
    groups = [[p * khs + h for p in range(ps)] for h in range(khs)]

    if new_k is not None:
        with jax.named_scope("pool_carry"):
            k_pool, v_pool, k_scale, v_scale = _append_kv(
                dims, tbl, (k_pool, v_pool, k_scale, v_scale), new_k, new_v,
                ps=ps, p_j=p_j, base=base, frames=Fp)

    # -- Phase 1: Q-routing --
    if dims.backend == "dense" and R > 0:
        # NCCL-collective baseline (Fig. 17): gather every peer's full q
        # buffer, then pick the rows the routed backend would have received.
        gathered = comm.allgather_backend(q, dims.data)            # [I, M, hl, dk]
        me = jax.lax.axis_index(dims.data)
        node0 = (me // W) * W
        recv_q = []
        for d in range(1, R + 1):
            # sender of zig-zag round d within the rotation window
            src = node0 + (me - node0 - comm.ring_delta(d)) % W
            recv_q.append(comm.gather_rows(gathered[src],
                                           tbl["q_recv_slot"][0, d - 1]))
    elif R > 0:
        recv_q = comm.route_rounds(
            lambda d, idx: comm.gather_rows(q, idx),
            tbl["q_send_idx"][0], R, axis=dims.data,
            axis_size=dims.data_size, node=W)
    else:
        recv_q = []
    q_pool = jnp.concatenate([q] + recv_q, axis=0) if recv_q else q

    # -- Phase 2: paged attention over the local sub-pool --
    wsrc = tbl["work_src"][0]                                      # [N]
    q_work = comm.gather_rows(q_pool, wsrc)                        # [N, hl, dk]
    if ps > 1:
        # assemble the kv-head group's G = ps*hl q heads within the stripe
        # subgroup (heads were chunk-permuted by to_decode_params so
        # ascending p concatenates in head order)
        q_grp = jax.lax.all_gather(q_work, dims.model, axis=0,
                                   axis_index_groups=groups)       # [ps,N,hl,dk]
        q_work = q_grp.transpose(1, 0, 2, 3).reshape(N, ps * hl, dk)
        bt_dev, len_dev = _split_pages(tbl["work_bt"][0], tbl["work_len"][0],
                                       ps, p_j, dims.MBT or dims.MB, dims.page)
    else:
        bt_dev, len_dev = tbl["work_bt"][0], tbl["work_len"][0]
    # the kernel reads the layer's pages where they lie in the whole pool
    P = k_pool.shape[0]
    kp = k_pool.reshape(P, page, kg, -1)                           # [P,page,kg,dk]
    # MLA passes no V pool: the kernel reads V from the latent it holds
    vp = v_pool.reshape(P, page, kg, dv) if v_pool is not None else None
    out, lse = ops.paged_decode_attention(
        q_work, kp, vp, bt_dev + base, len_dev,
        scale=dk ** -0.5 if cfg.attention != "mla" else
        cfg.mla_softmax_scale,
        # fused dequant: per-page scales follow the same local frame ids as
        # the sub-pool; MLA's shared latent pool has k_scale alone.
        k_scale=k_scale, v_scale=v_scale, v_dim=dv)
    if ps > 1:
        # merge the stripe partials within the subgroup, slice back to hl
        g_o = jax.lax.all_gather(out, dims.model, axis=0,
                                 axis_index_groups=groups)         # [ps,N,G,dv]
        g_l = jax.lax.all_gather(lse, dims.model, axis=0,
                                 axis_index_groups=groups)         # [ps,N,G]
        out, lse = ref.merge_lse(g_o.reshape(ps, -1, *g_o.shape[2:]),
                                 g_l.reshape(ps, -1, g_l.shape[-1]))
        out = jax.lax.dynamic_slice_in_dim(out, p_j * hl, hl, axis=1)
        lse = jax.lax.dynamic_slice_in_dim(lse, p_j * hl, hl, axis=1)

    # -- Phases 3+4: Res-routing and LSE merge --
    if dims.backend == "dense" and R > 0:
        # dense baseline: gather everyone's partials, index by owner tables
        g_out = comm.allgather_backend(out, dims.data)             # [I, N, Hl, dv]
        g_lse = comm.allgather_backend(lse, dims.data)             # [I, N, Hl]
        me = jax.lax.axis_index(dims.data)
        node0 = (me // W) * W
        d_mat = tbl["merge_round"][0]                              # [M, W]
        owner = node0 + (me - node0 + comm.ring_delta(d_mat)) % W
        row = tbl["merge_peer_row"][0]                             # [M, W]
        mask = row >= 0
        parts = g_out[owner, jnp.maximum(row, 0)].transpose(1, 0, 2, 3)
        plse = g_lse[owner, jnp.maximum(row, 0)].transpose(1, 0, 2)
        merged, _ = ref.merge_lse(parts, plse, mask=mask.T)
        return merged, k_pool, v_pool, k_scale, v_scale
    if R > 0:
        ret_o = comm.route_rounds(
            lambda d, idx: comm.gather_rows(out, idx),
            tbl["ret_send_idx"][0], R, axis=dims.data,
            axis_size=dims.data_size, node=W, reverse=True)
        ret_l = comm.route_rounds(
            lambda d, idx: comm.gather_rows(lse, idx),
            tbl["ret_send_idx"][0], R, axis=dims.data,
            axis_size=dims.data_size, node=W, reverse=True)
        o_pool = jnp.concatenate([out] + ret_o, axis=0)
        l_pool = jnp.concatenate([lse] + ret_l, axis=0)
    else:
        o_pool, l_pool = out, lse

    # -- Phase 4: LSE merge per slot --
    msrc = tbl["merge_src"][0]                                     # [M, W]
    parts = comm.gather_rows(o_pool, msrc.reshape(-1)).reshape(
        M, W, *out.shape[1:]).transpose(1, 0, 2, 3)                # [W, M, Hl, dv]
    plse = l_pool[jnp.maximum(msrc.reshape(-1), 0)].reshape(
        M, W, -1).transpose(1, 0, 2)                                # [W, M, Hl]
    merged, _ = ref.merge_lse(parts, plse, mask=(msrc.T >= 0))
    return merged, k_pool, v_pool, k_scale, v_scale


def _attn_layer(cfg, dims, lp, x, pos, pools, tbl, hl, geom, base, frames):
    """One GQA/MLA attention layer (per device).

    pools = (k_pool, v_pool, k_scale, v_scale), every layer's frames in
    one run, this layer's ``frames`` of them from frame ``base``; the
    scale entries are None for bf16 pools (MLA: (kv_pool, None, kv_scale,
    None)).
    """
    hd = cfg.head_dim_
    h = L.apply_norm(cfg, lp["ln1"], x)
    M = dims.M
    if cfg.is_mla:
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        kvr = cfg.kv_lora_rank
        mx = lp["mixer"]
        if cfg.q_lora_rank:
            cq = L.rms_norm_vec(h @ mx["wq_a"], mx["q_norm"])
            qn = (cq @ mx["wq_b"]).reshape(M, hl, dn + dr)
        else:
            qn = (h @ mx["wq"]).reshape(M, hl, dn + dr)
        q_nope, q_rope = qn[..., :dn], qn[..., dn:]
        q_rope = L.apply_rope(q_rope, pos, cfg.rope_theta, cfg.rope_scaling)
        # absorb W_uk: q_latent = q_nope @ wk_b[h]  -> [M, hl, kvr]
        q_lat = jnp.einsum("mhd,hkd->mhk", q_nope, mx["wk_b"])
        q = jnp.concatenate([q_lat, q_rope], axis=-1)              # [M,hl,kvr+dr]
        kv = h @ mx["wkv_a"]
        c_kv = L.rms_norm_vec(kv[..., :kvr], mx["kv_norm"])
        k_rope = L.apply_rope(kv[..., kvr:][:, None, :], pos,
                              cfg.rope_theta, cfg.rope_scaling)[:, 0, :]
        new_k = jnp.concatenate([c_kv, k_rope], axis=-1)           # [M, kvr+dr]
        merged, kp, _, ksc, _ = _dcp_attention(cfg, dims, q, pools[0], None,
                                               new_k, None, tbl, dk=kvr + dr,
                                               dv=kvr, geom=geom,
                                               k_scale=pools[2], base=base,
                                               frames=frames)
        o = jnp.einsum("mhk,hkd->mhd", merged, mx["wv_b"])         # [M,hl,dv]
        o = o.reshape(M, hl * dv) @ lp["mixer"]["wo"]
        return jax.lax.psum(o, dims.model), (kp, None, ksc, None)
    mx = lp["mixer"]
    kg = kv_group_size(cfg, dims.tp)
    q = h @ mx["wq"]
    k = h @ mx["wk"]
    v = h @ mx["wv"]
    if cfg.qkv_bias:
        q = q + mx["bq"].astype(q.dtype)
        k = k + mx["bk"].astype(k.dtype)
        v = v + mx["bv"].astype(v.dtype)
    q = q.reshape(M, hl, hd)
    k = k.reshape(M, kg, hd)                              # local kv-head group
    if cfg.qk_norm:
        q = L.rms_norm_vec(q, mx["q_norm"])
        k = L.rms_norm_vec(k, mx["k_norm"])
    q = L.apply_rope(q, pos, cfg.rope_theta, cfg.rope_scaling)
    k = L.apply_rope(k, pos, cfg.rope_theta,
                     cfg.rope_scaling).reshape(M, kg * hd)
    merged, kp, vp, ksc, vsc = _dcp_attention(cfg, dims, q, pools[0], pools[1],
                                              k, v, tbl, dk=hd, dv=hd,
                                              geom=geom, k_scale=pools[2],
                                              v_scale=pools[3], base=base,
                                              frames=frames)
    o = merged.reshape(M, hl * hd) @ mx["wo"]
    return jax.lax.psum(o, dims.model), (kp, vp, ksc, vsc)


def _ssm_layer(cfg, dims, lp, x, sstate):
    """One SSD decode layer (per device, heads TP over model)."""
    mx = lp["mixer"]
    conv_x, conv_B, conv_C, h_state = sstate
    M = dims.M
    h = L.apply_norm(cfg, lp["ln1"], x)
    z = h @ mx["wz"]                                     # [M, din/tp]
    xin = h @ mx["wx"]
    Bm = h @ mx["wB"]                                    # [M, ns] replicated
    Cm = h @ mx["wC"]
    dt = h @ mx["wdt"]                                   # [M, nh/tp]
    nh_l = dt.shape[-1]
    hd = cfg.ssm_head_dim

    def conv1(state, new, w, b):
        win = jnp.concatenate([state, new[:, None, :]], axis=1)    # [M, cw, c]
        out = jnp.einsum("mwc,wc->mc", win.astype(jnp.float32),
                         w.astype(jnp.float32)) + b
        return jax.nn.silu(out).astype(new.dtype), win[:, 1:, :]

    xin, conv_x = conv1(conv_x, xin, mx["conv_x"], mx["convb_x"])
    Bm, conv_B = conv1(conv_B, Bm, mx["conv_B"], mx["convb_B"])
    Cm, conv_C = conv1(conv_C, Cm, mx["conv_C"], mx["convb_C"])

    dtp = jax.nn.softplus(dt.astype(jnp.float32) + mx["dt_bias"])
    A = -jnp.exp(mx["A_log"])
    xh = xin.reshape(M, nh_l, hd).astype(jnp.float32)
    decay = jnp.exp(dtp * A)
    upd = jnp.einsum("ms,mh,mhd->mhds", Bm.astype(jnp.float32), dtp, xh)
    h_new = h_state * decay[..., None, None] + upd
    y = jnp.einsum("ms,mhds->mhd", Cm.astype(jnp.float32), h_new)
    y = y + xh * mx["D"][None, :, None]
    y = y.reshape(M, nh_l * hd).astype(x.dtype)
    # gated RMSNorm over the FULL (model-sharded) d_inner axis: psum the
    # mean-square across TP shards before normalising
    g = (y * jax.nn.silu(z)).astype(jnp.float32)
    ss = jax.lax.psum(jnp.sum(jnp.square(g), axis=-1, keepdims=True),
                      dims.model)
    g = g * jax.lax.rsqrt(ss / cfg.ssm_d_inner + 1e-6) * mx["norm"]
    out = jax.lax.psum(g.astype(x.dtype) @ mx["out_proj"], dims.model)
    return out, (conv_x, conv_B, conv_C, h_new)


def build_decode_step(cfg: ModelConfig, dims: DecodeDims):
    """Returns the per-device step fn (to be shard_mapped by the caller).

    step(params, state, tables) -> (new_state, next_tokens [1, M], logits)
    All array args are the per-device shards (leading I dim of size 1 on
    state/tables).
    """
    geom = attn_tp_geometry(cfg, dims.tp)
    hp = geom[0]
    hl = hp // dims.tp if hp else 0
    vs_local = cfg.padded_vocab // dims.tp
    n_attn = sum(1 for k in cfg.block_pattern() if k["mixer"] == "attn")
    # the state's pools in ``_attn_layer``'s (k, v, k_scale, v_scale) order
    pool_keys = (("kv_pool", None, "kv_scale", None) if cfg.is_mla else
                 ("k_pool", "v_pool", "k_scale", "v_scale"))

    def step(params, state, tbl):
        tokens = tbl["slot_token"][0]                              # [M]
        pos = tbl["slot_pos"][0]
        tbl = _mask_eos_slots(dims, tbl, tokens)
        x = _embed_lookup(params["embed"]["tok"], tokens, vs_local, dims.model)
        x = x.astype(params["embed"]["tok"].dtype)   # carry dtype = param dtype

        # The attention pools are carried IN PLACE: each is viewed as one
        # run of frames [nb*n_attn*F', page, lanes] (a reshape of the
        # device's [nb, n_attn, 1, 1, F', ...] block, a bitcast in the
        # tiled layout; scale sidecars [nb*n_attn*F']).  Layer (i, ai)
        # owns frames from (i*n_attn + ai)*F': the append writes its M new
        # rows there, and the kernel reads the layer's pages through block
        # tables offset by that much, so no step slices, writes back or
        # relays out a pool.  SSM states (small, per slot) travel as scan
        # carry with per-block dynamic-slice/update.  Each segment of the
        # trunk (``cfg.segments``: leading dense layers, then the main
        # stack) is one scan over its blocks, carrying the same buffers.
        state = dict(state)
        load = state.pop("expert_load", None)
        live = None if load is None else tbl["slot_active"][0]
        shapes = {k: state[k].shape for k in pool_keys if k in state}
        with jax.named_scope("pool_carry"):
            pools = tuple(state.pop(k).reshape((-1,) + shapes[k][5:])
                          if k in shapes else None for k in pool_keys)
        frames = next(iter(shapes.values()))[4] if shapes else 0

        def block_fn(pattern, carry, xs):
            x, pools, st, load = carry
            i, bp = xs["idx"], xs["params"]
            # fp8-stored weights dequantise at use (in-register on TPU; the
            # param stream is charged at fp8 width)
            bp = jax.tree.map(
                lambda w: w.astype(jnp.bfloat16)
                if w.dtype == jnp.float8_e4m3fn else w, bp)
            # named scopes (``pool_carry``, ``attention``, ``ffn``,
            # ``head``) tag each op's metadata, so a device trace can sum
            # the step's time by part; they change nothing XLA computes
            with jax.named_scope("pool_carry"):
                blk = {k: jax.lax.dynamic_index_in_dim(v, i, 0,
                                                       keepdims=False)
                       for k, v in st.items()}
            ai = si = 0
            upd = {}
            for li, kind in enumerate(pattern):
                lp = bp["layers"][li]
                if kind["mixer"] == "attn":
                    with jax.named_scope("attention"):
                        mix, pools = _attn_layer(
                            cfg, dims, lp, x, pos, pools, tbl, hl, geom,
                            base=(i * n_attn + ai) * frames, frames=frames)
                    ai += 1
                else:
                    with jax.named_scope("pool_carry"):
                        sstate = (blk["conv_x"][si, 0], blk["conv_B"][si, 0],
                                  blk["conv_C"][si, 0],
                                  blk["ssm_state"][si, 0])
                    mix, s_out = _ssm_layer(cfg, dims, lp, x, sstate)
                    for nm, vv in zip(("conv_x", "conv_B", "conv_C",
                                       "ssm_state"), s_out):
                        upd.setdefault(nm, []).append(vv)
                    si += 1
                x = x + mix
                if kind["ffn"] != "none":
                    h = L.apply_norm(cfg, lp["ln2"], x)
                    with jax.named_scope("ffn"):
                        if kind["ffn"] == "moe":
                            f = moe_decode_ffn(cfg, lp["ffn"], h,
                                               axis=dims.data,
                                               axis_size=dims.data_size,
                                               tp_axis=dims.model,
                                               load_mask=live)
                            if load is not None:
                                f, hits = f
                                load = load + hits[None]
                        else:
                            f = dense_decode_ffn(cfg, lp["ffn"], h,
                                                 tp_axis=dims.model)
                    x = x + f
            with jax.named_scope("pool_carry"):
                st = {k: jax.lax.dynamic_update_index_in_dim(
                    st[k], jnp.stack(upd[k])[:, None], i, 0) for k in st}
            return (x, pools, st, load), None

        carry = (x, pools, state, load)
        for key, pattern, n, first in cfg.segments():
            xs = {"params": params[key], "idx": jnp.arange(first, first + n)}
            carry, _ = jax.lax.scan(partial(block_fn, pattern), carry, xs)
        x, pools, new_state, load = carry
        with jax.named_scope("pool_carry"):
            for k, pool in zip(pool_keys, pools):
                if pool is not None:
                    new_state[k] = pool.reshape(shapes[k])
        if load is not None:
            new_state["expert_load"] = load

        with jax.named_scope("head"):
            x = L.apply_norm(cfg, params["final_norm"], x)
            if cfg.tie_embeddings:
                logits = x @ params["embed"]["tok"].T
            else:
                logits = x @ params["head"]["w"]
            logits = logits.astype(jnp.float32)
            nxt = _sample_greedy(logits, vs_local, dims.model)
            nxt = jnp.where(tbl["slot_active"][0].astype(bool), nxt, -1)
        return new_state, nxt[None, :], logits[None]

    return step


# =========================================================================== #
# encoder-decoder (whisper) decode: DCP over the cross-attention KV
# =========================================================================== #
def init_encdec_serve_state(cfg: ModelConfig, dims: DecodeDims,
                            num_instances: int, dtype=jnp.bfloat16) -> dict:
    """Cross-attn KV is the big DCP-managed paged pool (seq_len enc states);
    decoder self-attn KV is a small per-slot contiguous cache.  Last dim is
    kg*hd: each model chunk stores its whole kv-head group (kg=1 unless
    tp < num_kv_heads)."""
    I, L = num_instances, cfg.num_layers
    hd = cfg.head_dim_
    _, khs, ps = attn_tp_geometry(cfg, dims.tp)
    kg = kv_group_size(cfg, dims.tp)
    fp = -(-(dims.num_frames - 1) // ps) + 1
    T = cfg.max_target_positions
    return {
        "cross_k_pool": jnp.zeros((L, I, dims.tp, fp, dims.page, kg * hd), dtype),
        "cross_v_pool": jnp.zeros((L, I, dims.tp, fp, dims.page, kg * hd), dtype),
        "self_k": jnp.zeros((L, I, dims.tp, dims.M, T, kg * hd), dtype),
        "self_v": jnp.zeros((L, I, dims.tp, dims.M, T, kg * hd), dtype),
    }


def build_encdec_decode_step(cfg: ModelConfig, dims: DecodeDims):
    """Per-device whisper decode step.  ``slot_pos`` = decoder position (the
    new token's self-attn index); cross pools are read-only (no appends)."""
    geom = attn_tp_geometry(cfg, dims.tp)
    hp = geom[0]
    hl = hp // dims.tp
    hd = cfg.head_dim_
    kg = kv_group_size(cfg, dims.tp)
    vs_local = cfg.padded_vocab // dims.tp
    M = dims.M

    def self_attention(lp, h, pos, sk, sv):
        """Contiguous small self-attn cache: write at pos, attend [0..pos].
        sk/sv: [M, T, kg*hd] — the model chunk's kv-head group."""
        mx = lp["self_attn"]
        q = h @ mx["wq"]
        k = h @ mx["wk"]
        v = h @ mx["wv"]
        if cfg.qkv_bias:
            q = q + mx["bq"].astype(q.dtype)
            k = k + mx["bk"].astype(k.dtype)
            v = v + mx["bv"].astype(v.dtype)
        q = q.reshape(M, hl, hd)
        sk = sk.at[jnp.arange(M), pos].set(k.astype(sk.dtype))
        sv = sv.at[jnp.arange(M), pos].set(v.astype(sv.dtype))
        T = sk.shape[1]
        o, _ = ref.decode_attention_dense(q, sk.reshape(M, T, kg, hd),
                                          sv.reshape(M, T, kg, hd), pos + 1)
        o = o.reshape(M, hl * hd) @ mx["wo"]
        return jax.lax.psum(o, dims.model), sk, sv

    def step(params, state, tbl):
        tokens = tbl["slot_token"][0]
        pos = tbl["slot_pos"][0]                      # decoder position
        tbl = _mask_eos_slots(dims, tbl, tokens)
        x = _embed_lookup(params["embed"]["tok"], tokens, vs_local, dims.model)
        x = x + params["embed"]["pos_dec"][pos].astype(x.dtype)
        x = x.astype(params["embed"]["pos_dec"].dtype)

        def block_fn(carry, xs):
            x, st = carry
            i, lp = xs["idx"], xs["params"]
            blk = {k: jax.lax.dynamic_index_in_dim(st[k], i, 0, keepdims=False)
                   for k in ("self_k", "self_v", "cross_k_pool",
                             "cross_v_pool")}
            h = L.apply_norm(cfg, lp["ln1"], x)
            o, sk, sv = self_attention(lp, h, pos,
                                       blk["self_k"][0, 0], blk["self_v"][0, 0])
            x = x + o
            # cross attention through DCP (read-only pools)
            h = L.apply_norm(cfg, lp["ln_x"], x)
            mx = lp["cross_attn"]
            q = h @ mx["wq"]
            if cfg.qkv_bias:
                q = q + mx["bq"].astype(q.dtype)
            q = q.reshape(M, hl, hd)
            merged, _, _, _, _ = _dcp_attention(cfg, dims, q,
                                                blk["cross_k_pool"][0, 0],
                                                blk["cross_v_pool"][0, 0],
                                                None, None, tbl, dk=hd, dv=hd,
                                                geom=geom)
            o = merged.reshape(M, hl * hd) @ mx["wo"]
            x = x + jax.lax.psum(o, dims.model)
            h = L.apply_norm(cfg, lp["ln2"], x)
            f = dense_decode_ffn(cfg, lp["mlp"], h, tp_axis=dims.model)
            x = x + f
            st = dict(st)
            st["self_k"] = jax.lax.dynamic_update_index_in_dim(
                st["self_k"], sk[None, None, None], i, 0)
            st["self_v"] = jax.lax.dynamic_update_index_in_dim(
                st["self_v"], sv[None, None, None], i, 0)
            return (x, st), None

        xs = {"params": params["dec_blocks"],
              "idx": jnp.arange(cfg.num_layers)}
        (x, new_state), _ = jax.lax.scan(block_fn, (x, state), xs)
        x = L.apply_norm(cfg, params["final_norm"], x)
        logits = (x @ params["embed"]["tok"].T if cfg.tie_embeddings
                  else x @ params["head"]["w"]).astype(jnp.float32)
        nxt = _sample_greedy(logits, vs_local, dims.model)
        nxt = jnp.where(tbl["slot_active"][0].astype(bool), nxt, -1)
        return new_state, nxt[None, :], logits[None]

    return step


def to_encdec_decode_params(cfg: ModelConfig, params: dict, tp: int) -> dict:
    """Decoder-side decode layout for whisper (hybrid-sharded heads like the
    decoder-only path).  Encoder params are dropped (prefill-only)."""
    hd = cfg.head_dim_
    pad_q, pad_q_rows, tile_kv, _ = _head_tools(cfg, tp)

    def conv_attn(mx):
        m = {"wq": pad_q(mx["wq"], hd),
             "wk": tile_kv(mx["wk"], hd),
             "wv": tile_kv(mx["wv"], hd),
             "wo": pad_q_rows(mx["wo"], hd)}
        if cfg.qkv_bias:
            m["bq"] = pad_q(mx["bq"], hd)
            m["bk"] = tile_kv(mx["bk"], hd)
            m["bv"] = tile_kv(mx["bv"], hd)
        return m

    def conv_layer(lp):
        return {"ln1": lp["ln1"], "self_attn": conv_attn(lp["self_attn"]),
                "ln_x": lp["ln_x"], "cross_attn": conv_attn(lp["cross_attn"]),
                "ln2": lp["ln2"], "mlp": lp["mlp"]}

    dec = jax.vmap(conv_layer)(params["dec_blocks"])
    return {"embed": params["embed"], "dec_blocks": dec,
            "final_norm": params["final_norm"], "head": params["head"]}


def encdec_param_specs(cfg, decode_params, *, data="data", model="model",
                       extra_data_axes=()):
    def spec_of(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        name = names[-1]
        nd = leaf.ndim
        if name == "tok":
            return P(model, None)
        if name == "pos_dec":
            return P()
        if name == "w" and "head" in names:
            return P(None, model)
        if name in ("scale", "bias", "bo"):
            return P()
        if name in ("wq", "wk", "wv", "bq", "bk", "bv", "wi", "bi"):
            return P(*([None] * (nd - 1)), model)
        if name in ("wo",):
            return P(*([None] * (nd - 2)), model, None)
        raise KeyError("/".join(names))
    return jax.tree_util.tree_map_with_path(spec_of, decode_params)


def encdec_state_specs(state, *, data="data", model="model", extra_data_axes=()):
    da = (*extra_data_axes, data) if extra_data_axes else data
    return {
        "cross_k_pool": P(None, da, model, None, None, None),
        "cross_v_pool": P(None, da, model, None, None, None),
        "self_k": P(None, da, model, None, None, None),
        "self_v": P(None, da, model, None, None, None),
    }


def make_encdec_serve_step(cfg, dims: DecodeDims, mesh, decode_params, state,
                           tables, *, extra_data_axes=(), donate: bool = True):
    da = (*extra_data_axes, dims.data) if extra_data_axes else dims.data
    step = build_encdec_decode_step(cfg, dims)
    pspecs = encdec_param_specs(cfg, decode_params, data=dims.data,
                                model=dims.model,
                                extra_data_axes=extra_data_axes)
    sspecs = encdec_state_specs(state, data=dims.data, model=dims.model,
                                extra_data_axes=extra_data_axes)
    tspecs = table_specs(tables, data=dims.data,
                         extra_data_axes=extra_data_axes)
    out_specs = (sspecs, P(da, None), P(da, None, dims.model))
    fn = _shard_map(step, mesh=mesh, in_specs=(pspecs, sspecs, tspecs),
                    out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(1,) if donate else ())


# =========================================================================== #
# sharding specs (shared by shard_map wrapper, dry-run, tests)
# =========================================================================== #
_REPLICATED_LEAVES = frozenset({
    "scale", "bias",                       # norms
    "q_norm", "k_norm", "kv_norm",         # qk / MLA latent norms
    "wq_a", "wkv_a", "router",             # lora-down / router: small, shared
    "wB", "wC", "conv_B", "conv_C", "convb_B", "convb_C",   # SSM B/C (shared)
    "pos_dec", "bo",
    "e_score_correction_bias",             # router selection bias [E]
})
_COLUMN_LEAVES = frozenset({               # shard the LAST dim over model
    "wq", "wk", "wv", "wq_b", "wz", "wx", "wdt",
    "wi", "wi_gate", "wi_up",
    "bq", "bk", "bv", "bi", "convb_x",
    "A_log", "D", "dt_bias", "norm",       # per-head / per-channel SSM vectors
    "conv_x",
})
_ROW_LEAVES = frozenset({"wo", "out_proj"})  # shard dim -2 over model


def decode_param_specs(cfg: ModelConfig, decode_params, *, data="data",
                       model="model", extra_data_axes=()):
    """PartitionSpec tree matching ``to_decode_params`` output.

    Explicit per-leaf rules: column-parallel weights shard their last dim
    over `model`, row-parallel (wo / out_proj) shard dim -2, MoE expert
    weights additionally shard the expert dim over `data` (EP), vocab
    dims shard over `model`, small shared tensors replicate.
    """
    da = (*extra_data_axes, data) if extra_data_axes else data

    def spec_of(path, leaf):
        names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
        name = names[-1]
        nd = leaf.ndim
        in_moe = "ffn" in names and nd == 4 and name in (
            "wi_gate", "wi_up", "wo")
        if in_moe:                       # [nb, E, D, F] / [nb, E, F, D]
            # experts shard over `data` ONLY: each pod is an independent
            # EP group (paper's deployment unit), so experts replicate
            # across pods
            return (P(None, data, None, model) if name.startswith("wi")
                    else P(None, data, model, None))
        if name == "tok":
            return P(model, None)        # vocab-sharded embedding
        if name == "w" and "head" in names:
            return P(None, model)        # [D, Vp]
        if name in ("wk_b", "wv_b"):
            return P(None, model, None, None)   # [nb, hp, kvr, d]: shard heads
        if name in _REPLICATED_LEAVES:
            return P()
        if name in _ROW_LEAVES:
            return P(*([None] * (nd - 2)), model, None)
        if name in _COLUMN_LEAVES:
            return P(*([None] * (nd - 1)), model)
        raise KeyError(f"no decode sharding rule for param leaf {'/'.join(names)}")

    return jax.tree_util.tree_map_with_path(spec_of, decode_params)


def serve_state_specs(cfg: ModelConfig, state, *, data="data", model="model",
                      extra_data_axes=()):
    da = (*extra_data_axes, data) if extra_data_axes else data
    specs = {}
    for k, v in state.items():
        if k in ("k_pool", "v_pool", "kv_pool"):
            # [nb, n_attn, I, tp, F', page, (dk|hd)]
            specs[k] = P(None, None, da, model, None, None, None)
        elif k in ("k_scale", "v_scale", "kv_scale"):
            # per-page dequant scales: [nb, n_attn, I, tp, F']
            specs[k] = P(None, None, da, model, None)
        elif k in ("conv_x",):
            specs[k] = P(None, None, da, None, None, model)
        elif k in ("conv_B", "conv_C"):
            specs[k] = P(None, None, da, None, None, None)
        elif k == "ssm_state":
            specs[k] = P(None, None, da, None, model, None, None)
        elif k == "expert_load":
            specs[k] = P(da, None)
        else:
            raise KeyError(k)
    return specs


def table_specs(tables, *, data="data", extra_data_axes=()):
    da = (*extra_data_axes, data) if extra_data_axes else data
    return {k: P(da, *([None] * (v.ndim - 1))) for k, v in tables.items()}


# =========================================================================== #
# shard_map wrapper (the jit-able serve_step the AOT engine captures)
# =========================================================================== #
def make_serve_step(cfg: ModelConfig, dims: DecodeDims, mesh, decode_params,
                    state, tables, *, extra_data_axes=(), donate: bool = True):
    """Build jit(shard_map(step)) with full in/out shardings.

    ``decode_params`` / ``state`` / ``tables`` may be concrete arrays or
    ShapeDtypeStructs (spec derivation only needs shapes).  Returns the
    jitted function ``f(params, state, tables) -> (state, tokens, logits)``.
    """
    da = (*extra_data_axes, dims.data) if extra_data_axes else dims.data
    step = build_decode_step(cfg, dims)
    pspecs = decode_param_specs(cfg, decode_params, data=dims.data,
                                model=dims.model,
                                extra_data_axes=extra_data_axes)
    sspecs = serve_state_specs(cfg, state, data=dims.data, model=dims.model,
                               extra_data_axes=extra_data_axes)
    tspecs = table_specs(tables, data=dims.data,
                         extra_data_axes=extra_data_axes)
    out_specs = (sspecs, P(da, None), P(da, None, dims.model))
    fn = _shard_map(step, mesh=mesh, in_specs=(pspecs, sspecs, tspecs),
                    out_specs=out_specs, check_vma=False)
    return jax.jit(fn, donate_argnums=(1,) if donate else ())
