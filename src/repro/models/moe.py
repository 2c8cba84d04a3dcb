"""Mixture-of-Experts layer (model-level path: sort-grouped, capacity-bounded).

Two execution paths exist in this repo:
  * this module — train/prefill: tokens of each batch row are sort-grouped by
    expert and run through TP-sharded expert FFNs (no all-to-all; experts are
    weight-sharded over the `model` axis).  Capacity is per batch row.
  * ``core/moe_parallel.py`` — decode: GShard-style capacity dispatch +
    ``lax.all_to_all`` over the `data` axis (wide-EP, the paper's setting).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import layers


def make_moe_params(rng, cfg: ModelConfig) -> dict:
    """Router over all ``num_experts``; weights of the held experts only."""
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff_
    H = cfg.num_held_experts
    ks = jax.random.split(rng, 5)
    p = {
        "router": layers.dense_init(ks[0], (D, E), dtype=jnp.float32),
        "wi_gate": layers.dense_init(ks[1], (H, D, F)),
        "wi_up": layers.dense_init(ks[2], (H, D, F)),
        "wo": layers.dense_init(ks[3], (H, F, D)),
    }
    if cfg.score_correction_bias:
        p["e_score_correction_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.num_shared_experts:
        Fs = cfg.moe_d_ff_ * cfg.num_shared_experts
        p["shared"] = layers.make_mlp_params(ks[4], cfg, d_ff=Fs)
    return p


def router_topk(cfg: ModelConfig, p: dict, x: jax.Array):
    """x: [T, D] -> (weights [T, k] f32, idx [T, k] int32) over all
    ``num_experts``.

    softmax: softmax then top-k, the k weights renormalised.
    sigmoid (DeepSeek-V3 ``noaux_tc``): sigmoid scores; the selection adds
    the correction bias, keeps the ``topk_group`` groups whose top-2 biased
    scores sum highest, and takes the top-k inside them; the weights are
    the unbiased scores, renormalised (``norm_topk_prob``) and scaled by
    ``routed_scaling_factor``.  Ties go to the lower index."""
    k = cfg.num_experts_per_tok
    if cfg.scoring_func == "softmax":
        logits = x.astype(jnp.float32) @ p["router"]               # [T, E]
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        if cfg.norm_topk_prob:
            w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    else:
        assert cfg.scoring_func == "sigmoid", cfg.scoring_func
        logits = jnp.dot(x.astype(jnp.float32), p["router"],
                         precision=jax.lax.Precision.HIGHEST)
        scores = jax.nn.sigmoid(logits)                            # [T, E]
        choice = scores
        if cfg.score_correction_bias:
            choice = scores + p["e_score_correction_bias"]
        if cfg.n_group > 1:
            T, E = choice.shape
            grouped = choice.reshape(T, cfg.n_group, E // cfg.n_group)
            gscore = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # [T, G]
            _, gidx = jax.lax.top_k(gscore, cfg.topk_group)
            keep = jnp.any(jnp.arange(cfg.n_group)[None, :, None]
                           == gidx[:, None, :], axis=-1)           # [T, G]
            choice = jnp.where(keep[:, :, None], grouped,
                               -jnp.inf).reshape(T, E)
        _, idx = jax.lax.top_k(choice, k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        if cfg.norm_topk_prob:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
    if cfg.routed_scaling_factor != 1.0:
        w = w * cfg.routed_scaling_factor
    return w, idx.astype(jnp.int32)


def held_ids(cfg: ModelConfig, idx: jax.Array) -> jax.Array:
    """Router expert ids -> ids among the held experts; an expert held
    elsewhere maps to ``num_held_experts`` (never dispatched here)."""
    if not cfg.holds_share:
        return idx
    local = idx - cfg.held_experts_first
    H = cfg.num_held_experts
    return jnp.where((local >= 0) & (local < H), local, H)


def group_by_expert(topk_idx: jax.Array, num_experts: int, capacity: int,
                    outside: bool = False):
    """Sort-based grouping of (token, slot) assignments into expert bins.

    topk_idx: [T, k] ids in [0, num_experts]; with ``outside`` the id
    ``num_experts`` marks an assignment to an expert not held here, dropped
    like one past capacity.  Returns
      src_token [E*C] int32 (T == dropped/empty sentinel),
      slot_of   [T, k] int32 (position in the [E*C] buffer; E*C == dropped).
    """
    T, k = topk_idx.shape
    flat_e = topk_idx.reshape(-1)                                  # [T*k]
    flat_t = (jnp.arange(T * k, dtype=jnp.int32) // k)
    order = jnp.argsort(flat_e, stable=True)
    se = flat_e[order]
    st = flat_t[order]
    # position within its expert group
    first_of = jnp.searchsorted(se, jnp.arange(num_experts), side="left")
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - first_of[se].astype(jnp.int32)
    keep = pos_in_e < capacity
    if outside:
        keep = keep & (se < num_experts)
    slot = jnp.where(keep, se * capacity + pos_in_e, num_experts * capacity)
    src_token = jnp.full((num_experts * capacity + 1,), T, jnp.int32)
    src_token = src_token.at[slot].set(st, mode="drop").at[-1].set(T)
    # invert: slot of each (token, k) assignment (E*C for dropped)
    slot_of = jnp.full((T * k,), num_experts * capacity, jnp.int32)
    slot_of = slot_of.at[order].set(jnp.where(keep, slot, num_experts * capacity))
    return src_token[:-1], slot_of.reshape(T, k)


def capacity(cfg: ModelConfig, T: int, capacity_factor: float | None = None):
    """Per-expert capacity ceil(T*k/E * phi), E the router's width: at
    phi = E/k it is T, so no expert can drop a token."""
    phi = capacity_factor or cfg.capacity_factor
    return max(1, math.ceil(T * cfg.num_experts_per_tok / cfg.num_experts
                            * phi))


def moe_ffn(cfg: ModelConfig, p: dict, x: jax.Array,
            capacity_factor: float | None = None) -> jax.Array:
    """x: [T, D] -> [T, D]: the held experts' part of the routed output,
    plus the shared expert.  Per-call capacity ``capacity``."""
    T, D = x.shape
    H = cfg.num_held_experts
    C = capacity(cfg, T, capacity_factor)
    w, idx = router_topk(cfg, p, x)
    src_token, slot_of = group_by_expert(held_ids(cfg, idx), H, C,
                                         cfg.holds_share)

    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
    expert_in = x_pad[src_token].reshape(H, C, D)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, p["wi_gate"]))
    u = jnp.einsum("ecd,edf->ecf", expert_in, p["wi_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", g * u, p["wo"]).reshape(H * C, D)

    out_pad = jnp.concatenate([expert_out, jnp.zeros((1, D), expert_out.dtype)])
    gathered = out_pad[slot_of]                                    # [T, k, D]
    out = jnp.einsum("tk,tkd->td", w.astype(gathered.dtype), gathered)
    if cfg.num_shared_experts:
        out = out + layers.apply_mlp(cfg, p["shared"], x)
    return out.astype(x.dtype)


def moe_ffn_batched(cfg: ModelConfig, p: dict, x: jax.Array,
                    chunk: int = 4096) -> jax.Array:
    """x: [B, S, D]; grouping/capacity is per (batch row x seq chunk).

    Long sequences scan over ``chunk``-token slices so the dispatch/combine
    buffers peak at ONE chunk (the full-sequence buffers dominated prefill
    memory: ~9 GB/layer at 32k before chunking); a remainder shorter than a
    chunk is its own last slice."""
    B, S, D = x.shape

    def rows(xs):
        return jax.vmap(lambda row: moe_ffn(cfg, p, row))(xs)

    if S <= chunk:
        return rows(x)
    nch, tail = divmod(S, chunk)
    xc = x[:, :nch * chunk].reshape(B, nch, chunk, D).transpose(1, 0, 2, 3)
    _, out = jax.lax.scan(lambda _, xs: (None, rows(xs)), None, xc)
    out = out.transpose(1, 0, 2, 3).reshape(B, nch * chunk, D)
    if tail:
        out = jnp.concatenate([out, rows(x[:, nch * chunk:])], axis=1)
    return out


def aux_load_balance_loss(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """Switch-style load-balance auxiliary loss (training)."""
    T = x.shape[0]
    logits = x.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)                        # [T, E]
    _, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32).sum(1)
    frac_tokens = onehot.mean(0)
    frac_probs = probs.mean(0)
    return cfg.num_experts * jnp.sum(frac_tokens * frac_probs)
