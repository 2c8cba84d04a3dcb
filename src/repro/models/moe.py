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
    D, E, F = cfg.d_model, cfg.num_experts, cfg.moe_d_ff_
    ks = jax.random.split(rng, 5)
    p = {
        "router": layers.dense_init(ks[0], (D, E), dtype=jnp.float32),
        "wi_gate": layers.dense_init(ks[1], (E, D, F)),
        "wi_up": layers.dense_init(ks[2], (E, D, F)),
        "wo": layers.dense_init(ks[3], (E, F, D)),
    }
    if cfg.num_shared_experts:
        Fs = cfg.moe_d_ff_ * cfg.num_shared_experts
        p["shared"] = layers.make_mlp_params(ks[4], cfg, d_ff=Fs)
    return p


def router_topk(cfg: ModelConfig, router_w: jax.Array, x: jax.Array):
    """x: [T, D] -> (weights [T, k] f32, idx [T, k] int32). Softmax-then-topk."""
    logits = x.astype(jnp.float32) @ router_w                      # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)  # renormalise
    return w, idx.astype(jnp.int32)


def group_by_expert(topk_idx: jax.Array, num_experts: int, capacity: int):
    """Sort-based grouping of (token, slot) assignments into expert bins.

    topk_idx: [T, k] -> returns
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
    slot = jnp.where(keep, se * capacity + pos_in_e, num_experts * capacity)
    src_token = jnp.full((num_experts * capacity + 1,), T, jnp.int32)
    src_token = src_token.at[slot].set(st, mode="drop").at[-1].set(T)
    # invert: slot of each (token, k) assignment (E*C for dropped)
    slot_of = jnp.full((T * k,), num_experts * capacity, jnp.int32)
    slot_of = slot_of.at[order].set(jnp.where(keep, slot, num_experts * capacity))
    return src_token[:-1], slot_of.reshape(T, k)


def moe_ffn(cfg: ModelConfig, p: dict, x: jax.Array,
            capacity_factor: float | None = None) -> jax.Array:
    """x: [T, D] -> [T, D].  Per-call capacity = ceil(T*k/E * phi)."""
    T, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    phi = capacity_factor or cfg.capacity_factor
    C = max(1, math.ceil(T * k / E * phi))
    w, idx = router_topk(cfg, p["router"], x)
    src_token, slot_of = group_by_expert(idx, E, C)

    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
    expert_in = x_pad[src_token].reshape(E, C, D)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, p["wi_gate"]))
    u = jnp.einsum("ecd,edf->ecf", expert_in, p["wi_up"])
    expert_out = jnp.einsum("ecf,efd->ecd", g * u, p["wo"]).reshape(E * C, D)

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
