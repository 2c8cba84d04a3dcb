"""GQA attention layer (train/prefill path) + KV emission for caches."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from . import layers


def make_attn_params(rng, cfg: ModelConfig, cross: bool = False) -> dict:
    D, hd = cfg.d_model, cfg.head_dim_
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(rng, 6)
    p = {
        "wq": layers.dense_init(ks[0], (D, H * hd)),
        "wk": layers.dense_init(ks[1], (D, Hkv * hd)),
        "wv": layers.dense_init(ks[2], (D, Hkv * hd)),
        "wo": layers.dense_init(ks[3], (H * hd, D)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), jnp.float32)
        p["bk"] = jnp.zeros((Hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((Hkv * hd,), jnp.float32)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def qkv_proj(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
             rope: bool | None = None):
    """x: [B, S, D] -> q [B,S,H,hd], k/v [B,S,Hkv,hd] (rope applied)."""
    B, S, _ = x.shape
    hd = cfg.head_dim_
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, cfg.num_heads, hd)
    k = k.reshape(B, S, cfg.num_kv_heads, hd)
    v = v.reshape(B, S, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = layers.rms_norm_vec(q, p["q_norm"])
        k = layers.rms_norm_vec(k, p["k_norm"])
    use_rope = cfg.rope if rope is None else rope
    if use_rope:
        q = layers.apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = layers.apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
    return q, k, v


def self_attention(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
                   *, causal: bool = True) -> jax.Array:
    """Full self-attention layer body (no residual/norm)."""
    q, k, v = qkv_proj(cfg, p, x, positions)
    o = ops.attention(q, k, v, causal=causal)
    B, S = x.shape[:2]
    return o.reshape(B, S, -1) @ p["wo"]


def cross_attention(cfg: ModelConfig, p: dict, x: jax.Array, mem_k: jax.Array,
                    mem_v: jax.Array) -> jax.Array:
    """x: [B,Sq,D]; mem_k/v: [B,Skv,Hkv,hd] precomputed encoder KV."""
    B, Sq, _ = x.shape
    hd = cfg.head_dim_
    q = x @ p["wq"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
    q = q.reshape(B, Sq, cfg.num_heads, hd)
    o = ops.attention(q, mem_k, mem_v, causal=False)
    return o.reshape(B, Sq, -1) @ p["wo"]


def encoder_kv(cfg: ModelConfig, p: dict, mem: jax.Array):
    """Project encoder states to cross-attention K/V once per request."""
    B, S, _ = mem.shape
    hd = cfg.head_dim_
    k = mem @ p["wk"]
    v = mem @ p["wv"]
    if cfg.qkv_bias:
        k = k + p["bk"].astype(mem.dtype)
        v = v + p["bv"].astype(mem.dtype)
    return (k.reshape(B, S, cfg.num_kv_heads, hd),
            v.reshape(B, S, cfg.num_kv_heads, hd))
