"""Multi-head Latent Attention (DeepSeek-V2/V3, MiniCPM3).

Prefill/train path materialises per-head K/V from the latent; the decode path
(core/dcp.py) caches only (c_kv, k_rope) = kv_lora_rank + rope dims per token
and runs MQA over the latent with absorbed W_uk/W_uv — the FlashMLA analogue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from . import layers


def make_mla_params(rng, cfg: ModelConfig) -> dict:
    D, H = cfg.d_model, cfg.num_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(rng, 8)
    p = {
        "wkv_a": layers.dense_init(ks[2], (D, kvr + dr)),
        "kv_norm": jnp.ones((kvr,), jnp.float32),
        "wk_b": layers.dense_init(ks[3], (kvr, H * dn)),
        "wv_b": layers.dense_init(ks[4], (kvr, H * dv)),
        "wo": layers.dense_init(ks[5], (H * dv, D)),
    }
    if qr:
        p["wq_a"] = layers.dense_init(ks[0], (D, qr))
        p["q_norm"] = jnp.ones((qr,), jnp.float32)
        p["wq_b"] = layers.dense_init(ks[1], (qr, H * (dn + dr)))
    else:
        p["wq"] = layers.dense_init(ks[0], (D, H * (dn + dr)))
    return p


def mla_q(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    """Query projection -> q_nope [B,S,H,dn], q_rope [B,S,H,dr] (rope applied)."""
    B, S, _ = x.shape
    H = cfg.num_heads
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if cfg.q_lora_rank:
        cq = layers.rms_norm_vec(x @ p["wq_a"], p["q_norm"])
        q = cq @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = layers.apply_rope(q_rope, positions, cfg.rope_theta,
                               cfg.rope_scaling)
    return q_nope, q_rope


def mla_latent(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array):
    """KV latent: c_kv [B,S,kvr] (normed), k_rope [B,S,dr] (rope, head-shared)."""
    kvr, dr = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    kv = x @ p["wkv_a"]
    c_kv = layers.rms_norm_vec(kv[..., :kvr], p["kv_norm"])
    k_rope = layers.apply_rope(kv[..., kvr:][..., None, :], positions,
                               cfg.rope_theta, cfg.rope_scaling)[..., 0, :]
    return c_kv, k_rope


def mla_self_attention(cfg: ModelConfig, p: dict, x: jax.Array,
                       positions: jax.Array) -> jax.Array:
    """Prefill/train MLA (materialised K/V; causal)."""
    B, S, _ = x.shape
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q_nope, q_rope = mla_q(cfg, p, x, positions)
    c_kv, k_rope = mla_latent(cfg, p, x, positions)
    k_nope = (c_kv @ p["wk_b"]).reshape(B, S, H, dn)
    v = (c_kv @ p["wv_b"]).reshape(B, S, H, dv)
    k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, dr))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    o = ops.attention(q, k, v, causal=True, scale=cfg.mla_softmax_scale)
    return o.reshape(B, S, H * dv) @ p["wo"]


def mla_absorbed_q(cfg: ModelConfig, p: dict, q_nope: jax.Array):
    """Absorb W_uk into q for latent-space (MQA) decode.

    q_nope: [..., H, dn] -> q_latent [..., H, kvr]  (q_latent · c_kv == q · k_nope)
    """
    H = cfg.num_heads
    dn, kvr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    wk_b = p["wk_b"].reshape(kvr, H, dn)                     # [kvr, H, dn]
    return jnp.einsum("...hd,khd->...hk", q_nope, wk_b)


def mla_unabsorb_out(cfg: ModelConfig, p: dict, o_latent: jax.Array):
    """o_latent [..., H, kvr] -> per-head value output [..., H*dv] (pre-Wo)."""
    H = cfg.num_heads
    dv, kvr = cfg.v_head_dim, cfg.kv_lora_rank
    wv_b = p["wv_b"].reshape(kvr, H, dv)
    o = jnp.einsum("...hk,khd->...hd", o_latent, wv_b)
    return o.reshape(*o.shape[:-2], H * dv)
