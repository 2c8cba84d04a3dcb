"""Shared building blocks: norms, RoPE, MLPs, embeddings, init helpers."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig

Init = jax.nn.initializers.Initializer


def dense_init(rng, shape, in_axis: int = 0, dtype=jnp.bfloat16):
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


def make_norm_params(cfg: ModelConfig, dim: int) -> dict:
    p = {"scale": jnp.ones((dim,), jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((dim,), jnp.float32)
    return p


def apply_norm(cfg: ModelConfig, p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"]
    return out.astype(x.dtype)


def rms_norm_vec(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm over the last axis with an explicit scale vector (qk-norm etc.)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# --------------------------------------------------------------------------- #
# rotary embeddings
# --------------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Inverse frequencies [head_dim/2]; with ``scaling`` (a
    ``configs.base.YaRN``) the low frequencies are divided by its factor
    and the dims of its correction range blend linearly between."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                             / head_dim))
    if scaling is None:
        return freqs
    low, high = scaling.correction_range(head_dim, theta)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling=None) -> jax.Array:
    """x: [..., S, H, D] (or [..., H, D] with scalar-ish positions broadcast).

    positions: integer array broadcastable to x.shape[:-2].
    Rotates pairs (x[2i], x[2i+1]); ``scaling``: YaRN (``rope_freqs``).
    """
    D = x.shape[-1]
    freqs = rope_freqs(D, theta, scaling)                          # [D/2]
    ang = positions.astype(jnp.float32)[..., None, None] * freqs   # [..., 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------- #
# MLPs
# --------------------------------------------------------------------------- #
def make_mlp_params(rng, cfg: ModelConfig, d_ff: int | None = None) -> dict:
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    if cfg.act == "silu":
        return {"wi_gate": dense_init(ks[0], (D, F)),
                "wi_up": dense_init(ks[1], (D, F)),
                "wo": dense_init(ks[2], (F, D))}
    # plain (whisper gelu) with biases
    return {"wi": dense_init(ks[0], (D, F)),
            "bi": jnp.zeros((F,), jnp.float32),
            "wo": dense_init(ks[1], (F, D)),
            "bo": jnp.zeros((D,), jnp.float32)}


def apply_mlp(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.act == "silu":
        g = jax.nn.silu(x @ p["wi_gate"])
        u = x @ p["wi_up"]
        return (g * u) @ p["wo"]
    h = jax.nn.gelu(x @ p["wi"] + p["bi"].astype(x.dtype), approximate=True)
    return h @ p["wo"] + p["bo"].astype(x.dtype)


# --------------------------------------------------------------------------- #
# embeddings / head
# --------------------------------------------------------------------------- #
def make_embed_params(rng, cfg: ModelConfig) -> dict:
    Vp, D = cfg.padded_vocab, cfg.d_model
    ks = jax.random.split(rng, 3)
    p = {"tok": (jax.random.normal(ks[0], (Vp, D), jnp.float32) * 0.02
                 ).astype(jnp.bfloat16)}
    if not cfg.rope and cfg.is_encoder_decoder:
        p["pos_dec"] = (jax.random.normal(ks[1], (cfg.max_target_positions, D),
                                          jnp.float32) * 0.02).astype(jnp.bfloat16)
    return p


def embed_tokens(p: dict, tokens: jax.Array) -> jax.Array:
    return p["tok"][tokens]


def make_head_params(rng, cfg: ModelConfig) -> dict:
    if cfg.tie_embeddings:
        return {}
    return {"w": dense_init(rng, (cfg.d_model, cfg.padded_vocab))}


def apply_head(cfg: ModelConfig, head: dict, embed: dict, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return x @ embed["tok"].T
    return x @ head["w"]
