"""Plain float32 reference forward for decoder-only GQA models.

The semantics of record for comparing the serving path on the chip: each
layer is written out in ``jax.numpy`` at float32 under matmul precision
"highest" — no Pallas kernels, no ``kernels.ops`` platform dispatch, no KV
cache, no batching, no capacity buffers (MoE routing is dropless).  It
follows this repository's layer equations (``models/layers.py``,
``models/attention.py``, ``models/moe.py``): pre-norm blocks, interleaved
RoPE, causal GQA softmax attention, softmax-then-top-k routing with the
top-k weights renormalised, gated-SiLU experts.

Memory: weights stay in their stored dtype and are upcast to float32 one
expert at a time (a scan over experts), and attention runs one block of
queries at a time, so a full-width layer fits beside the stored weights on
one chip.  Each layer is one jitted program, shared by all blocks.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig

Q_BLOCK = 512


def _f32(w) -> jax.Array:
    return jnp.asarray(w, jnp.float32)


def _norm(cfg: ModelConfig, p: dict, x: jax.Array, eps: float = 1e-6):
    if cfg.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * _f32(p["scale"]) \
            + _f32(p["bias"])
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * _f32(p["scale"])


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x [T, H, D]: rotate the pairs (x[2i], x[2i+1]) by position."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv      # [T,1,D/2]
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def _attention(cfg: ModelConfig, p: dict, h: jax.Array) -> jax.Array:
    T = h.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    q = _rope((h @ _f32(p["wq"])).reshape(T, H, hd), cfg.rope_theta)
    k = _rope((h @ _f32(p["wk"])).reshape(T, Hkv, hd), cfg.rope_theta)
    v = (h @ _f32(p["wv"])).reshape(T, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    nq = -(-T // Q_BLOCK)
    qb = jnp.pad(q, ((0, nq * Q_BLOCK - T), (0, 0), (0, 0)))

    def block(i):                 # one block of queries at a time
        s = jnp.einsum("qhd,khd->hqk",
                       jax.lax.dynamic_slice_in_dim(qb, i * Q_BLOCK, Q_BLOCK),
                       k) / np.sqrt(hd)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        s = jnp.where(qpos >= jnp.arange(T)[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(nq)).reshape(nq * Q_BLOCK, H * hd)
    return o[:T] @ _f32(p["wo"])


def _mlp(x: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def _moe(cfg: ModelConfig, p: dict, h: jax.Array) -> jax.Array:
    probs = jax.nn.softmax(h @ _f32(p["router"]), axis=-1)          # [T, E]
    w, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    def expert(y, e):             # one expert's weights upcast at a time
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)          # [T]
        return y + gate[:, None] * _mlp(h, p["wi_gate"][e], p["wi_up"][e],
                                        p["wo"][e]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(cfg.num_experts))
    if cfg.num_shared_experts:
        sh = p["shared"]
        y = y + _mlp(h, sh["wi_gate"], sh["wi_up"], sh["wo"])
    return y


@partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg: ModelConfig, moe_ffn: bool, lp: dict, b, x: jax.Array):
    """One pre-norm layer; ``lp`` holds every block's weights (leading
    axis), ``b`` picks the block, so all blocks share one compile."""
    lp = jax.tree.map(lambda a: a[b], lp)
    x = x + _attention(cfg, lp["mixer"], _norm(cfg, lp["ln1"], x))
    h = _norm(cfg, lp["ln2"], x)
    if moe_ffn:
        return x + _moe(cfg, lp["ffn"], h)
    f = lp["ffn"]
    return x + _mlp(h, f["wi_gate"], f["wi_up"], f["wo"])


def forward_last_logits(cfg: ModelConfig, params: dict, tokens,
                        last: int) -> np.ndarray:
    """Logits [last, Vp] (float32, numpy) at the final ``last`` positions of
    ``tokens`` [T] — logits at position t predict token t+1."""
    assert cfg.attention == "gqa" and cfg.rope and cfg.family in ("dense",
                                                                  "moe")
    assert not (cfg.qkv_bias or cfg.qk_norm or cfg.tie_embeddings)
    assert cfg.act == "silu"
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["tok"][jnp.asarray(tokens)])          # [T, D]
        for b in range(cfg.num_blocks):
            for lp, kind in zip(params["blocks"]["layers"],
                                cfg.block_pattern()):
                x = _layer(cfg, kind["ffn"] == "moe", lp, jnp.int32(b), x)
        x = _norm(cfg, params["final_norm"], x[-last:])
        logits = x @ _f32(params["head"]["w"])
    return np.asarray(logits, np.float32)
