"""Plain float32 reference forward for decoder-only GQA and MLA models.

The semantics of record for comparing the serving path on the chip: each
layer is written out in ``jax.numpy`` at float32 under matmul precision
"highest" — no Pallas kernels, no ``kernels.ops`` platform dispatch, no KV
cache, no batching, no capacity buffers (MoE routing is dropless).  It
follows this repository's layer equations (``models/layers.py``,
``models/attention.py``, ``models/mla.py``, ``models/moe.py``): pre-norm
blocks, interleaved RoPE (with YaRN where the config scales it), causal
GQA softmax attention or MLA in its materialised form (low-rank query,
normed KV latent, per-head up-projections, a rotary part shared by all
heads, softmax scale (nope + rope)^-1/2 times YaRN's mscale squared),
leading dense layers (``first_k_dense``), and routing by

  * softmax then top-k, the top-k weights renormalised, or
  * sigmoid scores with a selection bias, the top ``topk_group`` of
    ``n_group`` groups by the sum of each group's two best biased scores,
    top-k inside them, the unbiased scores renormalised and scaled by
    ``routed_scaling_factor``;

gated-SiLU experts, of which only the held ones (``held_experts_first``,
``num_held_experts``) are summed, plus the shared expert.

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


def _inv_freq(cfg: ModelConfig, D: int) -> jax.Array:
    """Rotary inverse frequencies [D/2], YaRN-blended where configured."""
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    y = cfg.rope_scaling
    if y is None:
        return inv

    def dim_of(rot):              # the dim that turns ``rot`` times in orig
        return D * np.log(y.original_max_position / (rot * 2 * np.pi)) \
            / (2 * np.log(cfg.rope_theta))
    lo = max(int(np.floor(dim_of(y.beta_fast))), 0)
    hi = min(int(np.ceil(dim_of(y.beta_slow))), D - 1)
    ramp = jnp.clip((jnp.arange(D // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0.0, 1.0)
    return inv * (1.0 - ramp) + inv / y.factor * ramp


def _rope(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """x [T, H, D]: rotate the pairs (x[2i], x[2i+1]) by position."""
    T, _, D = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * _inv_freq(cfg, D)
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def _causal(q: jax.Array, k: jax.Array, v: jax.Array, scale) -> jax.Array:
    """q/k [T, H, dk], v [T, H, dv] -> [T, H*dv], one query block at a time."""
    T, H, _ = q.shape
    nq = -(-T // Q_BLOCK)
    qb = jnp.pad(q, ((0, nq * Q_BLOCK - T), (0, 0), (0, 0)))

    def block(i):                 # one block of queries at a time
        s = jnp.einsum("qhd,khd->hqk",
                       jax.lax.dynamic_slice_in_dim(qb, i * Q_BLOCK, Q_BLOCK),
                       k) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        s = jnp.where(qpos >= jnp.arange(T)[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(nq)).reshape(nq * Q_BLOCK, -1)
    return o[:T]


def _attention(cfg: ModelConfig, p: dict, h: jax.Array) -> jax.Array:
    T = h.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    q = _rope(cfg, (h @ _f32(p["wq"])).reshape(T, H, hd))
    k = _rope(cfg, (h @ _f32(p["wk"])).reshape(T, Hkv, hd))
    v = (h @ _f32(p["wv"])).reshape(T, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    return _causal(q, k, v, 1.0 / np.sqrt(hd)) @ _f32(p["wo"])


def _mla(cfg: ModelConfig, p: dict, h: jax.Array) -> jax.Array:
    T = h.shape[0]
    H = cfg.num_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    if cfg.q_lora_rank:
        q = _rms(h @ _f32(p["wq_a"]), p["q_norm"]) @ _f32(p["wq_b"])
    else:
        q = h @ _f32(p["wq"])
    q = q.reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(cfg, q[..., dn:])], -1)
    kv = h @ _f32(p["wkv_a"])
    c_kv = _rms(kv[:, :kvr], p["kv_norm"])
    k_rope = _rope(cfg, kv[:, None, kvr:])                        # [T, 1, dr]
    k_nope = (c_kv @ _f32(p["wk_b"])).reshape(T, H, dn)
    v = (c_kv @ _f32(p["wv_b"])).reshape(T, H, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (T, H, dr))], -1)
    scale = (dn + dr) ** -0.5
    if cfg.rope_scaling is not None:
        y = cfg.rope_scaling
        ms = 0.1 * y.mscale_all_dim * np.log(y.factor) + 1.0 \
            if y.factor > 1 else 1.0
        scale = scale * ms * ms
    return _causal(q, k, v, scale) @ _f32(p["wo"])


def _rms(x: jax.Array, scale, eps: float = 1e-6) -> jax.Array:
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * _f32(scale)


def _mlp(x: jax.Array, w_gate, w_up, w_down) -> jax.Array:
    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def gates(cfg: ModelConfig, p: dict, h: jax.Array) -> jax.Array:
    """The routed weight of every expert for every token, [T, E] (0 where
    the expert is not chosen)."""
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    logits = h @ _f32(p["router"])
    if cfg.scoring_func == "softmax":
        score = jax.nn.softmax(logits, axis=-1)
        choice = score
    else:
        score = jax.nn.sigmoid(logits)
        choice = score + (_f32(p["e_score_correction_bias"])
                          if cfg.score_correction_bias else 0.0)
        if cfg.n_group > 1:
            T = h.shape[0]
            g = choice.reshape(T, cfg.n_group, E // cfg.n_group)
            two = jnp.sort(g, axis=-1)[..., -2:].sum(-1)          # [T, G]
            # rank of each group (ties to the lower index), keep the best
            rank = jnp.sum((two[:, None, :] > two[:, :, None])
                           | ((two[:, None, :] == two[:, :, None])
                              & (jnp.arange(cfg.n_group)[None, None, :]
                                 < jnp.arange(cfg.n_group)[None, :, None])),
                           axis=-1)
            keep = jnp.repeat(rank < cfg.topk_group, E // cfg.n_group, -1)
            choice = jnp.where(keep, choice, -jnp.inf)
    _, idx = jax.lax.top_k(choice, k)
    chosen = jnp.any(idx[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    w = jnp.where(chosen, score, 0.0)
    if cfg.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w * cfg.routed_scaling_factor


def _moe(cfg: ModelConfig, p: dict, h: jax.Array) -> jax.Array:
    gate = gates(cfg, p, h)                                        # [T, E]
    first = cfg.held_experts_first if cfg.holds_share else 0

    def expert(y, e):             # one held expert's weights upcast at a time
        return y + gate[:, first + e, None] * _mlp(
            h, p["wi_gate"][e], p["wi_up"][e], p["wo"][e]), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(cfg.num_held_experts))
    if cfg.num_shared_experts:
        sh = p["shared"]
        y = y + _mlp(h, sh["wi_gate"], sh["wi_up"], sh["wo"])
    return y


@partial(jax.jit, static_argnums=(0, 1))
def _layer(cfg: ModelConfig, moe_ffn: bool, lp: dict, b, x: jax.Array):
    """One pre-norm layer; ``lp`` holds every block's weights (leading
    axis), ``b`` picks the block, so all blocks share one compile."""
    lp = jax.tree.map(lambda a: a[b], lp)
    mix = _mla if cfg.is_mla else _attention
    x = x + mix(cfg, lp["mixer"], _norm(cfg, lp["ln1"], x))
    h = _norm(cfg, lp["ln2"], x)
    if moe_ffn:
        return x + _moe(cfg, lp["ffn"], h)
    f = lp["ffn"]
    return x + _mlp(h, f["wi_gate"], f["wi_up"], f["wo"])


def forward_last_logits(cfg: ModelConfig, params: dict, tokens,
                        last: int) -> np.ndarray:
    """Logits [last, Vp] (float32, numpy) at the final ``last`` positions of
    ``tokens`` [T] — logits at position t predict token t+1."""
    assert cfg.attention in ("gqa", "mla") and cfg.rope
    assert cfg.family in ("dense", "moe")
    assert not (cfg.qkv_bias or cfg.qk_norm or cfg.tie_embeddings)
    assert cfg.act == "silu"
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["tok"][jnp.asarray(tokens)])          # [T, D]
        for key, pattern, n, _ in cfg.segments():
            for b in range(n):
                for lp, kind in zip(params[key]["layers"], pattern):
                    x = _layer(cfg, kind["ffn"] == "moe", lp, jnp.int32(b), x)
        x = _norm(cfg, params["final_norm"], x[-last:])
        logits = x @ _f32(params["head"]["w"])
    return np.asarray(logits, np.float32)
