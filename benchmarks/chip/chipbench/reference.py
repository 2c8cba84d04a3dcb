"""Plain float32 reference forward, the benchmark's own.

The semantics of record for ``correct``: each layer is written out in
``jax.numpy`` at float32 under matmul precision "highest", with no
kernels, no cache and no batching, and MoE routing dropless.  It imports
nothing of the program.  The GQA/MoE layer is a copy of the program's
``models/reference.py``; the MLA layer follows ``models/mla.py`` (the
materialised prefill form).  The layer equations are those of the model
as the repository implements it; ``PERF.md`` lists where they depart from
the published models (norm epsilon, rotary pairing, no biases, no
LongRoPE or muP scalings).

  * pre-norm blocks (layer norm or RMS norm, eps ``norm_eps``);
  * rotary embedding on interleaved pairs (x[2i], x[2i+1]);
  * causal softmax attention: GQA with ``num_kv_heads`` shared heads, or
    MLA with a low-rank query, a normed KV latent, per-head up-projections
    and a rotary part shared by all heads, scaled by (nope + rope)^-1/2;
  * MoE: softmax over experts, top-k, the k weights renormalised,
    gated-SiLU experts; dense layers: one gated-SiLU MLP.

Memory: weights stay bf16 and are upcast one layer (one expert) at a time;
attention runs one block of queries at a time; one jitted program per
layer shape serves every layer.  Sequences are padded at the end to a
length bucket, which a causal model never reads back.

``quant="fp8"`` is the control: every matmul weight rounded to float8
e4m3 with one scale per output column before it is used, the step below
the bf16 the configurations state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .model import Model

Q_BLOCK = 512
FP8_MAX = 448.0


def length_bucket(n: int) -> int:
    """Padded sequence length: whole 1024s up to 8192, then whole 4096s."""
    step = 1024 if n <= 8192 else 4096
    return -(-n // step) * step


def _w(w, quant):
    w = jnp.asarray(w, jnp.float32)
    if quant == "fp8":
        amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
        scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
        w = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return w


def _norm(m: Model, p: dict, x):
    if m.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + m.norm_eps) \
            * jnp.asarray(p["scale"], jnp.float32) \
            + jnp.asarray(p["bias"], jnp.float32)
    return _rms(x, p["scale"], m.norm_eps)


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * jnp.asarray(scale, jnp.float32)


def _rope(x, theta: float):
    """x [T, H, D]: rotate the pairs (x[2i], x[2i+1]) by position."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def _causal(q, k, v, scale):
    """q/k [T, H, dk], v [T, H, dv] -> [T, H*dv], one query block at a time."""
    T, H, _ = q.shape
    nq = T // Q_BLOCK

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        s = jnp.where(qpos >= jnp.arange(T)[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(nq))
    return o.reshape(T, -1)


def _gqa(m: Model, p: dict, h, quant):
    T = h.shape[0]
    H, Hkv, hd = m.num_heads, m.num_kv_heads, m.head_dim
    q = _rope((h @ _w(p["wq"], quant)).reshape(T, H, hd), m.rope_theta)
    k = _rope((h @ _w(p["wk"], quant)).reshape(T, Hkv, hd), m.rope_theta)
    v = (h @ _w(p["wv"], quant)).reshape(T, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    return _causal(q, k, v, hd ** -0.5) @ _w(p["wo"], quant)


def _mla(m: Model, p: dict, h, quant):
    T = h.shape[0]
    H = m.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    kvr = m.kv_lora_rank
    cq = _rms(h @ _w(p["wq_a"], quant), p["q_norm"], m.norm_eps)
    q = (cq @ _w(p["wq_b"], quant)).reshape(T, H, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], m.rope_theta)], -1)
    kv = h @ _w(p["wkv_a"], quant)
    c_kv = _rms(kv[:, :kvr], p["kv_norm"], m.norm_eps)
    k_rope = _rope(kv[:, None, kvr:], m.rope_theta)              # [T, 1, dr]
    k_nope = (c_kv @ _w(p["wk_b"], quant)).reshape(T, H, dn)
    v = (c_kv @ _w(p["wv_b"], quant)).reshape(T, H, dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (T, H, dr))], -1)
    return _causal(q, k, v, (dn + dr) ** -0.5) @ _w(p["wo"], quant)


def _mlp(x, w_gate, w_up, w_down, quant):
    return (jax.nn.silu(x @ _w(w_gate, quant)) * (x @ _w(w_up, quant))) \
        @ _w(w_down, quant)


def _moe(m: Model, p: dict, h, quant):
    """(output, margin): ``margin`` [T] is how far each token's k-th chosen
    expert lies above the best expert left out, in router probability."""
    probs = jax.nn.softmax(h @ _w(p["router"], quant), axis=-1)     # [T, E]
    k = m.num_experts_per_tok
    top, _ = jax.lax.top_k(probs, k + 1)
    margin = top[:, k - 1] - top[:, k]
    w, idx = jax.lax.top_k(probs, k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)

    def expert(y, e):             # one expert's weights upcast at a time
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)          # [T]
        return y + gate[:, None] * _mlp(h, p["wi_gate"][e], p["wi_up"][e],
                                        p["wo"][e], quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(m.num_experts))
    return y, margin


@partial(jax.jit, static_argnums=(0, 1))
def _layer(m: Model, quant, layers: dict, i, x):
    """One pre-norm layer; ``layers`` holds every layer's weights on a
    leading axis and ``i`` picks one, so all layers share one compile.
    Returns (x, router margin per token; +inf for a dense layer)."""
    lp = jax.tree.map(lambda a: a[i], layers)
    mix = _mla if m.attention == "mla" else _gqa
    x = x + mix(m, lp["mixer"], _norm(m, lp["ln1"], x), quant)
    h = _norm(m, lp["ln2"], x)
    f = lp["ffn"]
    if m.num_experts:
        y, margin = _moe(m, f, h, quant)
        return x + y, margin
    return x + _mlp(h, f["wi_gate"], f["wi_up"], f["wo"], quant), \
        jnp.full(x.shape[:1], jnp.inf, jnp.float32)


@partial(jax.jit, static_argnums=(0, 1, 5))
def _head(m: Model, quant, final_norm, head_w, x, n: int, start):
    xs = jax.lax.dynamic_slice_in_dim(x, start, n)
    return _norm(m, final_norm, xs) @ _w(head_w, quant)


def logits(m: Model, params: dict, tokens, start: int, n: int,
           quant: str | None = None) -> np.ndarray:
    """float32 logits [n, Vp] at positions start..start+n-1 of ``tokens``
    (the logits at position t predict token t+1)."""
    return logits_and_margins(m, params, tokens, start, n, quant)[0]


def logits_and_margins(m: Model, params: dict, tokens, start: int, n: int,
                       quant: str | None = None):
    """``logits`` and, at the same positions, the smallest router margin
    over the MoE layers (+inf for a model without experts): how close the
    reference's own expert choice there is to a tie."""
    T = len(tokens)
    Tp = length_bucket(T)
    toks = np.zeros(Tp, np.int32)
    toks[:T] = np.asarray(tokens, np.int32)
    layers = params["blocks"]["layers"][0]
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"]["tok"][jnp.asarray(toks)],
                        jnp.float32)
        margin = jnp.full((Tp,), jnp.inf, jnp.float32)
        for i in range(m.num_layers):
            x, mg = _layer(m, quant, layers, jnp.int32(i), x)
            margin = jnp.minimum(margin, mg)
        n_pad = min(-(-n // 128) * 128, Tp)
        s0 = min(start, Tp - n_pad)
        out = _head(m, quant, params["final_norm"], params["head"]["w"], x,
                    n_pad, jnp.int32(s0))
    lo = start - s0
    return (np.asarray(out, np.float32)[lo:lo + n],
            np.asarray(margin, np.float32)[start:start + n])
