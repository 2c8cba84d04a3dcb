"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the program's ``NanoCPEngine`` takes (embedding,
stacked per-layer leaves, final norm, head) and the dtypes it serves:
bf16 matrices, f32 norms and router.  The values are the benchmark's own,
so the reference (``reference.py``) reads the same tree without taking
anything the program made.

Matrices are normal with std fan_in^-1/2 (the embedding 0.02); norm scales
are 1 + 0.05 N(0, 1) and layer-norm biases 0.05 N(0, 1), so that a norm
applied wrongly shows in the logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .model import Model


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative integer seed (more bits than a
    signed 32-bit integer holds are fine)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _norm(m: Model, key) -> dict:
    k1, k2 = jax.random.split(key)
    p = {"scale": 1.0 + 0.05 * jax.random.normal(k1, (m.d_model,),
                                                  jnp.float32)}
    if m.norm == "layernorm":
        p["bias"] = 0.05 * jax.random.normal(k2, (m.d_model,), jnp.float32)
    return p


def _dense(key, shape, dtype=jnp.bfloat16):
    std = shape[-2] ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _layer(m: Model, key) -> dict:
    ks = iter(jax.random.split(key, 16))
    D, H = m.d_model, m.num_heads
    p = {"ln1": _norm(m, next(ks)), "ln2": _norm(m, next(ks))}
    if m.attention == "mla":
        qr, kvr = m.q_lora_rank, m.kv_lora_rank
        dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
        p["mixer"] = {
            "wq_a": _dense(next(ks), (D, qr)),
            "q_norm": 1.0 + 0.05 * jax.random.normal(next(ks), (qr,)),
            "wq_b": _dense(next(ks), (qr, H * (dn + dr))),
            "wkv_a": _dense(next(ks), (D, kvr + dr)),
            "kv_norm": 1.0 + 0.05 * jax.random.normal(next(ks), (kvr,)),
            "wk_b": _dense(next(ks), (kvr, H * dn)),
            "wv_b": _dense(next(ks), (kvr, H * dv)),
            "wo": _dense(next(ks), (H * dv, D)),
        }
    else:
        hd, Hkv = m.head_dim, m.num_kv_heads
        p["mixer"] = {"wq": _dense(next(ks), (D, H * hd)),
                      "wk": _dense(next(ks), (D, Hkv * hd)),
                      "wv": _dense(next(ks), (D, Hkv * hd)),
                      "wo": _dense(next(ks), (H * hd, D))}
    if m.num_experts:
        E, F = m.num_experts, m.moe_d_ff
        p["ffn"] = {"router": _dense(next(ks), (D, E), jnp.float32),
                    "wi_gate": _dense(next(ks), (E, D, F)),
                    "wi_up": _dense(next(ks), (E, D, F)),
                    "wo": _dense(next(ks), (E, F, D))}
    else:
        F = m.d_ff
        p["ffn"] = {"wi_gate": _dense(next(ks), (D, F)),
                    "wi_up": _dense(next(ks), (D, F)),
                    "wo": _dense(next(ks), (F, D))}
    return p


def _params(m: Model, key) -> dict:
    k_emb, k_layers, k_norm, k_head = jax.random.split(key, 4)
    layer_keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
        jnp.arange(m.num_layers))
    Vp = m.padded_vocab
    return {
        "embed": {"tok": (0.02 * jax.random.normal(
            k_emb, (Vp, m.d_model), jnp.float32)).astype(jnp.bfloat16)},
        "blocks": {"layers": [jax.vmap(lambda k: _layer(m, k))(layer_keys)]},
        "final_norm": _norm(m, k_norm),
        "head": {"w": _dense(k_head, (m.d_model, Vp))},
    }


def make_params(m: Model, seed: int, device=None) -> dict:
    """The whole tree, made on ``device`` by one jitted call."""
    fn = jax.jit(lambda k: _params(m, k))
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)
