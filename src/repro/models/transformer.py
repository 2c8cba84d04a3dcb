"""Decoder-only trunk: init + forward for dense / MoE / SSM / hybrid stacks.

The trunk is ``cfg.num_blocks`` repeats of a ``cfg.block_period``-layer block
pattern; block parameters are stacked on a leading axis and the forward pass
``lax.scan``s over them (compile time stays O(block), roofline extrapolates
trip counts).  Leading dense layers (``cfg.first_k_dense``) are a segment
of their own (``params["dense_blocks"]``), scanned before the main stack
(``cfg.segments``).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn_mod
from . import layers, mla, moe, ssm


def _identity_shard(x, name: str):
    return x


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #
def make_layer_params(rng, cfg: ModelConfig, kind: dict) -> dict:
    ks = jax.random.split(rng, 4)
    p: dict = {"ln1": layers.make_norm_params(cfg, cfg.d_model)}
    if kind["mixer"] == "attn":
        if cfg.is_mla:
            p["mixer"] = mla.make_mla_params(ks[0], cfg)
        else:
            p["mixer"] = attn_mod.make_attn_params(ks[0], cfg)
    else:
        p["mixer"] = ssm.make_ssm_params(ks[0], cfg)
    if kind["ffn"] != "none":
        p["ln2"] = layers.make_norm_params(cfg, cfg.d_model)
        if kind["ffn"] == "moe":
            p["ffn"] = moe.make_moe_params(ks[1], cfg)
        else:
            p["ffn"] = layers.make_mlp_params(ks[1], cfg)
    return p


def make_block_params(rng, cfg: ModelConfig, pattern: list[dict]) -> dict:
    ks = jax.random.split(rng, len(pattern))
    return {"layers": [make_layer_params(k, cfg, kind)
                       for k, kind in zip(ks, pattern)]}


def init_params(rng, cfg: ModelConfig) -> dict:
    ks = jax.random.split(rng, 4)
    params = {
        "embed": layers.make_embed_params(ks[1], cfg),
        "final_norm": layers.make_norm_params(cfg, cfg.d_model),
        "head": layers.make_head_params(ks[2], cfg),
    }
    for key, pattern, n, _ in cfg.segments():
        block_keys = jax.random.split(ks[0] if key == "blocks" else ks[3], n)
        params[key] = jax.vmap(
            lambda k, pt=pattern: make_block_params(k, cfg, pt))(block_keys)
    return params


# --------------------------------------------------------------------------- #
# forward (train / prefill)
# --------------------------------------------------------------------------- #
def apply_layer(cfg: ModelConfig, kind: dict, lp: dict, x: jax.Array,
                positions: jax.Array, collect_kv: bool,
                shard: Callable = _identity_shard):
    """One layer: pre-norm mixer + pre-norm ffn with residuals.

    Returns (x, aux) where aux holds prefill cache material (kv / ssm state).
    """
    aux = {}
    h = layers.apply_norm(cfg, lp["ln1"], x)
    if kind["mixer"] == "attn":
        if cfg.is_mla:
            if collect_kv:
                c_kv, k_rope = mla.mla_latent(cfg, lp["mixer"], h, positions)
                aux["kv"] = (c_kv, k_rope)
            mix = mla.mla_self_attention(cfg, lp["mixer"], h, positions)
        else:
            q, k, v = attn_mod.qkv_proj(cfg, lp["mixer"], h, positions)
            if collect_kv:
                aux["kv"] = (k, v)
            from ..kernels import ops
            o = ops.attention(q, k, v, causal=True)
            B, S = h.shape[:2]
            mix = o.reshape(B, S, -1) @ lp["mixer"]["wo"]
    else:
        mix, (conv_state, ssm_state) = ssm.ssm_block(cfg, lp["mixer"], h,
                                                     shard=shard)
        if collect_kv:
            aux["ssm"] = (conv_state, ssm_state)
    x = shard(x + mix, "hidden")
    if kind["ffn"] != "none":
        h = layers.apply_norm(cfg, lp["ln2"], x)
        if kind["ffn"] == "moe":
            f = moe.moe_ffn_batched(cfg, lp["ffn"], h)
        else:
            f = layers.apply_mlp(cfg, lp["ffn"], h)
        x = shard(x + f, "hidden")
    return x, aux


def forward(cfg: ModelConfig, params: dict, tokens: jax.Array, *,
            positions: jax.Array | None = None, collect_kv: bool = False,
            shard: Callable = _identity_shard, remat: str = "none"):
    """tokens [B, S] -> logits [B, S, Vp] (+ caches if collect_kv).

    Returns (logits, caches) where caches is a pytree of per-block stacked
    aux outputs (or None).
    """
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = layers.embed_tokens(params["embed"], tokens)
    x = shard(x, "hidden")

    def make_block_fn(pattern):
        def block_fn(carry, bp):
            x = carry
            auxes = []
            for i, kind in enumerate(pattern):
                layer = partial(apply_layer, cfg, kind)
                if remat == "full" and len(pattern) > 1 and not collect_kv:
                    # heterogeneous blocks (jamba: 8 layers): nested per-layer
                    # remat keeps backward peak at ONE layer's internals
                    layer = jax.checkpoint(layer, prevent_cse=False,
                                           static_argnums=(3, 4))
                x, aux = layer(bp["layers"][i], x, positions, collect_kv, shard)
                auxes.append(aux)
            return x, (auxes if collect_kv else None)

        if remat == "full":
            return jax.checkpoint(block_fn, prevent_cse=False)
        if remat == "dots":
            return jax.checkpoint(
                block_fn, prevent_cse=False,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
        return block_fn

    seg_caches = []
    for key, pattern, _, _ in cfg.segments():
        x, c = jax.lax.scan(make_block_fn(pattern), x, params[key])
        seg_caches.append(c)
    # caches of all segments on one leading block axis, as the pools hold them
    caches = (seg_caches[0] if len(seg_caches) == 1 else
              jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *seg_caches))
    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = layers.apply_head(cfg, params["head"], params["embed"], x)
    return shard(logits, "logits"), caches


def loss_fn(cfg: ModelConfig, params: dict, tokens: jax.Array,
            targets: jax.Array, *, shard: Callable = _identity_shard,
            remat: str = "none") -> jax.Array:
    """Mean next-token cross-entropy (targets = tokens shifted by caller)."""
    logits, _ = forward(cfg, params, tokens, shard=shard, remat=remat)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
