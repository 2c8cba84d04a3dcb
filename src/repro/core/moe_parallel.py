"""Wide-EP MoE dispatch/combine for decode (DeepEP analogue, §2.2).

Runs inside shard_map: experts are sharded over the `data` axis (each
instance hosts E/I experts, TP over `model` within the expert FFN); each
MoE layer performs the paper's two all-to-all phases:

  dispatch:  [E, C, D] capacity-bucketed send buffer -> all_to_all(`data`)
  combine :  expert outputs -> all_to_all(`data`) -> gate-weighted scatter

Capacity C bounds per-(instance, expert) tokens — the static-shape analogue
of DeepEP's bounded receive buffers.  Batch-size balance across instances
(the scheduler's B_s term) directly bounds the all-to-all payload, which is
exactly the straggler mechanism NanoCP's dual balance controls.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..models import layers as L
from ..models import moe as moe_mod


def moe_decode_ffn(cfg: ModelConfig, p: dict, x: jax.Array, *,
                   axis: str = "data", axis_size: int, capacity: int | None = None,
                   tp_axis: str = "model", load_mask: jax.Array | None = None):
    """x: [T, D] per-instance tokens -> [T, D]; EP over ``axis``.

    Routes over all ``num_experts`` and computes the part of the output
    that the held experts (``cfg.num_held_experts``, spread over the
    instances) give, plus the shared expert: where the mesh holds a share,
    the other experts' parts are computed by chips outside it and nothing
    here stands in for them.

    Param shards per device (from the decode layout):
      p["router"]  [D, E]        replicated (E = the router's width)
      p["wi_gate"] [H/I, D, F/tp]  (H = held experts)
      p["wi_up"]   [H/I, D, F/tp]
      p["wo"]      [H/I, F/tp, D]
      p["shared"]  optional dense-TP shared expert

    With ``load_mask`` [T] (1 for a live token) also returns the routed
    assignments of live tokens that reached each held expert, [H] int32.
    """
    T, D = x.shape
    H = cfg.num_held_experts
    I = axis_size
    assert H % I == 0, (H, I)
    e_local = H // I
    C = capacity or moe_mod.capacity(cfg, T)

    with jax.named_scope("router"):
        w, idx = moe_mod.router_topk(cfg, p, x)
        held = moe_mod.held_ids(cfg, idx)
        src_token, slot_of = moe_mod.group_by_expert(held, H, C,
                                                     cfg.holds_share)

    with jax.named_scope("routed_experts"):
        x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
        send = x_pad[src_token].reshape(H, C, D)                 # dispatch buffer
        # ---- dispatch all-to-all: split experts over instances ----
        recv = jax.lax.all_to_all(send.reshape(I, e_local * C, D), axis,
                                  split_axis=0, concat_axis=0, tiled=True)
        # recv: [I * e_local * C, D] == tokens for my local experts from everyone
        tok = recv.reshape(I, e_local, C, D).transpose(1, 0, 2, 3) \
                  .reshape(e_local, I * C, D)

        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", tok, p["wi_gate"]))
        u = jnp.einsum("ecd,edf->ecf", tok, p["wi_up"])
        out = jnp.einsum("ecf,efd->ecd", g * u, p["wo"])
        out = jax.lax.psum(out, tp_axis)                         # expert-TP reduce

        # ---- combine all-to-all: return tokens to their source instance ----
        back = out.reshape(e_local, I, C, D).transpose(1, 0, 2, 3) \
                  .reshape(I, e_local * C, D)
        comb = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0,
                                  tiled=True).reshape(H * C, D)

        out_pad = jnp.concatenate([comb, jnp.zeros((1, D), comb.dtype)])
        gathered = out_pad[slot_of]                              # [T, k, D]
        y = jnp.einsum("tk,tkd->td", w.astype(gathered.dtype), gathered)

    if cfg.num_shared_experts and "shared" in p:
        with jax.named_scope("shared_expert"):
            sh = p["shared"]
            s = (jax.nn.silu(x @ sh["wi_gate"]) * (x @ sh["wi_up"])) @ sh["wo"]
            y = y + jax.lax.psum(s, tp_axis)
    y = y.astype(x.dtype)
    if load_mask is None:
        return y
    with jax.named_scope("router"):
        hit = (held[:, :, None] == jnp.arange(H)[None, None, :])  # [T, k, H]
        load = jnp.sum(hit.astype(jnp.int32)
                       * load_mask.astype(jnp.int32)[:, None, None],
                       axis=(0, 1))
    return y, load


def dense_decode_ffn(cfg: ModelConfig, p: dict, x: jax.Array, *,
                     tp_axis: str = "model") -> jax.Array:
    """Dense TP FFN for decode (column/row-parallel + psum)."""
    if cfg.act == "silu":
        h = (jax.nn.silu(x @ p["wi_gate"]) * (x @ p["wi_up"])) @ p["wo"]
        return jax.lax.psum(h, tp_axis)
    h = jax.nn.gelu(x @ p["wi"] + p["bi"].astype(x.dtype), approximate=True)
    out = jax.lax.psum(h @ p["wo"], tp_axis)
    return out + p["bo"].astype(x.dtype)
