"""Operations and bytes the algorithm needs, from the configuration alone.

These count the work of the model, not of today's code, so that a change
to an implementation never changes what its share is measured against:

* weights and KV are counted at the configuration's dtype (bf16, 2 bytes),
  whatever the program's pools hold;
* an MoE layer counts the router and the top-k experts each token is
  routed to, never capacity padding;
* attention counts the context each query actually sees: causal prefill
  counts S(S+1)/2 query-key pairs, a decode row its own context length,
  never a padded page;
* MLA prefill counts the materialised form (per-head K/V up-projected once
  per token); MLA decode counts the absorbed form over the cached latent
  (kv_lora + rope per token), the algorithm a latent cache exists for;
* the head counts the positions whose logits are used: the last prompt
  position in prefill, every row in decode.

A multiply-add is 2 operations.
"""
from __future__ import annotations

from .model import Model

BYTES = 2          # bf16


def linear_per_token(m: Model) -> float:
    """Operations of every projection, router and routed expert of all
    layers for one token (attention scores and the head excluded)."""
    D, H = m.d_model, m.num_heads
    if m.attention == "mla":
        dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
        qr, kvr = m.q_lora_rank, m.kv_lora_rank
        attn = D * qr + qr * H * (dn + dr) + D * (kvr + dr) + H * dv * D
    else:
        hd, Hkv = m.head_dim, m.num_kv_heads
        attn = D * H * hd + 2 * D * Hkv * hd + H * hd * D
    if m.num_experts:
        ffn = D * m.num_experts + m.num_experts_per_tok * 3 * D * m.moe_d_ff
    else:
        ffn = 3 * D * m.d_ff
    return 2.0 * m.num_layers * (attn + ffn)


def head(m: Model) -> float:
    return 2.0 * m.d_model * m.padded_vocab


# ----------------------------------------------------------------- prefill
def prefill_attention(m: Model, S: int) -> tuple[float, float]:
    """(operations, bytes) of causal self-attention over an S-token prompt,
    all layers: scores and values over S(S+1)/2 pairs; q, k, v and the
    output read or written once."""
    pairs = S * (S + 1) / 2.0
    H = m.num_heads
    if m.attention == "mla":
        dk = m.qk_nope_head_dim + m.qk_rope_head_dim
        dv = m.v_head_dim
        ops = 2.0 * H * (dk + dv) * pairs
        byt = S * H * (dk + dk + dv + dv) * BYTES
    else:
        hd, Hkv = m.head_dim, m.num_kv_heads
        ops = 4.0 * H * hd * pairs
        byt = S * (H * hd + 2 * Hkv * hd + H * hd) * BYTES
    return m.num_layers * ops, m.num_layers * byt


def prefill(m: Model, S: int) -> float:
    """Operations of prefilling one S-token prompt."""
    extra = 0.0
    if m.attention == "mla":
        # per-token K/V up-projections of the materialised form
        extra = 2.0 * m.num_layers * m.kv_lora_rank * m.num_heads * (
            m.qk_nope_head_dim + m.v_head_dim)
    return S * (linear_per_token(m) + extra) \
        + prefill_attention(m, S)[0] + head(m)


# ------------------------------------------------------------------ decode
def decode_attention(m: Model, ctx: int) -> tuple[float, float]:
    """(operations, bytes) of one decode row's attention over ``ctx``
    cached tokens, all layers: the KV (or latent) read once at bf16."""
    H = m.num_heads
    if m.attention == "mla":
        kvr, dr = m.kv_lora_rank, m.qk_rope_head_dim
        ops = 2.0 * H * (kvr + dr) * ctx + 2.0 * H * kvr * ctx
        byt = ctx * (kvr + dr) * BYTES
    else:
        hd, Hkv = m.head_dim, m.num_kv_heads
        ops = 4.0 * H * hd * ctx
        byt = ctx * 2 * Hkv * hd * BYTES
    return m.num_layers * ops, m.num_layers * byt


def decode_token(m: Model, ctx: int) -> float:
    """Operations of one decode row with ``ctx`` tokens of context."""
    extra = 0.0
    if m.attention == "mla":
        # absorbing W_uk into the query, and W_uv into the output
        extra = 2.0 * m.num_layers * m.num_heads * m.kv_lora_rank * (
            m.qk_nope_head_dim + m.v_head_dim)
    return linear_per_token(m) + extra + decode_attention(m, ctx)[0] \
        + head(m)


def roofline_s(ops: float, byt: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound and
    the memory bound."""
    return max(ops / peak["bf16_flops"], byt / peak["hbm_bytes_per_s"])
