"""Pure-jnp oracles for every kernel in this package.

These are the semantics of record: Pallas kernels are asserted allclose
against these in tests, and the CPU dry-run / smoke tests compile these
directly (``ops.py`` dispatches by platform).

All functions accumulate in float32 regardless of input dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_expand(k: jax.Array, num_q_heads: int) -> jax.Array:
    """[..., Hkv, D] -> [..., Hq, D] by repeating kv heads."""
    hkv = k.shape[-2]
    if hkv == num_q_heads:
        return k
    assert num_q_heads % hkv == 0
    return jnp.repeat(k, num_q_heads // hkv, axis=-2)


# --------------------------------------------------------------------------- #
# prefill / training attention
# --------------------------------------------------------------------------- #
def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    q_offset: int = 0, kv_len: jax.Array | None = None):
    """Reference multi-head attention.

    q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D] (GQA broadcast).
    ``q_offset``: absolute position of q[0] (for chunked prefill).
    ``kv_len``: optional [B] valid kv lengths (padding mask).
    Returns out [B, Sq, Hq, D] (q.dtype), lse [B, Hq, Sq] (f32).

    Accepts any dtype; scores/softmax accumulate in f32.  Dv may differ
    from Dk (MLA).  Pinned by tests/test_kernels.py::test_flash_vs_oracle
    and ::test_flash_mla_dv_neq_dk.
    """
    orig_dtype = q.dtype
    B, Sq, Hq, D = q.shape
    Skv = k.shape[1]
    k = _gqa_expand(k, Hq)
    v = _gqa_expand(v, Hq)
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32) * scale
    s = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))
    if causal:
        qi = jnp.arange(Sq)[:, None] + q_offset
        ki = jnp.arange(Skv)[None, :]
        s = jnp.where(qi >= ki, s, NEG_INF)
    if kv_len is not None:
        mask = jnp.arange(Skv)[None, :] < kv_len[:, None]          # [B, Skv]
        s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    m = jnp.maximum(m, NEG_INF)                                     # all-masked rows
    p = jnp.exp(s - m)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bkhd->bqhd", p / jnp.maximum(denom, 1e-30),
                   v.astype(jnp.float32))
    lse = (m + jnp.log(jnp.maximum(denom, 1e-30)))[..., 0]          # [B, Hq, Sq]
    return o.astype(orig_dtype), lse


def flash_attention_blockwise(q, k, v, *, causal: bool = True,
                              scale: float | None = None, q_offset: int = 0,
                              kv_len: jax.Array | None = None, block_k: int = 512):
    """Memory-honest attention: online softmax scanned over kv blocks.

    Same semantics as ``flash_attention`` but never materialises the
    [Sq, Skv] score matrix — this is what the CPU dry-run lowers for long
    sequences so ``memory_analysis`` reflects a flash-class implementation.
    Differentiable (the scan body is checkpointed).  Requires Skv divisible
    by ``block_k``.  Pinned by tests/test_kernels.py::test_blockwise_matches_dense.
    """
    orig_dtype = q.dtype
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv
    bk = min(block_k, Skv)
    assert Skv % bk == 0, (Skv, bk)
    nk = Skv // bk
    scale = scale if scale is not None else D ** -0.5
    qg = ((q.astype(jnp.float32) * scale).astype(q.dtype)
          .reshape(B, Sq, Hkv, G, D))
    if kv_len is None:
        kv_len = jnp.full((B,), Skv, jnp.int32)
    rpos = jnp.arange(Sq) + q_offset

    def body(carry, ik):
        m, l, acc = carry
        # kv blocks stay in their stored dtype; grouped-head einsums with
        # f32 accumulation avoid head-expanded / f32 copies
        ks = jax.lax.dynamic_slice_in_dim(k, ik * bk, bk, 1)
        vs = jax.lax.dynamic_slice_in_dim(v, ik * bk, bk, 1)
        cpos = ik * bk + jnp.arange(bk)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ks,
                       preferred_element_type=jnp.float32)
        mask = (cpos[None, :] < kv_len[:, None])[:, None, None, None, :]
        if causal:
            mask = jnp.logical_and(
                mask, (rpos[:, None] >= cpos[None, :])[None, None, None])
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr[..., 0][..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(q.dtype), vs,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Hkv, G, Sq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, Sq, 1), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, Sq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), (m0, l0, acc0), jnp.arange(nk))
    safe_l = jnp.maximum(l, 1e-30)
    out = (acc / safe_l).reshape(B, Hq, Sq, Dv).transpose(0, 2, 1, 3)
    lse = (m + jnp.log(safe_l))[..., 0].reshape(B, Hq, Sq)
    return out.astype(orig_dtype), lse


# --------------------------------------------------------------------------- #
# paged decode attention (FlashMLA/paged-attention analogue)
# --------------------------------------------------------------------------- #
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           scale: float | None = None,
                           k_scale=None, v_scale=None,
                           v_dim: int | None = None):
    """Decode attention over a paged KV pool, with LSE output.

    q:            [N, Hq, Dk]      one query token per work row
    k_pages:      [P, page, Hkv, Dk]; a one-head pool may be wider than
                  Dk (stored at whole 128-lane tiles), and only its first
                  Dk lanes are read
    v_pages:      [P, page, Hkv, Dv], or None: V is then the first ``v_dim``
                  lanes of each K head (MLA's shared latent pool)
    block_tables: [N, MB] int32    page ids per row (entries >= lengths ignored)
    lengths:      [N]     int32    valid kv tokens per row; 0 => inactive row
    k_scale/v_scale: optional [P] f32 per-page dequant scales for quantized
                  (fp8/int8) pools; when given, gathered pages decode as
                  ``page * scale`` before use (``kernels/quant.py``). Pass
                  neither (bf16) or both; a shared V (``v_pages=None``)
                  takes ``k_scale`` and no ``v_scale``.
    v_dim:        Dv when ``v_pages`` is None.
    Returns out [N, Hq, Dv] (q.dtype), lse [N, Hq] (f32; -inf-ish for len 0).

    Layout contract: pages are the per-device sub-pool view [F', page, kg, D]
    of the striped pool (kg kv heads resident, ``attn_tp_geometry``); the
    kv-head axis is whatever slice the caller holds — this function never
    sees the stripe (ps) dim.  Pinned by tests/test_kernels.py::
    test_paged_decode_vs_oracle (dense geometry), test_paged_decode_grouped_
    subpool_view (kg > 1 view), and tests/test_quant.py (quantized pools).
    """
    orig_dtype = q.dtype
    N, Hq, Dk = q.shape
    P, page, Hkv, _ = k_pages.shape
    shared_v = v_pages is None
    Dv = v_dim if shared_v else v_pages.shape[-1]
    MB = block_tables.shape[1]
    G = Hq // Hkv
    scale = scale if scale is not None else Dk ** -0.5

    # gather pages in their STORED dtype; grouped-head einsums with f32
    # accumulation avoid ever materialising head-expanded / f32 KV copies
    # (this path is what the CPU dry-run lowers — memory must stay honest).
    k = k_pages[block_tables][..., :Dk].reshape(N, MB * page, Hkv, Dk)
    if k_scale is not None:
        # quantized pools: dequant only the gathered [N, MB*page] window.
        # Scales are per page, constant across the page's tokens/head-dims.
        ks = jnp.broadcast_to(k_scale[block_tables][..., None],
                              block_tables.shape + (page,)).reshape(N, MB * page)
        k = k.astype(jnp.float32) * ks[..., None, None]
    if shared_v:
        v = k[..., :Dv]              # the latent, gathered (and dequantized) once
    else:
        v = v_pages[block_tables].reshape(N, MB * page, Hkv, Dv)
        if v_scale is not None:
            vs = jnp.broadcast_to(v_scale[block_tables][..., None],
                                  block_tables.shape + (page,)
                                  ).reshape(N, MB * page)
            v = v.astype(jnp.float32) * vs[..., None, None]
    # scale the f32 scores, not q: q * scale rounded back to a bf16 q is
    # off by up to half a bf16 step of every score
    qg = q.reshape(N, Hkv, G, Dk)
    s = jnp.einsum("nhgd,nkhd->nhgk", qg, k,
                   preferred_element_type=jnp.float32) * scale  # [N,Hkv,G,L]
    valid = jnp.arange(MB * page)[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("nhgk,nkhd->nhgd", (p / jnp.maximum(denom, 1e-30)
                                       ).astype(q.dtype), v,
                   preferred_element_type=jnp.float32)
    o = o.reshape(N, Hq, Dv)
    lse = (m + jnp.log(jnp.maximum(denom, 1e-30)))[..., 0].reshape(N, Hq)
    lse = jnp.where(lengths[:, None] > 0, lse, NEG_INF)
    o = jnp.where(lengths[:, None, None] > 0, o, 0.0)
    return o.astype(orig_dtype), lse


def decode_attention_dense(q, k, v, lengths, *, scale: float | None = None):
    """Contiguous-KV decode reference: q [N,Hq,Dk], k [N,L,Hkv,Dk], v [N,L,Hkv,Dv].

    The degenerate one-page-per-row layout (page size L, identity block
    table) — used by the dense decode backend; exercised transitively by
    every test that pins ``paged_decode_attention``.
    """
    # Route through the paged oracle with one page (of size L) per row.
    N = q.shape[0]
    bt = jnp.arange(N, dtype=jnp.int32)[:, None]
    return paged_decode_attention(q, k, v, bt, lengths, scale=scale)


# --------------------------------------------------------------------------- #
# LSE merge (flash-decoding merge; NanoCP Phase-4)
# --------------------------------------------------------------------------- #
def merge_lse(partial_out, partial_lse, mask=None):
    """Merge CP-shard partial attention results.

    partial_out: [W, N, Hq, Dv] f32-or-lower; partial_lse: [W, N, Hq] f32.
    mask: optional [W, N] bool (False entries are ignored).
    Returns merged out [N, Hq, Dv] (partial_out.dtype), merged lse [N, Hq].

    Invariant: merging the per-shard outputs of a length-split attention
    equals the unsplit attention.  Pinned by tests/test_properties.py::
    test_merge_lse_split_invariance and ::test_merge_lse_permutation_invariance.
    """
    orig_dtype = partial_out.dtype
    o = partial_out.astype(jnp.float32)
    lse = partial_lse.astype(jnp.float32)
    if mask is not None:
        lse = jnp.where(mask[..., None], lse, NEG_INF)
    m = jnp.max(lse, axis=0, keepdims=True)                 # [1, N, Hq]
    m = jnp.maximum(m, NEG_INF)
    w = jnp.exp(lse - m)                                     # [W, N, Hq]
    denom = jnp.sum(w, axis=0)                               # [N, Hq]
    merged = jnp.einsum("wnh,wnhd->nhd", w, o) / jnp.maximum(denom, 1e-30)[..., None]
    merged_lse = m[0] + jnp.log(jnp.maximum(denom, 1e-30))
    return merged.astype(orig_dtype), merged_lse


__all__ = ["flash_attention", "paged_decode_attention", "decode_attention_dense",
           "merge_lse", "NEG_INF"]
