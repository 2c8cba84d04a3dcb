"""Public kernel entry points with platform dispatch.

TPU  -> Pallas kernels (``paged_attention.py`` / ``flash_attention.py``).
CPU  -> the jnp oracles in ``ref.py`` (this is what the dry-run lowers and
        what smoke tests execute; kernels themselves are validated against the
        oracles in interpret mode by ``tests/test_kernels_*.py``).

Set ``repro.kernels.ops.FORCE_IMPL`` to "ref" / "pallas" / "pallas_interpret"
to override (used by kernel tests and benchmarks).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref

FORCE_IMPL: str | None = None


def _backend() -> str:
    if FORCE_IMPL is not None:
        return FORCE_IMPL
    platform = jax.devices()[0].platform
    return "pallas" if platform == "tpu" else "ref"


# --------------------------------------------------------------------------- #
# flash attention (prefill / training)
# --------------------------------------------------------------------------- #
# kv lengths above this use the blockwise (flash-class memory) ref path
BLOCKWISE_THRESHOLD = 2048


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    q_offset: int = 0, kv_len=None):
    """Differentiable attention. See ``ref.flash_attention`` for semantics.

    q [B, Sq, Hq, D]; k/v [B, Skv, Hkv, D] (GQA: Hkv divides Hq); any float
    dtype, f32 accumulation.  Long kv (>= BLOCKWISE_THRESHOLD, 512-aligned)
    lowers the blockwise ref so dry-run memory stays flash-class.  Pinned by
    tests/test_kernels.py::test_flash_vs_oracle / ::test_blockwise_matches_dense.
    """
    impl = _backend()
    if impl == "ref":
        if k.shape[1] >= BLOCKWISE_THRESHOLD and k.shape[1] % 512 == 0:
            return ref.flash_attention_blockwise(
                q, k, v, causal=causal, scale=scale, q_offset=q_offset,
                kv_len=kv_len)
        return ref.flash_attention(q, k, v, causal=causal, scale=scale,
                                   q_offset=q_offset, kv_len=kv_len)
    from . import flash_attention as fa
    # the kernel tiles sequences longer than one block into whole blocks:
    # pad q and kv up to a block multiple, hide the kv pad behind kv_len,
    # and drop the padded query rows from the result
    B, Sq, Skv = q.shape[0], q.shape[1], k.shape[1]
    pad_q = _pad_to_blocks(Sq, fa.DEFAULT_BQ) - Sq
    pad_kv = _pad_to_blocks(Skv, fa.DEFAULT_BK) - Skv
    if pad_kv:
        if kv_len is None:
            kv_len = jnp.full((B,), Skv, jnp.int32)
        k = _pad_seq(k, pad_kv)
        v = _pad_seq(v, pad_kv)
    out, lse = fa.flash_attention(_pad_seq(q, pad_q), k, v, causal=causal,
                                  scale=scale, q_offset=q_offset,
                                  kv_len=kv_len,
                                  interpret=(impl == "pallas_interpret"))
    return out[:, :Sq], lse[:, :, :Sq]


def _pad_to_blocks(n: int, block: int) -> int:
    """Sequence length the flash kernel can tile: a single block of any
    size, else a whole number of ``block``-row blocks."""
    return n if n <= block else -(-n // block) * block


def _pad_seq(x, pad: int):
    """Zero-pad axis 1 (sequence) of [B, S, H, D] by ``pad`` rows."""
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))


def attention(q, k, v, **kw):
    """Attention without the LSE output (most call sites).

    Same layout contract as ``flash_attention``; forwards all kwargs.
    """
    return flash_attention(q, k, v, **kw)[0]


# --------------------------------------------------------------------------- #
# paged decode attention
# --------------------------------------------------------------------------- #
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           scale: float | None = None,
                           k_scale=None, v_scale=None,
                           v_dim: int | None = None):
    """Paged decode attention with LSE. See ``ref.paged_decode_attention``.

    q [N, Hq, Dk]; pages [P, page, Hkv, D] (per-device sub-pool view: the
    stripe (ps) dim is resolved by the caller's frame indices, the group
    (kg) dim is the Hkv axis; a one-head pool may be stored past Dk, at
    whole 128-lane tiles, and its pad lanes are never read).  In the decode
    step P spans every layer's frames and the block tables hold the
    layer's frame offset.  ``v_pages=None`` means V is the first
    ``v_dim`` lanes of each K head (MLA's shared latent pool), read once
    per page.  Quantized (fp8/int8) pools additionally pass per-page
    ``k_scale`` and, with a V pool, ``v_scale`` [P] f32 — dequant is fused
    into whichever impl runs (``kernels/quant.py`` defines the format).
    Pinned by tests/test_kernels.py::test_paged_decode_vs_oracle and
    tests/test_quant.py.
    """
    impl = _backend()
    if impl == "ref":
        return ref.paged_decode_attention(q, k_pages, v_pages, block_tables,
                                          lengths, scale=scale,
                                          k_scale=k_scale, v_scale=v_scale,
                                          v_dim=v_dim)
    from . import paged_attention as pa
    return pa.paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                                     scale=scale, k_scale=k_scale,
                                     v_scale=v_scale, v_dim=v_dim,
                                     interpret=(impl == "pallas_interpret"))


def merge_lse(partial_out, partial_lse, mask=None):
    """CP-shard LSE merge (always the ref impl — it is already fused-friendly).

    partial_out [W, N, Hq, Dv]; partial_lse [W, N, Hq] f32; optional mask
    [W, N].  Pinned by tests/test_properties.py::test_merge_lse_split_invariance.
    """
    return ref.merge_lse(partial_out, partial_lse, mask)


__all__ = ["flash_attention", "attention", "paged_decode_attention", "merge_lse",
           "FORCE_IMPL"]
