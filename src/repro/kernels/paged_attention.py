"""Pallas TPU paged decode-attention kernel with LSE output (FlashMLA analogue).

One query token per work row attends over its paged KV shard; emits the
partial output AND its log-sum-exp so NanoCP's Phase-4 merge can combine
CP shards (kernels/ref.py::merge_lse).

TPU mapping:
  * grid = (rows N, page blocks MB); pages stream HBM->VMEM via BlockSpec
    index maps driven by the scalar-prefetched block table (SMEM).
  * every block spans ALL kv heads of the (sub-)pool: pages enter as
    [P, page, Hkv*D] (the pool's own flattened layout), so the last two
    block dims are whole array dims — Mosaic's (8, 128)-or-full-dim rule
    holds for any Hkv, page and dtype.  The kernel loops over the kv heads
    in VMEM; head h is the lane slice [h*D, (h+1)*D).
  * GQA: the G = Hq/Hkv query heads of a kv head form the sublane dim of the
    q block; MXU matmuls are [G, Dk] x [Dk, page] and [page] x [page, Dv].
  * head-grouped TP (tp < Hkv, core/dcp.py): each device passes its resident
    kv-head GROUP as the Hkv axis (sub-pool [F', page, kg, Dk], q rows
    kv-head-major), so the same head loop indexes within the group — no
    separate kernel variant.
  * quantized pools: each row's per-page scales are gathered outside the
    kernel into [N, MB] tables and scalar-prefetched beside the block table,
    so the dequant multiply reads a scalar from SMEM.
  * online softmax: running (m, l, acc) in f32 VMEM scratch; rows with
    length 0 (CP padding) produce out=0, lse=-inf without touching pages.
  * pages past a row's length are masked; their FLOPs are skipped via
    @pl.when (the DMA for at most one excess page block is tolerated).

Tiling (pages per grid step, heads per block) is not tuned yet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF


def _kernel(
    # scalar prefetch
    block_tables_ref,   # [N, MB] int32 (SMEM)
    lengths_ref,        # [N]     int32 (SMEM)
    # then, iff quantized: ks_ref, vs_ref [N, MB] f32 (SMEM, per-page scales)
    # inputs
    # q_ref   [1, Hkv, G, Dk]   (VMEM block)
    # k_ref   [1, page, Hkv*Dk]
    # v_ref   [1, page, Hkv*Dv]
    # outputs
    # o_ref   [1, Hkv, G, Dv]
    # lse_ref [1, Hkv, G]
    # scratch
    # m_scr   [Hkv, G, 128] f32
    # l_scr   [Hkv, G, 128] f32
    # acc_scr [Hkv, G, Dv]  f32
    *refs,
    scale: float,
    page: int,
    num_page_blocks: int,
    num_kv_heads: int,
    dk: int,
    dv: int,
    quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    n = pl.program_id(0)
    b = pl.program_id(1)
    length = lengths_ref[n]

    @pl.when(b == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(b * page < length)
    def _compute():
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale             # [G, Dk]
            k = k_ref[0, :, h * dk:(h + 1) * dk].astype(jnp.float32)  # [page, Dk]
            v = v_ref[0, :, h * dv:(h + 1) * dv].astype(jnp.float32)  # [page, Dv]
            if quantized:
                # fused per-page dequant in VMEM, right after the upcast —
                # no dequantized copy of the pool ever exists in HBM
                k = k * ks_ref[n, b]
                v = v * vs_ref[n, b]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # [G, page]
            pos = b * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)

            m_prev = m_scr[h][:, :1]                               # [G, 1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                                 # [G, page]
            corr = jnp.exp(m_prev - m_new)                         # [G, 1]
            l_new = corr * l_scr[h][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(b == num_page_blocks - 1)
    def _finalize():
        active = length > 0
        for h in range(num_kv_heads):
            l = l_scr[h][:, :1]
            m = m_scr[h][:, :1]
            safe_l = jnp.maximum(l, 1e-30)
            o = jnp.where(active, acc_scr[h] / safe_l, 0.0)
            o_ref[0, h] = o.astype(o_ref.dtype)
            lse = jnp.where(active, m + jnp.log(safe_l), NEG_INF)
            lse_ref[0, h] = lse[:, 0].astype(lse_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           scale: float | None = None,
                           k_scale=None, v_scale=None,
                           interpret: bool = False):
    """See ``ref.paged_decode_attention`` for exact semantics.

    q [N, Hq, Dk]; k_pages [P, page, Hkv, Dk]; v_pages [P, page, Hkv, Dv];
    block_tables [N, MB] int32; lengths [N] int32.

    Quantized pools (fp8/int8, ``kernels/quant.py``): pass per-page
    ``k_scale``/``v_scale`` [P] f32.  Each row's scales are gathered by its
    block table into [N, MB] and scalar-prefetched, so ``_compute`` dequants
    in VMEM (upcast-then-multiply) before the MXU matmuls — the pool never
    exists dequantized in HBM.  Pass neither or both.

    Pinned against the jnp oracle (interpret mode) by tests/test_kernels.py::
    test_paged_decode_vs_oracle and tests/test_quant.py::test_pallas_interpret_
    matches_ref_quantized; compiled for a described v5e by
    tests/test_tpu_compile.py.
    """
    N, Hq, Dk = q.shape
    P, page, Hkv, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    MB = block_tables.shape[1]
    G = Hq // Hkv
    assert Hq % Hkv == 0
    assert (k_scale is None) == (v_scale is None)
    quantized = k_scale is not None
    scale = scale if scale is not None else Dk ** -0.5

    q4 = q.reshape(N, Hkv, G, Dk)            # group q heads by kv head
    # [P, page, Hkv, D] -> [P, page, Hkv*D]: a free reshape (the pools are
    # stored with the heads flattened into the last dim)
    kf = k_pages.reshape(P, page, Hkv * Dk)
    vf = v_pages.reshape(P, page, Hkv * Dv)
    scalars = [block_tables, lengths]
    if quantized:
        scalars += [k_scale.astype(jnp.float32)[block_tables],
                    v_scale.astype(jnp.float32)[block_tables]]

    kernel = functools.partial(_kernel, scale=scale, page=page,
                               num_page_blocks=MB, num_kv_heads=Hkv,
                               dk=Dk, dv=Dv, quantized=quantized)

    def page_map(n, b, bt, *_):
        return (bt[n, b], 0, 0)

    def row_map(n, b, *_):
        return (n, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(N, MB),
        in_specs=[
            pl.BlockSpec((1, Hkv, G, Dk), row_map),
            pl.BlockSpec((1, page, Hkv * Dk), page_map),
            pl.BlockSpec((1, page, Hkv * Dv), page_map),
        ],
        out_specs=[
            pl.BlockSpec((1, Hkv, G, Dv), row_map),
            pl.BlockSpec((1, Hkv, G), lambda n, b, *_: (n, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, 128), jnp.float32),
            pltpu.VMEM((Hkv, G, 128), jnp.float32),
            pltpu.VMEM((Hkv, G, Dv), jnp.float32),
        ],
    )

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((N, Hkv, G, Dv), q.dtype),
            jax.ShapeDtypeStruct((N, Hkv, G), jnp.float32),
        ],
        interpret=interpret,
    )(*scalars, q4, kf, vf)

    return out.reshape(N, Hq, Dv), lse.reshape(N, Hq)
