"""Pallas TPU paged decode-attention kernel with LSE output (FlashMLA analogue).

One query token per work row attends over its paged KV shard; emits the
partial output AND its log-sum-exp so NanoCP's Phase-4 merge can combine
CP shards (kernels/ref.py::merge_lse).

TPU mapping:
  * grid = (rows N, compute blocks ceil(MB / ppb)); a compute block is
    ``ppb`` consecutive pages of one row (``pages_per_block`` picks ppb
    from the page size, lane width and MB).
  * the pools are passed as [P, page, Hkv*D], their own flattened
    layout, where XLA holds them (``pl.ANY``).  In the decode step that is
    the whole stacked pool of every layer, in HBM, viewed as one run of
    frames (``core/dcp.py`` ``build_decode_step``): the caller adds the
    layer's first frame to its block table, so the kernel reads each
    layer's pages where they lie and no per-layer pool is ever sliced out
    (``tests/test_tpu_compile.py`` checks that the step has no pool-sized
    op but the append).  Each block's pages are gathered by the
    scalar-prefetched block table, one ``make_async_copy`` per page, into
    a VMEM buffer [2, ppb, page, lanes] (K, and V unless V is shared),
    ``lanes`` the pool's width in whole 128-lane tiles (``lane_tiles``).
    A one-head pool may be stored at that width already (MLA's 576-lane
    latent at 640: ``init_serve_state``), its pad lanes never computed on.
    In interpret mode a narrower pool is padded to that width with junk,
    so the CPU runs the same copies and buffers as the chip.
  * double buffering: a block's copies are started one block ahead, into
    the other half of the buffer — the row's next block, or the first
    block of the next row with a nonzero length — before the current
    block is computed.  The buffer half of a block is the parity of its
    rank among all blocks that do work (an exclusive cumsum of blocks per
    row, scalar-prefetched), so no state is carried between grid steps.
  * no work past a row's length: a block whose first token lies at or
    past ``lengths[n]`` issues no DMA and no compute; in a row's last
    block only the pages below the length are copied.  Table entries past
    the length may hold any id: their pages are never read, and
    (quantized) their scales weigh 0.  Rows of length 0 (CP padding)
    produce out=0, lse=-inf without touching pages.
  * heads: the kernel loops over the kv heads of the (sub-)pool in VMEM;
    head h is the lane slice [h*D, (h+1)*D).  The G = Hq/Hkv query heads
    of a kv head form the sublane dim of the q block, so the MXU matmuls
    are [G, Dk] x [Dk, ppb*page] and [G, ppb*page] x [ppb*page, Dv].
  * head-grouped TP (tp < Hkv, core/dcp.py): each device passes its
    resident kv-head GROUP as the Hkv axis (sub-pool [F', page, kg, Dk],
    q rows kv-head-major), so the same head loop indexes within the group.
  * MLA (``v_pages=None``): V is the first Dv lanes of each K head, so the
    latent pool is copied once per page and sliced in VMEM.
  * quantized pools: each row's per-page scales are gathered outside the
    kernel into [N, MB] tables (0 for pages past the length) and
    scalar-prefetched beside the block table.  Dequant is linear, so each
    page's scale multiplies its scores (K) and its softmax weights (V) —
    [G, ppb*page] operands, not the block — and the pool never exists
    dequantized anywhere.
  * online softmax over each whole block per kv head: running (m, l, acc)
    in f32 VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF

# VMEM bytes of one compute block of one pool in f32, the copy the kernel
# computes on.  On a v5e, f32 GQA pools (Phi-3.5-MoE, 8 x 128 lanes) read
# 7% faster at 2 MiB (32 pages) than at 1 MiB; two K and two V buffers of
# this size leave half of the 16 MiB of scoped VMEM to the rest.
BLOCK_BYTES = 2 << 20


def pages_per_block(page: int, lanes: int, num_pages: int) -> int:
    """Pages per compute block: the largest power of two whose block of
    ``page`` x ``lanes`` pages, upcast to f32 and padded to VMEM's (8, 128)
    tiles, fits in ``BLOCK_BYTES`` — never more than the row's
    ``num_pages``.  A narrower storage dtype holds the same block in fewer
    bytes, so the buffers never exceed it."""
    page_bytes = (-(-page // 8) * 8) * (-(-lanes // 128) * 128) * 4
    ppb = max(1, BLOCK_BYTES // page_bytes)
    return min(1 << (ppb.bit_length() - 1), num_pages)


def lane_tiles(lanes: int) -> int:
    """Lanes of a pool's page row as the TPU lays it out in memory: whole
    128-lane tiles (every TPU tiling of a 2-D minor block has 128 lanes,
    ``tests/test_tpu_compile.py`` checks the pools' compiled layout).
    Mosaic copies whole tiles only, so a page copy takes this many lanes,
    and for a width that is no multiple of 128 (MLA's 288 -> 384) the
    last tile's padding comes along and is never used."""
    return -(-lanes // 128) * 128


def _junk(dtype):
    return (jnp.nan if jnp.issubdtype(dtype, jnp.floating)
            else jnp.iinfo(dtype).max)


def _kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,   # [N, NB*ppb] int32 (padded past MB)
    lengths_ref,        # [N] int32, at most MB*page
    rank_ref,           # [N] int32: blocks doing work in rows before n
    next_ref,           # [N] int32: next row with length > 0, N if none
    # then, iff quantized: ks_ref, vs_ref [N, NB*ppb] f32 per-page scales
    # inputs: q_ref [1, Hkv, G, Dk] (VMEM block), k_pool [P, page, Hkv*Dk],
    #   then v_pool [P, page, Hkv*Dv] unless V is shared (where XLA put them)
    # outputs: o_ref [1, Hkv, G, Dv], lse_ref [1, Hkv, G]
    # scratch: k_buf [2, ppb, page, Hkv*Dk] (+ v_buf), DMA sems [2] (+ V's),
    #   m_scr, l_scr [Hkv, G, 128] f32, acc_scr [Hkv, G, Dv] f32
    *refs,
    scale: float,
    page: int,
    ppb: int,
    num_kv_heads: int,
    dk: int,
    dv: int,
    quantized: bool,
    shared_v: bool,
):
    if quantized:
        ks_ref, vs_ref, *refs = refs
    if shared_v:
        (q_ref, k_pool, o_ref, lse_ref, k_buf, k_sem,
         m_scr, l_scr, acc_scr) = refs
        pools = ((k_pool, k_buf, k_sem),)
    else:
        (q_ref, k_pool, v_pool, o_ref, lse_ref, k_buf, v_buf, k_sem, v_sem,
         m_scr, l_scr, acc_scr) = refs
        pools = ((k_pool, k_buf, k_sem), (v_pool, v_buf, v_sem))
    n = pl.program_id(0)
    b = pl.program_id(1)
    num_rows = pl.num_programs(0)
    length = lengths_ref[n]
    tokens = ppb * page

    def block_copies(row, blk, slot, wait: bool):
        """Start (or wait for) one copy per page of block ``blk`` of
        ``row`` that lies below the row's length."""
        live = jnp.minimum(ppb, pl.cdiv(lengths_ref[row], page) - blk * ppb)

        def one_page(j, carry):
            frame = block_tables_ref[row, blk * ppb + j]
            for pool, buf, sem in pools:
                # whole 128-lane tiles: past the pool's last lane this
                # reads its tile padding (``lane_tiles``), never used
                src = pool.at[frame, :, pl.ds(0, buf.shape[-1])]
                copy = pltpu.make_async_copy(src, buf.at[slot, j],
                                             sem.at[slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return carry

        jax.lax.fori_loop(0, live, one_page, 0)

    @pl.when(b == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(b * tokens < length)
    def _compute():
        rank = rank_ref[n] + b
        slot = rank % 2

        @pl.when(rank == 0)
        def _first():
            # slots past a short block keep whatever they held; zeroed once,
            # they only ever hold finite pool data, so 0-weight x V is 0
            for _, buf, _ in pools:
                buf[...] = jnp.zeros_like(buf)
            block_copies(n, b, slot, wait=False)

        last = (b + 1) * tokens >= length
        next_row = jnp.where(last, next_ref[n], n)

        @pl.when(next_row < num_rows)
        def _prefetch():
            block_copies(next_row, jnp.where(last, 0, b + 1), 1 - slot,
                         wait=False)

        block_copies(n, b, slot, wait=True)

        pos = b * tokens + jax.lax.broadcasted_iota(jnp.int32, (1, tokens), 1)
        valid = pos < length                                    # [1, T]
        if quantized:
            # per-token scale row: page j of the block covers tokens
            # [j*page, (j+1)*page)
            pid = (pos - b * tokens) // page
            k_sc = jnp.zeros((1, tokens), jnp.float32)
            v_sc = jnp.zeros((1, tokens), jnp.float32)
            for j in range(ppb):
                k_sc = jnp.where(pid == j, ks_ref[n, b * ppb + j], k_sc)
                v_sc = jnp.where(pid == j, vs_ref[n, b * ppb + j], v_sc)
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale            # [G, Dk]
            k = k_buf[slot, :, :, h * dk:(h + 1) * dk]              # [ppb,page,Dk]
            k = k.astype(jnp.float32).reshape(tokens, dk)
            if shared_v:
                v = k[:, :dv]
            else:
                v = v_buf[slot, :, :, h * dv:(h + 1) * dv]
                v = v.astype(jnp.float32).reshape(tokens, dv)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # [G, T]
            if quantized:
                s = s * k_sc
            s = jnp.where(valid, s, NEG_INF)

            m_prev = m_scr[h][:, :1]                               # [G, 1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            p = jnp.exp(s - m_new)                                 # [G, T]
            corr = jnp.exp(m_prev - m_new)                         # [G, 1]
            l_new = corr * l_scr[h][:, :1] + jnp.sum(p, axis=-1, keepdims=True)
            pv = p * v_sc if quantized else p
            acc_scr[h] = acc_scr[h] * corr + jax.lax.dot_general(
                pv, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(b == pl.num_programs(1) - 1)
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


@functools.partial(jax.jit, static_argnames=("scale", "v_dim", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths, *,
                           scale: float | None = None,
                           k_scale=None, v_scale=None,
                           v_dim: int | None = None,
                           interpret: bool = False):
    """See ``ref.paged_decode_attention`` for exact semantics.

    q [N, Hq, Dk]; k_pages [P, page, Hkv, Dk]; v_pages [P, page, Hkv, Dv],
    or None when V is the first ``v_dim`` lanes of each K head (MLA's
    shared latent: one DMA per page); block_tables [N, MB] int32 (entries
    at or past a row's length may hold any id); lengths [N] int32.  A
    one-head pool (Hkv == 1) may be stored at ``lane_tiles(Dk)`` lanes:
    only its first Dk lanes are computed on.

    Quantized pools (fp8/int8, ``kernels/quant.py``): pass per-page
    ``k_scale`` and, with a V pool, ``v_scale`` [P] f32 (a shared V takes
    K's scales).  Each row's scales are gathered by its block table into
    [N, MB] and scalar-prefetched; the kernel applies them to the scores
    and softmax weights in VMEM — the pool never exists dequantized.

    Pinned against the jnp oracle (interpret mode) by tests/test_kernels.py::
    test_paged_decode_vs_oracle and tests/test_quant.py::test_pallas_interpret_
    matches_ref_quantized; compiled for a described v5e by
    tests/test_tpu_compile.py.
    """
    N, Hq, Dk = q.shape
    P, page, Hkv, Dp = k_pages.shape
    assert Dp == Dk or (Hkv == 1 and Dp == lane_tiles(Dk)), (Dp, Dk, Hkv)
    shared_v = v_pages is None
    Dv = v_dim if shared_v else v_pages.shape[-1]
    MB = block_tables.shape[1]
    G = Hq // Hkv
    assert Hq % Hkv == 0
    if shared_v:
        assert Dv is not None and Dv <= Dk and v_scale is None
    else:
        assert (k_scale is None) == (v_scale is None)
    quantized = k_scale is not None
    scale = scale if scale is not None else Dk ** -0.5

    ppb = pages_per_block(page, Hkv * Dk, MB)
    NB = pl.cdiv(MB, ppb)
    # pad the tables to whole blocks; the padding is never read, as every
    # length is held to the MB pages the table has
    pad = ((0, 0), (0, NB * ppb - MB))
    lengths = jnp.minimum(lengths.astype(jnp.int32), MB * page)
    blocks = pl.cdiv(lengths, ppb * page)
    rank = jnp.cumsum(blocks) - blocks
    row = jnp.arange(N, dtype=jnp.int32)
    live_row = jnp.where(lengths > 0, row, N)
    # next live row after n: a reversed running min, shifted by one
    after = jax.lax.cummin(live_row[::-1])[::-1]
    next_live = jnp.concatenate([after[1:], jnp.full((1,), N, jnp.int32)])
    scalars = [jnp.pad(block_tables.astype(jnp.int32), pad), lengths,
               rank.astype(jnp.int32), next_live]
    if quantized:
        # a table entry past a row's length may hold any id, and the
        # scale it gathers, though its page weighs nothing, must be finite
        # (0 * NaN is NaN): such pages take scale 0
        live = (jnp.arange(MB)[None, :]
                < pl.cdiv(lengths, page)[:, None])                # [N, MB]
        ks = jnp.where(live, k_scale.astype(jnp.float32)[block_tables], 0.0)
        vs = ks if shared_v else jnp.where(
            live, v_scale.astype(jnp.float32)[block_tables], 0.0)
        scalars += [jnp.pad(ks, pad), jnp.pad(vs, pad)]

    q4 = q.reshape(N, Hkv, G, Dk)            # group q heads by kv head
    # [P, page, Hkv, D] -> [P, page, Hkv*D]: a free reshape (the pools are
    # stored with the heads flattened into the last dim)
    pools = [k_pages.reshape(P, page, Hkv * Dp)]
    if not shared_v:
        pools.append(v_pages.reshape(P, page, Hkv * Dv))
    if interpret:
        # stand-in for the tile padding (see ``lane_tiles``), NaN so a
        # padding lane that reached the math would show
        pools = [jnp.pad(p, ((0, 0), (0, 0), (0, lane_tiles(p.shape[-1])
                                                - p.shape[-1])),
                         constant_values=_junk(p.dtype)) for p in pools]
    bufs = [pltpu.VMEM((2, ppb, page, lane_tiles(p.shape[-1])), p.dtype)
            for p in pools]
    sems = [pltpu.SemaphoreType.DMA((2,)) for _ in pools]

    kernel = functools.partial(_kernel, scale=scale, page=page, ppb=ppb,
                               num_kv_heads=Hkv, dk=Dk, dv=Dv,
                               quantized=quantized, shared_v=shared_v)

    def row_map(n, b, *_):
        return (n, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(N, NB),
        in_specs=[pl.BlockSpec((1, Hkv, G, Dk), row_map)]
        + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
        out_specs=[
            pl.BlockSpec((1, Hkv, G, Dv), row_map),
            pl.BlockSpec((1, Hkv, G), lambda n, b, *_: (n, 0, 0)),
        ],
        scratch_shapes=bufs + sems + [
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
        # blocks hand DMAs across rows, so both grid axes run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*scalars, q4, *pools)

    return out.reshape(N, Hq, Dv), lse.reshape(N, Hq)
