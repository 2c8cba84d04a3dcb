"""Pallas TPU causal flash-attention (prefill/training) with LSE output.

Forward: blockwise online-softmax, grid (B, H, q blocks, kv blocks), f32
accumulators in VMEM scratch, GQA handled by indexing the kv head h*Hkv//Hq
(no materialised head expansion).  Fully-masked causal blocks skip their
FLOPs via @pl.when.  The kernel reads head-major [B, H, S, D] copies of
q/k/v, so every block's last two dims are (rows, D) — Mosaic's
(8, 128)-or-full-dim rule then holds for any head count.

Backward: flash-style *scanned jnp* backward (no S^2 materialisation) wired
through ``jax.custom_vjp`` — forward runs the kernel, backward recomputes
per-block probabilities from the saved LSE.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import NEG_INF, _gqa_expand

DEFAULT_BQ = 128
DEFAULT_BK = 128


def _fwd_kernel(kv_len_ref,
                q_ref, k_ref, v_ref,
                o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, q_offset: int,
                bq: int, bk: int, nk: int):
    b = pl.program_id(0)
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    kv_len = kv_len_ref[b]

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal skip: block contributes iff its first kv pos <= last q pos
    last_q = iq * bq + bq - 1 + q_offset
    needed = jnp.logical_and(ik * bk <= (last_q if causal else jnp.int32(2 ** 30)),
                             ik * bk < kv_len)

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)                  # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        cpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = cpos < kv_len
        if causal:
            rpos = iq * bq + q_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, rpos >= cpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = jnp.broadcast_to(
            corr * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True), l_scr.shape)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, 0] = (m_scr[:, :1] + jnp.log(l))[:, 0]


def _flash_fwd(q, k, v, scale, causal, q_offset, kv_len, interpret,
               bq=DEFAULT_BQ, bk=DEFAULT_BK):
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    bq = min(bq, Sq)
    bk = min(bk, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk
    if kv_len is None:
        kv_len = jnp.full((B,), Skv, jnp.int32)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, q_offset=q_offset,
        bq=bq, bk=bk, nk=nk)

    def kv_map(b, h, iq, ik, kl):
        return (b, h * Hkv // Hq, ik, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik, kl: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, Dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, iq, ik, kl: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, iq, ik, kl: (b, h, 0, iq)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
    )
    head_major = (0, 2, 1, 3)                   # [B, S, H, D] <-> [B, H, S, D]
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, Hq, 1, Sq), jnp.float32),
        ],
        interpret=interpret,
    )(kv_len, q.transpose(head_major), k.transpose(head_major),
      v.transpose(head_major))
    return out.transpose(head_major), lse.reshape(B, Hq, Sq)


# --------------------------------------------------------------------------- #
# flash-style scanned jnp backward (shared by the kernel path and usable as a
# memory-honest reference backward)
# --------------------------------------------------------------------------- #
def flash_backward(q, k, v, o, lse, do, *, scale, causal, q_offset=0,
                   kv_len=None, bk=DEFAULT_BK):
    """Block-scanned attention backward; returns (dq, dk, dv) in input dtypes.

    q [B, Sq, Hq, D]; k/v [B, Skv, Hkv, D(v)] (GQA grads sum over the group);
    o/do [B, Sq, Hq, Dv]; lse [B, Hq, Sq] f32 from the forward.  Requires
    Skv divisible by ``bk``.  Pinned (through the custom_vjp) by
    tests/test_kernels.py::test_flash_gradients_vs_oracle.
    """
    B, Sq, Hq, D = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    bk = min(bk, Skv)
    assert Skv % bk == 0
    nk = Skv // bk
    ke = _gqa_expand(k, Hq)
    ve = _gqa_expand(v, Hq)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    of = o.astype(jnp.float32)
    delta = jnp.sum(dof * of, axis=-1)                       # [B, Sq, Hq]
    if kv_len is None:
        kv_len = jnp.full((B,), Skv, jnp.int32)
    rpos = jnp.arange(Sq) + q_offset

    def body(dq_acc, ik):
        ks = jax.lax.dynamic_slice_in_dim(ke, ik * bk, bk, 1).astype(jnp.float32)
        vs = jax.lax.dynamic_slice_in_dim(ve, ik * bk, bk, 1).astype(jnp.float32)
        cpos = ik * bk + jnp.arange(bk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf * scale, ks)
        mask = (cpos[None, :] < kv_len[:, None])[:, None, None, :]
        if causal:
            mask = jnp.logical_and(mask, (rpos[:, None] >= cpos[None, :])[None, None])
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)   # [B,H,q,k]
        dv = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vs)
        ds = p * (dp - delta.transpose(0, 2, 1)[..., None])
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds, ks) * scale
        dk = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
        return dq_acc, (dk, dv)

    body = jax.checkpoint(body, prevent_cse=False)
    dq, (dks, dvs) = jax.lax.scan(body, jnp.zeros_like(qf), jnp.arange(nk))
    Dv = v.shape[-1]
    dk_full = jnp.moveaxis(dks, 0, 1).reshape(B, Skv, Hq, D)
    dv_full = jnp.moveaxis(dvs, 0, 1).reshape(B, Skv, Hq, Dv)
    if Hkv != Hq:
        g = Hq // Hkv
        dk_full = dk_full.reshape(B, Skv, Hkv, g, D).sum(3)
        dv_full = dv_full.reshape(B, Skv, Hkv, g, Dv).sum(3)
    return (dq.astype(q.dtype), dk_full.astype(k.dtype), dv_full.astype(v.dtype))


# --------------------------------------------------------------------------- #
# public entry (custom_vjp)
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 7))
def _flash(q, k, v, scale, causal, q_offset, kv_len, interpret):
    return _flash_fwd(q, k, v, scale, causal, q_offset, kv_len, interpret)


def _flash_vjp_fwd(q, k, v, scale, causal, q_offset, kv_len, interpret):
    out, lse = _flash_fwd(q, k, v, scale, causal, q_offset, kv_len, interpret)
    return (out, lse), (q, k, v, out, lse, kv_len)


def _flash_vjp_bwd(scale, causal, q_offset, interpret, res, cts):
    q, k, v, out, lse, kv_len = res
    do, _ = cts
    dq, dk, dv = flash_backward(q, k, v, out, lse, do, scale=scale,
                                causal=causal, q_offset=q_offset, kv_len=kv_len)
    dkv_len = None if kv_len is None else jnp.zeros_like(kv_len)
    return dq, dk, dv, dkv_len


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    q_offset: int = 0, kv_len=None, interpret: bool = False):
    """Kernel-path flash attention; see ``ref.flash_attention`` for semantics.

    q [B, Sq, Hq, D]; k/v [B, Skv, Hkv, D(v)]; Sq/Skv must divide into the
    128-element q/kv blocks (``ops.flash_attention`` pads longer sequences
    and masks the pad with ``kv_len``); bf16 or f32 in, f32 accumulation,
    out in q.dtype + lse [B, Hq, Sq] f32.  KV pools are never quantized on
    this path — prefill reads/writes full-precision activations; quantization
    happens when pages enter the paged pool (``core/migrate.py``).  Pinned by
    tests/test_kernels.py::test_flash_vs_oracle (interpret mode) and
    ::test_flash_gradients_vs_oracle.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return _flash(q, k, v, scale, causal, q_offset, kv_len, interpret)
