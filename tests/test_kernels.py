"""Pallas kernel sweeps vs the jnp oracles (interpret mode on CPU)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import junk_past_length
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (BLOCK_BYTES, lane_tiles,
                                           pages_per_block,
                                           paged_decode_attention)


@pytest.mark.parametrize("N,Hq,Hkv,Dk,Dv,page,MB,dtype,shared_v", [
    (4, 8, 2, 128, 128, 16, 4, jnp.float32, False),     # GQA
    (3, 4, 1, 256, 128, 8, 3, jnp.bfloat16, False),     # MLA-like (Dk != Dv, MQA)
    (5, 8, 8, 64, 64, 32, 2, jnp.float32, False),       # MHA
    (2, 16, 4, 128, 128, 64, 2, jnp.bfloat16, False),   # wide GQA, big pages
    (1, 2, 1, 128, 128, 8, 1, jnp.float32, False),      # single row/page
    # several compute blocks a row, MB not a multiple of pages_per_block
    (4, 16, 8, 128, 128, 16, 72, jnp.float32, False),   # 3 blocks of 32 pages
    (4, 16, 8, 128, 128, 16, 40, jnp.bfloat16, False),  # 2 blocks of 32 pages
    (5, 8, 2, 128, 128, 16, 300, jnp.float32, False),   # 3 blocks of 128 pages
    # MLA's shared latent (v_pages=None) against the oracle's explicit slice
    (3, 40, 1, 288, 256, 16, 136, jnp.float32, True),   # 3 blocks of 64 pages
    (3, 8, 1, 288, 256, 16, 8, jnp.bfloat16, True),     # one block
], ids=["4-8-2-128-128-16-4-float32", "3-4-1-256-128-8-3-bfloat16",
        "5-8-8-64-64-32-2-float32", "2-16-4-128-128-64-2-bfloat16",
        "1-2-1-128-128-8-1-float32", "multiblock-gqa-f32",
        "multiblock-gqa-bf16", "multiblock-gqa-long", "mla-shared-f32",
        "mla-shared-bf16"])
def test_paged_decode_vs_oracle(request, N, Hq, Hkv, Dk, Dv, page, MB, dtype,
                                shared_v):
    # data of its own per case, whatever ran before it in the session
    rng = np.random.default_rng(zlib.crc32(request.node.callspec.id.encode()))
    P = 64
    q = jnp.asarray(rng.standard_normal((N, Hq, Dk)), dtype)
    kp = jnp.asarray(rng.standard_normal((P, page, Hkv, Dk)), dtype)
    kp = kp.at[P - 1].set(jnp.nan)            # read only through junk ids
    vp = jnp.asarray(rng.standard_normal((P, page, Hkv, Dv)), dtype)
    vp = vp.at[P - 1].set(jnp.nan)
    bt = jnp.asarray(rng.integers(0, P - 1, (N, MB)), jnp.int32)
    lengths = np.asarray(rng.integers(0, MB * page + 1, (N,)), np.int32)
    lengths[0] = 0                               # inactive (CP padding) row
    if N > 1:
        lengths[1] = MB * page                   # full row
    if N > 2:
        # ends mid-page inside the second compute block
        ppb = pages_per_block(page, Hkv * Dk, MB)
        lengths[2] = min(ppb * page + page // 2 + 1, MB * page)
    if N > 4:
        # a zero-length row between live ones: the next block's copies
        # skip it
        lengths[3] = 0
        lengths[4] = max(lengths[4], 1)
    if shared_v:
        vp = kp[..., :Dv]                        # the oracle's explicit slice
    o_r, l_r = ref.paged_decode_attention(q, kp, vp, bt, lengths)
    o_k, l_k = paged_decode_attention(
        q, kp, None if shared_v else vp,
        junk_past_length(bt, lengths, page, P - 1), lengths, v_dim=Dv,
        interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol)
    active = lengths > 0
    np.testing.assert_allclose(np.asarray(l_k)[active], np.asarray(l_r)[active],
                               atol=1e-3)
    assert np.all(np.asarray(l_k)[~active] <= ref.NEG_INF)
    if shared_v:
        # the oracle's own shared form: V sliced from the gathered latent
        o_s, l_s = ref.paged_decode_attention(q, kp, None, bt, lengths,
                                              v_dim=Dv)
        np.testing.assert_array_equal(np.asarray(o_s), np.asarray(o_r))
        np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_r))


@pytest.mark.parametrize("page,lanes,dtype,MB", [
    (16, 8 * 128, jnp.float32, 1024),     # phi35moe.longdecode.1c: f32 GQA
    (16, 288, jnp.float32, 1024),         # minicpm3.longdecode.1c: f32 latent
    (16, 8 * 128, jnp.bfloat16, 4),       # the compile tests' shapes
    (16, 8 * 128, jnp.float32, 4),
    (16, 288, jnp.bfloat16, 4),
    (16, 8 * 128, jnp.int8, 4),
    (16, 8 * 128, jnp.float8_e4m3fn, 4),
    (16, 8 * 128, jnp.int8, 1024),
    (16, 288, jnp.int8, 1024),
    (16, 288, jnp.bfloat16, 1024),
    (64, 4 * 128, jnp.bfloat16, 2),
])
def test_pages_per_block_fits_vmem(page, lanes, dtype, MB):
    """A compute block never spans more pages than the table has, and its
    f32 copy plus two K and two V storage buffers of it fit v5e's 16 MiB of
    scoped VMEM with room for the rest of the kernel."""
    ppb = pages_per_block(page, lanes, MB)
    assert 1 <= ppb <= MB
    f32_block = ppb * (-(-page // 8) * 8) * (-(-lanes // 128) * 128) * 4
    assert f32_block <= BLOCK_BYTES
    itemsize = jnp.dtype(dtype).itemsize
    sub = 32 // itemsize                   # sublanes of one VMEM tile
    buffer = ppb * (-(-page // sub) * sub) * (-(-lanes // 128) * 128) * itemsize
    assert 4 * buffer + f32_block <= 12 << 20
    if ppb < MB:            # the largest power of two within the budget
        assert ppb & (ppb - 1) == 0 and 2 * f32_block > BLOCK_BYTES


@pytest.mark.parametrize("kg,g_out", [(2, 2), (4, 1), (2, 1)])
def test_paged_decode_grouped_subpool_view(rng, kg, g_out):
    """The head-grouped (tp < Hkv) device view: a flat sub-pool
    [F', page, kg*hd] reshaped to [F', page, kg, hd] with kv-head-major q
    rows must equal per-head oracle attention — i.e. the kernel's kv-head
    grid indexes WITHIN the resident group (core/dcp.py `_dcp_attention`)."""
    N, hd, page, P, MB = 3, 64, 8, 16, 2
    flat = jnp.asarray(rng.standard_normal((P, page, kg * hd)), jnp.float32)
    vflat = jnp.asarray(rng.standard_normal((P, page, kg * hd)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((N, kg * g_out, hd)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, P, (N, MB)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, MB * page + 1, (N,)), jnp.int32)
    kp = flat.reshape(P, page, kg, hd)
    vp = vflat.reshape(P, page, kg, hd)
    o, l = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    for h in range(kg):                       # per-kv-head oracle
        qs = q[:, h * g_out:(h + 1) * g_out]
        o_r, l_r = ref.paged_decode_attention(
            qs, kp[:, :, h:h + 1], vp[:, :, h:h + 1], bt, lengths)
        np.testing.assert_allclose(
            np.asarray(o[:, h * g_out:(h + 1) * g_out]), np.asarray(o_r),
            atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(l[:, h * g_out:(h + 1) * g_out]), np.asarray(l_r),
            atol=1e-3)


@pytest.mark.parametrize("Hq,Dk,Dv,dtype", [
    (8, 288, 256, jnp.float32),      # MiniCPM3's latent, stored at 384 lanes
    (16, 576, 512, jnp.bfloat16),    # DeepSeek-V3's, stored at 640
    (4, 40, 32, jnp.float32),        # the reduced configs' latent, at 128
    (8, 288, 256, jnp.int8),         # quantized, junk = the int8 maximum
], ids=["minicpm3-f32", "deepseekv3-bf16", "reduced-f32", "minicpm3-int8"])
def test_paged_decode_never_reads_pad_lanes(rng, Hq, Dk, Dv, dtype):
    """A one-head latent pool stored at whole 128-lane tiles
    (``init_serve_state``), with junk (NaN) in its pad lanes: the kernel
    (interpret mode) and the oracle both give the unpadded pool's result,
    so no pad lane reaches the arithmetic."""
    N, page, P, MB = 3, 16, 32, 8
    q = jnp.asarray(rng.standard_normal((N, Hq, Dk)), jnp.float32)
    if dtype == jnp.int8:
        kp = jnp.asarray(rng.integers(-127, 128, (P, page, 1, Dk)), dtype)
        junk = 127
        scales = dict(k_scale=jnp.asarray(
            rng.uniform(0.005, 0.02, (P,)), jnp.float32))
    else:
        kp = jnp.asarray(rng.standard_normal((P, page, 1, Dk)), dtype)
        junk = jnp.nan
        scales = {}
    padded = jnp.pad(kp, ((0, 0), (0, 0), (0, 0), (0, lane_tiles(Dk) - Dk)),
                     constant_values=junk)
    bt = jnp.asarray(rng.integers(0, P, (N, MB)), jnp.int32)
    lengths = jnp.asarray([MB * page, 37, 0], jnp.int32)
    o_r, l_r = ref.paged_decode_attention(q, kp, None, bt, lengths,
                                          v_dim=Dv, **scales)
    o_p, l_p = ref.paged_decode_attention(q, padded, None, bt, lengths,
                                          v_dim=Dv, **scales)
    o_k, l_k = paged_decode_attention(q, padded, None, bt, lengths, v_dim=Dv,
                                      interpret=True, **scales)
    np.testing.assert_array_equal(np.asarray(o_p), np.asarray(o_r))
    np.testing.assert_array_equal(np.asarray(l_p), np.asarray(l_r))
    assert np.all(np.isfinite(np.asarray(o_k)))
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_k)[:2], np.asarray(l_r)[:2],
                               atol=1e-3)


@pytest.mark.parametrize("B,Sq,Skv,Hq,Hkv,D,causal,dtype", [
    (2, 128, 128, 4, 2, 64, True, jnp.float32),
    (1, 256, 256, 2, 1, 128, True, jnp.bfloat16),
    (2, 128, 256, 4, 4, 64, False, jnp.float32),
    (1, 128, 128, 8, 2, 128, True, jnp.float32),
])
def test_flash_vs_oracle(rng, B, Sq, Skv, Hq, Hkv, D, causal, dtype):
    q = jnp.asarray(rng.standard_normal((B, Sq, Hq, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, Skv, Hkv, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, Skv, Hkv, D)), dtype)
    kv_len = jnp.asarray(rng.integers(Skv // 2, Skv + 1, (B,)), jnp.int32)
    o_r, l_r = ref.flash_attention(q, k, v, causal=causal, kv_len=kv_len)
    o_k, l_k = flash_attention(q, k, v, causal=causal, kv_len=kv_len,
                               interpret=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r), atol=1e-3)


@pytest.mark.parametrize("S,with_kv_len", [(37, False), (300, False),
                                           (300, True)])
def test_ops_flash_pads_any_length(rng, monkeypatch, S, with_kv_len):
    """Prompt lengths that are not a block multiple: ``ops.flash_attention``
    pads q/kv to whole 128-row blocks, masks the kv pad and slices the
    result back (GQA, causal prefill)."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "FORCE_IMPL", "pallas_interpret")
    q = jnp.asarray(rng.standard_normal((1, S, 8, 128)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, S, 2, 128)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, S, 2, 128)), jnp.float32)
    kv_len = jnp.asarray([S - 5], jnp.int32) if with_kv_len else None
    o_r, l_r = ref.flash_attention(q, k, v, causal=True, kv_len=kv_len)
    o_k, l_k = ops.flash_attention(q, k, v, causal=True, kv_len=kv_len)
    assert o_k.shape == o_r.shape and l_k.shape == l_r.shape
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r), atol=1e-3)


def test_flash_mla_dv_neq_dk(rng):
    """MLA train shape: Dk=96 (nope+rope), Dv=64."""
    q = jnp.asarray(rng.standard_normal((1, 128, 4, 96)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 128, 4, 96)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), jnp.float32)
    o_r, _ = ref.flash_attention(q, k, v, causal=True)
    o_k, _ = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=1e-5)


def test_flash_gradients_vs_oracle(rng):
    B, S, Hq, Hkv, D = 1, 128, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, D)), jnp.float32)

    def loss_k(q, k, v):
        o, _ = flash_attention(q, k, v, causal=True, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_r(q, k, v):
        o, _ = ref.flash_attention(q, k, v, causal=True)
        return jnp.sum(jnp.sin(o))

    gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_blockwise_matches_dense(rng):
    B, Sq, Skv, Hq, Hkv, D = 2, 64, 1024, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, Sq, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Skv, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Skv, Hkv, D)), jnp.float32)
    kv_len = jnp.array([700, 1024], jnp.int32)
    o1, l1 = ref.flash_attention(q, k, v, causal=False, kv_len=kv_len)
    o2, l2 = ref.flash_attention_blockwise(q, k, v, causal=False,
                                           kv_len=kv_len, block_k=256)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), atol=1e-5)
