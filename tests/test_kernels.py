"""Pallas kernel sweeps vs the jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_decode_attention


@pytest.mark.parametrize("N,Hq,Hkv,Dk,Dv,page,MB,dtype", [
    (4, 8, 2, 128, 128, 16, 4, jnp.float32),     # GQA
    (3, 4, 1, 256, 128, 8, 3, jnp.bfloat16),     # MLA-like (Dk != Dv, MQA)
    (5, 8, 8, 64, 64, 32, 2, jnp.float32),       # MHA
    (2, 16, 4, 128, 128, 64, 2, jnp.bfloat16),   # wide GQA, big pages
    (1, 2, 1, 128, 128, 8, 1, jnp.float32),      # single row/page
])
def test_paged_decode_vs_oracle(rng, N, Hq, Hkv, Dk, Dv, page, MB, dtype):
    P = 64
    q = jnp.asarray(rng.standard_normal((N, Hq, Dk)), dtype)
    kp = jnp.asarray(rng.standard_normal((P, page, Hkv, Dk)), dtype)
    vp = jnp.asarray(rng.standard_normal((P, page, Hkv, Dv)), dtype)
    bt = jnp.asarray(rng.integers(0, P, (N, MB)), jnp.int32)
    lengths = jnp.asarray(rng.integers(0, MB * page + 1, (N,)), jnp.int32)
    lengths = lengths.at[0].set(0)               # inactive (CP padding) row
    if N > 1:
        lengths = lengths.at[1].set(MB * page)   # full row
    o_r, l_r = ref.paged_decode_attention(q, kp, vp, bt, lengths)
    o_k, l_k = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol)
    active = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(l_k)[active], np.asarray(l_r)[active],
                               atol=1e-3)


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
