"""Quantized paged-KV numerics and scale-sidecar lifecycle (tier-1).

Three layers of the quantization stack (``kernels/quant.py``):

  * format round-trip: per-page symmetric quantize -> dequantize error is
    bounded by the dtype's step size relative to the page amax;
  * attention numerics: quantized pools + per-page scales through the
    paged decode reference stay within a per-dtype bound of the fp32
    oracle, across pool geometries (GQA, grouped kv view, MLA-like
    dv != dk), and the Pallas kernel's FUSED dequant (interpret mode)
    matches the reference on identical quantized inputs;
  * host lifecycle: the ``GlobalPageTable`` scale ledger stays in lockstep
    with frame ownership across allocate / append / cow_split / fork /
    move_pages / restore_ranges / drop_instance (``frame_audit`` enforces
    the invariant), and clones/moves inherit or max-propagate scales.

Device-side scale movement (dequant with src scales, requant with dst) is
covered end-to-end by the ``quant`` conformance cells
(tests/integration/engine_quant.py) and the reshard value test here.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from conftest import junk_past_length
from repro.configs import CONFIGS, reduced
from repro.core import dcp, migrate
from repro.core.page_table import SCALE_PENDING, GlobalPageTable
from repro.core.state import ClusterState
from repro.kernels import paged_attention as pa
from repro.kernels import quant, ref


# --------------------------------------------------------------------------- #
# format round-trip bounds
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("kv_dtype,rel", [
    # fp8 e4m3: 3 mantissa bits -> relative step 2^-4 of the value, so the
    # absolute error is <= amax * 2^-4 (asserted at 2x margin); int8
    # round-to-nearest: half a step = amax / 254 (asserted at one step)
    ("fp8", 1 / 8),
    ("int8", 1 / 127),
])
def test_quant_roundtrip_error_bound(kv_dtype, rel):
    rng = np.random.default_rng(0)
    # [P, page, H, d] pages at very different magnitudes: per-PAGE scaling
    # must keep the error proportional to each page's own amax
    x = rng.standard_normal((6, 16, 4, 32)).astype(np.float32)
    x *= np.float32(10.0) ** rng.integers(-3, 4, (6, 1, 1, 1))
    x = jnp.asarray(x)
    amax = jnp.max(jnp.abs(x.reshape(6, -1)), axis=1)
    scale = jnp.maximum(amax / quant.kv_qmax(kv_dtype), quant.SCALE_FLOOR)
    q = quant.quantize(x, scale[:, None, None, None], kv_dtype)
    assert q.dtype == quant.kv_storage_dtype(kv_dtype, jnp.bfloat16)
    back = quant.dequantize(q, scale[:, None, None, None])
    err = np.max(np.abs(np.asarray(back - x)), axis=(1, 2, 3))
    assert np.all(err <= np.asarray(amax) * rel), (kv_dtype, err / amax)


def test_bf16_is_not_quantized():
    assert not quant.is_quantized("bf16")
    assert quant.kv_storage_dtype("bf16", jnp.float32) == jnp.float32
    assert quant.kv_bytes_per_value("bf16") == 2.0
    assert quant.kv_bytes_per_value("fp8") == 1.0
    with pytest.raises(ValueError):
        quant.check_kv_dtype("fp16")


# --------------------------------------------------------------------------- #
# attention numerics per pool geometry
# --------------------------------------------------------------------------- #
def _quantized_pages(rng, P, page, H, d, kv_dtype):
    x = jnp.asarray(rng.standard_normal((P, page, H, d)), jnp.float32)
    amax = jnp.max(jnp.abs(x.reshape(P, -1)), axis=1)
    sc = jnp.maximum(amax / quant.kv_qmax(kv_dtype), quant.SCALE_FLOOR)
    return x, quant.quantize(x, sc[:, None, None, None], kv_dtype), sc


GEOMS = [
    # (name, Hq, Hkv, dk, dv) — the kernel sees the per-device sub-pool
    # view, so striping (ps) is exercised via frame indexing upstream;
    # grouped covers the kg > 1 merged-head view, mla the dv != dk latent
    ("gqa", 4, 4, 32, 32),
    ("grouped", 4, 2, 32, 32),
    ("mla", 4, 1, 64, 48),
]


@pytest.mark.parametrize("kv_dtype,tol", [("fp8", 0.35), ("int8", 0.08)])
@pytest.mark.parametrize("name,Hq,Hkv,dk,dv", GEOMS)
def test_quantized_paged_decode_error_bound(name, Hq, Hkv, dk, dv,
                                            kv_dtype, tol):
    rng = np.random.default_rng(1)
    N, P, page, MB = 4, 8, 16, 2
    q = jnp.asarray(rng.standard_normal((N, Hq, dk)), jnp.float32)
    k, kq, ks = _quantized_pages(rng, P, page, Hkv, dk, kv_dtype)
    v, vq, vs = _quantized_pages(rng, P, page, Hkv, dv, kv_dtype)
    bt = jnp.asarray(rng.permutation(P)[:N * MB].reshape(N, MB), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, MB * page + 1, (N,)), jnp.int32)

    exact, lse = ref.paged_decode_attention(q, k, v, bt, lengths)
    got, lse_q = ref.paged_decode_attention(q, kq, vq, bt, lengths,
                                            k_scale=ks, v_scale=vs)
    delta = float(np.max(np.abs(np.asarray(got - exact))))
    assert delta <= tol, (name, kv_dtype, delta)
    # the softmax normalizer moves with the same bound
    assert float(np.max(np.abs(np.asarray(lse_q - lse)))) <= tol


@pytest.mark.parametrize("N,P,page,MB,Hq,Hkv,dk,dv", [
    (4, 8, 16, 3, 4, 2, 32, 32),         # one compute block a row
    (3, 80, 16, 72, 16, 8, 128, 128),    # three blocks of 32 pages, MB % 32 != 0
    (3, 80, 16, 136, 40, 1, 288, 256),   # MLA shared latent (v_pages=None)
], ids=["oneblock", "multiblock", "mla-shared"])
def test_pallas_interpret_matches_ref_quantized(N, P, page, MB, Hq, Hkv, dk,
                                                dv):
    """The FUSED per-page dequant inside the Pallas kernel computes the
    same function as the reference's gather-then-dequant (same quantized
    operands, same scales) — interpret mode, so it runs anywhere.  Each
    page's scale is its own amax's, so a page of a multi-page block read
    with a neighbour's scale would show.  The kernel's table holds junk
    ids past each row's length (a NaN frame, ids out of range), whose
    pages and scales must weigh nothing."""
    rng = np.random.default_rng(2)
    shared = dk != dv
    q = jnp.asarray(rng.standard_normal((N, Hq, dk)), jnp.float32)
    _, kq, ks = _quantized_pages(rng, P, page, Hkv, dk, "fp8")
    kq, ks = kq.at[P - 1].set(jnp.nan), ks.at[P - 1].set(jnp.nan)
    if shared:
        vq, vs = None, None
    else:
        _, vq, vs = _quantized_pages(rng, P, page, Hkv, dv, "fp8")
        vq, vs = vq.at[P - 1].set(jnp.nan), vs.at[P - 1].set(jnp.nan)
    bt = jnp.asarray(rng.integers(0, P - 1, (N, MB)), jnp.int32)
    lengths = jnp.asarray(rng.integers(1, MB * page + 1, (N,)), jnp.int32)
    lengths = lengths.at[0].set(MB * page - page // 2)

    o_ref, l_ref = ref.paged_decode_attention(q, kq, vq, bt, lengths,
                                              k_scale=ks, v_scale=vs,
                                              v_dim=dv)
    o_pl, l_pl = pa.paged_decode_attention(
        q, kq, vq, junk_past_length(bt, lengths, page, P - 1), lengths,
        k_scale=ks, v_scale=vs, v_dim=dv, interpret=True)
    np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(l_pl), np.asarray(l_ref),
                               atol=1e-5, rtol=1e-5)


# --------------------------------------------------------------------------- #
# device scale movement: reshard preserves values across a re-quantization
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("kv_dtype,tol", [("fp8", 0.25), ("int8", 0.05)])
def test_reshard_moves_scales_with_values(kv_dtype, tol):
    """Scatter prefill KV into quantized pools on shard 0, move the tail to
    shard 1 through ``KVReshard``, and check the DEQUANTIZED destination
    values still match the original fp32 KV within quantization error —
    i.e. the re-shard dequantized with source scales and requantized with
    destination scales instead of copying raw codes across scale domains."""
    cfg = reduced(CONFIGS["tinyllama-1.1b"])
    I, page, L, tp = 2, 8, 37, 2
    dims = dcp.DecodeDims(M=4, S=0, N=4, MB=8, W=I, num_frames=65, page=page,
                          data_size=I, tp=tp, kv_dtype=kv_dtype)
    cl = ClusterState(num_instances=I, instances_per_node=I,
                      kv_capacity_tokens=64 * page, page_size=page)
    cl.page_table.allocate(0, {0: L})
    nb, hkv, hd = cfg.num_blocks, cfg.num_kv_heads, cfg.head_dim_
    na = sum(1 for b in cfg.block_pattern() if b["mixer"] == "attn")
    rng = np.random.default_rng(3)
    k_np = rng.standard_normal((nb, na, L, hkv, hd)).astype(np.float32)
    v_np = rng.standard_normal((nb, na, L, hkv, hd)).astype(np.float32)

    state = dcp.init_serve_state(cfg, dims, I, dtype=jnp.float32)
    assert "k_scale" in state and "v_scale" in state
    sc = migrate.PrefillScatter(cfg, dims, I)
    coords = migrate.prefill_coords(cl, 0, page, sc.ps)
    khs = sc.khs
    state = sc.scatter_kv(state, jnp.asarray(k_np[..., :khs, :]),
                          jnp.asarray(v_np[..., :khs, :]), coords)

    moved = 16
    src, dst = cl.page_table.move_pages(0, [(0, 1, moved)])
    rs = migrate.KVReshard(sc)
    state = rs(state, src, dst)
    cl.page_table.frame_audit()

    # decode the moved tokens back out of shard 1's pool
    kp = np.asarray(state["k_pool"], np.float32)
    ksc = np.asarray(state["k_scale"], np.float32)
    ps = sc.ps
    worst = 0.0
    for t in range(moved):
        i, f, o = (int(dst[0][t]), int(dst[1][t]), int(dst[2][t]))
        tok = L - moved + t
        for h in range(khs):
            c = (f % ps) * khs + h
            got = kp[:, :, i, c, f // ps, o] * \
                ksc[:, :, i, c, f // ps][..., None]
            worst = max(worst, float(np.max(np.abs(
                got - k_np[:, :, tok, h]))))
    assert worst <= tol, (kv_dtype, worst)


# --------------------------------------------------------------------------- #
# host lifecycle: the scale ledger tracks ownership exactly
# --------------------------------------------------------------------------- #
def test_frame_scale_ledger_lifecycle():
    pt = GlobalPageTable(3, frames_per_instance=8, page_size=4)
    pt.allocate(0, {0: 10, 1: 6})
    pt.frame_audit()
    # every claimed frame starts PENDING (device arrays own the numbers)
    for s in (0, 1):
        for f in pt.shard_frames(0, s):
            assert pt.frame_scale(s, f) == SCALE_PENDING

    # mirror a device-derived scale, then fork: the shared full frames keep
    # their entries, the CoW tail clone inherits the parent's scale
    tail0 = pt.shard_frames(0, 0)[-1]
    pt.set_frame_scale(0, tail0, 0.125)
    pt.fork_request(1, 0)
    pt.frame_audit()
    ctail = pt.shard_frames(1, 0)[-1]
    assert ctail != tail0
    assert pt.frame_scale(0, ctail) == 0.125

    # move_pages: the new dst frames inherit the max KNOWN contributor
    # scale (0.125 from the mirrored src tail), not PENDING
    for f in pt.shard_frames(0, 0):
        pt.set_frame_scale(0, f, 0.125)
    src, dst = pt.move_pages(0, [(0, 2, 6)])
    pt.frame_audit()
    for f in pt.shard_frames(0, 2):
        assert pt.frame_scale(2, f) == 0.125

    # cow_split of a shared frame: clone inherits, original keeps its entry
    shared = pt.shard_frames(1, 1)[0]
    assert pt.frame_shared(1, 1, shared)
    pt.set_frame_scale(1, shared, 2.0)
    pt.cow_split(1, 1, shared)
    pt.frame_audit()
    clone = pt.shard_frames(1, 1)[0]
    assert clone != shared
    assert pt.frame_scale(1, clone) == 2.0
    assert pt.frame_scale(1, shared) == 2.0    # rid 0 still owns it

    # decode appends into existing tail slack keep that frame's scale; the
    # append that GROWS a page creates a fresh PENDING entry, and pop
    # removes it with the frame
    slack = pt.shard_tail_slack(0, 2)
    for _ in range(slack):
        f, _ = pt.append_token(0, 2)
        assert pt.frame_scale(2, f) == 0.125
    f, _ = pt.append_token(0, 2)
    assert pt.frame_scale(2, f) == SCALE_PENDING
    for _ in range(slack + 1):
        pt.pop_token(0, 2)
    pt.frame_audit()

    # failure: the dead instance's entries purge with its ownership, and
    # recovery re-prefill allocates fresh PENDING frames
    lost = pt.drop_instance(2)
    pt.frame_audit()
    assert all(k[0] != 2 for k in pt._frame_scale)
    _, coords = pt.restore_ranges(0, {1: sum(l for _, l in lost[0])},
                                  lost[0])
    pt.frame_audit()
    for f in set(int(x) for x in coords[1]):
        assert pt.frame_scale(1, f) == SCALE_PENDING

    # teardown drains the ledger to empty alongside the refcounts
    pt.free_request(0)
    pt.free_request(1)
    pt.frame_audit()
    assert not pt._frame_scale


def test_frame_scale_rejects_unowned_and_nonpositive():
    pt = GlobalPageTable(1, frames_per_instance=4, page_size=4)
    pt.allocate(0, {0: 4})
    f = pt.shard_frames(0, 0)[0]
    with pytest.raises(AssertionError):
        pt.set_frame_scale(0, f + 1, 1.0)      # unowned frame
    with pytest.raises(AssertionError):
        pt.set_frame_scale(0, f, 0.0)          # scales strictly positive
    pt.set_frame_scale(0, f, 1.0)
    pt.frame_audit()
