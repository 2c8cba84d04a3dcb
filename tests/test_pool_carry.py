"""The decode step's in-place pool carry and MLA's whole-tile latent pools.

``init_serve_state`` stores a latent pool whose width is no multiple of
128 lanes at whole 128-lane tiles (the reduced configs' 32 + 8 = 40 lanes
at 128), and every writer fills only the first lanes of a row: the pad
lanes stay zero through prefill scatters, decode appends and re-shards.
``build_decode_step`` carries every layer's pool as one run of frames and
must decode exactly as before: these cases hold it to the fp32 reference
for an MLA, a quantized GQA and an SSM-hybrid configuration.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs import CONFIGS, reduced
from repro.core import dcp, migrate
from repro.core.state import ClusterState
from repro.kernels.paged_attention import lane_tiles
from repro.models import init_params, reference, transformer
from repro.serving.engine import NanoCPEngine

MLA = "minicpm3-4b"


def _latent_width(cfg) -> int:
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_latent_pad_lanes_stay_zero_through_scatter_and_reshard(kv_dtype):
    """Prefill scatter, then an escalation move of the tail to instance 1
    and a migration back: the pad lanes stay zero throughout, and (f32
    pools) the first dk lanes hold what the numpy loader writes into an
    unpadded pool, at the original coordinates after the round trip."""
    cfg = reduced(CONFIGS[MLA])
    dk = _latent_width(cfg)
    I, page, L, tp = 2, 8, 37, 2
    dims = dcp.DecodeDims(M=4, S=0, N=4, MB=8, W=I, num_frames=65, page=page,
                          data_size=I, tp=tp, kv_dtype=kv_dtype)
    cl = ClusterState(num_instances=I, instances_per_node=I,
                      kv_capacity_tokens=64 * page, page_size=page)
    cl.page_table.allocate(0, {0: L})
    nb = cfg.num_blocks
    rng = np.random.default_rng(5)
    lat = rng.standard_normal((nb, 1, L, dk)).astype(np.float32)

    state = dcp.init_serve_state(cfg, dims, I, dtype=jnp.float32)
    assert dk % 128 and state["kv_pool"].shape[-1] == lane_tiles(dk)
    sc = migrate.PrefillScatter(cfg, dims, I)
    coords = migrate.prefill_coords(cl, 0, page, sc.ps)
    state = sc.scatter_kv(state, jnp.asarray(lat[:, :, :, None]), None,
                          coords)

    def pad_is_zero(st):
        return not np.any(np.asarray(st["kv_pool"], np.float32)[..., dk:])

    assert pad_is_zero(state)
    if kv_dtype == "bf16":
        # the numpy loader into an unpadded pool of the same frames
        shape = state["kv_pool"].shape[:-1] + (dk,)
        want = {"kv_pool": np.zeros(shape, np.float32)}
        migrate.load_prefill_kv(cfg, cl, dims, want, 0,
                                [(lat[b, 0, :, :cfg.kv_lora_rank],
                                  lat[b, 0, :, cfg.kv_lora_rank:])
                                 for b in range(nb)])
        first = np.asarray(state["kv_pool"])[..., :dk].copy()
        np.testing.assert_array_equal(first, want["kv_pool"])

    rs = migrate.KVReshard(sc)
    moved = 16
    src, dst = cl.page_table.move_pages(0, [(0, 1, moved)])
    state = rs(state, src, dst)
    assert pad_is_zero(state)
    back_src, back_dst = cl.page_table.move_pages(0, [(1, 0, moved)])
    state = rs(state, back_src, back_dst)
    cl.page_table.frame_audit()
    assert pad_is_zero(state)
    if kv_dtype == "bf16":
        # every token of the request sits where the loader put it
        after = np.asarray(state["kv_pool"], np.float32)
        for t in range(L):
            i, c, f, o = (int(x) for x in migrate.prefill_coords(
                cl, 0, page, sc.ps)[:, t])
            np.testing.assert_array_equal(
                after[:, :, i, c, f, o, :dk], lat[:, :, t])


def _engine(cfg, params, kv_dtype="bf16"):
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    return NanoCPEngine(cfg, params, mesh, num_instances=1,
                        instances_per_node=1, kv_capacity_tokens=1024,
                        page_size=16, keep_logits=True, kv_dtype=kv_dtype)


def _f32_params(cfg, seed=0):
    return jax.tree.map(lambda x: x.astype(jnp.float32),
                        init_params(jax.random.PRNGKey(seed), cfg))


def _decode(eng, prompts, new=5):
    rids = [eng.add_request(p, max_new_tokens=new) for p in prompts]
    with jax.default_matmul_precision("highest"):
        eng.run(max_iters=60)
    out = []
    for rid, prompt in zip(rids, prompts):
        toks = eng.results[rid].tokens
        steps = np.stack([np.asarray(s, np.float32)
                          for s in eng.step_logits[rid]])
        assert len(toks) == new and len(steps) == new - 1
        out.append((list(prompt) + list(toks), steps))
    return out


def test_flat_carry_mla_decode_matches_the_fp32_reference():
    """MLA (two latent layers over a 40-lane latent stored at 128): every
    decode step's logits match the fp32 reference, greedy tokens agree,
    and the appends leave the pad lanes zero."""
    cfg = reduced(CONFIGS[MLA], vocab_size=128)
    params = _f32_params(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, (n,)) for n in (70, 45)]
    eng = _engine(cfg, params)
    for seq, steps in _decode(eng, prompts):
        ref = reference.forward_last_logits(cfg, params, seq[:-1], last=5)
        np.testing.assert_allclose(steps, ref[1:], atol=2e-4, rtol=2e-4)
        assert seq[-5:] == [int(t) for t in ref.argmax(-1)]
    pool = np.asarray(eng.state["kv_pool"])
    assert pool.shape[-1] == 128
    assert np.any(pool[..., :_latent_width(cfg)])
    assert not np.any(pool[..., _latent_width(cfg):])


def test_flat_carry_int8_gqa_decode_matches_the_fp32_reference():
    """GQA with int8 pools and their per-page scales (two kv heads on one
    device, a head-grouped pool): teacher-forced on the engine's own
    transcript, every decode step's logits stay within the int8 bound of
    the quantized conformance cell (``engine_quant.py``), and each token
    is the reference's argmax outside a near-tie."""
    tol = 0.5
    cfg = reduced(CONFIGS["tinyllama-1.1b"], vocab_size=128)
    params = _f32_params(cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, (n,)) for n in (50, 33)]
    eng = _engine(cfg, params, kv_dtype="int8")
    assert "k_scale" in eng.state
    for seq, steps in _decode(eng, prompts):
        ref = reference.forward_last_logits(cfg, params, seq[:-1], last=5)
        assert np.max(np.abs(steps - ref[1:])) <= tol
        for j in range(1, 5):
            top2 = np.sort(ref[j])[-2:]
            if seq[-5 + j] != int(ref[j].argmax()):
                assert top2[1] - top2[0] <= tol


def test_flat_carry_ssm_hybrid_decode_matches_the_fp32_forward():
    """Jamba's hybrid blocks (one attention layer among seven SSM layers,
    MoE): the pools ride the flat carry while the SSM states keep their
    per-block carry; decode logits match the fp32 forward pass."""
    cfg = reduced(CONFIGS["jamba-v0.1-52b"], vocab_size=128,
                  capacity_factor=8.0)
    params = _f32_params(cfg)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 128, (n,)) for n in (21, 38)]
    eng = _engine(cfg, params)
    for seq, steps in _decode(eng, prompts, new=4):
        with jax.default_matmul_precision("highest"):
            logits, _ = transformer.forward(cfg, params,
                                            jnp.asarray(seq[:-1])[None])
        ref = np.asarray(logits[0, -3:], np.float32)
        np.testing.assert_allclose(steps, ref, atol=1e-3, rtol=1e-3)
        assert seq[-3:] == [int(t) for t in ref.argmax(-1)]
