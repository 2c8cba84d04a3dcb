"""DeepSeek-V3's equations in the program, at small sizes on the CPU.

The sigmoid group-limited router against a NumPy transcription of the
published gate; YaRN against the published formula; the expert layer that
holds a share of the router's experts (all shares together are the uncut
layer); the softmax path bitwise as it was; and the whole program (prefill,
then decode through the pools of ``NanoCPEngine``) against the plain fp32
reference (``models/reference.py``).
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.compat import shard_map
from repro.configs import CONFIGS, reduced
from repro.configs.base import YaRN
from repro.core import moe_parallel
from repro.models import init_params, layers, moe, reference, transformer
from repro.serving.engine import NanoCPEngine
from jax.sharding import PartitionSpec as P

DSV3 = CONFIGS["deepseek-v3"]


def _router_cfg(**kw):
    """E experts in n_group groups, top-k of the top topk_group groups."""
    base = dict(num_experts=16, num_experts_per_tok=4, n_group=4,
                topk_group=2, d_model=16)
    base.update(kw)
    return dataclasses.replace(reduced(DSV3), **base)


def _np_gate(logits, bias, n_group, topk_group, k, scaling):
    """DeepSeek-V3's ``noaux_tc`` gate (inference/model.py ``Gate``) in
    NumPy: ties go to the lower index."""
    scores = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    choice = scores + bias
    T, E = choice.shape
    g = choice.reshape(T, n_group, -1)
    gscore = np.sort(g, axis=-1)[..., -2:].sum(-1)
    w = np.zeros((T, k))
    idx = np.zeros((T, k), np.int64)
    for t in range(T):
        groups = sorted(range(n_group), key=lambda j: (-gscore[t, j], j))
        keep = np.zeros(E, bool)
        for j in groups[:topk_group]:
            keep[j * (E // n_group):(j + 1) * (E // n_group)] = True
        masked = np.where(keep, choice[t], -np.inf)
        top = sorted(range(E), key=lambda e: (-masked[e], e))[:k]
        idx[t] = top
        w[t] = scores[t, top] / scores[t, top].sum() * scaling
    return w, idx


def _route(cfg, logits, bias):
    """The program's router on chosen logits (an identity router matrix)."""
    E = cfg.num_experts
    p = {"router": jnp.eye(E, dtype=jnp.float32),
         "e_score_correction_bias": jnp.asarray(bias, jnp.float32)}
    w, idx = moe.router_topk(cfg, p, jnp.asarray(logits, jnp.float32))
    return np.asarray(w), np.asarray(idx)


def test_sigmoid_group_router_matches_the_published_gate():
    cfg = _router_cfg()
    rng = np.random.default_rng(0)
    logits = rng.standard_normal((64, 16)).astype(np.float32)
    bias = (0.3 * rng.standard_normal(16)).astype(np.float32)
    w, idx = _route(cfg, logits, bias)
    w_np, idx_np = _np_gate(logits, bias, 4, 2, 4, 2.5)
    np.testing.assert_array_equal(idx, idx_np)
    # f32 sigmoid against f64: a few ulps of the scaled weights
    np.testing.assert_allclose(w, w_np, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)


def test_router_group_ties_go_to_the_lower_group():
    cfg = _router_cfg(topk_group=1, num_experts_per_tok=2)
    # groups 1 and 2 tie on the sum of their two best biased scores
    logits = np.full((1, 16), -4.0, np.float32)
    logits[0, [5, 6]] = 2.0
    logits[0, [9, 10]] = 2.0
    w, idx = _route(cfg, logits, np.zeros(16, np.float32))
    assert sorted(idx[0]) == [5, 6]
    w_np, idx_np = _np_gate(logits, np.zeros(16), 4, 1, 2, 2.5)
    np.testing.assert_array_equal(np.sort(idx), np.sort(idx_np))


def test_router_bias_changes_the_choice_not_the_weight():
    cfg = _router_cfg(topk_group=4, num_experts_per_tok=2)
    logits = np.zeros((1, 16), np.float32)
    logits[0, :3] = [3.0, 2.0, 1.0]
    bias = np.zeros(16, np.float32)
    _, idx = _route(cfg, logits, bias)
    assert sorted(idx[0]) == [0, 1]
    bias[2] = 0.5                   # expert 2 now beats expert 1 on choice
    w, idx = _route(cfg, logits, bias)
    assert list(idx[0]) == [2, 0]
    s = 1.0 / (1.0 + np.exp(-np.array([1.0, 3.0])))   # unbiased scores
    np.testing.assert_allclose(w[0], s / s.sum() * 2.5, rtol=1e-6)


def test_yarn_frequencies_and_mscale_are_the_published_formula():
    y = DSV3.rope_scaling
    dim, base = DSV3.qk_rope_head_dim, DSV3.rope_theta
    # DeepSeek-V3 inference/model.py precompute_freqs_cis, in NumPy
    def corr_dim(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr_dim(32)), 0)
    high = min(math.ceil(corr_dim(1)), dim - 1)
    assert (low, high) == (10, 23) == y.correction_range(dim, base)
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    smooth = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    want = freqs / 40 * (1 - smooth) + freqs * smooth
    got = np.asarray(layers.rope_freqs(dim, base, y))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    mscale = 0.1 * math.log(40) + 1
    assert y.softmax_mult == pytest.approx(mscale ** 2) \
        == pytest.approx(1.8738, abs=1e-4)
    assert DSV3.mla_softmax_scale == pytest.approx(192 ** -0.5 * mscale ** 2)
    # no scaling: the frequencies and scale of every other configuration
    np.testing.assert_array_equal(
        np.asarray(layers.rope_freqs(dim, base)),
        np.asarray(1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                                   / dim))))
    assert CONFIGS["minicpm3-4b"].mla_softmax_scale == 96 ** -0.5


# ----------------------------------------------------------- held shares
def _share_cfg(first, held):
    """64 experts in 8 groups, top-8 of the top-4 groups: 32 shares of 2."""
    return dataclasses.replace(
        reduced(DSV3), d_model=32, moe_d_ff=16, num_experts=64,
        num_experts_per_tok=8, n_group=8, topk_group=4,
        held_experts_first=first, held_experts=held, capacity_factor=8.0)


def _share_params(p_all, first, held):
    p = dict(p_all)
    for k in ("wi_gate", "wi_up", "wo"):
        p[k] = p_all[k][first:first + held]
    return p


def _decode_ffn(cfg, p, x):
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    fn = shard_map(lambda p, x: moe_parallel.moe_decode_ffn(
        cfg, p, x, axis_size=1), mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), p), P()), out_specs=P(),
        check_vma=False)
    return jax.jit(fn)(p, x)


@pytest.mark.parametrize("path", ["prefill", "decode"])
def test_all_shares_sum_to_the_uncut_layer(path):
    uncut = _share_cfg(0, 0)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     moe.make_moe_params(jax.random.PRNGKey(3), uncut))
    p["e_score_correction_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(4), (64,))
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 32), jnp.float32)
    # prefill: all 32 shares of 2; decode (one compile a share): 8 of 8
    held = 2 if path == "prefill" else 8
    run = ((lambda c, q: moe.moe_ffn(c, q, x)) if path == "prefill"
           else (lambda c, q: _decode_ffn(c, q, x)))
    with jax.default_matmul_precision("highest"):
        want = reference._moe(uncut, p, x)             # the uncut reference
        shared = layers.apply_mlp(uncut, p["shared"], x)
        parts = [run(_share_cfg(f, held), _share_params(p, f, held))
                 for f in range(0, 64, held)]
    assert all(_share_cfg(f, held).holds_share for f in range(0, 64, held))
    got = sum(q - shared for q in parts) + shared
    # 32 partial sums in f32 against one: rounding of the summation order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(run(uncut, p)), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # a share computes only its own experts: no part is the whole layer
    assert all(not np.allclose(np.asarray(q), np.asarray(want)) for q in parts)


def test_decode_counts_the_assignments_each_held_expert_got():
    cfg = _share_cfg(6, 4)
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     moe.make_moe_params(jax.random.PRNGKey(3), cfg))
    x = jax.random.normal(jax.random.PRNGKey(6), (10, 32), jnp.float32)
    live = jnp.asarray([1] * 7 + [0] * 3, jnp.int32)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    fn = shard_map(lambda p, x, m: moe_parallel.moe_decode_ffn(
        cfg, p, x, axis_size=1, load_mask=m), mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(), p), P(), P()),
        out_specs=(P(), P()), check_vma=False)
    _, load = jax.jit(fn)(p, x, live)
    _, idx = moe.router_topk(cfg, p, x)
    idx = np.asarray(idx)[:7]
    want = [(idx == 6 + e).sum() for e in range(4)]
    np.testing.assert_array_equal(np.asarray(load), want)


# ---------------------------------------------- the softmax path as it was
def _router_before(cfg, router_w, x):
    logits = x.astype(jnp.float32) @ router_w
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, idx.astype(jnp.int32)


def _group_before(topk_idx, num_experts, capacity):
    T, k = topk_idx.shape
    flat_e = topk_idx.reshape(-1)
    flat_t = (jnp.arange(T * k, dtype=jnp.int32) // k)
    order = jnp.argsort(flat_e, stable=True)
    se, st = flat_e[order], flat_t[order]
    first_of = jnp.searchsorted(se, jnp.arange(num_experts), side="left")
    pos_in_e = jnp.arange(T * k, dtype=jnp.int32) - first_of[se].astype(jnp.int32)
    keep = pos_in_e < capacity
    slot = jnp.where(keep, se * capacity + pos_in_e, num_experts * capacity)
    src = jnp.full((num_experts * capacity + 1,), T, jnp.int32)
    src = src.at[slot].set(st, mode="drop").at[-1].set(T)
    slot_of = jnp.full((T * k,), num_experts * capacity, jnp.int32)
    slot_of = slot_of.at[order].set(jnp.where(keep, slot,
                                              num_experts * capacity))
    return src[:-1], slot_of.reshape(T, k)


def _moe_before(cfg, p, x):
    T, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    C = max(1, math.ceil(T * k / E * cfg.capacity_factor))
    w, idx = _router_before(cfg, p["router"], x)
    src, slot_of = _group_before(idx, E, C)
    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
    ein = x_pad[src].reshape(E, C, D)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein, p["wi_gate"]))
    u = jnp.einsum("ecd,edf->ecf", ein, p["wi_up"])
    eout = jnp.einsum("ecf,efd->ecd", g * u, p["wo"]).reshape(E * C, D)
    out_pad = jnp.concatenate([eout, jnp.zeros((1, D), eout.dtype)])
    out = jnp.einsum("tk,tkd->td", w.astype(eout.dtype), out_pad[slot_of])
    return out.astype(x.dtype)


@pytest.mark.parametrize("cf", [8.0, 1.25])     # dropless, and dropping
def test_softmax_path_is_bitwise_unchanged(cf):
    cfg = reduced(CONFIGS["phi3.5-moe-42b-a6.6b"], capacity_factor=cf)
    p = moe.make_moe_params(jax.random.PRNGKey(7), cfg)
    x = jax.random.normal(jax.random.PRNGKey(8), (40, cfg.d_model),
                          jnp.bfloat16)
    w0, i0 = _router_before(cfg, p["router"], x)
    w1, i1 = moe.router_topk(cfg, p, x)
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    want = jax.jit(lambda p, x: _moe_before(cfg, p, x))(p, x)
    got = jax.jit(lambda p, x: moe.moe_ffn(cfg, p, x))(p, x)
    np.testing.assert_array_equal(np.asarray(got.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    got_d = _decode_ffn(cfg, p, x)
    np.testing.assert_array_equal(np.asarray(got_d.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))


# ------------------------------------ the program against the fp32 reference
def tiny_cfg():
    """1 dense + 2 MoE layers, 16 experts in 4 groups (top-2 of the top-2
    groups), 8 held from id 4, a shared expert, YaRN scaled so that every
    rotary dim at these lengths sees it."""
    return dataclasses.replace(
        reduced(DSV3), held_experts_first=4, held_experts=8,
        capacity_factor=16 / 2,
        rope_scaling=YaRN(factor=40.0, original_max_position=64))


def tiny_params(cfg, seed=0):
    """f32 weights; norms, the latent norms and the selection bias drawn
    away from their init so that a skipped one shows in the logits."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          init_params(jax.random.PRNGKey(seed), cfg))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def jitter(path, a):
        name = jax.tree_util.keystr(path)
        if any(s in name for s in ("scale", "q_norm", "kv_norm")):
            return a + 0.1 * jax.random.normal(next(keys), a.shape)
        if "e_score_correction_bias" in name:
            return 0.05 * jax.random.normal(next(keys), a.shape)
        return a
    return jax.tree_util.tree_map_with_path(jitter, params)


def test_program_prefill_and_paged_decode_match_the_reference():
    cfg = tiny_cfg()
    assert [k["ffn"] for k in cfg.layer_kinds()] == ["dense", "moe", "moe"]
    assert [s[0] for s in cfg.segments()] == ["dense_blocks", "blocks"]
    params = tiny_params(cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n in (70, 45)]

    # prefill: the program's forward (flash path) against the reference
    want = reference.forward_last_logits(cfg, params, prompts[0], last=6)
    with jax.default_matmul_precision("highest"):
        got, _ = transformer.forward(cfg, params,
                                     jnp.asarray(prompts[0])[None])
    # f32 on both sides; the program's MoE sums experts in another order
    np.testing.assert_allclose(np.asarray(got[0, -6:]), want, atol=2e-4,
                               rtol=2e-4)

    # decode through the engine's pools (paged latent, absorbed MLA)
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    eng = NanoCPEngine(cfg, params, mesh, num_instances=1,
                       instances_per_node=1, kv_capacity_tokens=1024,
                       page_size=16, keep_logits=True)
    rids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
    with jax.default_matmul_precision("highest"):
        eng.run(max_iters=50)
    for rid, prompt in zip(rids, prompts):
        toks = eng.results[rid].tokens
        steps = eng.step_logits[rid]
        assert len(toks) == 5 and len(steps) == 4
        seq = list(prompt) + list(toks)
        ref = reference.forward_last_logits(cfg, params, seq[:-1], last=5)
        # rows 1.. are the decode steps (row 0 is the prefill's)
        np.testing.assert_allclose(
            np.stack([np.asarray(s) for s in steps]), ref[1:],
            atol=2e-4, rtol=2e-4)
        assert toks == [int(t) for t in ref.argmax(-1)]
    # the MoE layer's parts are named inside the decode step's ffn scope
    hlo = eng.aot.executable(eng.last_bucket).as_text()
    for scope in ("router", "routed_experts", "shared_expert"):
        assert f"/ffn/{scope}/" in hlo, scope
    load = eng.expert_load()
    assert load.shape == (8,)
    # 2 MoE layers x top-2 x (2 rows x 4 decode steps), of which the held
    # 8 of 16 experts get some
    assert 0 < load.sum() <= 2 * 2 * 8


def test_only_a_share_carries_the_load_counter():
    from repro.core import dcp
    dims = dcp.DecodeDims(M=2, S=0, N=2, MB=4, W=1, num_frames=9, page=8,
                          data_size=1, tp=1)
    st = dcp.init_serve_state(tiny_cfg(), dims, 1)
    assert st["expert_load"].shape == (1, 8)
    for arch in ("phi3.5-moe-42b-a6.6b", "minicpm3-4b", "deepseek-v3"):
        assert "expert_load" not in dcp.init_serve_state(
            reduced(CONFIGS[arch]), dims, 1)
