"""``archs/deepseek_v3.py``: weights, reference, counts and the program
check, at a tiny size on the CPU.

The tiny configuration is a copy of ``configs/deepseek_v3.json`` with every
size cut (1 dense + 2 MoE layers, 16 routed experts in 4 groups, 4 held
from id 4); the program gets the same sizes as overrides.  The weights
must have the program's layout; the reference must agree with the
program's own fp32 reference and forward, and the fp8 control must not;
the counts at the published share are written out by hand; and a tiny
closed set served by ``NanoCPEngine`` through the harness is ``correct``.
"""
import copy
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

from chipbench import harness, spec                       # noqa: E402

CELL = "deepseekv3.longdecode.1c"

TINY = dict(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=4,
            num_hidden_layers=3, first_k_dense_replace=1, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=4, n_group=4, topk_group=2,
            num_experts_per_tok=2, vocab_size=256)
TINY_PROGRAM = dict(num_layers=3, first_k_dense=1, d_model=64, num_heads=4,
                    num_kv_heads=4, d_ff=128, moe_d_ff=32, q_lora_rank=32,
                    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                    v_head_dim=16, num_experts=16, held_experts_first=4,
                    held_experts=4, num_experts_per_tok=2, n_group=4,
                    topk_group=2, vocab_size=256, capacity_factor=8.0)


@pytest.fixture(scope="module")
def arch():
    return spec.architecture("deepseek_v3")


def tiny_config() -> dict:
    with open(os.path.join(spec.BENCH_DIR, "configs",
                           "deepseek_v3.json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg.update(TINY)
    cfg["published"] = dict(cfg["published"], n_routed_experts=16)
    cfg["deployment"] = dict(cfg["deployment"], held_experts_first=4)
    cfg["program"]["overrides"] = dict(TINY_PROGRAM)
    cfg["kv_capacity_tokens"] = 2048
    return cfg


def tiny_cell(limit: float = 0.1) -> spec.Cell:
    real = spec.load_cell(CELL)
    cfg = tiny_config()
    tr = copy.deepcopy(real.traffic)
    tr.update(growth_tokens=400, max_new_tokens=400)
    tr["prompt"]["intervals"] = [[300, 600, 1.0]]
    return spec.Cell("tiny.deepseek_v3.sessions", dict(real.entry), cfg,
                     real.arch.from_config(cfg), tr,
                     {"max_logit_gap": limit, "router_margin_min": 0.005},
                     real.end_to_end, real.per_layer, real.arch)


def test_weights_have_the_programs_layout(arch):
    from repro.models import transformer
    cell = tiny_cell()
    params = arch.make_params(cell.model, 2**31 + 5)
    pcfg = harness.program_config(cell)
    want = jax.eval_shape(lambda: transformer.init_params(
        jax.random.PRNGKey(0), pcfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert jax.tree.structure(got) == jax.tree.structure(
        jax.tree.map(lambda a: (a.shape, a.dtype), want))
    assert got == jax.tree.map(lambda a: (a.shape, a.dtype), want)
    # the same seed gives the same weights, another seed others
    again = arch.make_params(cell.model, 2**31 + 5)
    other = arch.make_params(cell.model, 2**31 + 6)
    w = params["blocks"]["layers"][0]["ffn"]["e_score_correction_bias"]
    assert np.array_equal(w, again["blocks"]["layers"][0]["ffn"][
        "e_score_correction_bias"])
    assert not np.array_equal(w, other["blocks"]["layers"][0]["ffn"][
        "e_score_correction_bias"])


def test_reference_agrees_with_the_programs_and_the_control_does_not(arch):
    from repro.models import reference, transformer
    cell = tiny_cell()
    m = cell.model
    params = arch.make_params(m, 2**31 + 7)
    pcfg = harness.program_config(cell)
    toks = np.random.default_rng(0).integers(0, m.vocab_size, 700)
    got, margin = arch.logits_and_margins(m, params, toks, 690, 8)
    assert got.shape == (8, m.padded_vocab)
    # the program's own plain reference and its forward, at f32 weights:
    # three transcriptions of the same equations, f32 rounding apart
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    want = reference.forward_last_logits(pcfg, f32, toks[:698], last=8)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    with jax.default_matmul_precision("highest"):
        fwd, _ = transformer.forward(pcfg, f32, jnp.asarray(toks)[None])
    np.testing.assert_allclose(got, np.asarray(fwd[0, 690:698]), atol=2e-4,
                               rtol=2e-4)
    assert np.all(np.isfinite(margin)) and np.all(margin >= 0)
    ctrl, _ = arch.logits_and_margins(m, params, toks, 690, 8, "fp8")
    assert np.max(np.abs(ctrl - got)) > 10 * 2e-4


def test_router_margin_is_the_smaller_of_the_two_gaps(arch):
    m = tiny_cell().model
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.standard_normal((5, m.d_model)), jnp.float32)
    p = {"router": jnp.asarray(rng.standard_normal((m.d_model, 16)) / 8,
                               jnp.float32),
         "e_score_correction_bias": jnp.asarray(
             0.05 * rng.standard_normal(16), jnp.float32)}
    with jax.default_matmul_precision("highest"):
        gate, margin = arch.route(m, p, h, None)
    s = 1 / (1 + np.exp(-np.asarray(h, np.float64) @ np.asarray(p["router"])))
    c = s + np.asarray(p["e_score_correction_bias"])
    for t in range(5):
        g = np.sort(c[t].reshape(4, 4), axis=-1)[:, -2:].sum(-1)
        order = sorted(range(4), key=lambda j: (-g[j], j))
        inside = np.concatenate([c[t, 4 * j:4 * j + 4] for j in order[:2]])
        top = np.sort(inside)[::-1]
        want = min(top[1] - top[2], g[order[1]] - g[order[2]])
        assert margin[t] == pytest.approx(want, abs=1e-5)
        chosen = np.argsort(-np.where(np.isin(np.arange(16) // 4, order[:2]),
                                      c[t], -np.inf))[:2]
        w = s[t, chosen] / s[t, chosen].sum() * 2.5
        np.testing.assert_allclose(np.asarray(gate)[t, chosen], w, rtol=1e-5)
        assert np.count_nonzero(np.asarray(gate)[t]) == 2


def test_counts_at_the_published_share(arch):
    m = spec.load_cell(CELL).model
    D, H = 7168, 128
    attn = D * 1536 + 1536 * H * 192 + D * 576 + H * 128 * D
    moe = D * 256 + (8 * 8 / 256 + 1) * 3 * D * 2048
    assert arch.linear_per_token(m) == 2.0 * (5 * attn + 3 * D * 18432
                                              + 4 * moe)
    assert arch.head(m) == 2.0 * D * 16256
    # per token and layer: 2 * 128 * (512 + 64 + 512) operations over
    # (512 + 64) bf16 values, 242 operations a byte
    ops, byt = arch.decode_attention(m, 1000)
    assert (ops, byt) == (5 * 1000 * 2.0 * H * 1088, 5 * 1000 * 576 * 2)
    absorb = 2.0 * 5 * H * 512 * (128 + 128)
    assert arch.decode_token(m, 1000) == arch.linear_per_token(m) + absorb \
        + ops + arch.head(m)
    pairs = 100 * 101 / 2
    assert arch.prefill_attention(m, 100) == (
        5 * 2.0 * H * 320 * pairs, 5 * 100 * H * 640 * 2)
    assert arch.prefill(m, 100) == 100 * (
        arch.linear_per_token(m) + 2.0 * 5 * 512 * H * 256) \
        + arch.prefill_attention(m, 100)[0] + arch.head(m)


def test_program_is_held_to_the_file():
    cell = spec.load_cell(CELL)
    pcfg = harness.program_config(cell)
    assert (pcfg.num_experts, pcfg.num_held_experts, pcfg.first_k_dense,
            pcfg.num_layers) == (256, 8, 1, 5)
    for key, value in (("n_group", 4), ("routed_scaling_factor", 1.0),
                       ("first_k_dense_replace", 2),
                       ("n_routed_experts", 16)):
        cfg = copy.deepcopy(cell.config)
        cfg[key] = value
        bad = dataclasses.replace(cell, config=cfg,
                                  model=cell.arch.from_config(cfg))
        with pytest.raises(ValueError):
            harness.program_config(bad)
    cfg = copy.deepcopy(cell.config)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], factor=4)
    bad = dataclasses.replace(cell, config=cfg,
                              model=cell.arch.from_config(cfg))
    with pytest.raises(ValueError, match="rope_scaling_params"):
        harness.program_config(bad)


def test_tiny_closed_set_serves_correct_and_the_control_does_not():
    """One run with the control on: ``correct`` reads the fp8 control,
    which fails, and the program's own reading (``_info``) passes.  At
    this size (d_model 64, 2 of 8 candidate experts) bf16 rounding of the
    router's input flips the choice up to a margin of about 0.02 (CPU
    readings, three seeds: program gaps up to 0.49 at margins under 0.03,
    0.018-0.042 from 0.03 up), so positions under 0.03 are left out; the
    control still reads 1.15-2.75 there."""
    cell = tiny_cell()
    cell.limits["router_margin_min"] = 0.03
    out = harness.run(cell, 2**31 + 40, 1.0, False, control=True,
                      allow_cpu=True)
    assert out["_info"]["program_max_logit_gap"] <= 0.1, out["_info"]
    assert not out["correct"]
    assert out["check"]["max_logit_gap"]["value"] > 0.1
