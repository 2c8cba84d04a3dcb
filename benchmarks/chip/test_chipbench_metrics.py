"""Metric arithmetic: end-to-end numbers from token stamps, the FLOP and
byte counts on hand-worked cases, and the per-layer readers on a small
synthetic run."""
import math

import numpy as np
import pytest

from chipbench import flops, spec, stats
from chipbench.client import StepRecord
from chipbench.model import Model


def _req(due, stamps, in_window=True):
    return {"due": due, "stamps": stamps, "in_window": in_window}


def test_end_to_end_from_stamps():
    reqs = [_req(0.0, [0.1, 0.2, 0.3]),            # ttft 0.1, tpot 0.1
            _req(1.0, [1.5, 1.7]),                 # ttft 0.5, tpot 0.2
            _req(2.0, [2.3]),                      # ttft 0.3, no tpot
            _req(3.0, []),                         # cut off: waited 7.0
            _req(9.0, [9.5, 9.6], in_window=False)]
    out = stats.end_to_end(reqs, (0.0, 5.0), give_up=10.0)
    assert out["requests"] == 4
    assert out["requests_without_first_token"] == 1
    assert out["tpot_requests"] == 2
    ttfts = [0.1, 0.5, 0.3, 7.0]
    assert out["ttft_p95_ms"] == pytest.approx(np.percentile(ttfts, 95) * 1e3)
    assert out["ttft_p50_ms"] == pytest.approx(400.0)
    assert out["tpot_p50_ms"] == pytest.approx(150.0)
    # tokens stamped inside [0, 5): 3 + 2 + 1; the 9.5/9.6 ones are not
    assert out["output_tokens_per_s"] == pytest.approx(6 / 5.0)


def test_cut_off_request_counts_with_its_wait():
    reqs = [_req(0.0, [0.1]) for _ in range(19)] + [_req(4.0, [])]
    out = stats.end_to_end(reqs, (0.0, 5.0), give_up=64.0)
    assert out["ttft_p95_ms"] > 100.0 and math.isfinite(out["ttft_p95_ms"])


GQA = Model(name="g", attention="gqa", norm="rmsnorm", norm_eps=1e-6,
            num_layers=2, d_model=8, num_heads=2, num_kv_heads=1, head_dim=4,
            d_ff=16, vocab_size=100, rope_theta=1e4, num_experts=4,
            num_experts_per_tok=2, moe_d_ff=16)
MLA = Model(name="m", attention="mla", norm="rmsnorm", norm_eps=1e-6,
            num_layers=1, d_model=8, num_heads=2, num_kv_heads=2, head_dim=0,
            d_ff=16, vocab_size=128, rope_theta=1e4, q_lora_rank=4,
            kv_lora_rank=6, qk_nope_head_dim=3, qk_rope_head_dim=2,
            v_head_dim=5)


def test_counts_by_hand_gqa_moe():
    # per layer: q 8*8, k 8*4, v 8*4, o 8*8 = 192; router 8*4 = 32;
    # two experts 2 * 3 * 8 * 16 = 768 -> (192 + 32 + 768) * 2 * 2 layers
    assert flops.linear_per_token(GQA) == 2 * 2 * (192 + 32 + 768)
    assert GQA.padded_vocab == 128
    assert flops.head(GQA) == 2 * 8 * 128
    # decode over 10 cached tokens: 4 * H * hd * ctx per layer,
    # K and V of 1 kv head x 4 dims at 2 bytes per layer
    ops, byt = flops.decode_attention(GQA, 10)
    assert ops == 2 * 4 * 2 * 4 * 10
    assert byt == 2 * 10 * 2 * 1 * 4 * 2
    # causal prefill of S=3: 6 query-key pairs
    ops, byt = flops.prefill_attention(GQA, 3)
    assert ops == 2 * 4 * 2 * 4 * 6
    assert byt == 2 * 3 * (8 + 8 + 8) * 2
    assert flops.prefill(GQA, 3) == 3 * flops.linear_per_token(GQA) \
        + ops + flops.head(GQA)


def test_counts_by_hand_mla():
    # q_a 8*4, q_b 4*2*5, kv_a 8*8, o 2*5*8 = 32 + 40 + 64 + 80 = 216;
    # dense FFN 3*8*16 = 384
    assert flops.linear_per_token(MLA) == 2 * (216 + 384)
    ops, byt = flops.decode_attention(MLA, 7)
    # absorbed: scores over kv_lora + rope = 8, values over kv_lora = 6
    assert ops == 2 * 2 * 8 * 7 + 2 * 2 * 6 * 7
    assert byt == 7 * 8 * 2
    absorb = 2 * 2 * 6 * (3 + 5)
    assert flops.decode_token(MLA, 7) == flops.linear_per_token(MLA) \
        + absorb + ops + flops.head(MLA)


def test_roofline_takes_the_larger_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(1000.0, 5.0, peak) == 10.0
    assert flops.roofline_s(10.0, 50.0, peak) == 5.0


def _step(ctx, spans, prefilled=(), traced=False):
    return StepRecord(len(ctx), tuple(ctx), spans, tuple(prefilled),
                      traced)


def test_program_span_readers():
    steps = [_step([10, 20], {"step_us": 1000.0, "harvest_us": 300.0,
                              "prefill_us": 0.0, "lower_us": 0.0,
                              "dispatch_us": 0.0}),
             _step([11, 21], {"step_us": 5000.0, "harvest_us": 100.0,
                              "prefill_us": 4000.0, "lower_us": 0.0,
                              "dispatch_us": 0.0}, prefilled=(3,))]
    peak = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e9}
    run = {"arch": spec.architecture(), "model": GQA, "peaks": peak,
           "steps": steps, "trace": None}
    host = spec.reader("engine.host_ms_per_step")(run)
    assert host == pytest.approx(((1000 - 300) + (5000 - 100 - 4000))
                                 / 2 / 1e3)
    assert spec.reader("prefill.ms_per_ktok")(run) == pytest.approx(
        4.0 / (3 / 1e3))
    assert spec.reader("prefill.step_share_pct")(run) == pytest.approx(
        100 * 4000 / 6000)
    want = sum(flops.decode_token(GQA, c) for c in (10, 20, 11, 21))
    assert spec.reader("decode_step.mfu")(run) == pytest.approx(
        100 * want / (2000e-6 * 1e9))
    assert spec.reader("prefill.mfu")(run) == pytest.approx(
        100 * flops.prefill(GQA, 3) / (4000e-6 * 1e9))
    # no trace: the trace readers find nothing and say nothing
    for name in ("decode_step.device_ms", "paged_attn_roofline",
                 "flash_prefill_roofline", "device.idle_pct"):
        assert spec.reader(name)(run) is None
