"""Tiny configurations and cells for the chip benchmark's CPU tests.

The tests run the harness on XLA's CPU backend at these sizes; nothing
here is ever a benchmark cell.
"""
from __future__ import annotations

import copy
import json
import os

from chipbench import model as model_mod
from chipbench import spec

HERE = os.path.dirname(os.path.abspath(__file__))

GQA_MOE = dict(hidden_size=64, intermediate_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               num_local_experts=4, vocab_size=256)
GQA_MOE_PROGRAM = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                       head_dim=16, d_ff=64, moe_d_ff=64, num_experts=4,
                       vocab_size=256, capacity_factor=2.0)
MLA = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
           num_key_value_heads=4, num_hidden_layers=2, q_lora_rank=32,
           kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
           v_head_dim=16, vocab_size=256)
MLA_PROGRAM = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                   d_ff=128, q_lora_rank=32, kv_lora_rank=32,
                   qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                   vocab_size=256)


def _json(rel: str) -> dict:
    with open(os.path.join(HERE, rel)) as f:
        return json.load(f)


def config(kind: str) -> dict:
    """A tiny copy of a real configuration file, same keys."""
    if kind == "mla":
        cfg = copy.deepcopy(_json("configs/minicpm3_4b.json"))
        cfg.update(MLA)
        cfg["program"]["overrides"] = dict(MLA_PROGRAM)
    else:
        cfg = copy.deepcopy(_json("configs/phi3_5_moe.json"))
        cfg.update(GQA_MOE)
        cfg["program"]["overrides"] = dict(GQA_MOE_PROGRAM)
    cfg["kv_capacity_tokens"] = 4096
    return cfg


def cell(kind: str, traffic_kind: str, limit: float) -> spec.Cell:
    """A tiny cell: ``kind`` gqa_moe | mla, ``traffic_kind`` chat |
    sessions; the metrics of ``BENCHMARK.json`` that such a cell reports."""
    cfg = config(kind)
    if traffic_kind == "chat":
        tr = _json("traffic/github_mixed.json")
        tr.update(rate_per_s=2.0, tail_s=1.0,
                  output={"lo": 2, "hi": 4})
        tr["prompt"] = {"dataset": "sharegpt4o", "cap": 192,
                        "ladder": [96, 192]}
    else:
        tr = _json("traffic/long_sessions.json")
        tr.update(growth_tokens=1500, max_new_tokens=1500)
        tr["prompt"]["intervals"] = [[300, 600, 1.0]]
    real = spec.load_cell("minicpm3.longdecode.1c")
    return spec.Cell(f"tiny.{kind}.{traffic_kind}", dict(real.entry), cfg,
                     model_mod.from_config(cfg), tr,
                     {"max_logit_gap": limit}, real.end_to_end,
                     real.per_layer)
