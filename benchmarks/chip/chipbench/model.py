"""The benchmark's own description of a configuration's architecture.

Built from a configuration file under ``configs/``: the published sizes
under their Hugging Face ``config.json`` names, as run (after the cuts
listed in ``reduced``), plus the few facts of the layer equations that a
config.json does not state (``attention``, ``norm``, ``norm_eps``).
Nothing here comes from the program.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Model:
    name: str
    attention: str              # gqa | mla
    norm: str                   # layernorm | rmsnorm
    norm_eps: float
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    num_experts: int = 0
    num_experts_per_tok: int = 0
    moe_d_ff: int = 0

    @property
    def padded_vocab(self) -> int:
        """The vocabulary rounded up to 128 rows: the program serves (and
        takes its argmax over) the padded head, so the reference does too."""
        return -(-self.vocab_size // 128) * 128


def from_config(cfg: dict) -> Model:
    """A ``Model`` from a configuration file's contents."""
    mla = cfg["attention"] == "mla"
    heads = int(cfg["num_attention_heads"])
    return Model(
        name=cfg["name"],
        attention=cfg["attention"],
        norm=cfg["norm"],
        norm_eps=float(cfg["norm_eps"]),
        num_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        num_heads=heads,
        num_kv_heads=int(cfg.get("num_key_value_heads", heads)),
        head_dim=(0 if mla else int(cfg.get(
            "head_dim", int(cfg["hidden_size"]) // heads))),
        d_ff=int(cfg["intermediate_size"]),
        vocab_size=int(cfg["vocab_size"]),
        rope_theta=float(cfg["rope_theta"]),
        q_lora_rank=int(cfg.get("q_lora_rank", 0)),
        kv_lora_rank=int(cfg.get("kv_lora_rank", 0)),
        qk_nope_head_dim=int(cfg.get("qk_nope_head_dim", 0)),
        qk_rope_head_dim=int(cfg.get("qk_rope_head_dim", 0)),
        v_head_dim=int(cfg.get("v_head_dim", 0)),
        num_experts=int(cfg.get("num_local_experts", 0)),
        num_experts_per_tok=int(cfg.get("num_experts_per_tok", 0)),
        moe_d_ff=int(cfg.get("intermediate_size", 0)
                     if cfg.get("num_local_experts") else 0),
    )
