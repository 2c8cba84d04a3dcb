"""The default architecture: the family the benchmark first ran.

Pre-norm decoder layers, all of one kind: GQA or MLA attention (rotary on
interleaved pairs, no scaling), then either one gated-SiLU MLP or a
softmax top-k MoE whose router is as wide as the experts held
(``num_local_experts``, each ``intermediate_size`` wide), with no shared
expert.  A configuration that names no ``architecture`` runs as this one.

The equations are ``chipbench/model.py`` (the description),
``weights.py``, ``reference.py`` and ``flops.py``; the harness, the check
and the counting readers reach them only through this file.
"""
from chipbench import flops, model, reference, weights

from_config = model.from_config
make_params = weights.make_params
logits_and_margins = reference.logits_and_margins
linear_per_token = flops.linear_per_token
head = flops.head
prefill_attention = flops.prefill_attention
prefill = flops.prefill
decode_attention = flops.decode_attention
decode_token = flops.decode_token


def program_sizes(m) -> dict:
    """The program's ``ModelConfig`` attributes that must equal the file:
    every size, the layer kinds, and the parts the reference leaves out
    (biases, q/k norms, tied embeddings)."""
    want = {"attention": m.attention, "norm": m.norm,
            "num_layers": m.num_layers, "d_model": m.d_model,
            "num_heads": m.num_heads, "d_ff": m.d_ff,
            "vocab_size": m.vocab_size, "padded_vocab": m.padded_vocab,
            "rope_theta": m.rope_theta, "num_experts": m.num_experts,
            "num_experts_per_tok": m.num_experts_per_tok,
            "qkv_bias": False, "qk_norm": False, "tie_embeddings": False,
            "act": "silu", "block_period": 1}
    if m.attention == "mla":
        want.update(q_lora_rank=m.q_lora_rank, kv_lora_rank=m.kv_lora_rank,
                    qk_nope_head_dim=m.qk_nope_head_dim,
                    qk_rope_head_dim=m.qk_rope_head_dim,
                    v_head_dim=m.v_head_dim)
    else:
        want.update(num_kv_heads=m.num_kv_heads, head_dim_=m.head_dim)
    if m.num_experts:
        want["moe_d_ff_"] = m.moe_d_ff
    return want
