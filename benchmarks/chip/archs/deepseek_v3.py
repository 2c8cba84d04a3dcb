"""DeepSeek-V3 (hf:deepseek-ai/DeepSeek-V3, arXiv:2412.19437) at one chip's
share of a wide-EP deployment.

The equations, written from the published description:

* pre-norm layers, RMS norm (eps ``rms_norm_eps``);
* MLA: a low-rank query (``q_lora_rank``, normed), a normed KV latent
  (``kv_lora_rank``) and a rotary part (``qk_rope_head_dim``) shared by all
  heads, per-head up-projections (nope, v); rotary on interleaved pairs
  with YaRN frequencies (``rope_scaling``); softmax scale
  (nope + rope)^-1/2 times YaRN's mscale(``mscale_all_dim``) squared;
* the first ``first_k_dense_replace`` layers have a gated-SiLU MLP of
  ``intermediate_size``; the rest route each token over all
  ``published.n_routed_experts`` experts: sigmoid scores, plus the
  correction bias for selection only, the top ``topk_group`` of
  ``n_group`` groups by the sum of each group's two best biased scores,
  the top ``num_experts_per_tok`` inside them, weighted by the unbiased
  scores renormalised and scaled by ``routed_scaling_factor``; gated-SiLU
  experts of ``moe_intermediate_size``, plus ``n_shared_experts``;
* the chip's share: it holds ``n_routed_experts`` experts from
  ``deployment.held_experts_first``; the part of the routed output that
  they give, plus the shared expert, is the layer's output here (the
  other experts' parts are computed on other chips of the deployment and
  are left out alike in the program and in this reference).

Weights are random from the seed in the program's layout (the leading
dense layers under ``dense_blocks``, the MoE layers under ``blocks``).
The reference is plain ``jax.numpy`` at float32 under matmul precision
"highest", with no kernels, cache or batching; attention runs a group of
heads and a block of queries at a time, and the MLPs a slice of their
width at a time, so that a layer fits beside the weights on one chip.
Nothing here is imported from the program.
"""

import math
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference as base_ref
from chipbench.weights import seed_key

BYTES = 2                  # bf16
FP8_MAX = 448.0
Q_BLOCK = 256              # queries per attention block in the reference
HEAD_GROUP = 16            # heads per attention group in the reference
F_SLICE = 2048             # MLP width per slice in the reference


@dataclass(frozen=True)
class Model:
    name: str
    num_layers: int
    first_k_dense: int
    d_model: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int
    moe_d_ff: int
    num_experts: int           # the router's width (published)
    held_first: int
    held: int                  # experts held here
    num_experts_per_tok: int
    num_shared_experts: int
    n_group: int
    topk_group: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    vocab_size: int
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    norm_eps: float
    capacity_factor: float

    @property
    def padded_vocab(self) -> int:
        """The vocabulary rounded up to 128 rows, as the program serves it."""
        return -(-self.vocab_size // 128) * 128

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense


def from_config(cfg: dict) -> Model:
    rs = cfg["rope_scaling"]
    assert rs["type"] == "yarn" and cfg["scoring_func"] == "sigmoid" \
        and cfg["topk_method"] == "noaux_tc" and cfg["moe_layer_freq"] == 1
    dep = cfg["deployment"]
    return Model(
        name=cfg["name"],
        num_layers=int(cfg["num_hidden_layers"]),
        first_k_dense=int(cfg["first_k_dense_replace"]),
        d_model=int(cfg["hidden_size"]),
        num_heads=int(cfg["num_attention_heads"]),
        q_lora_rank=int(cfg["q_lora_rank"]),
        kv_lora_rank=int(cfg["kv_lora_rank"]),
        qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
        qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
        v_head_dim=int(cfg["v_head_dim"]),
        d_ff=int(cfg["intermediate_size"]),
        moe_d_ff=int(cfg["moe_intermediate_size"]),
        num_experts=int(cfg["published"]["n_routed_experts"]),
        held_first=int(dep["held_experts_first"]),
        held=int(cfg["n_routed_experts"]),
        num_experts_per_tok=int(cfg["num_experts_per_tok"]),
        num_shared_experts=int(cfg["n_shared_experts"]),
        n_group=int(cfg["n_group"]),
        topk_group=int(cfg["topk_group"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        vocab_size=int(cfg["vocab_size"]),
        rope_theta=float(cfg["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original=int(rs["original_max_position_embeddings"]),
        yarn_beta_fast=float(rs["beta_fast"]),
        yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]),
        yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        capacity_factor=float(cfg["program"]["overrides"]["capacity_factor"]),
    )


def program_sizes(m: Model) -> dict:
    """The program's ``ModelConfig`` attributes that must equal the file:
    every size, the router, YaRN, the layer kinds and the share."""
    return {
        "attention": "mla", "norm": "rmsnorm", "act": "silu",
        "qkv_bias": False, "qk_norm": False, "tie_embeddings": False,
        "num_layers": m.num_layers, "first_k_dense": m.first_k_dense,
        "block_period": 1, "d_model": m.d_model, "num_heads": m.num_heads,
        "q_lora_rank": m.q_lora_rank, "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim, "v_head_dim": m.v_head_dim,
        "d_ff": m.d_ff, "moe_d_ff_": m.moe_d_ff,
        "num_experts": m.num_experts, "num_held_experts": m.held,
        "held_experts_first": m.held_first,
        "num_experts_per_tok": m.num_experts_per_tok,
        "num_shared_experts": m.num_shared_experts,
        "scoring_func": "sigmoid", "score_correction_bias": True,
        "n_group": m.n_group, "topk_group": m.topk_group,
        "norm_topk_prob": m.norm_topk_prob,
        "routed_scaling_factor": m.routed_scaling_factor,
        # dropless: capacity T * k / E * phi >= T
        "capacity_factor": m.capacity_factor,
        "vocab_size": m.vocab_size, "padded_vocab": m.padded_vocab,
        "rope_theta": m.rope_theta,
        "rope_scaling_params": {
            "factor": m.yarn_factor,
            "original_max_position": m.yarn_original,
            "beta_fast": m.yarn_beta_fast, "beta_slow": m.yarn_beta_slow,
            "mscale": m.yarn_mscale, "mscale_all_dim": m.yarn_mscale_all_dim},
    }


# ---------------------------------------------------------------- weights
def _norm_scale(key, n):
    return 1.0 + 0.05 * jax.random.normal(key, (n,), jnp.float32)


def _dense(key, shape, dtype=jnp.bfloat16):
    std = shape[-2] ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _mlp(key, D, F):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi_gate": _dense(k1, (D, F)), "wi_up": _dense(k2, (D, F)),
            "wo": _dense(k3, (F, D))}


def _layer(m: Model, moe: bool, key) -> dict:
    ks = iter(jax.random.split(key, 16))
    D, H = m.d_model, m.num_heads
    qr, kvr = m.q_lora_rank, m.kv_lora_rank
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    p = {"ln1": {"scale": _norm_scale(next(ks), D)},
         "ln2": {"scale": _norm_scale(next(ks), D)},
         "mixer": {
             "wq_a": _dense(next(ks), (D, qr)),
             "q_norm": _norm_scale(next(ks), qr),
             "wq_b": _dense(next(ks), (qr, H * (dn + dr))),
             "wkv_a": _dense(next(ks), (D, kvr + dr)),
             "kv_norm": _norm_scale(next(ks), kvr),
             "wk_b": _dense(next(ks), (kvr, H * dn)),
             "wv_b": _dense(next(ks), (kvr, H * dv)),
             "wo": _dense(next(ks), (H * dv, D))}}
    if not moe:
        p["ffn"] = _mlp(next(ks), D, m.d_ff)
        return p
    E, Hx, F = m.num_experts, m.held, m.moe_d_ff
    p["ffn"] = {
        "router": _dense(next(ks), (D, E), jnp.float32),
        # a trained bias balances load; drawn at the spread of the scores'
        # own gaps so that it moves the choice
        "e_score_correction_bias": 0.02 * jax.random.normal(
            next(ks), (E,), jnp.float32),
        "wi_gate": _dense(next(ks), (Hx, D, F)),
        "wi_up": _dense(next(ks), (Hx, D, F)),
        "wo": _dense(next(ks), (Hx, F, D)),
        "shared": _mlp(next(ks), D, F * m.num_shared_experts)}
    return p


def _params(m: Model, key) -> dict:
    k_emb, k_layers, k_norm, k_head = jax.random.split(key, 4)
    Vp = m.padded_vocab

    def stack(first, n, moe):
        keys = jax.vmap(lambda i: jax.random.fold_in(k_layers, i))(
            jnp.arange(first, first + n))
        return {"layers": [jax.vmap(lambda k: _layer(m, moe, k))(keys)]}

    out = {"embed": {"tok": (0.02 * jax.random.normal(
               k_emb, (Vp, m.d_model), jnp.float32)).astype(jnp.bfloat16)},
           "blocks": stack(m.first_k_dense, m.num_moe_layers, True),
           "final_norm": {"scale": _norm_scale(k_norm, m.d_model)},
           "head": {"w": _dense(k_head, (m.d_model, Vp))}}
    if m.first_k_dense:
        out["dense_blocks"] = stack(0, m.first_k_dense, False)
    return out


def make_params(m: Model, seed: int, device=None) -> dict:
    """The whole tree, made on ``device`` by one jitted call."""
    fn = jax.jit(lambda k: _params(m, k))
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)


# -------------------------------------------------------------- reference
def _w(w, quant):
    """A weight at float32, or rounded to float8 e4m3 with one scale per
    output column first (the control)."""
    w = jnp.asarray(w, jnp.float32)
    if quant == "fp8":
        amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
        scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
        w = (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    elif quant is not None:
        raise ValueError(f"unknown quant {quant!r}")
    return w


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * jnp.asarray(scale, jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(m: Model) -> np.ndarray:
    """YaRN's rotary frequencies [rope/2] (DeepSeek-V3's
    ``precompute_freqs_cis``)."""
    dim, base = m.qk_rope_head_dim, m.rope_theta
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def corr(rot):
        return dim * math.log(m.yarn_original / (rot * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(corr(m.yarn_beta_fast)), 0)
    high = min(math.ceil(corr(m.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    smooth = 1 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (freqs / m.yarn_factor * (1 - smooth)
            + freqs * smooth).astype(np.float32)


def _rope(m: Model, x):
    """x [T, H, dr]: rotate the pairs (x[2i], x[2i+1]) by position."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq(m))
    mult = yarn_mscale(m.yarn_factor, m.yarn_mscale) \
        / yarn_mscale(m.yarn_factor, m.yarn_mscale_all_dim)
    c, s = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def softmax_scale(m: Model) -> float:
    return (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5 \
        * yarn_mscale(m.yarn_factor, m.yarn_mscale_all_dim) ** 2


def _mla(m: Model, p: dict, h, quant):
    """Materialised MLA, one group of heads at a time; returns [T, D]."""
    T = h.shape[0]
    H = m.num_heads
    G = min(HEAD_GROUP, H)
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    kvr = m.kv_lora_rank
    cq = _rms(h @ _w(p["wq_a"], quant), p["q_norm"], m.norm_eps)
    kv = h @ _w(p["wkv_a"], quant)
    c_kv = _rms(kv[:, :kvr], p["kv_norm"], m.norm_eps)
    k_rope = _rope(m, kv[:, None, kvr:])                         # [T, 1, dr]
    # the weights of each group of G heads, on a leading axis
    wq = _w(p["wq_b"], quant).reshape(-1, H // G, G * (dn + dr))
    wk = _w(p["wk_b"], quant).reshape(kvr, H // G, G * dn)
    wv = _w(p["wv_b"], quant).reshape(kvr, H // G, G * dv)
    wo = _w(p["wo"], quant).reshape(H // G, G * dv, -1)
    scale = softmax_scale(m)

    def group(y, g):
        q = (cq @ wq[:, g]).reshape(T, G, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(m, q[..., dn:])], -1)
        k = jnp.concatenate([(c_kv @ wk[:, g]).reshape(T, G, dn),
                             jnp.broadcast_to(k_rope, (T, G, dr))], -1)
        v = (c_kv @ wv[:, g]).reshape(T, G, dv)
        return y + _causal(q, k, v, scale) @ wo[g], None

    y, _ = jax.lax.scan(group, jnp.zeros((T, wo.shape[-1]), jnp.float32),
                        jnp.arange(H // G))
    return y


def _causal(q, k, v, scale):
    """q/k [T, G, dk], v [T, G, dv] -> [T, G*dv], a block of queries at a
    time (T is a multiple of ``Q_BLOCK``)."""
    T = q.shape[0]

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)[:, None]
        s = jnp.where(qpos >= jnp.arange(T)[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return jax.lax.map(block, jnp.arange(T // Q_BLOCK)).reshape(T, -1)


def _mlp_fwd(x, w_gate, w_up, w_down, quant):
    """Gated SiLU MLP, a slice of its width at a time (the fp8 control's
    column scales are per output column, so slicing the width keeps them)."""
    F = w_gate.shape[-1]
    n = max(1, F // F_SLICE)
    assert F % n == 0, (F, F_SLICE)
    wg = jnp.asarray(w_gate).reshape(w_gate.shape[0], n, -1)
    wu = jnp.asarray(w_up).reshape(w_up.shape[0], n, -1)
    wd = jnp.asarray(w_down).reshape(n, -1, w_down.shape[-1])
    amax_d = None
    if quant == "fp8":            # the down projection's columns span F
        full = jnp.asarray(w_down, jnp.float32)
        amax_d = jnp.max(jnp.abs(full), axis=0, keepdims=True)

    def piece(y, i):
        g = jax.nn.silu(x @ _w(wg[:, i], quant)) * (x @ _w(wu[:, i], quant))
        d = jnp.asarray(wd[i], jnp.float32)
        if amax_d is not None:
            scale = jnp.where(amax_d > 0, amax_d / FP8_MAX, 1.0)
            d = (d / scale).astype(jnp.float8_e4m3fn).astype(
                jnp.float32) * scale
        return y + g @ d, None

    y, _ = jax.lax.scan(piece, jnp.zeros((x.shape[0], wd.shape[-1]),
                                         jnp.float32), jnp.arange(n))
    return y


def route(m: Model, p: dict, h, quant):
    """(gate [T, E]: each expert's routed weight, 0 where not chosen;
    margin [T]: the smaller of the k-th chosen expert's biased score over
    the best one left out in the chosen groups, and the last chosen
    group's score over the best group left out)."""
    E, k = m.num_experts, m.num_experts_per_tok
    score = jax.nn.sigmoid(h @ _w(p["router"], quant))           # [T, E]
    choice = score + jnp.asarray(p["e_score_correction_bias"], jnp.float32)
    T = h.shape[0]
    g = choice.reshape(T, m.n_group, E // m.n_group)
    gscore = jax.lax.top_k(g, 2)[0].sum(-1)                       # [T, G]
    gtop, gidx = jax.lax.top_k(gscore, m.topk_group + 1)
    keep = jnp.any(jnp.arange(m.n_group)[None, :, None]
                   == gidx[:, None, :m.topk_group], -1)           # [T, G]
    masked = jnp.where(keep[:, :, None], g, -jnp.inf).reshape(T, E)
    top, idx = jax.lax.top_k(masked, k + 1)
    chosen = jnp.any(idx[:, :k, None] == jnp.arange(E)[None, None, :], 1)
    w = jnp.where(chosen, score, 0.0)
    if m.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    margin = jnp.minimum(top[:, k - 1] - top[:, k],
                         gtop[:, m.topk_group - 1] - gtop[:, m.topk_group])
    return w * m.routed_scaling_factor, margin


def _moe(m: Model, p: dict, h, quant):
    gate, margin = route(m, p, h, quant)

    def expert(y, e):             # one held expert's weights at a time
        return y + gate[:, m.held_first + e, None] * _mlp_fwd(
            h, p["wi_gate"][e], p["wi_up"][e], p["wo"][e], quant), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(m.held))
    sh = p["shared"]
    return y + _mlp_fwd(h, sh["wi_gate"], sh["wi_up"], sh["wo"], quant), margin


@partial(jax.jit, static_argnums=(0, 1, 2))
def _ref_layer(m: Model, quant, moe: bool, layers: dict, i, x):
    """One layer; ``layers`` holds its stack's weights on a leading axis
    and ``i`` picks one, so a stack's layers share one compile.  Returns
    (x, router margin per token; +inf for a dense layer)."""
    lp = jax.tree.map(lambda a: a[i], layers)
    x = x + _mla(m, lp["mixer"], _rms(x, lp["ln1"]["scale"], m.norm_eps),
                 quant)
    h = _rms(x, lp["ln2"]["scale"], m.norm_eps)
    f = lp["ffn"]
    if moe:
        y, margin = _moe(m, f, h, quant)
        return x + y, margin
    return x + _mlp_fwd(h, f["wi_gate"], f["wi_up"], f["wo"], quant), \
        jnp.full(x.shape[:1], jnp.inf, jnp.float32)


@partial(jax.jit, static_argnums=(0, 1, 5))
def _head(m: Model, quant, final_norm, head_w, x, n: int, start):
    xs = jax.lax.dynamic_slice_in_dim(x, start, n)
    return _rms(xs, final_norm["scale"], m.norm_eps) @ _w(head_w, quant)


def logits_and_margins(m: Model, params: dict, tokens, start: int, n: int,
                       quant: str | None = None):
    """float32 logits [n, Vp] at positions start..start+n-1 of ``tokens``
    (the logits at position t predict token t+1), and there the smallest
    router margin over the MoE layers (``route``)."""
    T = len(tokens)
    Tp = base_ref.length_bucket(T)
    toks = np.zeros(Tp, np.int32)
    toks[:T] = np.asarray(tokens, np.int32)
    stacks = []
    if m.first_k_dense:
        stacks.append((False, params["dense_blocks"]["layers"][0],
                       m.first_k_dense))
    stacks.append((True, params["blocks"]["layers"][0], m.num_moe_layers))
    with jax.default_matmul_precision("highest"):
        x = jnp.asarray(params["embed"]["tok"][jnp.asarray(toks)],
                        jnp.float32)
        margin = jnp.full((Tp,), jnp.inf, jnp.float32)
        for moe, layers, count in stacks:
            for i in range(count):
                x, mg = _ref_layer(m, quant, moe, layers, jnp.int32(i), x)
                margin = jnp.minimum(margin, mg)
        n_pad = min(-(-n // 128) * 128, Tp)
        s0 = min(start, Tp - n_pad)
        out = _head(m, quant, params["final_norm"], params["head"]["w"], x,
                    n_pad, jnp.int32(s0))
    lo = start - s0
    return (np.asarray(out, np.float32)[lo:lo + n],
            np.asarray(margin, np.float32)[start:start + n])


# ----------------------------------------------------------------- counts
# As ``chipbench/flops.py`` defines them: the model's work, bf16 bytes,
# a multiply-add is 2 operations.  A routed expert is counted for the
# tokens it receives: of the router's E experts each token picks k, so a
# token brings k * held / E expert passes here (0.25 at 8 of 256, top-8).
def linear_per_token(m: Model) -> float:
    """Operations of every projection, the router, the dense MLPs and the
    held and shared experts, of all layers, for one token (attention
    scores and the head excluded)."""
    D, H = m.d_model, m.num_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    qr, kvr = m.q_lora_rank, m.kv_lora_rank
    attn = D * qr + qr * H * (dn + dr) + D * (kvr + dr) + H * dv * D
    dense = 3 * D * m.d_ff
    routed = m.num_experts_per_tok * m.held / m.num_experts
    moe = D * m.num_experts + (routed + m.num_shared_experts) \
        * 3 * D * m.moe_d_ff
    return 2.0 * (m.num_layers * attn + m.first_k_dense * dense
                  + m.num_moe_layers * moe)


def head(m: Model) -> float:
    return 2.0 * m.d_model * m.padded_vocab


def prefill_attention(m: Model, S: int) -> tuple[float, float]:
    """(operations, bytes) of causal MLA over an S-token prompt in the
    materialised form, all layers: S(S+1)/2 pairs; q, k, v and the
    output read or written once."""
    pairs = S * (S + 1) / 2.0
    H = m.num_heads
    dk = m.qk_nope_head_dim + m.qk_rope_head_dim
    dv = m.v_head_dim
    ops = 2.0 * H * (dk + dv) * pairs
    byt = S * H * (dk + dk + dv + dv) * BYTES
    return m.num_layers * ops, m.num_layers * byt


def prefill(m: Model, S: int) -> float:
    """Operations of prefilling one S-token prompt (per-token K/V
    up-projections of the materialised form included)."""
    extra = 2.0 * m.num_layers * m.kv_lora_rank * m.num_heads * (
        m.qk_nope_head_dim + m.v_head_dim)
    return S * (linear_per_token(m) + extra) \
        + prefill_attention(m, S)[0] + head(m)


def decode_attention(m: Model, ctx: int) -> tuple[float, float]:
    """(operations, bytes) of one decode row's absorbed MLA over ``ctx``
    cached latents, all layers: per token 2 H (kvr + dr + kvr) operations
    and (kvr + dr) bf16 values read once."""
    H, kvr, dr = m.num_heads, m.kv_lora_rank, m.qk_rope_head_dim
    ops = 2.0 * H * (kvr + dr + kvr) * ctx
    byt = ctx * (kvr + dr) * BYTES
    return m.num_layers * ops, m.num_layers * byt


def decode_token(m: Model, ctx: int) -> float:
    """Operations of one decode row with ``ctx`` tokens of context: the
    linear work (routed experts at their expected share), absorbing W_uk
    into the query and W_uv into the output, attention and the head."""
    absorb = 2.0 * m.num_layers * m.num_heads * m.kv_lora_rank * (
        m.qk_nope_head_dim + m.v_head_dim)
    return linear_per_token(m) + absorb + decode_attention(m, ctx)[0] \
        + head(m)
