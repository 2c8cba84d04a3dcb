"""EOS semantics: a request whose sampled token is EOS at step t appends
exactly t KV entries — the speculative slot-step of the lookahead pipeline
must not leave a stray KV append behind (device-side stop-token mask), and
the non-pipelined reference path must never run the speculative step at all.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs import CONFIGS, reduced
from repro.core.bucketing import ShapeBuckets
from repro.models import init_params, transformer
from repro.serving.engine import NanoCPEngine

PROMPT_LEN = 20
VOCAB = 128


def _cfg_params():
    cfg = reduced(CONFIGS["tinyllama-1.1b"], num_layers=2, vocab_size=VOCAB,
                  num_kv_heads=1)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _engine(cfg, params, prompt, *, eos, pipeline, max_new=8):
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    eng = NanoCPEngine(cfg, params, mesh, num_instances=1,
                       instances_per_node=1, kv_capacity_tokens=1024,
                       page_size=16, eos_token=eos, pipeline=pipeline,
                       shape_buckets=ShapeBuckets(m_buckets=(1, 2, 4),
                                                  s_buckets=(0,), window=1))
    eng.add_request(prompt, max_new_tokens=max_new)
    return eng


def _ref_greedy(cfg, params, prompt, n):
    seq = list(map(int, prompt))
    out = []
    for _ in range(n):
        logits, _ = transformer.forward(cfg, params, jnp.asarray(seq)[None])
        t = int(jnp.argmax(logits[0, -1]))
        out.append(t)
        seq.append(t)
    return out


def _kv_entries(eng) -> int:
    """Distinct (frame, offset) pool positions holding a written KV entry,
    scratch frame (last frame of the sub-pool) excluded."""
    kp = np.asarray(eng.state["k_pool"])   # [nb, na, I, tp, F', page, kg*hd]
    nz = np.abs(kp).max(axis=(0, 1, -1))[0, 0]          # [F', page]
    return int((nz[:-1] > 0).sum())


def _pick_eos(cfg, params, prompt, min_step: int, horizon: int = 8):
    """A stop token the model really samples at some emission ``at >=
    min_step`` (0-based over the engine's emitted tokens) and at no
    emission before it.  Returns ``(eos, at)``; searching instead of taking
    the token at a fixed step keeps the choice valid for any weights."""
    ref = _ref_greedy(cfg, params, prompt, horizon)
    for at in range(min_step, horizon):
        if ref[at] not in ref[:at]:
            return ref[at], at
    pytest.fail(f"no token first sampled at or after step {min_step}: {ref}")


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "non-pipelined"])
def test_eos_appends_exactly_t_kv_entries(pipeline):
    cfg, params = _cfg_params()
    prompt = np.random.default_rng(0).integers(0, VOCAB, (PROMPT_LEN,))
    eos, at = _pick_eos(cfg, params, prompt, 2)   # 3rd emission or later

    eng = _engine(cfg, params, prompt, eos=eos, pipeline=pipeline)
    res = eng.run(max_iters=30)
    toks = res[0].tokens
    assert toks[-1] == eos and len(toks) == at + 1, toks
    assert eng.finished and eng.finished[0].rid == 0
    # emissions: prefill-sampled t0, then decode steps with inputs t0, t1
    # (the EOS itself is never legitimately appended).  The speculative
    # slot-step exists only in the pipelined engine and must be masked.
    expect = PROMPT_LEN + len(toks) - 1
    assert _kv_entries(eng) == expect, (pipeline, _kv_entries(eng), expect)
    spec = eng.hot_path_stats["speculative_slots"]
    assert spec == (1 if pipeline else 0), eng.hot_path_stats


@pytest.mark.parametrize("pipeline", [True, False],
                         ids=["pipelined", "non-pipelined"])
def test_eos_at_prefill_finishes_without_decode(pipeline):
    """EOS sampled straight from the prefill logits: zero decode iterations,
    zero decode KV appends (exactly the prompt's entries remain)."""
    cfg, params = _cfg_params()
    prompt = np.random.default_rng(0).integers(0, VOCAB, (PROMPT_LEN,))
    eos = _ref_greedy(cfg, params, prompt, 1)[0]

    eng = _engine(cfg, params, prompt, eos=eos, pipeline=pipeline)
    done = eng.step()
    assert [r.rid for r in done] == [0]   # finish visible in step()'s return
    res = eng.run(max_iters=10)
    assert res[0].tokens == [eos]
    assert eng.hot_path_stats["prefill_eos_finishes"] == 1
    assert eng.hot_path_stats["speculative_slots"] == 0
    assert _kv_entries(eng) == PROMPT_LEN
    assert not eng.cluster.active and not eng.cluster.waiting


def test_eos_tokens_match_reference_up_to_stop():
    """With a stop token set, the engine's emissions are exactly the
    reference greedy sequence truncated at (and including) the first EOS."""
    cfg, params = _cfg_params()
    prompt = np.random.default_rng(0).integers(0, VOCAB, (PROMPT_LEN,))
    eos, _ = _pick_eos(cfg, params, prompt, 3)
    ref = _ref_greedy(cfg, params, prompt, 8)
    eng = _engine(cfg, params, prompt, eos=eos, pipeline=True)
    res = eng.run(max_iters=30)
    assert res[0].tokens == ref[:ref.index(eos) + 1]
