"""The benchmark's fp32 reference agrees with the program at a small size.

Both read the benchmark's own weights; the program runs its prefill
forward (``models.transformer.forward``) on the CPU, the reference its
plain per-layer float32 path.  A GQA-MoE and an MLA configuration.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

import chipbench_tiny as tiny                              # noqa: E402
from chipbench import harness, reference, weights          # noqa: E402
from chipbench import model as model_mod                   # noqa: E402


@pytest.mark.parametrize("kind", ["gqa_moe", "mla"])
def test_reference_matches_program_forward(kind):
    from repro.models import transformer
    cfg = tiny.config(kind)
    m = model_mod.from_config(cfg)
    cell = tiny.cell(kind, "sessions", 1.0)
    pcfg = harness.program_config(cell)
    params = weights.make_params(m, 2**31 + 3)
    toks = np.random.default_rng(0).integers(0, m.vocab_size, 300)
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        want, _ = transformer.forward(pcfg, f32, jnp.asarray(toks)[None])
    got = reference.logits(m, params, toks, start=250, n=50)
    want = np.asarray(want[0, 250:300], np.float32)
    assert got.shape == (50, m.padded_vocab)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_fp8_control_moves_the_logits():
    m = model_mod.from_config(tiny.config("gqa_moe"))
    params = weights.make_params(m, 5)
    toks = np.arange(200) % m.vocab_size
    a = reference.logits(m, params, toks, start=150, n=50)
    b = reference.logits(m, params, toks, start=150, n=50, quant="fp8")
    d = np.abs(a - b).max()
    assert d > 1e-2


def test_length_bucket():
    assert reference.length_bucket(1) == 1024
    assert reference.length_bucket(8192) == 8192
    assert reference.length_bucket(8193) == 12288
    assert reference.length_bucket(32768) == 32768
