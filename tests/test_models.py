"""Per-architecture reduced-config smoke tests: one forward + one train step
on CPU, asserting output shapes and no NaNs (the FULL configs are exercised
via the dry-run only)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import ASSIGNED, CONFIGS, reduced
from repro.models import ssm
from repro.training import optimizer, train_step


@pytest.mark.parametrize("arch", list(ASSIGNED) + ["deepseek-v3"])
def test_smoke_forward_and_train(arch):
    cfg = reduced(CONFIGS[arch])
    rng = jax.random.PRNGKey(0)
    params = models.init_params(rng, cfg)
    B, S = 2, 64
    batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
             "targets": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    if cfg.is_encoder_decoder:
        batch["frames"] = jax.random.normal(rng, (B, S, cfg.d_model),
                                            jnp.bfloat16)
        batch["tokens"] = batch["tokens"][:, :32]
        batch["targets"] = batch["targets"][:, :32]
    logits = models.forward(cfg, params, batch)
    exp_s = 32 if cfg.is_encoder_decoder else S
    assert logits.shape == (B, exp_s, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())

    step = jax.jit(train_step.make_train_step(
        cfg, optimizer.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)))
    opt = optimizer.init_opt_state(params)
    params2, opt2, stats = step(params, opt, batch)
    assert np.isfinite(float(stats["loss"]))
    assert np.isfinite(float(stats["grad_norm"]))


def test_ssd_chunked_matches_naive(rng):
    cfg = reduced(CONFIGS["mamba2-370m"])
    B, S = 2, 64
    nh, hd, ns = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state
    xh = jnp.asarray(rng.standard_normal((B, S, nh, hd)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((B, S, nh)),
                                     jnp.float32))
    A = -jnp.exp(jnp.asarray(rng.standard_normal((nh,)) * 0.3, jnp.float32))
    Bm = jnp.asarray(rng.standard_normal((B, S, ns)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((B, S, ns)), jnp.float32)

    h = jnp.zeros((B, nh, hd, ns))
    ys = []
    for t in range(S):
        decay = jnp.exp(dt[:, t] * A)
        upd = jnp.einsum("bs,bh,bhd->bhds", Bm[:, t], dt[:, t], xh[:, t])
        h = h * decay[..., None, None] + upd
        ys.append(jnp.einsum("bs,bhds->bhd", Cm[:, t], h))
    y_naive = jnp.stack(ys, 1)
    y_chunk, h_chunk = ssm.ssd_chunked(cfg, xh, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_naive),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(h), atol=1e-4)


def test_ssm_decode_continues_prefill(rng):
    cfg = reduced(CONFIGS["mamba2-370m"])
    p = ssm.make_ssm_params(jax.random.PRNGKey(2), cfg)
    p = jax.tree.map(lambda x: x.astype(jnp.float32), p)
    B, S = 2, 64
    x = jnp.asarray(rng.standard_normal((B, S + 1, cfg.d_model)), jnp.float32)
    y_full, _ = ssm.ssm_block(cfg, p, x)
    y_pre, (conv, h) = ssm.ssm_block(cfg, p, x[:, :S])
    y_step, _, _ = ssm.ssm_decode_step(cfg, p, x[:, S], conv, h)
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full[:, S]),
                               atol=1e-4)


@pytest.mark.parametrize("chunk", [32, 24])   # 24: a 16-token remainder
def test_moe_chunked_matches_unchunked(rng, chunk):
    from repro.models import moe
    cfg = reduced(CONFIGS["phi3.5-moe-42b-a6.6b"], capacity_factor=8.0)
    p = moe.make_moe_params(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.standard_normal((2, 64, cfg.d_model)), jnp.float32)
    p = jax.tree.map(lambda v: v.astype(jnp.float32), p)
    full = moe.moe_ffn_batched(cfg, p, x, chunk=64)
    chunked = moe.moe_ffn_batched(cfg, p, x, chunk=chunk)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked),
                               atol=1e-4)


def test_param_counts_match_published():
    expect = {"tinyllama-1.1b": 1.10e9, "qwen2.5-14b": 14.8e9,
              "minicpm3-4b": 4.26e9, "phi3.5-moe-42b-a6.6b": 41.9e9,
              "mamba2-370m": 0.37e9, "jamba-v0.1-52b": 51.5e9,
              "deepseek-v3": 671e9}
    for arch, n in expect.items():
        got = CONFIGS[arch].param_counts()["total"]
        assert abs(got - n) / n < 0.05, (arch, got, n)
