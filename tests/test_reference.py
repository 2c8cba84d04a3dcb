"""The plain float32 reference (models/reference.py) computes the same
function as the model's own forward pass, so comparing the serving path
with it on the chip compares against the model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS, reduced
from repro.models import init_params, reference, transformer


@pytest.mark.parametrize("arch", ["phi3.5-moe-42b-a6.6b", "tinyllama-1.1b"])
def test_reference_matches_model_forward(arch):
    over = {"capacity_factor": 8.0} if CONFIGS[arch].is_moe else {}
    cfg = reduced(CONFIGS[arch], **over)   # E/k capacity: nothing dropped
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          init_params(jax.random.PRNGKey(0), cfg))
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (700,))
    want, _ = transformer.forward(cfg, params, jnp.asarray(toks)[None])
    got = reference.forward_last_logits(cfg, params, toks, last=5)
    np.testing.assert_allclose(got, np.asarray(want[0, -5:]), atol=2e-4,
                               rtol=2e-4)
