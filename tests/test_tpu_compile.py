"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (tests/test_kernels.py) never checks Mosaic's block-shape
and memory rules; these cases compile each kernel ahead of time for a
described (not attached) v5e chip and check that the kernel is in the
program.  They need no accelerator.

The topology is described only inside the module fixture below: loading the
TPU compiler takes a process-wide lock, so it must happen in the one test
worker that runs this file, never while modules are imported.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels import paged_attention as pa

N, MB, P, PAGE = 8, 4, 64, 16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("Hq,Hkv,Dk,Dv,kv_dtype", [
    (32, 8, 128, 128, jnp.bfloat16),      # Phi-3.5-MoE at tp=1 (GQA)
    (32, 8, 128, 128, jnp.float32),       # ... with the engine's f32 pools
    (40, 1, 288, 256, jnp.bfloat16),      # MiniCPM3 MLA latent (Dk != Dv)
    (32, 8, 128, 128, jnp.int8),          # quantized pools, per-page scales
    (32, 8, 128, 128, jnp.float8_e4m3fn),
], ids=["phi3.5-gqa-bf16", "phi3.5-gqa-f32", "minicpm3-mla-bf16",
        "gqa-int8", "gqa-fp8"])
def test_paged_decode_compiles_for_v5e(one_chip, Hq, Hkv, Dk, Dv, kv_dtype):
    args = [_sds(one_chip, (N, Hq, Dk), jnp.bfloat16),
            _sds(one_chip, (P, PAGE, Hkv, Dk), kv_dtype),
            _sds(one_chip, (P, PAGE, Hkv, Dv), kv_dtype),
            _sds(one_chip, (N, MB), jnp.int32),
            _sds(one_chip, (N,), jnp.int32)]
    if kv_dtype in (jnp.bfloat16, jnp.float32):
        fn = pa.paged_decode_attention
    else:
        args += [_sds(one_chip, (P,), jnp.float32)] * 2

        def fn(q, k, v, bt, ln, ks, vs):
            return pa.paged_decode_attention(q, k, v, bt, ln, k_scale=ks,
                                             v_scale=vs)
    assert "tpu_custom_call" in _compiled_hlo(fn, *args)


def test_flash_prefill_compiles_for_v5e_at_any_length(one_chip, monkeypatch):
    """GQA prefill at a prompt length that is not a block multiple: the
    padding in ``ops.flash_attention`` makes it tileable."""
    monkeypatch.setattr(ops, "FORCE_IMPL", "pallas")
    S = 300

    def fn(q, k, v):
        return ops.flash_attention(q, k, v, causal=True)

    hlo = _compiled_hlo(fn, _sds(one_chip, (1, S, 32, 128), jnp.bfloat16),
                        _sds(one_chip, (1, S, 8, 128), jnp.bfloat16),
                        _sds(one_chip, (1, S, 8, 128), jnp.bfloat16))
    assert "tpu_custom_call" in hlo
