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
import re

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


def _kernel_operand_layouts(hlo: str, lead: int) -> list[str]:
    """Layouts of the Pallas custom call's operands whose leading dim is
    ``lead`` (the pools), as the compiled program lays them out."""
    call = next(l for l in hlo.splitlines() if "tpu_custom_call" in l)
    names = re.findall(r"%([\w.\-]+)",
                       call.split("custom-call(", 1)[1].split(")", 1)[0])
    layouts = []
    for name in names:
        m = re.search(rf"%{re.escape(name)} = \w+\[(\d+)[,\]][^{{]*\{{([^}}]*)\}}",
                      hlo)
        if m and int(m.group(1)) == lead:
            layouts.append(m.group(2))
    return layouts


@pytest.mark.parametrize("Hq,Hkv,Dk,Dv,kv_dtype,shared_v,n,mb,p", [
    (32, 8, 128, 128, jnp.bfloat16, False, N, MB, P),   # Phi-3.5-MoE at tp=1 (GQA)
    (32, 8, 128, 128, jnp.float32, False, N, MB, P),    # ... with the engine's f32 pools
    (40, 1, 288, 256, jnp.bfloat16, False, N, MB, P),   # MiniCPM3 MLA latent (Dk != Dv)
    (32, 8, 128, 128, jnp.int8, False, N, MB, P),       # quantized pools, per-page scales
    (32, 8, 128, 128, jnp.float8_e4m3fn, False, N, MB, P),
    # MLA's one latent pool, V read from K's lanes (v_pages=None)
    (40, 1, 288, 256, jnp.float32, True, N, MB, P),
    (40, 1, 288, 256, jnp.int8, True, N, MB, P),
    # the chip cells' decode buckets: f32 pools, ~1024 page slots a row
    (32, 8, 128, 128, jnp.float32, False, 8, 1024, 8193),   # phi35moe.longdecode.1c
    (40, 1, 288, 256, jnp.float32, True, 4, 1024, 3841),    # minicpm3.longdecode.1c
    # DeepSeek-V3: 128 heads over a 512 + 64 = 576-lane latent (640 in HBM)
    (128, 1, 576, 512, jnp.bfloat16, True, N, MB, P),
    (128, 1, 576, 512, jnp.float32, True, 8, 1175, 8193),  # deepseekv3.longdecode.1c
], ids=["phi3.5-gqa-bf16", "phi3.5-gqa-f32", "minicpm3-mla-bf16",
        "gqa-int8", "gqa-fp8", "minicpm3-mla-f32-shared",
        "minicpm3-mla-int8-shared", "phi3.5-cell-f32-mb1024",
        "minicpm3-cell-f32-shared-mb1024", "deepseekv3-mla-bf16-shared",
        "deepseekv3-cell-f32-shared-mb1175"])
def test_paged_decode_compiles_for_v5e(one_chip, Hq, Hkv, Dk, Dv, kv_dtype,
                                       shared_v, n, mb, p):
    quantized = kv_dtype not in (jnp.bfloat16, jnp.float32)
    args = [_sds(one_chip, (n, Hq, Dk), jnp.bfloat16),
            _sds(one_chip, (p, PAGE, Hkv, Dk), kv_dtype),
            None if shared_v else _sds(one_chip, (p, PAGE, Hkv, Dv), kv_dtype),
            _sds(one_chip, (n, mb), jnp.int32),
            _sds(one_chip, (n,), jnp.int32)]
    args += [_sds(one_chip, (p,), jnp.float32) if quantized else None,
             _sds(one_chip, (p,), jnp.float32)
             if quantized and not shared_v else None]

    def fn(q, k, v, bt, ln, ks, vs):
        return pa.paged_decode_attention(q, k, v, bt, ln, k_scale=ks,
                                         v_scale=vs, v_dim=Dv)
    hlo = _compiled_hlo(fn, *args)
    assert "tpu_custom_call" in hlo
    # the pools reach the kernel in 128-lane tiles, which the page copies'
    # whole-tile reads rely on
    layouts = _kernel_operand_layouts(hlo, p)
    assert len(layouts) == (1 if shared_v else 2), layouts
    for layout in layouts:
        assert re.search(r":T\(\d+,128\)", layout), layout


def _pool_sized_ops(hlo: str, frames) -> list[str]:
    """Lines of a compiled program that make a value with a pool's frame
    count (any of ``frames``) among its dims, other than passing the pool
    along (parameter, tuple, get-tuple-element, bitcast, while) and the
    in-place append (a scatter or dynamic-update-slice, alone or as the
    root of its fusion)."""
    roots = {}
    comp = None
    for line in hlo.splitlines():
        m = re.match(r"\s*%?([\w.\-]+) .*\{\s*$", line)
        if m and "=" not in line.split("{", 1)[0]:
            comp = m.group(1)
        m = re.match(r"\s*ROOT %[\w.\-]+ = .*? ([a-z][\w\-]*)\(", line)
        if m and comp:
            roots[comp] = m.group(1)
    passing = {"parameter", "tuple", "get-tuple-element", "bitcast", "while"}
    append = {"scatter", "dynamic-update-slice"}
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(",
                     line)
        if not m:
            continue
        dims = {int(d) for shape in re.findall(r"\[([\d,]+)\]", m.group(1))
                for d in shape.split(",")}
        op = m.group(2)
        if not dims & set(frames) or op in passing | append:
            continue
        if op == "fusion":
            called = re.search(r"calls=%([\w.\-]+)", line)
            if called and roots.get(called.group(1)) in append:
                continue
        found.append(line.strip())
    return found


@pytest.mark.parametrize("Hq,Hkv,Dk,Dv,shared_v,n,p,flat", [
    (40, 1, 288, 256, True, 4, 3841, False),      # minicpm3.longdecode.1c
    (32, 8, 128, 128, False, 8, 8193, False),     # phi35moe.longdecode.1c
    (128, 1, 576, 512, True, 8, 8193, False),     # deepseekv3.longdecode.1c
    # the flat in-place carry (``build_decode_step``), whole-tile latents
    (40, 1, 288, 256, True, 4, 3841, True),
    (32, 8, 128, 128, False, 8, 8193, True),
    (128, 1, 576, 512, True, 8, 8193, True),
], ids=["minicpm3-cell", "phi3.5-cell", "deepseekv3-cell",
        "minicpm3-cell-flat", "phi3.5-cell-flat", "deepseekv3-cell-flat"])
def test_paged_decode_adds_no_pool_copy_in_the_step(one_chip, Hq, Hkv, Dk,
                                                    Dv, shared_v, n, p, flat):
    """The decode step's shape around the kernel, two ways.  Stacked: each
    layer's f32 pool is sliced out of the stacked serve state, takes the
    new token, is read by the kernel and written back; XLA puts the slice
    where it likes (MiniCPM3's fits VMEM) and the kernel reads it there.
    Flat (``build_decode_step``): the layers' pools are one run of frames,
    a latent stored at whole 128-lane tiles; the token is written into it
    in place and the kernel reads the layer's pages through offset block
    tables.  Either way no copy of a whole pool may appear in the program
    (a memory-space pin on the kernel's pool operands adds one per layer),
    and the flat carry has no pool-sized op but the append."""
    layers, mb = 2, 1024
    if flat:
        lanes = pa.lane_tiles(Dk) if Hkv == 1 else Dk

        def step(k_st, v_st, q, bt, ln, slot):
            def layer(carry, i):
                x, k_st, v_st = carry
                new = x[0, :1, :Dk].astype(jnp.float32)
                row = (i * p + slot // PAGE, slot % PAGE)
                k_st = k_st.at[row + (slice(0, Hkv * Dk),)].set(
                    jnp.broadcast_to(new, (Hkv, Dk)).reshape(-1))
                if not shared_v:
                    v_st = v_st.at[row].set(
                        jnp.broadcast_to(new, (Hkv, Dv)).reshape(-1))
                o, _ = pa.paged_decode_attention(
                    x, k_st.reshape(layers * p, PAGE, Hkv, -1),
                    None if shared_v else
                    v_st.reshape(layers * p, PAGE, Hkv, Dv),
                    bt + i * p, ln, v_dim=Dv)
                x = x + jnp.pad(o, ((0, 0), (0, 0), (0, Dk - Dv))
                                ).astype(x.dtype)
                return (x, k_st, v_st), None
            (x, k_st, v_st), _ = jax.lax.scan(layer, (q, k_st, v_st),
                                              jnp.arange(layers))
            return x, k_st, v_st

        pool = (layers * p, PAGE, Hkv * lanes)
        args = [_sds(one_chip, pool, jnp.float32),
                _sds(one_chip, pool[:-1] + (Hkv * Dv,), jnp.float32),
                _sds(one_chip, (n, Hq, Dk), jnp.bfloat16),
                _sds(one_chip, (n, mb), jnp.int32),
                _sds(one_chip, (n,), jnp.int32),
                _sds(one_chip, (), jnp.int32)]
        hlo = jax.jit(step, donate_argnums=(0, 1)).lower(
            *args).compile().as_text()
        assert "tpu_custom_call" in hlo
        found = _pool_sized_ops(hlo, (layers * p,))
        assert not found, found[:2]
        return

    def step(k_st, v_st, q, bt, ln, slot):
        def layer(carry, i):
            x, k_st, v_st = carry
            new = x[0, :1, :Dk].astype(jnp.float32)

            def take(st):
                pool = jax.lax.dynamic_index_in_dim(st, i, 0, keepdims=False)
                return pool.at[slot // PAGE, slot % PAGE].set(
                    jnp.broadcast_to(new, pool.shape[2:]))
            kp = take(k_st)
            vp = None if shared_v else take(v_st)
            o, _ = pa.paged_decode_attention(x, kp, vp, bt, ln, v_dim=Dv)
            x = x + jnp.pad(o, ((0, 0), (0, 0), (0, Dk - Dv))).astype(x.dtype)
            k_st = jax.lax.dynamic_update_index_in_dim(k_st, kp[None], i, 0)
            if not shared_v:
                v_st = jax.lax.dynamic_update_index_in_dim(v_st, vp[None],
                                                           i, 0)
            return (x, k_st, v_st), None
        (x, k_st, v_st), _ = jax.lax.scan(layer, (q, k_st, v_st),
                                          jnp.arange(layers))
        return x, k_st, v_st

    pool = (layers, p, PAGE, Hkv, Dk)
    args = [_sds(one_chip, pool, jnp.float32),
            _sds(one_chip, pool[:-1] + (Dv,), jnp.float32),
            _sds(one_chip, (n, Hq, Dk), jnp.bfloat16),
            _sds(one_chip, (n, mb), jnp.int32),
            _sds(one_chip, (n,), jnp.int32),
            _sds(one_chip, (), jnp.int32)]
    hlo = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    copies = [l for l in hlo.splitlines()
              if re.search(rf"= \(?\w+\[{p},", l)
              and re.search(r"\bcopy(-start)?\(", l)]
    assert not copies, copies[:2]


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


# the chip cells' serve steps: (program config, overrides, pool tokens,
# slots M, page slots MB a work row)
CELL_STEPS = {
    "phi35moe": ("phi3.5-moe-42b-a6.6b",
                 dict(num_layers=2, capacity_factor=8.0), 131072, 8, 1024),
    "minicpm3": ("minicpm3-4b", dict(num_layers=31), 61440, 4, 1024),
    "deepseekv3": ("deepseek-v3",
                   dict(num_layers=5, first_k_dense=1, held_experts=8,
                        vocab_size=16160, capacity_factor=32.0),
                   131072, 8, 1175),
}


def _cell_serve_step(one_chip, cell: str):
    """The cell's real serve step (f32 pools, one instance, tp 1) with the
    Pallas kernel, compiled for a described v5e; returns (compiled, state
    shapes, frames a layer)."""
    import dataclasses
    from repro import compat
    from repro.configs import CONFIGS
    from repro.core import dcp
    from repro.models import transformer
    arch, over, tokens, M, MB = CELL_STEPS[cell]
    cfg = dataclasses.replace(CONFIGS[arch], **over)
    dev = next(iter(one_chip.device_set))
    mesh = compat.make_mesh((1, 1), ("data", "model"), devices=[dev])
    frames = tokens // 16 + 1
    d = dcp.DecodeDims(M=M, S=0, N=M, MB=MB, W=1, num_frames=frames,
                       page=16, data_size=1, tp=1, rounds_used=0)
    params = jax.eval_shape(lambda: dcp.to_decode_params(
        cfg, transformer.init_params(jax.random.PRNGKey(0), cfg), 1))
    state = jax.eval_shape(lambda: dcp.init_serve_state(
        cfg, d, 1, dtype=jnp.float32))
    shapes = {"slot_rid": (1, M), "slot_token": (1, M), "slot_pos": (1, M),
              "slot_active": (1, M), "append_frame": (1, M),
              "append_off": (1, M), "q_send_idx": (1, 0, 0),
              "q_recv_slot": (1, 0, 0), "work_src": (1, M),
              "work_bt": (1, M, MB), "work_len": (1, M),
              "ret_send_idx": (1, 0, 0), "merge_src": (1, M, 1),
              "merge_round": (1, M, 1), "merge_peer_row": (1, M, 1)}
    tables = {k: jax.ShapeDtypeStruct(v, jnp.int32)
              for k, v in shapes.items()}
    ops_force = ops.FORCE_IMPL
    ops.FORCE_IMPL = "pallas"
    try:
        fn = dcp.make_serve_step(cfg, d, mesh, params, state, tables)
        compiled = fn.lower(params, state, tables).compile()
    finally:
        ops.FORCE_IMPL = ops_force
    return compiled, state, frames


def _assert_pools_carried_in_place(compiled, state, frames):
    """No pool-sized op but the append, in a layer's pool or the flat run
    of all of them; the pools aliased to the step's output; temporaries
    under 5% of the pools."""
    pools = [v for k, v in state.items() if k.endswith("_pool")]
    pool_bytes = sum(v.size * v.dtype.itemsize for v in pools)
    flat = {v.shape[0] * v.shape[1] * v.shape[4] for v in pools}
    found = _pool_sized_ops(compiled.as_text(), flat | {frames})
    assert not found, found[:3]
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool_bytes
    assert ma.temp_size_in_bytes < 0.05 * pool_bytes, \
        (ma.temp_size_in_bytes, pool_bytes)


@pytest.mark.parametrize("cell", ["phi35moe", "minicpm3"])
def test_cell_decode_step_moves_no_pool(one_chip, cell):
    """Phi-3.5-MoE (2 layers, GQA, 131072 tokens) and MiniCPM3 (31
    layers, a 288-lane latent stored at 384, 61440 tokens): the serve step
    writes only the appended rows of its f32 pools, and the kernel reads
    the pages where they lie — no slice, write-back or relayout of a pool
    (``build_decode_step``'s in-place carry)."""
    compiled, state, frames = _cell_serve_step(one_chip, cell)
    assert "tpu_custom_call" in compiled.as_text()
    if cell == "minicpm3":
        assert state["kv_pool"].shape == (31, 1, 1, 1, frames, 16, 384)
    _assert_pools_carried_in_place(compiled, state, frames)


def test_deepseek_v3_share_decode_step_compiles_for_v5e(one_chip):
    """DeepSeek-V3 at one chip's share (1 dense + 4 MoE layers, 128 heads
    over a 576-lane latent stored at 640 lanes, 8 of 256 experts, the
    cell's 131072-token f32 pool): the serve step lowers and compiles for
    a v5e with the paged kernel in each of the trunk's two segments, and
    the pool stays one buffer carried in place (aliased to the step's
    output) with no pool-sized op but the append: no per-layer slice or
    write-back, and no relayout of the stacked state at the step's entry
    and exit."""
    compiled, state, frames = _cell_serve_step(one_chip, "deepseekv3")
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2       # dense segment, MoE segment
    assert state["kv_pool"].shape == (5, 1, 1, 1, frames, 16, 640)
    _assert_pools_carried_in_place(compiled, state, frames)
