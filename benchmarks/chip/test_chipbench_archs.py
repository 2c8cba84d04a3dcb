"""Architectures load by name from ``archs/``.

Golden readings of the default architecture, recorded from the harness
before the architecture files existed (``fixtures/golden_default_arch.json``):
at a tiny size on the CPU the seeded weights are bitwise equal and the
reference's logits and router margins (fp32 and the fp8 control) equal to
the last bit; at the published sizes every count is equal; and the
program is held to the same sizes.  Then an architecture added by files
alone, in a copy of the benchmark, serves a tiny closed-set cell that
comes out ``correct`` and is counted by the readers.
"""
import hashlib
import json
import os
import shutil
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "src"))

import chipbench_tiny as tiny                              # noqa: E402
from chipbench import flops, harness, spec                 # noqa: E402
from chipbench.client import StepRecord                    # noqa: E402

with open(os.path.join(spec.BENCH_DIR, "fixtures",
                       "golden_default_arch.json")) as _f:
    GOLDEN = json.load(_f)
COUNTS = ("linear_per_token", "head", "prefill_attention", "prefill",
          "decode_attention", "decode_token")


def _sha(a) -> str:
    a = np.asarray(a)
    h = hashlib.sha256()
    h.update(f"{a.dtype}{a.shape}".encode())
    h.update(a.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def default():
    return spec.architecture()


def _tiny(default, kind):
    m = default.from_config(tiny.config(kind))
    return m, default.make_params(m, GOLDEN["param_seed"])


@pytest.mark.parametrize("kind", ["gqa_moe", "mla"])
def test_default_weights_are_bitwise_the_recorded(default, kind):
    _, params = _tiny(default, kind)
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    got = {jax.tree_util.keystr(k): _sha(v) for k, v in flat}
    assert got == GOLDEN["tiny"][kind]["params"]


@pytest.mark.parametrize("kind", ["gqa_moe", "mla"])
@pytest.mark.parametrize("quant", [None, "fp8"])
def test_default_reference_is_bitwise_the_recorded(default, kind, quant):
    m, params = _tiny(default, kind)
    toks = np.random.default_rng(GOLDEN["token_seed"]).integers(
        0, m.vocab_size, GOLDEN["tokens"])
    logits, margins = default.logits_and_margins(
        m, params, toks, GOLDEN["start"], GOLDEN["n"], quant)
    want = GOLDEN["tiny"][kind]
    assert _sha(logits) == want[f"logits.{quant}"]
    assert _sha(margins) == want[f"margins.{quant}"]


@pytest.mark.parametrize("config", ["phi3_5_moe", "minicpm3_4b"])
@pytest.mark.parametrize("count", COUNTS)
def test_default_counts_are_the_recorded(default, config, count):
    with open(os.path.join(spec.BENCH_DIR, "configs", f"{config}.json")) as f:
        m = default.from_config(json.load(f))
    fn = getattr(default, count)
    if count in ("linear_per_token", "head"):
        got = fn(m)
    else:
        lens = GOLDEN["prefill_lengths" if count.startswith("prefill")
                      else "decode_contexts"]
        got = [list(r) if isinstance(r, tuple) else r
               for r in (fn(m, n) for n in lens)]
    assert got == GOLDEN["counts"][config][count]


@pytest.mark.parametrize("cell", sorted(GOLDEN["program_sizes"]))
def test_program_is_held_to_the_recorded_sizes(cell):
    if cell.startswith("tiny."):
        c = tiny.cell(cell.split(".")[1], "sessions", 1.0)
    else:
        c = spec.load_cell(cell)
    assert c.arch.program_sizes(c.model) == GOLDEN["program_sizes"][cell]
    harness.program_config(c)           # the program matches: no error


# ------------------------------------------------- an architecture by files
TOY = '''

CALLS = []


def _counted(name, fn):
    def call(*args, **kw):
        CALLS.append(name)
        return fn(*args, **kw)
    return call


for _name in ("make_params", "logits_and_margins", "program_sizes",
              "prefill_attention", "prefill", "decode_attention",
              "decode_token"):
    globals()[_name] = _counted(_name, globals()[_name])
'''


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of the benchmark with one architecture, configuration,
    traffic mix and cell added as files; nothing in it is edited."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    bench = root / os.path.relpath(spec.BENCH_DIR, spec.ROOT)
    shutil.copytree(spec.BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__"))
    with open(bench / "archs" / "default.py") as f:
        (bench / "archs" / "toy.py").write_text(f.read() + TOY)

    def config(name, arch, **overrides):
        cfg = dict(tiny.config("mla"), name=name, architecture=arch)
        cfg["program"] = dict(cfg["program"], overrides=dict(
            cfg["program"]["overrides"], **overrides))
        (bench / "configs" / f"{name}.json").write_text(json.dumps(cfg))

    config("toy_tiny", "toy")
    config("toy_tiny_3", "toy", num_layers=3)
    config("nothing_tiny", "no_such_arch")
    tr = tiny.cell("mla", "sessions", 1.0).traffic
    (bench / "traffic" / "tiny_sessions.json").write_text(json.dumps(tr))
    for name, cfg in (("tiny.toy.sessions", "toy_tiny"),
                      ("tiny.toy3.sessions", "toy_tiny_3"),
                      ("tiny.nothing.sessions", "nothing_tiny")):
        entry = {"name": name, "config": cfg, "traffic": "tiny_sessions",
                 "chips": 1, "why": "a tiny closed set on the CPU"}
        (bench / "cells" / f"{name}.json").write_text(json.dumps(
            {"cell": entry, "limits": {"max_logit_gap": 0.1}}))
    return str(root)


def test_added_architecture_serves_correct_and_is_counted(checkout):
    cell = spec.load_cell("tiny.toy.sessions", root=checkout)
    assert cell.arch.__file__ == os.path.join(
        checkout, os.path.relpath(spec.BENCH_DIR, spec.ROOT), "archs",
        "toy.py")
    out = harness.run(cell, 2**31 + 21, 3.0, False, allow_cpu=True)
    assert out["correct"], (out["check"], out["_info"])
    assert out["check"]["max_logit_gap"]["value"] <= 0.1
    assert {"program_sizes", "make_params",
            "logits_and_margins"} <= set(cell.arch.CALLS)

    # the counting readers reach the new architecture's counts
    default, m = spec.architecture(), cell.model
    steps = [StepRecord(2, (300, 500), {"step_us": 3000.0,
                                        "prefill_us": 1000.0}, (400,), True),
             StepRecord(2, (301, 501), {"step_us": 2000.0,
                                        "prefill_us": 0.0}, (), False)]
    trace = {"window_ns": [0, 10_000], "host": [], "devices": {
        "/device:TPU:0": {"modules": [], "ops": [
            ["%paged_decode_attention.1 = f32[] custom-call()", 0, 500],
            ["%_flash_fwd_kernel.2 = f32[] custom-call()", 600, 900]]}}}
    peak = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e6}
    run = {"arch": cell.arch, "model": m, "peaks": peak, "steps": steps,
           "trace": trace}
    cell.arch.CALLS.clear()
    got = {name: spec.reader(name)(run) for name in (
        "decode_step.mfu", "paged_attn_roofline", "prefill.mfu",
        "flash_prefill_roofline")}
    assert set(cell.arch.CALLS) == {"decode_token", "decode_attention",
                                    "prefill", "prefill_attention"}
    ops = default.decode_token(m, 301) + default.decode_token(m, 501)
    assert got["decode_step.mfu"] == pytest.approx(
        100 * ops / (2000e-6 * 1e9))
    att = [default.decode_attention(m, c) for c in (300, 500)]
    assert got["paged_attn_roofline"] == pytest.approx(100 * flops.roofline_s(
        sum(a[0] for a in att), sum(a[1] for a in att), peak) / 500e-9)
    assert got["prefill.mfu"] == pytest.approx(
        100 * default.prefill(m, 400) / (1000e-6 * 1e9))
    ops, byt = default.prefill_attention(m, 400)
    assert got["flash_prefill_roofline"] == pytest.approx(
        100 * flops.roofline_s(ops, byt, peak) / 900e-9)


def test_added_architecture_still_holds_the_program_to_its_sizes(checkout):
    cell = spec.load_cell("tiny.toy3.sessions", root=checkout)
    with pytest.raises(ValueError, match="num_layers"):
        harness.program_config(cell)


def test_unknown_architecture_fails_at_load_by_name(checkout):
    with pytest.raises(KeyError, match="no_such_arch"):
        spec.load_cell("tiny.nothing.sessions", root=checkout)
