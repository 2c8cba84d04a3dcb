"""The engine's phase spans and the decode step's named scopes.

``NanoCPEngine.span`` times each phase of ``step`` into ``engine.timings``
and, while ``jax.profiler`` records, names it ``nanocp.<phase>`` on the
host's trace line; ``build_decode_step`` tags its ops with the scopes
``pool_carry``, ``attention``, ``ffn`` and ``head``.  These cases pin both
on tiny engines on the CPU."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs import CONFIGS, reduced
from repro.core.bucketing import ShapeBuckets
from repro.models import init_params
from repro.serving.engine import NanoCPEngine

# the phases of one steady decode step, each its own stretch of ``step``
STEADY_PHASES = ("schedule", "lower", "harvest", "harvest.record", "upload",
                 "dispatch", "bookkeep")
# phases that never nest in one another (children such as
# ``prefill.forward`` lie inside ``prefill``)
DISJOINT_PHASES = ("handoff", "schedule", "reshard", "prefill", "lower",
                   "harvest", "harvest.record", "upload", "dispatch",
                   "bookkeep")
PREFILL_PHASES = ("prefill", "prefill.forward", "prefill.scatter",
                  "prefill.readback")
SCOPES = ("pool_carry", "attention", "ffn", "head")


def _key(phase: str) -> str:
    return phase.replace(".", "_") + "_us"


def _engine(arch: str, lengths=(20, 33), max_new: int = 8) -> NanoCPEngine:
    over = {"capacity_factor": 8.0} if CONFIGS[arch].is_moe else {}
    cfg = reduced(CONFIGS[arch], vocab_size=128, **over)
    params = jax.tree.map(lambda x: x.astype(jnp.float32),
                          init_params(jax.random.PRNGKey(0), cfg))
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    eng = NanoCPEngine(cfg, params, mesh, num_instances=1,
                       instances_per_node=1, kv_capacity_tokens=1024,
                       page_size=16,
                       shape_buckets=ShapeBuckets(m_buckets=(1, 2, 4),
                                                  s_buckets=(0,), window=1))
    rng = np.random.default_rng(0)
    for n in lengths:
        eng.add_request(rng.integers(0, 128, (n,)), max_new_tokens=max_new)
    return eng


@pytest.fixture(scope="module")
def step_timings():
    """``engine.timings`` after each step of a tiny engine, to the end."""
    eng = _engine("tinyllama-1.1b")
    out = []
    while eng.cluster.active or eng.cluster.waiting or eng._inflight:
        eng.step()
        out.append(dict(eng.timings))
    return out


def _steady(timings: list) -> list:
    return [t for t in timings if "dispatch_us" in t and "prefill_us" not in t
            and "harvest_us" in t]


@pytest.mark.parametrize("phase", STEADY_PHASES + ("step",))
def test_steady_steps_carry_each_phase(step_timings, phase):
    steady = _steady(step_timings)
    assert len(steady) >= 3
    for t in steady:
        assert t[_key(phase)] >= 0.0


@pytest.mark.parametrize("phase", PREFILL_PHASES)
def test_admission_step_carries_prefill_phases(step_timings, phase):
    first = step_timings[0]
    assert first[_key(phase)] > 0.0
    assert first[_key(phase)] <= first["prefill_us"]


@pytest.mark.parametrize("which", ["steady", "every"])
def test_phases_sum_within_step(step_timings, which):
    steps = _steady(step_timings) if which == "steady" else step_timings
    for t in steps:
        parts = sum(t.get(_key(p), 0.0) for p in DISJOINT_PHASES)
        assert 0.0 < parts <= t["step_us"]


def test_removed_keys_are_gone(step_timings):
    eng = _engine("tinyllama-1.1b")
    assert "copy_tokens" not in eng.hot_path_stats
    assert all("lookup_us" not in t for t in step_timings)


# ------------------------------------------------------- profiler spans
@pytest.fixture(scope="module")
def host_spans(tmp_path_factory):
    """[[name, start, end, args]] of the ``nanocp.*`` spans a profiler
    trace of a tiny engine's admissions and decode steps holds."""
    from jax.profiler import ProfileData
    eng = _engine("tinyllama-1.1b")
    eng.step()                  # compiles outside the trace
    eng.add_request(np.arange(5, 40) % 128, max_new_tokens=4)
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        while eng.cluster.active or eng.cluster.waiting or eng._inflight:
            eng.step()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{d}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("nanocp."):
                    s = float(e.start_ns)
                    out.append([e.name, s, s + float(e.duration_ns),
                                dict(e.stats)])
    return out


@pytest.mark.parametrize("phase", STEADY_PHASES + PREFILL_PHASES)
def test_each_phase_span_lies_inside_a_step_span(host_spans, phase):
    steps = [(s, e) for n, s, e, _ in host_spans if n == "nanocp.step"]
    spans = [(s, e) for n, s, e, _ in host_spans
             if n == "nanocp." + phase]
    assert steps and spans
    for s, e in spans:
        assert any(s0 <= s and e <= e0 for s0, e0 in steps), (phase, s, e)


def test_prefill_spans_carry_the_request(host_spans):
    fwd = [a for n, _, _, a in host_spans if n == "nanocp.prefill.forward"]
    assert fwd
    for a in fwd:
        assert a["tokens"] == 35 and a["rid"] == 2


# ------------------------------------------------------- decode-step scopes
@pytest.fixture(scope="module", params=["phi3.5-moe-42b-a6.6b",
                                        "minicpm3-4b"])
def step_hlo(request):
    """The compiled decode step's HLO text, for a GQA+MoE and an MLA
    config."""
    eng = _engine(request.param, max_new=3)
    for _ in range(3):
        eng.step()
    return eng.aot.executable(eng.last_bucket).as_text()


@pytest.mark.parametrize("scope", SCOPES)
def test_decode_step_ops_carry_each_scope(step_hlo, scope):
    assert f"/{scope}/" in step_hlo
