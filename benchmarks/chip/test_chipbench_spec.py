"""Everything loads by name from files: every entry of ``BENCHMARK.json``,
every configuration and traffic file, every metric's reader, and the
four-chip cell that is kept out of ``BENCHMARK.json``."""
import glob
import json
import os
import re

import pytest

from chipbench import spec, traffic

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert BENCH["command"][1] == "benchmarks/chip/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads(cell):
    c = spec.load_cell(cell)
    assert c.entry["chips"] in (1, 4)
    assert c.model.num_layers >= 1
    assert c.limits.get("max_logit_gap", 0) > 0
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    # a per-layer metric only where the cell reports what it moves
    for m in c.per_layer:
        assert m["moves"] in names


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    spec.BENCH_DIR, "configs", "*.json"))))
def test_every_config_file(path):
    cfg = json.load(open(path))
    assert os.path.basename(path) == cfg["name"] + ".json"
    for key, published in cfg["published"].items():
        assert cfg[key] != published and key in cfg["reduced"]
    listed = [c for c in BENCH["configs"] if c["name"] == cfg["name"]]
    for c in listed:
        assert c["file"] == os.path.relpath(path, spec.ROOT)
        assert c["reduced"] == cfg["reduced"] and c["source"] == cfg["source"]


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    spec.BENCH_DIR, "traffic", "*.json"))))
def test_every_traffic_file(path):
    tr = traffic.load(path)
    if tr["kind"] == "open_loop":
        reqs, n = traffic.open_loop(tr, 2**31 + 99, 51)
        assert n >= 50 and len(reqs) >= n
    else:
        k = traffic.session_count(tr, 131072, 16, 16)
        assert k >= 1 and traffic.sessions(tr, 1, k)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.reader(metric))


def test_four_chip_cell_builds_from_its_files():
    """``phi35moe.dcp.4c`` is not in BENCHMARK.json; it builds from
    ``cells/``, ``configs/`` and ``traffic/`` alone."""
    from chipbench import harness
    assert "phi35moe.dcp.4c" not in [w["name"] for w in BENCH["workloads"]]
    c = spec.load_cell("phi35moe.dcp.4c")
    dep = c.config["deployment"]
    assert c.entry["chips"] == 4 and dep["mesh"] == [4, 1]
    assert dep["instances"] * dep["experts_per_chip"] == c.model.num_experts
    pcfg = harness.program_config(c)
    assert pcfg.num_layers == 2 and pcfg.capacity_factor == 8.0
    reqs, n = traffic.open_loop(c.traffic, 7, BENCH["run_seconds"])
    lens = [r.prompt_len for r in reqs[:n]]
    assert max(lens) > 32768          # long requests the CP buckets split
    assert sum(x > 32768 for x in lens) == round(0.05 * n)


def test_four_chip_cell_plans_its_cross_instance_buckets():
    """Set-up of ``phi35moe.dcp.4c`` captures the decode buckets that send
    rows across the four instances (S > 0, every ring round), not only
    those of one instance; the engine here is its bucket logic alone."""
    from types import SimpleNamespace

    from chipbench import harness
    from repro.core.aot import AOTGraphEngine
    from repro.core.bucketing import DEFAULT_BUCKETS, ShapeBuckets
    from repro.serving.engine import NanoCPEngine
    c = spec.load_cell("phi35moe.dcp.4c")
    dep = c.config["deployment"]
    W = dep["instances"]
    eng = SimpleNamespace(
        shape_buckets=ShapeBuckets(window=W),
        aot=AOTGraphEngine(None, r_ladder=NanoCPEngine._r_ladder(
            W, dep["instances_per_node"])),
        scheduler=SimpleNamespace(buckets=DEFAULT_BUCKETS))
    pl = harness.plan(c, eng, 7, BENCH["run_seconds"])
    local = [k for k in pl.keys if k[1] == 0]
    cross = [k for k in pl.keys if k[1] > 0]
    assert local and cross
    assert all(k[3] == W for k in pl.keys)
    # every rotation round of the ring, and sends up to the rows that fit
    assert {k[4] for k in cross} == {eng.aot.quantise(1, 1, 8, W, r)[4]
                                     for r in range(1, W)}
    fit = W * c.config["kv_capacity_tokens"] // DEFAULT_BUCKETS.edges[0]
    assert max(k[1] for k in cross) == ShapeBuckets().round_s(fit)
    # a split request's longest shard holds at least 32768 / W tokens
    assert min(k[2] for k in cross) >= 32768 // W // 16
    # a one-instance deployment sends nothing across
    one = spec.load_cell("phi35moe.longdecode.1c")
    eng1 = SimpleNamespace(shape_buckets=ShapeBuckets(window=1),
                           aot=AOTGraphEngine(None),
                           scheduler=SimpleNamespace(buckets=DEFAULT_BUCKETS))
    assert all(k[1] == 0 for k in harness.plan(one, eng1, 7, 51).keys)
