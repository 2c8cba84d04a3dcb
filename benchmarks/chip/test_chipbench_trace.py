"""The trace reduction on a small recorded trace (``fixtures/``): busy
union, kernel and program sums, the longest idle gaps and what the host
was doing in them."""
import json
import os

import pytest

from chipbench import names, spec, trace

FIXTURE = os.path.join(spec.BENCH_DIR, "fixtures", "trace_small.json")


@pytest.fixture(scope="module")
def rec():
    with open(FIXTURE) as f:
        return json.load(f)


def _union_by_hand(rec, dev):
    t0, t1 = rec["window_ns"]
    pts = sorted((max(s, t0), min(s + d, t1))
                 for _, s, d in rec["devices"][dev]["ops"])
    tot, cur_s, cur_e = 0, None, None
    for s, e in pts:
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                tot += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        tot += cur_e - cur_s
    return tot


def test_busy_is_the_union_of_op_intervals(rec):
    devs = list(rec["devices"])
    want = sum(_union_by_hand(rec, d) for d in devs) / len(devs) / 1e9
    assert trace.busy_s(rec) == pytest.approx(want)
    assert 0 < trace.busy_s(rec) <= trace.window_s(rec)


def test_kernel_and_step_sums(rec):
    s, n = trace.op_time_s(rec, names.PAGED_ATTENTION_KERNEL)
    assert n > 0 and s > 0
    want = sum(d for dev in rec["devices"].values()
               for name, _, d in dev["ops"] if "paged" in name) / 1e9
    assert s == pytest.approx(want)
    m, k = trace.module_time_s(rec, names.DECODE_STEP_MODULE)
    assert k > 0 and m >= s / k


def test_idle_gaps_are_the_longest_and_named(rec):
    gaps = trace.idle_gaps(rec)
    assert gaps and len(gaps) <= 10
    secs = [g[1] for g in gaps]
    assert secs == sorted(secs, reverse=True)
    busy = trace.busy_s(rec)
    assert sum(secs) <= trace.window_s(rec) - busy + 1e-9
    assert all(g[0] == "no span" or g[0].startswith("chipbench.")
               for g in gaps)


def test_top_ops_group_instances(rec):
    top = trace.top_ops(rec)
    assert 0 < len(top) <= 10
    assert top == sorted(top, key=lambda x: -x[1])
    assert trace.op_family("fusion.123") == "fusion"
    assert trace.op_family("copy-start") == "copy-start"


def test_synthetic_case_by_hand():
    rec = {"window_ns": [0, 100],
           "devices": {"/device:TPU:0": {
               "ops": [["%while.1 = (s32[]) while(...)", 10, 30],
                       ["%a.1 = f32[8] fusion(%while.1)", 12, 10],
                       ["b", 25, 10], ["a.2", 70, 10], ["c", 95, 50]],
               "modules": [["jit_step", 10, 30]]}},
           "host": [["chipbench.step", 0, 60], ["chipbench.wait", 60, 40]]}
    # busy: [10, 40) + [70, 80) + [95, 100) = 45 of 100
    assert trace.busy_s(rec) == pytest.approx(45e-9)
    gaps = trace.idle_gaps(rec)
    assert [round(g[1] * 1e9) for g in gaps] == [30, 15, 10]
    assert [g[0] for g in gaps] == ["chipbench.step", "chipbench.wait",
                                    "chipbench.step"]
    # self time: the while's 30 less the 20 of its body
    assert trace.top_ops(rec) == [["c", 50e-9], ["a", 20e-9],
                                  ["while", 10e-9], ["b", 10e-9]]
    assert trace.op_time_s(rec, "a") == (20e-9, 2)
    assert trace.op_time_s(rec, "fusion") == (0.0, 0)
