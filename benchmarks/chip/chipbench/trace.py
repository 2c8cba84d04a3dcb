"""From a profiler trace to numbers: busy time, kernel time, idle gaps.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes (with
``jax.profiler.ProfileData``, nothing else) into a small plain record:

    {"window_ns": [t0, t1],
     "devices": {plane name: {"ops": [[name, start_ns, dur_ns], ...],
                              "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[span name, start_ns, dur_ns], ...]}

where ``ops`` are the device's XLA operations (kernels among them),
``modules`` the compiled programs they ran in, and ``host`` the
benchmark's own ``TraceAnnotation`` spans (``chipbench.*``).  Everything
after that works on the record, so the reduction is tested on a small
recorded trace kept in ``fixtures/``.
"""
from __future__ import annotations

import glob
import os
import re

HOST_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.traced_window"     # marks the traced slice
# the device's op-level and program-level lines in a TPU trace
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str, window_ns: tuple) -> dict:
    """The plain record of one trace, cut to ``window_ns`` (host clock,
    the clock the profiler puts every plane on)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    t0, t1 = window_ns
    devices, host = {}, []

    def cut(events):
        out = []
        for e in events:
            s, d = int(e.start_ns), int(e.duration_ns)
            if s + d <= t0 or s >= t1:
                continue
            out.append([e.name, s, d])
        return out

    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            rec = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    rec["ops"] = cut(line.events)
                elif line.name == MODULES_LINE:
                    rec["modules"] = cut(line.events)
            devices[plane.name] = rec
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in cut(line.events)
                         if e[0].startswith(HOST_PREFIX)]
    return {"window_ns": [int(t0), int(t1)], "devices": devices,
            "host": sorted(host, key=lambda e: e[1])}


def _merge(intervals: list) -> list:
    """Union of [start, end) intervals, sorted and merged."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(rec: dict, device: str) -> list:
    t0, t1 = rec["window_ns"]
    ops = rec["devices"][device]["ops"]
    return _merge([[max(s, t0), min(s + d, t1)] for _, s, d in ops
                   if min(s + d, t1) > max(s, t0)])


def busy_s(rec: dict) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    devs = list(rec["devices"])
    if not devs:
        return 0.0
    tot = sum(sum(e - s for s, e in busy_intervals(rec, d)) for d in devs)
    return tot / len(devs) / 1e9


def window_s(rec: dict) -> float:
    t0, t1 = rec["window_ns"]
    return (t1 - t0) / 1e9


def op_name(event_name: str) -> str:
    """The op's own name: a TPU trace names an op event by its whole HLO
    instruction (``%fusion.12 = bf16[...] fusion(...)``); keep
    ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def op_time_s(rec: dict, pattern: str) -> tuple[float, int]:
    """(seconds, count) of the device ops whose own name matches
    ``pattern`` (a regular expression from its start), summed over
    devices."""
    rx = re.compile(pattern)
    tot, n = 0, 0
    for dev in rec["devices"].values():
        for name, _, d in dev["ops"]:
            if rx.match(op_name(name)):
                tot += d
                n += 1
    return tot / 1e9, n


def module_time_s(rec: dict, pattern: str) -> tuple[float, int]:
    """(seconds, count) of the compiled programs matching ``pattern``."""
    rx = re.compile(pattern)
    tot, n = 0, 0
    for dev in rec["devices"].values():
        for name, _, d in dev["modules"]:
            if rx.search(name):
                tot += d
                n += 1
    return tot / 1e9, n


def op_family(name: str) -> str:
    """An op's name without its instance number: ``fusion.12`` and
    ``fusion.7`` are one family."""
    return re.sub(r"[.:]\d+$", "", op_name(name))


def self_times(ops: list) -> list:
    """[[name, self ns]]: each op's duration less the ops nested inside it
    (a ``while`` holds its body's ops on the same line)."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    out, stack = [], []                 # stack: [index in out, end]
    for name, s, d in evs:
        while stack and s >= stack[-1][1]:
            stack.pop()
        if stack:
            out[stack[-1][0]][1] -= min(d, stack[-1][1] - s)
        out.append([name, d])
        stack.append([len(out) - 1, s + d])
    return out


def top_ops(rec: dict, n: int = 10) -> list:
    """The op families that took most device time of their own (nested
    ops not counted twice): [[name, seconds]]."""
    tot: dict = {}
    for dev in rec["devices"].values():
        for name, d in self_times(dev["ops"]):
            k = op_family(name)
            tot[k] = tot.get(k, 0) + d
    ndev = max(len(rec["devices"]), 1)
    return [[k, v / 1e9 / ndev]
            for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(rec: dict, n: int = 10) -> list:
    """The longest stretches in which the first device ran nothing, each
    named by the host span that covered most of it ("no span" where the
    host was outside the benchmark's spans; the span that marks the traced
    slice itself names nothing): [[name, seconds]]."""
    devs = sorted(rec["devices"])
    if not devs:
        return []
    t0, t1 = rec["window_ns"]
    gaps, prev = [], t0
    for s, e in busy_intervals(rec, devs[0]) + [[t1, t1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [h for h in rec["host"] if h[0] != WINDOW_SPAN]
    out = []
    for gs, ge in gaps[:n]:
        best, cover = "no span", 0
        for name, s, d in spans:
            ov = min(ge, s + d) - max(gs, s)
            if ov > cover:
                best, cover = name, ov
        out.append([best, (ge - gs) / 1e9])
    return out
