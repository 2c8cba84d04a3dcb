"""One run of one cell: set-up, the measured window, the check, the line.

    set-up   weights from the seed (one jitted call), the engine, the
             cell's AOT decode buckets (``aot.capture``), one prefill of
             every prompt length the cell's traffic uses, and for a closed
             set the sessions' own prefills.  All of it is ``setup_s``.
    window   ``--seconds`` of traffic through ``add_request`` / ``step``
             (``client.py``), with the profiler on for a slice of it when
             ``--trace 1``.
    after    peak device memory is read, the engine is released, and a
             sample of the served requests goes through the fp32
             reference (``check.py``).  None of that is in any metric.

The last line on stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``check``: each number compared with its limit); the last lines on
stderr repeat the check.
"""
from __future__ import annotations

import dataclasses
import gc
import inspect
import json
import math
import os
import shutil
import sys
import time

import jax
import numpy as np

from . import check, stats, trace as trace_mod, traffic
from .spec import BENCH_DIR, ROOT, Cell, peaks, reader

GIVE_UP_S = 60.0        # longest the client serves past the window close
SETUP_WAIT_S = 900.0    # longest one set-up request may take (cold compile)
TRACE_AT = 0.3          # traced slice: starts at this share of the window
TRACE_MAX_S = 8.0       # ... and lasts at most this long (or 40%)
CHECK_REQUESTS = 16     # requests the reference covers, at most
CHECK_POSITIONS = 384   # served positions compared, shared among them


class NoChip(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def configure_compile_cache() -> None:
    """Keep JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR``
    says, or else at one fixed path in the checkout; cache every program,
    so that a second run compiles nothing."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_chips(n: int, allow_cpu: bool = False) -> list:
    devs = jax.devices()
    if not allow_cpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, found {len(devs)}")
    return devs[:n]


# --------------------------------------------------------------- program
def program_config(cell: Cell):
    """The program's ``ModelConfig`` for this configuration, checked size
    by size against what the cell's architecture reads from the file."""
    from repro.configs import CONFIGS
    prog = cell.config["program"]
    pcfg = dataclasses.replace(CONFIGS[prog["repo_config"]],
                               **prog.get("overrides", {}))
    want = cell.arch.program_sizes(cell.model)
    bad = {k: (getattr(pcfg, k), v) for k, v in want.items()
           if getattr(pcfg, k) != v}
    if bad:
        raise ValueError(f"the program's {prog['repo_config']} differs from "
                         f"configs/{cell.entry['config']}.json: {bad}")
    return pcfg


def engine_defaults() -> dict:
    from repro.serving.engine import NanoCPEngine
    sig = inspect.signature(NanoCPEngine.__init__).parameters
    return {"slots": sig["max_slots_per_instance"].default,
            "page": sig["page_size"].default}


def build_engine(cell: Cell, pcfg, params, devices):
    """The engine as the deployment fixes it; every tuning option (page
    size, slots, shape and CP buckets, kernels) at the engine's default."""
    from repro import compat
    from repro.serving.engine import NanoCPEngine
    dep = cell.config["deployment"]
    mesh = compat.make_mesh(tuple(dep["mesh"]), ("data", "model"),
                            devices=devices)
    return NanoCPEngine(pcfg, params, mesh,
                        num_instances=dep["instances"],
                        instances_per_node=dep["instances_per_node"],
                        kv_capacity_tokens=cell.config["kv_capacity_tokens"])


def decode_keys(eng, rows: list, min_len: int, max_len: int, page: int,
                long_rows: int = 0) -> list:
    """Every AOT decode bucket a step can need while between 1 and
    max(rows) rows are active with the longest row between ``min_len`` and
    ``max_len`` tokens.

    Where the ring holds more than one instance and requests reach the
    first length the engine's CP buckets split, up to ``long_rows`` such
    rows also send queries across instances: every send bucket S up to
    that many rows, in every rotation round the ring has, with the longest
    shard at least that length over the ring's width (a split, or an
    escalation up to the whole ring, never makes a shard shorter)."""
    sb = eng.shape_buckets
    W = sb.window

    def pages(lo, hi):
        return range(max(1, -(-lo // page)), -(-hi // page) + 1)

    ms = sorted({sb.round_m(r) for r in rows})
    keys = {eng.aot.quantise(M, 0, p, W, 0)
            for M in ms for p in pages(min_len, max_len)}
    split = split_length(eng)
    if W > 1 and long_rows > 0 and split is not None and max_len >= split:
        ss = sorted({sb.round_s(s) for s in range(1, long_rows + 1)})
        keys |= {eng.aot.quantise(M, S, p, W, R)
                 for M in ms for S in ss for R in range(1, W)
                 for p in pages(max(min_len, split // W), max_len)}
    return sorted(keys)


def split_length(eng) -> int | None:
    """The shortest length the engine's CP buckets give a degree above 1
    (None where they never split)."""
    cp = getattr(eng.scheduler, "buckets", None)
    if cp is None:
        return None
    return next((e for e, d in zip(cp.edges, cp.degrees[1:]) if d > 1),
                None)


def long_row_bound(cell: Cell, eng, lens: list, out_hi: int) -> int:
    """How many rows long enough for a CP split can be resident at once:
    no more than the traffic sends, the decode slots hold, or the
    instances' KV pools fit at the first split length."""
    split = split_length(eng)
    if split is None:
        return 0
    dep = cell.config["deployment"]
    n = sum(1 for x in lens if x + out_hi >= split)
    fit = dep["instances"] * cell.config["kv_capacity_tokens"] // split
    return min(n, fit, engine_defaults()["slots"] * dep["instances"])


# ------------------------------------------------------------------- run
@dataclasses.dataclass
class Plan:
    reqs: list              # traffic.Req
    n_window: int           # the first n_window are due inside the window
    prompts: list           # token ids per request
    warm_lengths: list      # prompt lengths set-up prefills once
    keys: list              # AOT decode buckets


def plan(cell: Cell, eng, seed: int, seconds: float) -> Plan:
    d = engine_defaults()
    tr = cell.traffic
    m = cell.model
    if tr["kind"] == "open_loop":
        reqs, n_win = traffic.open_loop(tr, seed, seconds)
        # every rung of the ladder, whatever this seed's window holds
        lens = sorted(set(tr["prompt"]["ladder"])
                      | {r.prompt_len for r in reqs})
        out_hi = tr["output"]["hi"]
        keys = decode_keys(eng, list(range(1, d["slots"] + 1)),
                           min(lens) + 1, max(lens) + out_hi, d["page"],
                           long_row_bound(cell, eng,
                                          [r.prompt_len for r in reqs],
                                          out_hi))
        warm = lens
    else:
        k = traffic.session_count(tr, cell.config["kv_capacity_tokens"],
                                  d["slots"], d["page"])
        reqs, n_win = traffic.sessions(tr, seed, k), k
        lens = [r.prompt_len for r in reqs]
        grow = tr["growth_tokens"]
        keys = decode_keys(eng, list(range(1, k + 1)), max(lens),
                           max(lens) + grow, d["page"],
                           long_row_bound(cell, eng, lens, grow))
        warm = []
    prompts = traffic.prompt_tokens(seed, reqs, m.vocab_size)
    return Plan(reqs, n_win, prompts, warm, keys)


def _compile_counter():
    """Counts programs compiled or loaded from the persistent cache."""
    box = {"n": 0, "s": 0.0}

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            box["n"] += 1
            box["s"] += duration
    jax.monitoring.register_event_duration_secs_listener(listener)
    return box


_COMPILES = None


def _setup(cell: Cell, seed: int, seconds: float, allow_cpu: bool):
    """Weights, engine, AOT buckets and prefill warm-up (and a closed set's
    own prefills): everything ``setup_s`` counts."""
    global _COMPILES
    from repro.kernels import ops
    from .client import Client

    devices = require_chips(int(cell.entry["chips"]), allow_cpu)
    if ops.FORCE_IMPL is not None and not allow_cpu:
        raise NoChip(f"ops.FORCE_IMPL={ops.FORCE_IMPL!r} bypasses the "
                     "kernels")
    if _COMPILES is None:
        _COMPILES = _compile_counter()
    t_setup = time.perf_counter()
    pcfg = program_config(cell)
    params = cell.arch.make_params(cell.model, seed, devices[0])
    jax.block_until_ready(params)
    t_w = time.perf_counter() - t_setup
    eng = build_engine(cell, pcfg, params, devices)
    pl = plan(cell, eng, seed, seconds)
    t0 = time.perf_counter()
    eng.aot.capture(pl.keys)
    t_cap = time.perf_counter() - t0
    drv = Client(eng)
    rng = np.random.default_rng([seed, 3])
    t0 = time.perf_counter()
    for n in pl.warm_lengths:       # each prompt length once
        w = drv.add(rng.integers(0, cell.model.vocab_size, n), 2,
                    time.perf_counter(), False)
        _step_until(drv, lambda: w.rid not in drv.open,
                    f"the {n}-token warm-up request")
    if cell.traffic["kind"] == "sessions":
        # one session a step, as they would arrive: each admission's
        # prefill and KV scatter then stand alone
        for r, p in zip(pl.reqs, pl.prompts):
            t = drv.add(p, r.max_new_tokens, time.perf_counter(), True)
            _step_until(drv, lambda: bool(t.stamps),
                        f"the {r.prompt_len}-token session's first token")
        for _ in range(4):          # the first decode steps, off the clock
            drv.step()
    drv.sync()
    t_warm = time.perf_counter() - t0
    setup_s = time.perf_counter() - t_setup
    log(f"set-up {setup_s:.3f}s: weights {t_w:.3f}s, capture "
        f"{len(pl.keys)} buckets {t_cap:.3f}s, prefill/warm {t_warm:.3f}s")
    return devices, params, eng, drv, pl, setup_s


def _step_until(drv, done, what: str) -> None:
    """Steps the engine until ``done()``; a set-up the engine cannot finish
    (a request it never admits) is an error, not a hang."""
    t0 = time.perf_counter()
    while not done():
        if time.perf_counter() - t0 > SETUP_WAIT_S:
            raise RuntimeError(f"set-up: no progress on {what} in "
                               f"{SETUP_WAIT_S:.0f}s")
        drv.step()


def _serve_open_loop(drv, reqs, prompts, n_win: int, seconds: float,
                     traced: bool, trace_dir: str):
    """Offers ``reqs`` at their due times; after the window closes, serves
    on (the tail still arriving) until every request due inside it has its
    first token, or ``GIVE_UP_S`` has passed."""
    t_trace0 = seconds * TRACE_AT
    t_trace1 = t_trace0 + min(TRACE_MAX_S, 0.4 * seconds)
    ann = None
    tracked = []
    t_open = time.perf_counter()
    t_close = t_open + seconds
    due = [t_open + r.due for r in reqs]
    i = 0
    while True:
        now = time.perf_counter()
        while i < len(reqs) and due[i] <= now:
            tracked.append(drv.add(prompts[i], reqs[i].max_new_tokens,
                                   due[i], i < n_win))
            i += 1
        if now >= t_close and (
                all(t.stamps for t in tracked if t.in_window)
                or now >= t_close + GIVE_UP_S):
            break
        if traced:
            ann = _trace_edge(drv, ann, now - t_open, t_trace0, t_trace1,
                              trace_dir)
        if drv.busy():
            drv.step()
        elif i < len(reqs):
            drv.wait_until(due[i])
        else:
            break
    if ann is not None:         # the window ended inside the traced slice
        _trace_edge(drv, ann, math.inf, 0, 0, trace_dir)
    return tracked, t_open, t_close, time.perf_counter()


def _serve_sessions(drv, seconds: float, traced: bool, trace_dir: str):
    """Steps the closed set for the whole window."""
    t_trace0 = seconds * TRACE_AT
    t_trace1 = t_trace0 + min(TRACE_MAX_S, 0.4 * seconds)
    ann = None
    tracked = [drv.tracked[rid] for rid in sorted(drv.tracked)
               if drv.tracked[rid].in_window]
    t_open = time.perf_counter()
    t_close = t_open + seconds
    while True:
        now = time.perf_counter()
        if now >= t_close or not drv.busy():
            break
        if traced:
            ann = _trace_edge(drv, ann, now - t_open, t_trace0, t_trace1,
                              trace_dir)
        drv.step()
    if ann is not None:
        _trace_edge(drv, ann, math.inf, 0, 0, trace_dir)
    return tracked, t_open, t_close, time.perf_counter()


def run(cell: Cell, seed: int, seconds: float, traced: bool, *,
        control: bool = False, allow_cpu: bool = False) -> dict:
    devices, params, eng, drv, pl, setup_s = _setup(cell, seed, seconds,
                                                    allow_cpu)
    dev0 = devices[0]
    peak = peaks(dev0.device_kind) if not allow_cpu else \
        peaks("TPU v5 lite")

    # ------------------------------------------------------------ window
    aot0 = eng.aot.stats.online_compiles
    comp0 = (_COMPILES["n"], _COMPILES["s"])
    drv.steps.clear()
    drv.lateness.clear()
    trace_dir = os.path.join(BENCH_DIR, "out", f"trace_{cell.name}_{seed}")
    if cell.traffic["kind"] == "open_loop":
        tracked, t_open, t_close, give_up = _serve_open_loop(
            drv, pl.reqs, pl.prompts, pl.n_window, seconds, traced,
            trace_dir)
    else:
        tracked, t_open, t_close, give_up = _serve_sessions(
            drv, seconds, traced, trace_dir)
    online = eng.aot.stats.online_compiles - aot0
    compiles = (_COMPILES["n"] - comp0[0], _COMPILES["s"] - comp0[1])
    e2e = stats.end_to_end(
        [{"due": t.due, "stamps": t.stamps, "in_window": t.in_window}
         for t in tracked], (t_open, t_close), give_up)
    late = sorted(drv.lateness) or [0.0]
    log(f"window {seconds}s: {e2e['requests']} requests, "
        f"{len(drv.steps)} steps, client lateness p95 "
        f"{stats.percentile(late, 95) * 1e3:.3f} ms, online_compiles "
        f"{online}, programs compiled or loaded in the window "
        f"{compiles[0]} ({compiles[1]:.3f}s)")
    mem = [d.memory_stats() or {} for d in devices]
    mem_peak = max(int(s.get("peak_bytes_in_use", 0)) for s in mem)

    # ----------------------------------------------------------- outcome
    res = eng.results
    measured = [t for t in tracked if t.in_window]
    failed = sum(1 for t in measured if not t.stamps
                 or res[t.rid].oom or res[t.rid].rejected
                 or res[t.rid].shed)
    served = [{"rid": t.rid, "prompt": res[t.rid].prompt,
               "tokens": list(res[t.rid].tokens)} for t in measured
              if t.stamps and (cell.traffic["kind"] == "sessions"
                               or len(t.stamps) >= t.max_new_tokens)]
    run_rec = {"arch": cell.arch, "model": cell.model, "peaks": peak,
               "setup_s": setup_s, "e2e": e2e, "steps": list(drv.steps),
               "trace": None}
    del eng, drv
    gc.collect()

    breakdown = None
    if traced:
        rec = trace_mod.extract(trace_mod.find_xplane(trace_dir),
                                _traced_window(trace_dir))
        run_rec["trace"] = rec
        with open(trace_dir + ".json", "w") as f:   # the reduced record
            json.dump(rec, f)
        breakdown = {"device_ops": trace_mod.top_ops(rec),
                     "idle_gaps": trace_mod.idle_gaps(rec)}
        shutil.rmtree(trace_dir, ignore_errors=True)

    t0 = time.perf_counter()
    sample = check.sample(served, seed, CHECK_REQUESTS, CHECK_POSITIONS)
    min_margin = float(cell.limits.get("router_margin_min", 0.0))
    prog_gap, ctrl_gap, ntok = [], [], 0
    all_g, all_m, all_c = [], [], []
    for s in sample:
        if control:
            g, mg, c = check.control_gaps(cell.arch, cell.model, params, s)
            ctrl_gap.append(check.widest(c, mg, min_margin))
            all_c.append(c)
        else:
            g, mg = check.gaps(cell.arch, cell.model, params, s)
        prog_gap.append(check.widest(g, mg, min_margin))
        all_g.append(g)
        all_m.append(mg)
        ntok += len(s["tokens"])
    ref_s = time.perf_counter() - t0
    # with the control on, the control stands in the program's place
    compared = ctrl_gap if control else prog_gap
    max_gap = max(compared) if compared else None
    limit = cell.limits.get("max_logit_gap")
    log(f"reference over {len(sample)} requests, {ntok} served tokens, "
        f"{sum(len(s['at']) for s in sample)} positions compared "
        f"(prompts {[len(s['prompt']) for s in sample]}): {ref_s:.3f}s")
    del params
    gc.collect()

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = reader(m["name"])(run_rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": mem_peak}
    if traced:
        device["busy_s"] = trace_mod.busy_s(run_rec["trace"])
        device["window_s"] = trace_mod.window_s(run_rec["trace"])
    correct = (max_gap is not None and limit is not None
               and max_gap <= limit and not failed)
    out = {"correct": correct, "attempted": len(measured), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {"max_logit_gap": {"value": max_gap, "limit": limit}}
    out["_info"] = {"online_compiles": online,
                    "compiles_in_window": compiles[0],
                    "lateness_p95_ms": stats.percentile(late, 95) * 1e3,
                    "reference_s": ref_s, "e2e": e2e,
                    "capture_keys": len(pl.keys),
                    **_step_split(run_rec["steps"]),
                    "program_max_logit_gap": (max(prog_gap) if prog_gap
                                              else None),
                    "program_gap_per_request": prog_gap,
                    "program_gaps": _gap_stats(all_g),
                    "margins": _margin_stats(all_g, all_m, all_c)}
    if control:
        out["_info"]["control_gap_per_request"] = ctrl_gap
        out["_info"]["control_gaps"] = _gap_stats(all_c)
    return out


def _step_split(steps: list) -> dict:
    """The window's steps split into the engine's host work and its wait
    for the device's tokens (``harvest_us``), per step: which side a slow
    run lost its time on."""
    n = max(len(steps), 1)
    harvest = sum(s.spans["harvest_us"] for s in steps)
    step = sum(s.spans["step_us"] for s in steps)
    return {"steps": len(steps), "step_ms": step / n / 1e3,
            "harvest_ms": harvest / n / 1e3,
            "host_ms": (step - harvest) / n / 1e3}


def _margin_stats(gaps: list, margins: list, ctrl: list) -> dict:
    """The widest program (and control) gap when positions with a router
    margin under each candidate are left out, and the margins at the
    program's ten widest gaps: the readings ``router_margin_min`` is set
    from."""
    if not gaps:
        return {}
    g, m = np.concatenate(gaps), np.concatenate(margins)
    c = np.concatenate(ctrl) if ctrl else None
    out = {"at_widest": [[float(g[i]), float(m[i])]
                         for i in np.argsort(g)[-10:]]}
    for d in (0.0, 1e-3, 2e-3, 3e-3, 5e-3, 1e-2, 2e-2, 3e-2):
        keep = m >= d
        out[f"{d:g}"] = {"left_out": int((~keep).sum()),
                         "program": float(g[keep].max(initial=0.0)),
                         "control": (float(c[keep].max(initial=0.0))
                                     if c is not None else None)}
    return out


def _gap_stats(gaps: list) -> dict:
    if not gaps:
        return {}
    g = np.concatenate(gaps)
    return {"n": int(g.size), "nonzero": int((g > 0).sum()),
            "mean": float(g.mean()),
            **{f"p{q}": float(np.percentile(g, q)) for q in (50, 90, 99)},
            "max": float(g.max()),
            "top5": [float(x) for x in np.sort(g)[-5:]]}


def _trace_edge(drv, ann, t: float, t0: float, t1: float, trace_dir: str):
    """Starts the profiler when the window reaches ``t0`` and stops it at
    ``t1``, each at a step boundary with the device drained, so the traced
    slice holds exactly the steps dispatched inside it."""
    if ann is None and t0 <= t < t1 and not drv.tracing:
        drv.sync()
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
        ann = jax.profiler.TraceAnnotation("chipbench.traced_window")
        ann.__enter__()
        drv.tracing = True
    elif ann is not None and t >= t1:
        drv.sync()
        ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        drv.tracing = False
        ann = None
    return ann


def _traced_window(trace_dir: str) -> tuple:
    """[start, end) of the ``chipbench.traced_window`` span, in the
    trace's own clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace_mod.find_xplane(trace_dir))
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                if e.name == "chipbench.traced_window":
                    return int(e.start_ns), int(e.start_ns + e.duration_ns)
    raise ValueError("the trace holds no chipbench.traced_window span")


def result_line(out: dict) -> str:
    keep = {k: v for k, v in out.items() if not k.startswith("_")}
    chk = keep.pop("check")
    keep["check"] = chk                 # the compared numbers come last
    return json.dumps(keep)
