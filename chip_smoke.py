"""Serve a few requests through NanoCP on a TPU and check the logits.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the cross-chip path on a 2x2 host

Drives the normal entry points — ``NanoCPEngine`` -> ``add_request`` ->
``step``/``run`` — so the scheduler, page table, ``routing.lower_plan``, the
AOT decode step with the paged Pallas kernel, the flash prefill kernel and
the MoE layer all run as a user would run them.  Model: Phi-3.5-MoE at its
published widths (d_model 4096, 32 q / 8 kv heads x 128, 16 experts top-2,
expert width 6400, vocab 32064) with random bf16 weights from ``--seed``.
Two cuts, both stated in ``smoke_config``:

  * depth: 2 of the 32 layers (the layer period is 1, so two whole periods);
  * expert capacity factor E/k = 8, so no routed assignment is ever dropped
    (dropless routing is not built yet) and the reference needs no capacity.

Every decode step's logits are compared with ``models.reference`` — a plain
float32 forward under matmul precision "highest" — over prompt + generated
tokens.  The last line of stdout is one JSON object; it says ``"ok": true``
only when every request produced its tokens, the logits are within
``LOGIT_TOL`` of the reference and every compiled decode step holds the
Pallas kernel.  Without a TPU, or with ``ops.FORCE_IMPL`` set, the script
exits non-zero before doing anything.

One process does everything and starts no other: the chip belongs to the
process that first touches JAX.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax                                              # noqa: E402
import numpy as np                                      # noqa: E402

from repro import compat                                # noqa: E402
from repro.configs import CONFIGS                       # noqa: E402
from repro.core.bucketing import DEFAULT_BUCKETS, CPBuckets  # noqa: E402
from repro.kernels import ops                           # noqa: E402
from repro.models import init_params, reference         # noqa: E402
from repro.serving.engine import NanoCPEngine           # noqa: E402

MODEL = "phi3.5-moe-42b-a6.6b"
NUM_LAYERS = 2
PAGE = 16
NEW_TOKENS = 8
KV_TOKENS_PER_INSTANCE = 8192
# prompt lengths, none a multiple of the 128-row prefill block
PROMPTS = {1: (37, 300, 1000, 2500),
           # the 6k request is the one whose CP degree is above 1
           4: (37, 300, 1000, 6000)}
# 4 chips: length -> CP degree, cut from the profiled thresholds
# (core/bucketing.py, 32k+) so that a 6k request spans two instances
FOUR_CHIP_BUCKETS = CPBuckets(edges=(4096,), degrees=(1, 2))
# max |engine logit - reference logit| over every decode step, request and
# vocabulary entry.  Logits are O(1) here (unit-variance normed hidden
# state, head std d_model^-1/2).  The engine computes in bf16 with f32
# accumulation; its logits are also rounded to bf16 before they are
# returned (0.016 at |logit| 4).  See PERF.md for the measured error and
# why a wrong page, a wrong expert or bf16 accumulation exceeds the bound.
LOGIT_TOL = 0.25


def smoke_config():
    base = CONFIGS[MODEL]
    return dataclasses.replace(
        base, num_layers=NUM_LAYERS,
        capacity_factor=base.num_experts / base.num_experts_per_tok)


def configure_compile_cache() -> None:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says; without it, at one fixed path in the
    checkout (the path is part of the cache key)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))


def serve(cfg, params, mesh, prompts: dict, *,
          buckets: CPBuckets = DEFAULT_BUCKETS) -> dict:
    """Serve ``prompts`` (rid -> token list) for NEW_TOKENS tokens each and
    return what the engine produced, then release the engine."""
    I = mesh.shape["data"]
    t0 = time.perf_counter()
    eng = NanoCPEngine(cfg, params, mesh, num_instances=I,
                       instances_per_node=I,
                       kv_capacity_tokens=KV_TOKENS_PER_INSTANCE,
                       page_size=PAGE, buckets=buckets, keep_logits=True)
    for rid, toks in prompts.items():
        assert eng.add_request(toks, max_new_tokens=NEW_TOKENS) == rid
    eng.step()                  # admission, prefill, first decode dispatch
    bindings = {rid: list(eng.cluster.active[rid].kv_binding)
                for rid in eng.cluster.active}
    rounds = eng.last_rounds_used
    eng.run()
    wall = time.perf_counter() - t0
    out = {
        "tokens": {rid: list(r.tokens) for rid, r in eng.results.items()},
        "logits": {rid: np.stack(v) for rid, v in eng.step_logits.items()},
        "bindings": bindings,
        "rounds_used": rounds,
        "aot": eng.aot.stats.as_dict(),
        "kernel_in_step": all(
            "tpu_custom_call" in eng.aot.executable(k).as_text()
            for k in eng.aot.cached_keys()),
        "steps": eng.hot_path_stats["steps"],
        "wall_s": wall,
    }
    del eng
    gc.collect()
    return out


def compare(cfg, params, prompts: dict, served: dict) -> dict:
    """Reference logits over prompt + generated tokens for every request:
    max |d logit| of each decode step, and greedy-token agreement wherever
    the reference's top-2 margin exceeds twice the tolerance."""
    V = cfg.vocab_size
    per_req, mismatches = {}, []
    for rid, prompt in prompts.items():
        toks = served["tokens"][rid]
        seq = list(prompt) + toks[:-1]
        ref = reference.forward_last_logits(cfg, params, seq,
                                            last=len(toks))[:, :V]
        got = served["logits"][rid][:, :V]       # decode steps: toks[1:]
        per_req[rid] = float(np.max(np.abs(got - ref[1:])))
        for j, t in enumerate(toks):
            top2 = np.sort(ref[j])[-2:]
            if top2[1] - top2[0] > 2 * LOGIT_TOL and t != int(ref[j].argmax()):
                mismatches.append((rid, j, t, int(ref[j].argmax())))
    return {"max_abs_dlogit": per_req, "token_mismatches": mismatches}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    if ops.FORCE_IMPL is not None:
        print(f"chip_smoke: ops.FORCE_IMPL={ops.FORCE_IMPL!r} would bypass "
              "the kernels", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(jax.devices())}", file=sys.stderr)
        return 2
    configure_compile_cache()

    cfg = smoke_config()
    print(f"model {MODEL}: {NUM_LAYERS} of 32 layers, published widths, "
          f"capacity factor {cfg.capacity_factor:g}, bf16 weights, "
          f"seed {args.seed}; chips {args.chips}", flush=True)
    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(args.seed), cfg)
    jax.block_until_ready(params)
    print(f"init params {time.perf_counter() - t0:.1f}s", flush=True)
    rng = np.random.default_rng(args.seed)
    prompts = {rid: rng.integers(0, cfg.vocab_size, n).tolist()
               for rid, n in enumerate(PROMPTS[args.chips])}
    mesh = compat.make_mesh((args.chips, 1), ("data", "model"),
                            devices=jax.devices()[:args.chips])

    served = serve(cfg, params, mesh, prompts,
                   buckets=(FOUR_CHIP_BUCKETS if args.chips == 4
                            else DEFAULT_BUCKETS))
    peak = [d.memory_stats()["peak_bytes_in_use"]
            for d in jax.devices()[:args.chips]]
    t0 = time.perf_counter()
    cmp = compare(cfg, params, prompts, served)
    ref_s = time.perf_counter() - t0

    failures = []
    for rid, prompt in prompts.items():
        n = len(served["tokens"][rid])
        print(f"request {rid}: prompt {len(prompt)} tokens, generated {n}, "
              f"kv binding {served['bindings'].get(rid)}, max |d logit| "
              f"{cmp['max_abs_dlogit'][rid]:.4f}", flush=True)
        if n != NEW_TOKENS:
            failures.append(f"request {rid} generated {n} tokens")
    worst = max(cmp["max_abs_dlogit"].values())
    print(f"max |d logit| vs fp32 reference: {worst:.4f} "
          f"(tolerance {LOGIT_TOL})")
    if not worst <= LOGIT_TOL:
        failures.append(f"logits off by {worst:.4f} > {LOGIT_TOL}")
    if cmp["token_mismatches"]:
        failures.append(f"greedy tokens differ: {cmp['token_mismatches']}")
    aot = served["aot"]
    print(f"decode steps {served['steps']}, online_compiles "
          f"{aot['online_compiles']}, capture_seconds "
          f"{aot['capture_seconds']:.1f}, serve wall {served['wall_s']:.1f}s, "
          f"reference {ref_s:.1f}s")
    print("peak_bytes_in_use per device: " + ", ".join(map(str, peak)))
    print(f"decode step HLO holds tpu_custom_call: "
          f"{served['kernel_in_step']}")
    if not served["kernel_in_step"]:
        failures.append("a compiled decode step has no Pallas kernel")
    if args.chips == 4:
        wide = {r: b for r, b in served["bindings"].items() if len(b) > 1}
        print(f"bindings over more than one instance: {wide}; rotation "
              f"rounds used {served['rounds_used']}")
        if not wide or served["rounds_used"] < 1:
            failures.append("no request spanned instances over the ring")
    for f in failures:
        print(f"FAIL: {f}")
    print(json.dumps({"ok": not failures,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
