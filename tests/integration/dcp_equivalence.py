"""Extended DCP equivalence: MLA, MoE, SSM, hybrid families on 8 fake devices."""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp

from repro import compat
from repro.configs import CONFIGS, reduced
from repro.models import transformer
from repro.models import init_params
from repro.core import dcp, migrate, routing
from repro.core.state import ClusterState, Request
from repro.core.scheduler import DualBalancedScheduler
from repro.core.bucketing import CPBuckets, ShapeBuckets

# max |logit - ref| / max |ref|, all in f32 on the CPU: the decode path
# measures <= 1e-5 here, while one page of KV read from the wrong place
# measures >= 1e-2.  Token equality is checked wherever the reference's
# top-2 margin is above the same bound.
LOGIT_RTOL = 1e-4


def run_equiv(arch, backend="routed", steps=4, seed=0, I=4, TP=2):
    over = {}
    if CONFIGS[arch].is_moe:
        over["capacity_factor"] = 8.0
    cfg = reduced(CONFIGS[arch], vocab_size=256, **over)
    rng = jax.random.PRNGKey(seed)
    params = jax.tree.map(lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
                          init_params(rng, cfg))

    W, PAGE = I, 16
    from repro.core.dcp import attn_tp_geometry
    _, _khs, _ps = attn_tp_geometry(CONFIGS[arch], TP) if CONFIGS[arch].has_attention else (0, 1, 1)
    cluster = ClusterState(num_instances=I, instances_per_node=W,
                           kv_capacity_tokens=2048, page_size=PAGE,
                           kv_stripes=_ps)
    is_ssm_family = cfg.family in ("ssm", "hybrid")
    buckets = CPBuckets(edges=(100, 256), degrees=(1, 2, 3))
    # this harness is the data plane: it loads KV once and never applies
    # a plan's KV moves, so the scheduler must not escalate or relax
    # (escalation/relaxation have their own conformance cells)
    sched = DualBalancedScheduler(buckets=buckets,
                                  allow_rebalance=not is_ssm_family,
                                  has_kv=cfg.has_attention,
                                  allow_escalation=False)
    prompts = {0: 50, 1: 130, 2: 40, 3: 260, 4: 64}
    rng_np = np.random.default_rng(seed)
    prompt_tokens = {r: rng_np.integers(0, cfg.vocab_size, (L,))
                     for r, L in prompts.items()}
    for r, L in prompts.items():
        cluster.enqueue(Request(rid=r, prompt_len=L, max_new_tokens=steps))
    plan = sched.schedule(cluster)
    assert len(plan.admitted) == len(prompts)

    mesh = compat.make_mesh((I, TP), ("data", "model"))
    M0 = 8 if is_ssm_family else 2
    dims0 = dcp.DecodeDims(M=M0, S=2, N=M0 + 3 * 2, MB=0, W=W,
                           num_frames=cluster.page_table.frames_per_instance + 1,
                           page=PAGE, data_size=I, tp=TP, backend=backend)
    state = dcp.init_serve_state(cfg, dims0, I, dtype=jnp.float32)
    state_np = {k: np.zeros(v.shape, np.float32) for k, v in state.items()}

    # ---- prefill each request on the reference path, migrate caches ----
    next_tok = {}
    for r, toks in prompt_tokens.items():
        logits, caches = transformer.forward(cfg, params,
                                             jnp.asarray(toks)[None, :],
                                             collect_kv=True)
        next_tok[r] = int(np.argmax(np.asarray(logits[0, -1], np.float32)))
        kv_layers, ssm_layers = [], []
        for bi in range(cfg.num_blocks):
            for li, kind in enumerate(cfg.block_pattern()):
                aux = caches[li]
                if kind["mixer"] == "attn":
                    a, b = aux["kv"]
                    kv_layers.append((np.asarray(a[bi, 0], np.float32),
                                      np.asarray(b[bi, 0], np.float32)))
                else:
                    cs, hs = aux["ssm"]
                    ssm_layers.append((np.asarray(cs[bi, 0], np.float32),
                                       np.asarray(hs[bi, 0], np.float32)))
        if kv_layers:
            migrate.load_prefill_kv(cfg, cluster, dims0, state_np, r, kv_layers)
        if ssm_layers:
            inst, slot = cluster.slot_map[r]
            migrate.load_prefill_ssm(cfg, state_np, inst, slot, ssm_layers)

    state = {k: jnp.asarray(v) for k, v in state_np.items()}
    decode_params = jax.jit(lambda p: dcp.to_decode_params(cfg, p, TP))(params)
    gen_ref = {r: [next_tok[r]] for r in prompts}

    step_fn, d_key = None, None
    shape_buckets = ShapeBuckets(m_buckets=(8,) if is_ssm_family else (1,2,4,8), s_buckets=(0,1,2,4,8), window=W)
    for t in range(steps):
        plan = sched.schedule(cluster)
        assert not (plan.escalations or plan.relaxations or plan.copies), plan
        tbl = routing.lower_plan(cluster, plan, buckets=shape_buckets,
                                 append_tokens=cfg.has_attention,
                                 next_tokens=next_tok)
        tbl_dev = routing.as_device_arrays(tbl)
        d = dcp.DecodeDims(M=tbl.M, S=tbl.S, N=tbl.N, MB=tbl.MB, MBT=tbl.MBT,
                           W=W, num_frames=dims0.num_frames, page=PAGE,
                           data_size=I, tp=TP, backend=backend)
        key = (d.M, d.S, d.N, d.MB, d.MBT)
        if step_fn is None or key != d_key:       # mini AOT cache
            step_fn, d_key = dcp.make_serve_step(
                cfg, d, mesh, decode_params, state, tbl_dev,
                donate=False), key
        state, toks, logits = step_fn(decode_params, state, tbl_dev)
        toks, logits = np.asarray(toks), np.asarray(logits)
        max_err = 0.0
        for r in prompts:
            seq = np.concatenate([prompt_tokens[r], gen_ref[r]])
            ref_logits, _ = transformer.forward(cfg, params,
                                                jnp.asarray(seq)[None, :])
            ref_last = np.asarray(ref_logits[0, -1], np.float32)
            i, b = cluster.slot_map[r]
            got = logits[i, b]
            scale = np.max(np.abs(ref_last)) + 1e-9
            err = np.max(np.abs(got - ref_last)) / scale
            max_err = max(max_err, err)
            assert err <= LOGIT_RTOL, (arch, t, r, err)
            tok_ref = int(np.argmax(ref_last))
            top2 = np.sort(ref_last)[-2:]
            if top2[1] - top2[0] > LOGIT_RTOL * scale:   # not a near-tie
                assert int(toks[i, b]) == tok_ref, \
                    (arch, t, r, int(toks[i, b]), tok_ref, err)
            gen_ref[r].append(tok_ref)
            next_tok[r] = tok_ref
        for r in list(cluster.active):
            cluster.active[r].generated += 1
        print(f"  step {t}: ok (max rel err {max_err:.1e})")
    print(f"{arch} [{backend}]: PASS")


if __name__ == "__main__":
    import sys
    if len(sys.argv) > 1:
        arch, I, TP = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
        run_equiv(arch, I=I, TP=TP, steps=3)
    else:
        for arch, I, TP in [("tinyllama-1.1b", 2, 4), ("minicpm3-4b", 2, 4),
                            ("phi3.5-moe-42b-a6.6b", 4, 2),
                            ("jamba-v0.1-52b", 2, 4)]:
            run_equiv(arch, I=I, TP=TP, steps=3)
