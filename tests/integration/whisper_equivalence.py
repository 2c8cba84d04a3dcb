"""Whisper enc-dec DCP equivalence: cross-attn KV sharded across instances."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp

from repro import compat
from repro.configs import CONFIGS, reduced
from repro.models import encdec, init_params
from repro.core import dcp, migrate, routing
from repro.core.state import ClusterState, Request
from repro.core.scheduler import DualBalancedScheduler
from repro.core.bucketing import CPBuckets, ShapeBuckets

# max |logit - ref| / max |ref| in f32 on the CPU: measured <= 1e-6 here; a
# cross-KV page read from the wrong place measures >= 1e-2
LOGIT_RTOL = 1e-4

cfg = reduced(CONFIGS["whisper-base"], vocab_size=256)
rng = jax.random.PRNGKey(0)
params = jax.tree.map(lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
                      init_params(rng, cfg))

I, W, PAGE, TP, STEPS = 4, 4, 16, 2, 4
cluster = ClusterState(num_instances=I, instances_per_node=W,
                       kv_capacity_tokens=2048, page_size=PAGE)
# cross pools are read-only and this harness never applies a plan's KV
# moves: no escalation (and so no relaxation), as in the engine
sched = DualBalancedScheduler(buckets=CPBuckets(edges=(100, 256), degrees=(1, 2, 3)),
                              allow_escalation=False)
# (enc frames, decoder prefix tokens)
reqs = {0: (80, 3), 1: (300, 5), 2: (150, 2), 3: (48, 4)}
rng_np = np.random.default_rng(0)
frames = {r: rng_np.standard_normal((L, cfg.d_model)).astype(np.float32)
          for r, (L, _) in reqs.items()}
dec_prefix = {r: rng_np.integers(0, cfg.vocab_size, (t0,))
              for r, (_, t0) in reqs.items()}
for r, (L, t0) in reqs.items():
    cluster.enqueue(Request(rid=r, prompt_len=L, max_new_tokens=STEPS,
                            dec_prefix_len=t0))
plan = sched.schedule(cluster)
assert len(plan.admitted) == len(reqs)
print("bindings:", {q.rid: (q.moe_binding, q.kv_binding) for q in cluster.active.values()})

mesh = compat.make_mesh((I, TP), ("data", "model"))
dims = dcp.DecodeDims(M=1, S=1, N=4, MB=0, W=W,
                      num_frames=cluster.page_table.frames_per_instance + 1,
                      page=PAGE, data_size=I, tp=TP)
state = dcp.init_encdec_serve_state(cfg, dims, I, dtype=jnp.float32)
state_np = {k: np.zeros(v.shape, np.float32) for k, v in state.items()}

enc_states = {}
next_tok = {}
for r, (L, t0) in reqs.items():
    enc = encdec.encode(cfg, params, jnp.asarray(frames[r])[None])
    enc_states[r] = enc
    logits, caches = encdec.decode_forward(cfg, params,
                                           jnp.asarray(dec_prefix[r])[None],
                                           enc, collect_kv=True)
    next_tok[r] = int(np.argmax(np.asarray(logits[0, -1], np.float32)))
    cross_layers = [(np.asarray(caches["cross_kv"][0][l, 0], np.float32),
                     np.asarray(caches["cross_kv"][1][l, 0], np.float32))
                    for l in range(cfg.num_layers)]
    self_layers = [(np.asarray(caches["self_kv"][0][l, 0], np.float32),
                    np.asarray(caches["self_kv"][1][l, 0], np.float32))
                   for l in range(cfg.num_layers)]
    migrate.load_prefill_cross_kv(cfg, cluster, dims, state_np, r, cross_layers)
    inst, slot = cluster.slot_map[r]
    migrate.load_prefill_self_kv(cfg, dims, state_np, inst, slot, self_layers)

state = {k: jnp.asarray(v) for k, v in state_np.items()}
decode_params = jax.jit(lambda p: dcp.to_encdec_decode_params(cfg, p, TP))(params)
gen = {r: [next_tok[r]] for r in reqs}

step_fn, d_key = None, None
sb = ShapeBuckets(m_buckets=(1, 2), s_buckets=(1, 2), window=W)
for t in range(STEPS):
    plan = sched.schedule(cluster)
    assert not (plan.escalations or plan.relaxations or plan.copies), plan
    tbl = routing.lower_plan(cluster, plan, buckets=sb, append_tokens=False,
                             next_tokens=next_tok)
    tbl_dev = routing.as_device_arrays(tbl)
    d = dcp.DecodeDims(M=tbl.M, S=tbl.S, N=tbl.N, MB=tbl.MB, W=W,
                       num_frames=dims.num_frames, page=PAGE,
                       data_size=I, tp=TP)
    key = (d.M, d.S, d.N, d.MB)
    if key != d_key:
        step_fn, d_key = dcp.make_encdec_serve_step(
            cfg, d, mesh, decode_params, state, tbl_dev, donate=False), key
    state, toks, logits = step_fn(decode_params, state, tbl_dev)
    toks, logits = np.asarray(toks), np.asarray(logits)
    maxe = 0.0
    for r in reqs:
        seq = np.concatenate([dec_prefix[r], gen[r]])
        ref_logits, _ = encdec.decode_forward(cfg, params,
                                              jnp.asarray(seq)[None],
                                              enc_states[r])
        ref_last = np.asarray(ref_logits[0, -1], np.float32)
        i, b = cluster.slot_map[r]
        scale = np.max(np.abs(ref_last)) + 1e-9
        err = np.max(np.abs(logits[i, b] - ref_last)) / scale
        maxe = max(maxe, err)
        assert err <= LOGIT_RTOL, (t, r, err)
        tok_ref = int(np.argmax(ref_last))
        top2 = np.sort(ref_last)[-2:]
        if top2[1] - top2[0] > LOGIT_RTOL * scale:     # not a near-tie
            assert int(toks[i, b]) == tok_ref, (t, r, int(toks[i, b]), tok_ref, err)
        gen[r].append(tok_ref)
        next_tok[r] = tok_ref
    for r in list(cluster.active):
        cluster.active[r].generated += 1
    print(f"step {t}: ok (max rel err {maxe:.1e})")
print("whisper enc-dec DCP == reference. PASS")
