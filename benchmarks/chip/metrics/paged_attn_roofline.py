"""The paged decode kernel's share of its roofline in the traced slice:
the least time the chip needs for the traced steps' attention (KV of each
row's actual context read once at bf16: the cell's architecture's
``decode_attention``) over the kernel's device time from the trace, in
percent."""
from chipbench import flops, names, trace


def read(run):
    rec = run["trace"]
    if rec is None:
        return None
    t, n = trace.op_time_s(rec, names.PAGED_ATTENTION_KERNEL)
    ctxs = [c for s in run["steps"] if s.traced for c in s.ctx_list]
    if not n or not ctxs or t <= 0:
        return None
    arch, m = run["arch"], run["model"]
    ops = sum(arch.decode_attention(m, c)[0] for c in ctxs)
    byt = sum(arch.decode_attention(m, c)[1] for c in ctxs)
    return 100.0 * flops.roofline_s(ops, byt, run["peaks"]) / t
