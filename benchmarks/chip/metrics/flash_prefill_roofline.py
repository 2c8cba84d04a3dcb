"""The flash prefill kernel's share of its roofline in the traced slice:
the least time the chip needs for the causal attention of the prompts
prefilled there (the cell's architecture's ``prefill_attention``) over
the kernel's device time from the trace, in percent."""
from chipbench import flops, names, trace


def read(run):
    rec = run["trace"]
    if rec is None:
        return None
    t, n = trace.op_time_s(rec, names.FLASH_PREFILL_KERNEL)
    lens = [p for s in run["steps"] if s.traced for p in s.prefilled]
    if not n or not lens or t <= 0:
        return None
    arch, m = run["arch"], run["model"]
    ops = sum(arch.prefill_attention(m, S)[0] for S in lens)
    byt = sum(arch.prefill_attention(m, S)[1] for S in lens)
    return 100.0 * flops.roofline_s(ops, byt, run["peaks"]) / t
