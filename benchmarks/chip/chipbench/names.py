"""How the benchmark finds the program's work in a trace, by name.

Regular expressions over the names the TPU profiler gives device events:
``XLA Modules`` events are compiled programs, named after the jitted
function (``jit_step(<id>)`` for the AOT decode step); ``XLA Ops`` events
are operations, each named by its HLO instruction, a Pallas kernel among
them as a custom call named after the function that calls it
(``paged_decode_attention.7``).  The patterns match from the start of the
op's own name (``trace.op_name``).
"""
DECODE_STEP_MODULE = r"jit_step\("
PAGED_ATTENTION_KERNEL = r"paged_decode_attention"
FLASH_PREFILL_KERNEL = r"(_?flash|_fwd_kernel)"
