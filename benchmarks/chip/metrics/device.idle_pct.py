"""Share of the traced slice in which the device ran no operation (one
less the union of the ops' intervals over the slice), in percent."""
from chipbench import trace


def read(run):
    rec = run["trace"]
    if rec is None or not rec["devices"]:
        return None
    return 100.0 * (1.0 - trace.busy_s(rec) / trace.window_s(rec))
