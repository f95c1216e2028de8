"""1 - the union of device-operation intervals over the traced window."""

from chipbench import tracereader


def read(rec):
    if rec.trace is None or not rec.trace.ops:
        return None
    lo, hi = rec.trace.window()
    return 100.0 * (1.0 - tracereader.busy_ns(rec.trace) / (hi - lo))
