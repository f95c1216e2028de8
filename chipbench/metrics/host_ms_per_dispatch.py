"""Host time per dispatch: the part of the benchmark's ``step()`` spans in
which the device runs no operation, over the dispatches of the window."""

from chipbench import tracereader
from chipbench.traffic.loop import SPAN_STEP


def read(rec):
    if rec.trace is None or not rec.trace.ops or rec.delta("dispatches") == 0:
        return None
    dev = sorted(rec.trace.ops)[0]
    busy = tracereader.busy(rec.trace, dev)
    host_ns = sum((e - s) - tracereader.overlap(busy, (s, e))
                  for s, e in tracereader.spans_named(rec.trace, SPAN_STEP))
    return host_ns / 1e6 / rec.delta("dispatches")
