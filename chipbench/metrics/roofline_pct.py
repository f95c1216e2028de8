"""Frames served in the traced window times the least time one frame can
take (``work.least_time``), over the device's busy time in that window.
Busy time counts every device operation, so work moved out of a kernel
still counts."""

from chipbench import tracereader


def read(rec):
    if rec.trace is None or rec.least is None:
        return None
    busy_ns = tracereader.busy_ns(rec.trace)
    if busy_ns <= 0:
        return None
    return 100.0 * rec.delta("served") * rec.least["seconds"] / (busy_ns / 1e9)
