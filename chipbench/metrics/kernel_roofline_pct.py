"""Frames served in the traced window times the least time one frame can
take (``work.least_time``), over the device time of the program's Mosaic
kernels in that window (the union of the ``custom-call`` ops of its
``jit_ub_<kernel>`` modules).  Unlike ``roofline_pct``, the views and
copies around the kernels are not counted: it is the kernels' own share
of the roofline."""

from chipbench import phases


def read(rec):
    if rec.least is None:
        return None
    ms = phases.module_ms_per_dispatch(rec, kernels=True)
    if not ms:
        return None
    kernel_s = ms * rec.delta("dispatches") / 1e3
    return 100.0 * rec.delta("served") * rec.least["seconds"] / kernel_s
