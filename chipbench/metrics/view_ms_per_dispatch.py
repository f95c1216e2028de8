"""Device time of every other op of the program's ``jit_ub_<kernel>``
modules (relayout copies, slices, segment loops, fusions) in the window,
over its dispatches."""

from chipbench import phases


def read(rec):
    return phases.module_ms_per_dispatch(rec, kernels=False)
