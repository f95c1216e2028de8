"""Device time of the Mosaic kernels (the ``custom-call`` ops of the
program's ``jit_ub_<kernel>`` modules) in the window, over its
dispatches."""

from chipbench import phases


def read(rec):
    return phases.module_ms_per_dispatch(rec, kernels=True)
