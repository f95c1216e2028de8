"""Copy-back per dispatch: device-idle time inside the program's
``ub.copy_back`` spans (every kernel's output to the host, intermediates
included), over the dispatches of the window."""

from chipbench import phases


def read(rec):
    return phases.idle_ms_per_dispatch(rec, phases.COPY_BACK)
