"""The host's finite check per dispatch: device-idle time inside the
program's ``ub.finite_check`` spans, over the dispatches of the window."""

from chipbench import phases


def read(rec):
    return phases.idle_ms_per_dispatch(rec, phases.FINITE_CHECK)
