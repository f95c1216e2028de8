"""Host staging per dispatch: device-idle time inside the program's
``ub.stack`` (padding to slots, float32 stacking) and ``ub.to_device``
(inputs onto the device) spans, over the dispatches of the window."""

from chipbench import phases


def read(rec):
    return phases.idle_ms_per_dispatch(rec, phases.STAGE)
