"""Live frames over the slots dispatched in the window, from the server's
own counters (``served``, ``dispatches``, ``batch_slots``)."""


def read(rec):
    dispatches = rec.delta("dispatches")
    if dispatches == 0:
        return None
    return 100.0 * rec.delta("served") / (dispatches * rec.stats_after["batch_slots"])
