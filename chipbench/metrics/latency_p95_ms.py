"""95th percentile, by nearest rank, over every frame due in the window of
completion time - due time (host clock).  A failed frame is infinitely
late.  Frames still queued at the window's close count once drained."""

import math


def read(rec):
    lat = sorted(f.completed - f.due if f.ok else math.inf for f in rec.run.frames)
    if not lat:
        return None
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]
