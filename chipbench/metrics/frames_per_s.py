"""Correct frames completed inside the window over the window's seconds
(host clock).  A failed frame is not completed."""


def read(rec):
    return sum(1 for f in rec.run.completed_in_window() if f.ok) / rec.seconds
