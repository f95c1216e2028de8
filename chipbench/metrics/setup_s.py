"""Set-up: process start to the first timed submit (host clock)."""


def read(rec):
    return rec.setup_s
