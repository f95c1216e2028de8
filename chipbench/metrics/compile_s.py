"""Plan, verify, emit and the Mosaic compile of every kernel ahead of
serving: host clock around ``PipelineServer(...)`` and the compiles."""


def read(rec):
    return rec.compile_s
