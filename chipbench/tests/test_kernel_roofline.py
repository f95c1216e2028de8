"""``kernel_roofline_pct``: frames served times the least time per frame
over the union of the program's Mosaic kernel ops, against hand-worked
values; nothing to read without a trace, without the work counts, or
where no op runs in a ``jit_ub_`` module."""

import pytest

from chipbench.harness import reader
from chipbench.tests.test_phases import DEV, Rec, nested

READ = reader("kernel_roofline_pct.throughput")


def test_by_hand():
    # the kernel runs 30-40 ns; 2 frames of at least 1 ns each
    rec = Rec(nested(), dispatches=2, served=2, least={"seconds": 1e-9})
    assert READ(rec) == pytest.approx(100.0 * 2 * 1e-9 / 10e-9, rel=1e-12)


def test_views_and_copies_are_not_counted():
    t = nested()
    t.ops[DEV].append(("jit_ub_k(123)/ub_k.2 custom-call", 35, 60))
    rec = Rec(t, dispatches=4, served=8, least={"seconds": 3e-9})
    # the union of the two kernels is 30-60; the copy and loop add nothing
    assert READ(rec) == pytest.approx(100.0 * 8 * 3e-9 / 30e-9, rel=1e-12)


def test_nothing_to_read():
    assert READ(Rec(None, least={"seconds": 1e-9})) is None
    assert READ(Rec(nested(), least=None)) is None
    t = nested()
    t.ops[DEV] = [(n.replace("jit_ub_k", "jit__invoke"), s, e)
                  for n, s, e in t.ops[DEV]]
    assert READ(Rec(t, least={"seconds": 1e-9})) is None


def test_recorded_trace_of_a_program_without_kernel_names(recorded_trace):
    assert READ(Rec(recorded_trace, dispatches=6, served=6,
                    least={"seconds": 1e-6})) is None


@pytest.fixture(scope="module")
def recorded_trace(tmp_path_factory):
    import gzip
    from pathlib import Path

    from chipbench import phases

    src = Path(__file__).parent / "data" / "camera_stream1.xplane.pb.gz"
    dst = tmp_path_factory.mktemp("trace") / "camera_stream1.xplane.pb"
    dst.write_bytes(gzip.decompress(src.read_bytes()))
    return phases.load(dst)
