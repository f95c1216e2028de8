"""Busy union, idle share, gap attribution and breakdown order, against
hand-worked values."""

import pytest

from chipbench import tracereader as tr
from chipbench.tracereader import Trace

DEV = "/device:TPU:0"


def synthetic() -> Trace:
    # window 0..100; ops (ns): a 10-30, b 20-40 (overlaps a), a 50-55,
    # c 90-120 (runs past the window's close), d -10-5 (starts before it)
    t = Trace()
    t.ops[DEV] = [("a", 10, 30), ("b", 20, 40), ("a", 50, 55),
                  ("c", 90, 120), ("d", -10, 5)]
    t.spans = [
        ("chipbench.window", 0, 100),
        ("chipbench.wait_arrival", 0, 8),
        ("chipbench.submit", 8, 12),
        ("chipbench.step", 12, 60),
        ("chipbench.check", 60, 62),
        ("chipbench.wait_arrival", 62, 95),
    ]
    return t


def test_union_merges_overlapping_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]


def test_busy_is_the_union_clipped_to_the_window():
    t = synthetic()
    # d 0-5, a+b 10-40, a 50-55, c 90-100
    assert tr.busy(t, DEV) == [(0, 5), (10, 40), (50, 55), (90, 100)]
    assert tr.busy_ns(t) == 5 + 30 + 5 + 10


def test_idle_share():
    t = synthetic()
    lo, hi = t.window()
    assert 1 - tr.busy_ns(t) / (hi - lo) == pytest.approx(0.5)


def test_gaps_are_the_complement():
    assert tr.gaps([(0, 5), (10, 40), (50, 55), (90, 100)], (0, 100)) == [
        (5, 10), (40, 50), (55, 90)]
    assert tr.gaps([], (3, 9)) == [(3, 9)]


def test_idle_gaps_charged_to_the_open_span():
    # gaps 5-10 (wait 5-8, submit 8-10), 40-50 (step), 55-90 (step 55-60,
    # check 60-62, wait 62-90)
    idle = tr.idle_by_span(synthetic(), DEV)
    assert idle == {
        "chipbench.wait_arrival": 3 + 28,
        "chipbench.submit": 2,
        "chipbench.step": 10 + 5,
        "chipbench.check": 2,
    }
    assert sum(idle.values()) == 50


def test_idle_outside_every_span_goes_to_none():
    t = Trace()
    t.ops[DEV] = [("a", 0, 10)]
    t.spans = [("chipbench.window", 0, 30), ("chipbench.step", 0, 12)]
    assert tr.idle_by_span(t, DEV) == {"chipbench.step": 2, tr.NO_SPAN: 18}


def test_top_ops_by_time_inside_the_window():
    # a 20+5, b 20, c 10 (clipped), d 5 (clipped)
    assert tr.top_ops(synthetic(), DEV, 3) == [("a", 25e-9), ("b", 20e-9), ("c", 10e-9)]


def test_step_spans_and_host_time_inside_them():
    t = synthetic()
    steps = tr.spans_named(t, "chipbench.step")
    assert steps == [(12, 60)]
    busy = tr.busy(t, DEV)
    # step 12-60 holds device time 12-40 and 50-55: 33 of 48 busy
    assert tr.overlap(busy, steps[0]) == 33


def test_window_must_be_one_span():
    t = synthetic()
    t.spans.append(("chipbench.window", 200, 300))
    with pytest.raises(ValueError):
        t.window()


# -- a recorded trace ----------------------------------------------------------
# camera_stream1.xplane.pb.gz: 0.2 s of camera_isp_1080.stream1 traced on
# one TPU v5e ("TPU v5 lite"): six dispatches of the two camera kernels.

@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip
    from pathlib import Path

    src = Path(__file__).parent / "data" / "camera_stream1.xplane.pb.gz"
    dst = tmp_path_factory.mktemp("trace") / "camera_stream1.xplane.pb"
    dst.write_bytes(gzip.decompress(src.read_bytes()))
    return tr.load(dst)


def test_recorded_trace_holds_one_device_and_the_spans(recorded):
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert len(recorded.ops[DEV]) == 26196
    names = [n for n, _s, _e in recorded.spans]
    assert names.count("chipbench.window") == 1
    assert names.count("chipbench.step") == 6
    assert names.count("chipbench.submit") == 6
    assert recorded.window() == (recorded.window()[0], recorded.window()[0] + 215279722.0)


def test_recorded_busy_against_a_plain_sweep(recorded):
    # an independent count: walk every op boundary in time order
    lo, hi = recorded.window()
    edges = sorted([(max(s, lo), 1) for _n, s, e in recorded.ops[DEV] if e > lo and s < hi]
                   + [(min(e, hi), -1) for _n, s, e in recorded.ops[DEV] if e > lo and s < hi])
    busy, depth, since = 0.0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert tr.busy_ns(recorded) == busy == 52598498.0


def test_recorded_idle_adds_up(recorded):
    lo, hi = recorded.window()
    idle = tr.idle_by_span(recorded, DEV)
    assert sum(idle.values()) == pytest.approx((hi - lo) - tr.busy_ns(recorded), abs=1)
    assert max(idle, key=idle.get) == "chipbench.step"


def test_recorded_breakdown_order(recorded):
    top = tr.top_ops(recorded, DEV, 10)
    assert len(top) == 10
    assert [s for _n, s in top] == sorted((s for _n, s in top), reverse=True)
    # the camera kernel, then the relayout copy of its output
    assert top[0] == ("jit__invoke(8948252764127170059)/_invoke.1 custom-call", 0.032054457)
    assert top[1][0] == "jit__invoke(8948252764127170059)/copy.21 copy"


def test_recorded_host_time_per_dispatch(recorded):
    from chipbench.harness import reader

    class Rec:
        trace = recorded
        stats_after = {"dispatches": 6}

        def delta(self, key):
            return 6

    busy = tr.busy(recorded, DEV)
    steps = tr.spans_named(recorded, "chipbench.step")
    want = sum((e - s) - tr.overlap(busy, (s, e)) for s, e in steps) / 6 / 1e6
    assert reader("host_ms_per_dispatch.latency")(Rec()) == pytest.approx(want)
    assert 10 < want < 40
