"""The open- and closed-loop schedules, lateness and latency from the due
time, on a fake clock and a fake server."""

import math

import pytest

from chipbench.traffic.loop import Loop, open_schedule


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += s


class FakePort:
    """A server that takes ``service`` seconds per dispatch of up to
    ``slots`` queued frames; frames listed in ``bad`` fail."""

    def __init__(self, clock, slots, service, bad=()):
        self.clock, self.slots, self.service = clock, slots, service
        self.queue, self.bad, self.dispatches = [], set(bad), []

    def submit(self, index):
        self.queue.append(index)

    def pending(self):
        return len(self.queue)

    def step(self):
        batch, self.queue = self.queue[:self.slots], self.queue[self.slots:]
        self.dispatches.append(len(batch))
        self.clock.t += self.service
        return [(i, i not in self.bad) for i in batch]


def make(mix, service, slots=4, bad=()):
    clock = FakeClock()
    port = FakePort(clock, slots, service, bad)
    return Loop(mix, port, batch_slots=slots, clock=clock, sleep=clock.sleep), port, clock


def test_open_schedule_counts_and_sync():
    assert open_schedule(1, 30, 1.0) == [k / 30 for k in range(30)]
    s = open_schedule(4, 30, 0.1)
    assert s == [0.0] * 4 + [1 / 30] * 4 + [2 / 30] * 4
    assert len(open_schedule(1, 30, 20)) == 600


def test_open_loop_latency_from_due_time():
    # 10 frames/s, 0.05 s per dispatch: each frame is served alone, on time
    loop, port, clock = make({"loop": "open", "streams": 1, "fps": 10}, 0.05)
    run = loop.run(1.0)
    loop.drain()
    assert len(run.frames) == 10
    assert port.dispatches == [1] * 10
    for k, f in enumerate(run.frames):
        assert f.due == pytest.approx(100.0 + k / 10)
        assert f.submitted - f.due == pytest.approx(0.0)
        assert f.completed - f.due == pytest.approx(0.05)
    assert clock.t == pytest.approx(101.0)          # waited out the window


def test_open_loop_backlog_counts_the_wait_and_the_lateness():
    # 8 frames/s (due 0, 1/8, ...) but 1/4 s per dispatch of one slot:
    # frames queue up, and those still queued at the close are drained
    loop, port, clock = make({"loop": "open", "streams": 1, "fps": 8}, 0.25, slots=1)
    run = loop.run(0.625)
    assert [f.done for f in run.frames] == [True, True, True, False, False]
    loop.drain()
    f = run.frames
    assert [x.completed - x.due for x in f] == [0.25, 0.375, 0.5, 0.625, 0.75]
    assert [x.submitted - x.due for x in f] == [0.0, 0.125, 0.0, 0.125, 0.0]
    assert len(run.completed_in_window()) == 2     # the third ends past the close


def test_open_loop_four_streams_fill_one_dispatch():
    loop, port, _ = make({"loop": "open", "streams": 4, "fps": 30}, 0.01)
    run = loop.run(1.0)
    loop.drain()
    assert len(run.frames) == 120
    assert port.dispatches == [4] * 30


def test_closed_loop_keeps_its_frames_outstanding():
    loop, port, clock = make({"loop": "closed", "outstanding_per_slot": 2}, 0.125)
    run = loop.run(1.0)
    assert port.dispatches == [4] * 8                 # every dispatch full
    assert len(run.completed_in_window()) == 32
    assert port.pending() == 4                         # 8 out: 4 queued
    loop.drain()
    assert len(run.frames) == 8 + 4 * 7                # refilled but after the last
    assert all(f.done for f in run.frames)
    assert port.dispatches == [4] * 9


def test_failed_frames_are_marked():
    loop, _port, _ = make({"loop": "closed", "outstanding_per_slot": 1}, 0.125, bad={2})
    run = loop.run(0.375)
    loop.drain()
    assert [f.ok for f in run.frames] == [i != 2 for i in range(len(run.frames))]


def test_unknown_loop_is_refused():
    with pytest.raises(ValueError):
        make({"loop": "burst"}, 0.1)


def test_latency_p95_by_nearest_rank_and_failures_infinitely_late():
    from chipbench.metrics import latency_p95_ms  # noqa: F401  (a package file)
    from chipbench.harness import reader

    class Rec:
        pass

    loop, _port, _ = make({"loop": "open", "streams": 1, "fps": 20}, 0.0078125, bad={19})
    rec = Rec()
    rec.run = loop.run(1.0)
    loop.drain()
    read = reader("latency_p95_ms")
    # 20 frames: 19 at 7.8125 ms, one failed; nearest rank 19 of 20
    assert read(rec) == pytest.approx(7.8125)
    rec.run.frames[0].ok = False
    assert math.isinf(read(rec))
