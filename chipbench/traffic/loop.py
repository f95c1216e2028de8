"""The one traffic generator: open and closed loops over a served port.

A mix is a data file of parameters (``traffic/<name>.json``):

* ``{"loop": "open", "streams": S, "fps": F}`` -- S cameras that fire in
  sync: S frames are due together every 1/F s, whether or not earlier
  frames have completed.  A frame's latency is timed from its due time, so
  a stall is charged to every frame it delays, and the generator's own
  lateness (submit time - due time) is recorded beside it.
* ``{"loop": "closed", "outstanding_per_slot": K}`` -- K x batch_slots
  frames are kept outstanding: each completion is replaced by a new
  submission until the window closes.

The loop runs on one thread against a *port*: ``submit(index)`` queues
frame ``index``, ``pending()`` says how many are queued, and ``step()``
serves one dispatch and returns ``[(index, ok), ...]`` for the frames that
left the system.  Frame ``index`` is the index-th frame of the run; the
port maps it to its pixels.  ``span(name)`` wraps each phase of the loop
(``chipbench.wait_arrival``, ``.submit``, ``.step``, ``.check``) so a trace
can say what the host was doing while the device sat idle.
"""

from __future__ import annotations

import contextlib
import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List

SPAN_WAIT = "chipbench.wait_arrival"
SPAN_SUBMIT = "chipbench.submit"
SPAN_STEP = "chipbench.step"
SPAN_CHECK = "chipbench.check"


@dataclass
class Frame:
    """One frame of the run; times are clock readings in seconds."""

    index: int
    due: float
    submitted: float = math.nan
    completed: float = math.nan
    ok: bool = False

    @property
    def done(self) -> bool:
        return not math.isnan(self.completed)


@dataclass
class Run:
    """What the loop saw: every frame of the window, and the window."""

    start: float
    end: float                       # start + seconds: the window's close
    frames: List[Frame] = field(default_factory=list)

    def completed_in_window(self) -> List[Frame]:
        return [f for f in self.frames if f.done and f.completed <= self.end]


def open_schedule(streams: int, fps: float, seconds: float) -> List[float]:
    """Due offsets (s from the window's start) of every frame due in
    ``[0, seconds)``: ``streams`` frames at each tick of 1/``fps``."""
    ticks = math.ceil(seconds * fps - 1e-9)
    return [k / fps for k in range(ticks) for _ in range(streams)]


def _no_span(name: str):
    return contextlib.nullcontext()


def spin(seconds: float) -> None:
    """Wait by polling the clock.  An open loop that sleeps between frames
    lets the host's cores idle, and on the chip's host that put whole runs
    into a regime where every dispatch took ~17 ms longer (PERF.md); a
    server that polls for its next frame does not see it."""
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


class Loop:
    """Drives one port with one mix.  ``run()`` serves the window and
    returns at its close; ``drain()`` then serves what is still queued, so
    that every frame of the window completes or fails."""

    def __init__(
        self,
        mix: Dict,
        port,
        *,
        batch_slots: int,
        clock: Callable[[], float] = time.perf_counter,
        sleep: Callable[[float], None] = spin,
        span: Callable = _no_span,
    ) -> None:
        if mix["loop"] not in ("open", "closed"):
            raise ValueError(f"unknown loop {mix['loop']!r}")
        self.mix = mix
        self.port = port
        self.batch_slots = batch_slots
        self.clock = clock
        self.sleep = sleep
        self.span = span
        self._by_index: Dict[int, Frame] = {}
        self.run_: Run = None

    def _submit(self, frame: Frame) -> None:
        frame.submitted = self.clock()
        self._by_index[frame.index] = frame
        self.port.submit(frame.index)

    def _step(self) -> List[Frame]:
        with self.span(SPAN_STEP):
            left = self.port.step()
        t = self.clock()
        with self.span(SPAN_CHECK):
            out = []
            for index, ok in left:
                f = self._by_index.pop(index)
                f.completed, f.ok = t, bool(ok)
                out.append(f)
        return out

    def run(self, seconds: float) -> Run:
        start = self.clock()
        self.run_ = Run(start=start, end=start + seconds)
        if self.mix["loop"] == "open":
            self._run_open(seconds)
        else:
            self._run_closed()
        return self.run_

    def _run_open(self, seconds: float) -> None:
        r = self.run_
        r.frames = [
            Frame(index=i, due=r.start + off)
            for i, off in enumerate(open_schedule(
                int(self.mix["streams"]), float(self.mix["fps"]), seconds))
        ]
        nxt = 0
        while True:
            now = self.clock()
            if nxt < len(r.frames) and r.frames[nxt].due <= now:
                with self.span(SPAN_SUBMIT):
                    while nxt < len(r.frames) and r.frames[nxt].due <= now:
                        self._submit(r.frames[nxt])
                        nxt += 1
            if now >= r.end:             # every frame is due, and submitted
                return
            if self.port.pending():
                self._step()
            else:
                until = r.frames[nxt].due if nxt < len(r.frames) else r.end
                with self.span(SPAN_WAIT):
                    self.sleep(max(0.0, until - self.clock()))

    def _run_closed(self) -> None:
        r = self.run_
        outstanding = int(self.mix["outstanding_per_slot"]) * self.batch_slots

        def submit_one():
            f = Frame(index=len(r.frames), due=self.clock())
            r.frames.append(f)
            self._submit(f)

        with self.span(SPAN_SUBMIT):
            for _ in range(outstanding):
                submit_one()
        while True:
            left = self._step()
            if self.clock() >= r.end:
                return
            with self.span(SPAN_SUBMIT):
                for _ in left:
                    submit_one()

    def drain(self) -> None:
        """Serve every frame still queued after the window's close."""
        while self.port.pending():
            self._step()
        missing = [f.index for f in self.run_.frames if not f.done]
        if missing:
            raise RuntimeError(f"frames {missing[:8]} never left the server")
