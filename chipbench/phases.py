"""The program's own spans in a trace, and idle time by the innermost span.

The served program marks each phase of a dispatch with a host span whose
name starts with ``ub.`` (``ub.step`` around the service step, inside it
``ub.stack``, ``ub.to_device``, ``ub.kernel.<kernel>``, ``ub.copy_back``,
``ub.finite_check``), and names every kernel's module ``jit_ub_<kernel>``.
A program without them -- an older checkout -- leaves every reader here
with nothing to read.

``tracereader.load`` keeps the benchmark's spans alone; :func:`load` adds
the program's to them.  The program's spans nest inside the benchmark's
``chipbench.step``, so :func:`idle_by_innermost_span` charges each idle
part to the innermost span open over it: the latest-starting of those that
cover it.  Where no spans nest it gives what ``tracereader.idle_by_span``
gives.

    python chipbench/phases.py TRACE.xplane.pb   # the split by span
"""

from __future__ import annotations

import bisect
import heapq
import json
import sys
from collections import defaultdict
from typing import Dict, Iterable, List, Optional

from chipbench import tracereader
from chipbench.tracereader import NO_SPAN, WINDOW_SPAN, Interval, Trace

PREFIX = "ub."                       # the program's span names
STEP = "ub.step"
STAGE = ("ub.stack", "ub.to_device")
COPY_BACK = ("ub.copy_back",)
FINITE_CHECK = ("ub.finite_check",)
MODULE_PREFIX = "jit_ub_"            # the program's kernel modules
KERNEL_OPCODE = " custom-call"       # a Mosaic kernel among a module's ops


def load(path: str) -> Trace:
    """``tracereader.load(path)`` with the program's ``ub.`` spans added."""
    from jax.profiler import ProfileData

    trace = tracereader.load(path)
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        trace.spans.append(
                            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    trace.spans.sort(key=lambda s: s[1])
    return trace


def _covered(merged: List[Interval], starts: List[float], span: Interval) -> float:
    """Length of ``span`` covered by ``merged`` (sorted, disjoint), whose
    starts are ``starts``."""
    lo, hi = span
    i = max(0, bisect.bisect_right(starts, lo) - 1)
    out = 0.0
    while i < len(merged) and merged[i][0] < hi:
        out += max(0.0, min(hi, merged[i][1]) - max(lo, merged[i][0]))
        i += 1
    return out


def idle_by_innermost_span(trace: Trace, device: str) -> Dict[str, float]:
    """Idle ns of ``device`` in the window, each part charged to the
    innermost span open over it (the window span is not a phase; where no
    other span is open the part goes to ``(none)``)."""
    lo, hi = trace.window()
    idle = tracereader.gaps(tracereader.busy(trace, device), (lo, hi))
    starts = [s for s, _e in idle]
    spans = sorted((max(s, lo), min(e, hi), n) for n, s, e in trace.spans
                   if n != WINDOW_SPAN and e > lo and s < hi)
    cuts = sorted({lo, hi} | {t for s, e, _n in spans for t in (s, e)})
    out: Dict[str, float] = defaultdict(float)
    open_: List = []                     # (-start, end, order, name)
    nxt = 0
    for t0, t1 in zip(cuts, cuts[1:]):
        while nxt < len(spans) and spans[nxt][0] <= t0:
            s, e, n = spans[nxt]
            heapq.heappush(open_, (-s, e, nxt, n))
            nxt += 1
        while open_ and open_[0][1] <= t0:
            heapq.heappop(open_)
        part = _covered(idle, starts, (t0, t1))
        if part:
            out[open_[0][3] if open_ else NO_SPAN] += part
    return dict(out)


def _first_device(trace: Trace) -> Optional[str]:
    return sorted(trace.ops)[0] if trace.ops else None


def idle_ms_per_dispatch(rec, names: Iterable[str]) -> Optional[float]:
    """Device-idle time charged to the program spans ``names``, in ms per
    dispatch of the window; ``None`` where the trace holds no program span."""
    trace = rec.trace
    if (trace is None or rec.delta("dispatches") == 0
            or not any(n.startswith(PREFIX) for n, _s, _e in trace.spans)):
        return None
    dev = _first_device(trace)
    if dev is None:
        return None
    idle = idle_by_innermost_span(trace, dev)
    return sum(idle.get(n, 0.0) for n in names) / 1e6 / rec.delta("dispatches")


def module_ms_per_dispatch(rec, kernels: bool) -> Optional[float]:
    """Device time of the program's modules in the window, in ms per
    dispatch: of their Mosaic kernels (``kernels``), or of the rest of
    their ops (relayout copies, slices, loops, fusions) where no kernel
    runs.  Each is a union of intervals, so an op inside a loop, which the
    loop's own event spans too, counts once.  ``None`` where no op runs in
    a ``jit_ub_`` module."""
    trace = rec.trace
    if trace is None or not trace.ops or rec.delta("dispatches") == 0:
        return None
    ops = [(s, e, name.endswith(KERNEL_OPCODE))
           for name, s, e in trace.ops[_first_device(trace)]
           if name.startswith(MODULE_PREFIX)]
    if not ops:
        return None
    window = trace.window()

    def busy(keep) -> float:
        return tracereader.length(tracereader.union(tracereader.clip(
            ((s, e) for s, e, k in ops if keep(k)), window)))

    kernel_ns = busy(lambda k: k)
    ns = kernel_ns if kernels else busy(lambda k: True) - kernel_ns
    return ns / 1e6 / rec.delta("dispatches")


def count_spans(trace: Trace, name: str) -> int:
    """Spans called ``name`` that start inside the window."""
    lo, hi = trace.window()
    return sum(1 for n, s, _e in trace.spans if n == name and lo <= s < hi)


def main(path: str) -> None:
    trace = load(path)
    idle = idle_by_innermost_span(trace, _first_device(trace))
    print(json.dumps({
        "busy_s": tracereader.busy_ns(trace) / 1e9,
        "step_spans": count_spans(trace, STEP),
        "idle_by_innermost_span_s": {k: v / 1e9 for k, v in
                                     sorted(idle.items(), key=lambda kv: -kv[1])},
    }, indent=1))


if __name__ == "__main__":
    main(sys.argv[1])
