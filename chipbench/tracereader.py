"""From a profiler trace to the device's busy time, idle gaps and spans.

``load(path)`` reads one ``.xplane.pb`` (``jax.profiler.ProfileData``) into
a :class:`Trace`: the operations each device ran and the benchmark's own
host spans (``chipbench.*``, written with ``jax.profiler.TraceAnnotation``),
all on the trace's one clock in nanoseconds.  The rest are plain functions
over ``(start, end)`` intervals, so they can be checked by hand:

* busy time is the union of a device's operation intervals inside the
  window -- every operation counts, kernels and XLA slices or copies alike;
* the idle share is 1 - busy / window;
* each idle gap is charged to the benchmark span open over it (the host
  was waiting for an arrival, submitting, inside ``step()`` or doing the
  loop's own bookkeeping), or to ``(none)``.

    python chipbench/tracereader.py TRACE.xplane.pb   # what the trace holds
"""

from __future__ import annotations

import bisect
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
NO_SPAN = "(none)"
# the line of a TPU device plane that holds one event per operation run
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class Trace:
    """A trace reduced to what the metrics read.  ``ops`` holds, for each
    device plane, ``(name, start_ns, end_ns)`` per operation; ``spans``
    the benchmark's host spans as ``(name, start_ns, end_ns)``."""

    ops: Dict[str, List[Tuple[str, float, float]]] = field(default_factory=dict)
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    def window(self) -> Interval:
        """The one ``chipbench.window`` span: the traced window."""
        found = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if len(found) != 1:
            raise ValueError(f"trace holds {len(found)} {WINDOW_SPAN} spans, not 1")
        return found[0]


def _is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CUSTOM")


def op_name(hlo: str) -> str:
    """``"%copy.21 = f32[...] copy(...), ..."`` -> ``"copy.21 copy"``: the
    instruction's name and opcode, without its shapes and operands."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    m = _OPCODE.search(rest)
    return f"{head.lstrip('%')} {m.group(1)}" if m else head.lstrip("%")


_OPCODE = re.compile(r"(?:^|\s)([a-z][\w.-]*)\(")


def load(path: str) -> Trace:
    """Read one trace.  Each device operation is named
    ``<module>/<instruction> <opcode>``, by the module whose run holds it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    trace = Trace()
    names: Dict[Tuple[str, str], str] = {}
    for plane in pd.planes:
        if _is_device_plane(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                          for ev in lines[MODULES_LINE].events) if MODULES_LINE in lines else []
            starts = [m[0] for m in mods]
            ops = trace.ops.setdefault(plane.name, [])
            for ev in lines[OPS_LINE].events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and s < mods[i][1] else "-"
                key = (mod, ev.name)
                name = names.get(key)
                if name is None:
                    name = names[key] = f"{mod}/{op_name(ev.name)}"
                ops.append((name, s, e))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        trace.spans.append(
                            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    trace.spans.sort(key=lambda s: s[1])
    return trace


def profiler_options():
    """Trace options for a run: no Python tracer and only the host's
    first-level events (the benchmark's spans among them), which keeps the
    tracer off the host path it measures; no HLO protos in the file."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return opts


# -- interval arithmetic ----------------------------------------------------


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals, sorted by start."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def overlap(merged: Sequence[Interval], span: Interval) -> float:
    """Length of ``span`` covered by the merged, sorted ``merged``."""
    return length(clip(merged, span))


def gaps(merged: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that ``merged`` (sorted, disjoint) leaves."""
    out, t = [], window[0]
    for s, e in clip(merged, window):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < window[1]:
        out.append((t, window[1]))
    return out


def busy(trace: Trace, device: str) -> List[Interval]:
    """One device's busy intervals inside the window, merged."""
    return union(clip(((s, e) for _n, s, e in trace.ops[device]), trace.window()))


def busy_ns(trace: Trace) -> float:
    """Busy time inside the window, averaged over the devices."""
    if not trace.ops:
        return 0.0
    return sum(length(busy(trace, d)) for d in trace.ops) / len(trace.ops)


def idle_by_span(trace: Trace, device: str) -> Dict[str, float]:
    """Idle ns of ``device`` in the window, charged to the benchmark span
    open over each part of each gap (the window span itself is not a
    phase; where no other span is open the part goes to ``(none)``)."""
    phases = union_by_name(
        (n, s, e) for n, s, e in trace.spans if n != WINDOW_SPAN)
    out: Dict[str, float] = defaultdict(float)
    for gap in gaps(busy(trace, device), trace.window()):
        covered = 0.0
        for name, ivs in phases.items():
            part = overlap(ivs, gap)
            if part:
                out[name] += part
                covered += part
        rest = (gap[1] - gap[0]) - covered
        if rest > 0:
            out[NO_SPAN] += rest
    return dict(out)


def union_by_name(spans: Iterable[Tuple[str, float, float]]) -> Dict[str, List[Interval]]:
    by: Dict[str, List[Interval]] = defaultdict(list)
    for n, s, e in spans:
        by[n].append((s, e))
    return {n: union(v) for n, v in by.items()}


def spans_named(trace: Trace, name: str) -> List[Interval]:
    lo, hi = trace.window()
    return [(s, e) for n, s, e in trace.spans if n == name and s >= lo and e <= hi]


def top_ops(trace: Trace, device: str, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` operation names that took the most device time in the
    window, with their total seconds, longest first."""
    window = trace.window()
    tot: Dict[str, float] = defaultdict(float)
    for name, s, e in trace.ops[device]:
        for cs, ce in clip([(s, e)], window):
            tot[name] += ce - cs
    ranked = sorted(tot.items(), key=lambda kv: (-kv[1], kv[0]))[:n]
    return [(name, ns / 1e9) for name, ns in ranked]


def describe(path: str, per_line: int = 3) -> None:
    """Print every plane, line and a few events: for looking at a trace
    by hand before writing code against it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                print(f"    {ev.name!r} start_ns={ev.start_ns} "
                      f"dur_ns={ev.duration_ns} stats={dict(ev.stats)}")


if __name__ == "__main__":
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 3)
