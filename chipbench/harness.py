"""One run of one cell: set-up, the timed window, the check, the metrics.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` -- the app, its ``make_app`` arguments, the
  request dtype, the reference to compare with and its limit, the work
  counts (the file is named by the configuration's ``file`` entry);
* ``references/<reference>.py`` -- the plain reference;
* ``traffic/<traffic>.json`` -- the mix, driven by ``traffic/loop.py``;
* ``metrics/<metric>.py`` (or ``metrics/<part before the first dot>.py``)
  -- ``read(record)`` gives the metric from a :class:`Record`, or ``None``
  where it finds nothing to read.

The timed window drives the served path only: ``PipelineServer.submit`` /
``step`` on a server compiled in ``mode`` (always ``"compiled"`` from
``run.py``).  Frames come from a pool of distinct seeded frames made in
set-up, so no two slots of a dispatch share one; the window keeps a seeded
sample of the outputs it served and compares them with the reference only
after the window has closed and the server is freed.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import tempfile
import time
import warnings
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from chipbench import references, tracereader, work
from chipbench.traffic.loop import Loop, Run

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# full dispatches of distinct pool frames before the window: the first
# dispatches of a process run slower while the host's allocator settles
WARM_DISPATCHES = 8


# -- the specification ------------------------------------------------------


def load_spec(root: Path = ROOT) -> Dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(spec: Dict, name: str, root: Path = ROOT) -> Tuple[Dict, Dict, Dict]:
    """``(cell, config, mix)`` of the cell ``name``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = json.loads((BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, config, mix


def metrics_for(spec: Dict, cell: str, traced: bool) -> List[Dict]:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    ones.  A metric without ``workloads`` is reported by every cell; a
    per-layer one without it by every cell that reports its ``moves``."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]


def reader(name: str) -> Callable:
    """``read`` of ``metrics/<name>.py``, else of the file named by the
    part of ``name`` before its first dot (``roofline_pct.latency`` is
    read by ``metrics/roofline_pct.py``)."""
    base = BENCH_DIR / "metrics"
    for stem in (name, name.split(".", 1)[0]):
        path = base / f"{stem}.py"
        if path.is_file():
            spec = importlib.util.spec_from_file_location(
                f"chipbench.metrics.{stem}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under {base}")


# -- what a metric reads ----------------------------------------------------


@dataclass
class Record:
    """Everything a metric reader may read about one run."""

    cell: Dict
    config: Dict
    seconds: float
    setup_s: float
    compile_s: float
    run: Run                     # every frame of the window, with its times
    stats_before: Dict           # PipelineServer.stats() at the window's start
    stats_after: Dict            # ... and at its close
    trace: Optional[tracereader.Trace] = None
    least: Optional[Dict] = None  # work.least_time of one frame

    def delta(self, key: str) -> int:
        return self.stats_after[key] - self.stats_before[key]


# -- frames and the served port -----------------------------------------------


def seed_sequence(seed: int) -> np.random.SeedSequence:
    return np.random.SeedSequence(seed % (1 << 64))


def make_pool(config: Dict, input_extents: Dict, seed: int) -> List[Dict]:
    """``pool_frames`` distinct seeded frames, made in bulk."""
    req = config["request"]
    rng = np.random.default_rng(seed_sequence(seed))
    n = int(config["pool_frames"])
    if n < int(config["batch_slots"]):
        raise ValueError("pool_frames < batch_slots: a dispatch would repeat a frame")
    stacks = {
        name: rng.integers(req["low"], req["high"], size=(n,) + tuple(shape),
                           dtype=np.dtype(req["dtype"]), endpoint=True)
        for name, shape in sorted(input_extents.items())
    }
    return [{name: s[i] for name, s in stacks.items()} for i in range(n)]


class ServerPort:
    """The loop's view of a ``PipelineServer``.  Frame ``i`` is pool frame
    ``i % len(pool)``.  A step whose dispatch raised a fault counter or a
    ``DegradedModeWarning`` fails every frame it served.  A seeded
    reservoir keeps ``sample_frames`` of the outputs served, copied into
    buffers made (and touched) in set-up: holding the server's own arrays
    would keep whole dispatches alive and change how the host allocates
    the next ones."""

    def __init__(self, server, pool, output: str, out_shape, sample_frames: int,
                 rng: np.random.Generator, is_degraded: Callable[[], int]):
        self.server = server
        self.pool = pool
        self.output = output
        self.rng = rng
        self.is_degraded = is_degraded
        self._buf = np.full((sample_frames,) + tuple(out_shape), 0.0, np.float32)
        self._kept: List[int] = []         # frame index held in each buffer
        self._live: Dict[int, Tuple[int, object]] = {}
        self._n_ok = 0
        self._faults = sum(server.fault_counters.values())

    @property
    def sample(self) -> List[Tuple[int, np.ndarray]]:
        return [(index, self._buf[j]) for j, index in enumerate(self._kept)]

    def submit(self, index: int) -> None:
        req = self.server.submit(self.pool[index % len(self.pool)])
        self._live[id(req)] = (index, req)

    def pending(self) -> int:
        return len(self.server.pending)

    def step(self) -> List[Tuple[int, bool]]:
        degraded = self.is_degraded()
        left = self.server.step()
        faults = sum(self.server.fault_counters.values())
        clean = faults == self._faults and self.is_degraded() == degraded
        self._faults = faults
        out = []
        for req in left:
            index, _ = self._live.pop(id(req))
            ok = bool(req.ok and clean)
            if ok:
                self._keep(index, req.outputs[self.output])
            out.append((index, ok))
        return out

    def _keep(self, index: int, arr) -> None:
        n, self._n_ok = self._n_ok, self._n_ok + 1
        k = len(self._buf)
        if n < k:
            j = n
            self._kept.append(index)
        else:
            j = int(self.rng.integers(0, n + 1))
            if j >= k:
                return
            self._kept[j] = index
        if np.shape(arr) != self._buf.shape[1:]:
            raise ValueError(f"output shape {np.shape(arr)} != {self._buf.shape[1:]}")
        np.copyto(self._buf[j], arr)


# -- the check ----------------------------------------------------------------


def compare(config: Dict, pool: List[Dict], sample, failed: int) -> Dict:
    """Each number compared, with its limit and rule.  ``sample`` is
    ``[(frame index, output)]``; the reference runs once per pool frame."""
    ref = references.load(config["reference"]).reference
    refs: Dict[int, np.ndarray] = {}
    worst, bad = 0.0, 0
    for index, out in sample:
        p = index % len(pool)
        if p not in refs:
            refs[p] = np.asarray(ref(pool[p]), np.float64)
        want = refs[p]
        out = np.asarray(out)
        if out.shape != want.shape or not np.isfinite(out).all():
            bad += 1
            continue
        worst = max(worst, float(np.max(np.abs(out.astype(np.float64) - want))))
    return {
        "max_abs_err": {"value": worst, "limit": config["check"]["max_abs_err"],
                        "rule": "<="},
        "failed_frames": {"value": failed, "limit": 0, "rule": "<="},
        "bad_frames": {"value": bad, "limit": 0, "rule": "<="},
        "frames_compared": {"value": len(sample), "limit": 1, "rule": ">="},
    }


def passes(checks: Dict) -> bool:
    return all(
        (c["value"] <= c["limit"]) if c["rule"] == "<=" else (c["value"] >= c["limit"])
        for c in checks.values())


# -- the run ------------------------------------------------------------------


class _CompileCounter:
    """Counts jit traces (each a lowering, and a compile or a cache read)
    while registered."""

    EVENT = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration=None, **kw):
        if event == self.EVENT:
            self.n += 1

    @contextlib.contextmanager
    def counting(self):
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self)
        try:
            yield self
        finally:
            mon.unregister_event_duration_listener(self)


def aot_compile(pp, device) -> None:
    """Lower and compile every kernel of ``pp`` for ``device`` before
    serving: the lowered text must hold the Mosaic kernel, and a compiler
    refusal raises here instead of being quarantined by the server."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    cap = pp.plan.notes["batch_capacity"]
    on_chip = SingleDeviceSharding(device)
    for ck in pp.kernels:
        args = tuple(
            jax.ShapeDtypeStruct(
                (cap,) + tuple(pp.pipeline.buffer_boxes[b].extents),
                jnp.float32, sharding=on_chip)
            for b in ck.buffer_order)
        lowered = ck.jitted.lower(args)
        if "tpu_custom_call" not in lowered.as_text():
            raise RuntimeError(f"kernel {ck.name!r}: no tpu_custom_call in its lowering")
        lowered.compile()


@dataclass
class Served:
    """A server set up for one configuration: compiled, warmed up, with its
    pool of frames."""

    config: Dict
    server: object
    pool: List[Dict]
    output: str
    out_shape: Tuple[int, ...]
    compile_s: float
    warm_s: float


def build(config: Dict, seed: int, device, *, mode: str = "compiled",
          make_app_overrides: Optional[Dict] = None) -> Served:
    """Make the app, plan, verify, emit and compile it into a
    ``PipelineServer``, make the pool of frames and warm up the one shape
    the server dispatches (every dispatch is padded to ``batch_slots``)
    with ``WARM_DISPATCHES`` full dispatches."""
    from repro.apps.paper_apps import make_app
    from repro.backend import PipelineServer

    app = make_app(config["app"], **{**config["make_app"], **(make_app_overrides or {})})
    slots = int(config["batch_slots"])
    t0 = time.perf_counter()
    server = PipelineServer(app.pipeline, batch_slots=slots, mode=mode)
    if mode == "compiled":
        aot_compile(server.pipeline, device)
    compile_s = time.perf_counter() - t0
    pool = make_pool(config, app.input_extents, seed)
    out_shape = None
    t_warm = time.perf_counter()
    for i in range(WARM_DISPATCHES):
        first = (i * slots) % len(pool)
        done = server.run([pool[(first + j) % len(pool)] for j in range(slots)])
        if not all(r.ok for r in done):
            raise RuntimeError(f"warm-up failed: {[str(r.error) for r in done]}")
        out_shape = np.shape(done[0].outputs[app.pipeline.output])
    return Served(config, server, pool, app.pipeline.output, out_shape, compile_s,
                  time.perf_counter() - t_warm)


@dataclass
class Window:
    """What one window left behind."""

    run: Run
    port: ServerPort
    stats_before: Dict
    stats_after: Dict
    jit_traces: int
    started: float               # perf_counter at the first timed submit


def serve_window(served: Served, mix: Dict, seconds: float, seed: int,
                 trace_dir: Optional[str] = None) -> Window:
    """Serve ``mix`` for ``seconds``, then drain.  With ``trace_dir`` the
    window (and the step in flight at its close) is traced there."""
    import jax

    from repro.backend import DegradedModeWarning

    server = served.server
    counter = _CompileCounter()
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always", DegradedModeWarning)

        def n_degraded() -> int:
            return sum(issubclass(w.category, DegradedModeWarning) for w in wlog)

        port = ServerPort(server, served.pool, served.output, served.out_shape,
                          int(served.config["check"]["sample_frames"]),
                          np.random.default_rng(seed_sequence(seed).spawn(1)[0]),
                          n_degraded)
        loop = Loop(mix, port, batch_slots=server.batch_slots,
                    span=jax.profiler.TraceAnnotation)
        if trace_dir:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=tracereader.profiler_options())
        stats_before = server.stats()
        with counter.counting():
            started = time.perf_counter()
            with jax.profiler.TraceAnnotation(tracereader.WINDOW_SPAN):
                run = loop.run(seconds)
            stats_after = server.stats()
        if trace_dir:
            jax.profiler.stop_trace()
        loop.drain()
    return Window(run, port, stats_before, stats_after, counter.n, started)


def run_cell(
    name: str,
    seed: int,
    seconds: float,
    traced: bool,
    *,
    t_start: float,
    mode: str = "compiled",
    peaks: Optional[Dict] = None,
    make_app_overrides: Optional[Dict] = None,
    root: Path = ROOT,
    log: Callable[[str], None] = print,
) -> Tuple[Dict, List[str]]:
    """Run cell ``name`` once; return the result line (a dict) and the
    lines that give each compared number beside its limit.  ``t_start`` is
    the process's start on ``time.perf_counter``'s clock."""
    import jax

    spec = load_spec(root)
    cell, config, mix = find_cell(spec, name, root)
    wanted = metrics_for(spec, name, traced)
    readers = {m["name"]: reader(m["name"]) for m in wanted}
    device = jax.devices()[0]

    served = build(config, seed, device, mode=mode,
                   make_app_overrides=make_app_overrides)
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    w = serve_window(served, mix, seconds, seed, tdir)
    setup_s = w.started - t_start

    memory_peak = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    trace = None
    if traced:
        found = sorted(Path(tdir).rglob("*.xplane.pb"))
        if len(found) != 1:
            raise RuntimeError(f"expected one trace file, found {found}")
        trace = tracereader.load(found[0])
        shutil.rmtree(tdir, ignore_errors=True)

    # free the program's state before the reference runs
    sample, pool, compile_s, warm_s = (w.port.sample, served.pool,
                                       served.compile_s, served.warm_s)
    w.port.server = served.server = None
    del served
    gc.collect()

    run = w.run
    failed = sum(1 for f in run.frames if not f.ok)
    t_check = time.perf_counter()
    checks = compare(config, pool, sample, failed)
    check_s = time.perf_counter() - t_check
    correct = passes(checks)

    record = Record(
        cell=cell, config=config, seconds=seconds, setup_s=setup_s, compile_s=compile_s, run=run,
        stats_before=w.stats_before, stats_after=w.stats_after, trace=trace,
        least=work.least_time(config["work"], peaks) if peaks else None,
    )
    metrics = {}
    for m in wanted:
        value = readers[m["name"]](record)
        if value is not None:
            metrics[m["name"]] = {"value": value if math.isfinite(value) else None,
                                  "unit": m["unit"]}

    late = [f.submitted - f.due for f in run.frames] if mix["loop"] == "open" else []
    log(f"window: {len(run.frames)} frames, {record.delta('dispatches')} "
        f"dispatches, {w.jit_traces} jit traces in the window, generator late "
        f"p50/max {_ms(late, 0.5)}/{_ms(late, 1.0)} ms; compile_s={compile_s!r} "
        f"warm_up_s={warm_s!r} setup_s={setup_s!r} check_s={check_s!r}")
    if mix["loop"] == "open":
        lat = [f.completed - f.due for f in run.frames if f.ok]
        log("latency ms p50/p90/p95/p99/max " + "/".join(
            _ms(lat, q) for q in (0.5, 0.9, 0.95, 0.99, 1.0)))
    if record.least:
        log(f"least time per frame {record.least['seconds']!r} s, bound by "
            f"{record.least['bound']} ({record.least['bytes']} B, {record.least['ops']} ops)")

    result = {
        "correct": correct,
        "attempted": len(run.frames),
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak,
        },
        "compiles_in_window": w.jit_traces,
    }
    if trace is not None:
        lo, hi = trace.window()
        result["device"]["busy_s"] = tracereader.busy_ns(trace) / 1e9
        result["device"]["window_s"] = (hi - lo) / 1e9
        if trace.ops:
            dev = sorted(trace.ops)[0]
            idle = tracereader.idle_by_span(trace, dev)
            result["breakdown"] = {
                "device_ops": [list(x) for x in tracereader.top_ops(trace, dev, 10)],
                "idle_gaps": [[k, v / 1e9] for k, v in
                              sorted(idle.items(), key=lambda kv: -kv[1])][:10],
            }
    result["checks"] = checks
    lines = [f"check {k}: {c['value']!r} (limit {c['rule']} {c['limit']!r})"
             for k, c in checks.items()]
    return result, lines


def _ms(xs: List[float], q: float) -> str:
    if not xs:
        return "-"
    s = sorted(xs)
    return f"{1e3 * s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]:.3f}"
