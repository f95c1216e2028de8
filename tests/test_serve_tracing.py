"""The serving path's profiler spans, stable kernel names and transfer
counters.

``PipelineServer.step`` marks one ``ub.step`` span per service step and,
inside it, one span per phase of each dispatch (``backend/tracing.py``);
every emitted kernel's module is named ``jit_ub_<kernel>``; ``stats()``
counts the bytes each dispatch moves to and from the device.  A trace
taken on the CPU (interpret mode) holds the same host spans as one taken
on the chip.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import SWEEP_SEED, sweep_inputs
from repro.apps.paper_apps import make_app
from repro.backend import DegradedModeWarning, PipelineServer, faults, tracing

pytestmark = pytest.mark.serve


def _program_spans(path):
    """``(name, start_ns, end_ns, stats)`` of every ``ub.`` host event of
    a trace file, by start."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(tracing.PREFIX):
                        out.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return sorted(out, key=lambda s: s[1])


def _traced(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        fn()
    [path] = tmp_path.rglob("*.xplane.pb")
    return _program_spans(path)


@pytest.fixture(scope="module")
def camera():
    app = make_app("camera", size=16)
    srv = PipelineServer(app.pipeline, batch_slots=2)
    tiles = [sweep_inputs(app, SWEEP_SEED + i, "u4") for i in range(3)]
    srv.run(tiles[:1])                     # compile outside any trace
    return app, srv, tiles


def test_one_dispatch_traces_its_phases_in_order(camera, tmp_path):
    _app, srv, tiles = camera
    n = srv.dispatches
    spans = _traced(tmp_path, lambda: srv.run(tiles[:1]))
    assert [s[0] for s in spans] == [
        tracing.STEP, tracing.STACK, tracing.TO_DEVICE,
        tracing.KERNEL + "denoise", tracing.KERNEL + "camera",
        tracing.COPY_BACK, tracing.FINITE_CHECK]
    _name, lo, hi, stats = spans[0]
    assert stats == {"dispatch": n}
    assert all(lo <= s <= e <= hi for _n, s, e, _st in spans[1:])
    # the phases follow one another
    assert all(a[2] <= b[1] for a, b in zip(spans[1:], spans[2:]))


def test_one_step_span_per_dispatch(camera, tmp_path):
    _app, srv, tiles = camera
    n = srv.dispatches
    spans = _traced(tmp_path, lambda: srv.run(tiles * 2))     # 6 tiles, 2 slots
    steps = [s for s in spans if s[0] == tracing.STEP]
    assert [s[3]["dispatch"] for s in steps] == [n, n + 1, n + 2]
    assert srv.dispatches == n + 3


def test_fault_paths_are_marked(camera, tmp_path):
    _app, srv, tiles = camera
    poisoned = faults.mark_poison(dict(tiles[1]))

    def serve():
        with faults.kernel_raise(srv, at_dispatch=1):
            srv.run(tiles[:1])
        with faults.poison_output(srv):
            srv.run([tiles[0], poisoned])

    with pytest.warns(DegradedModeWarning):
        names = [s[0] for s in _traced(tmp_path, serve)]
    assert names.count(tracing.RECOMPILE) == 1
    # the poisoned pair bisects into two single-tile probes
    assert names.count(tracing.QUARANTINE) == 3


def test_kernel_modules_are_named_stably(camera):
    _app, srv, _tiles = camera
    pp = srv.pipeline
    cap = pp.plan.notes["batch_capacity"]
    for ck in pp.kernels:
        args = tuple(
            jax.ShapeDtypeStruct(
                (cap,) + tuple(pp.pipeline.buffer_boxes[b].extents), jnp.float32)
            for b in ck.buffer_order)
        text = ck.jitted.lower(args).as_text()
        assert f"module @jit_ub_{ck.name} " in text
        assert ck.span == tracing.KERNEL + ck.name


def test_transfer_counters_count_the_moved_arrays():
    """Six tiles through four slots: two dispatches, the second padded
    with filler tiles, which move too."""
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=4, block_h=4)
    tiles = [sweep_inputs(app, SWEEP_SEED + i, "u4") for i in range(6)]
    before = srv.stats()
    done = srv.run(tiles)
    after = srv.stats()
    slots = 2 * srv.batch_slots
    to_device = slots * sum(
        np.asarray(tiles[0][n], np.float32).nbytes for n in app.pipeline.inputs)
    from_device = slots * sum(a.nbytes for a in done[0].outputs.values())
    assert after["bytes_to_device"] - before["bytes_to_device"] == to_device
    assert after["bytes_from_device"] - before["bytes_from_device"] == from_device
    assert from_device > 0 and to_device > 0
