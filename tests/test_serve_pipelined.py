"""The serving layer keeps one batch in flight.

``PipelineServer.step`` launches the next full same-shape batch (stage,
kernels, finite flags, device→host copies started) before it finishes the
head batch, and the next ``step()`` finishes that launch.  What a caller
sees must not change: each step returns the requests a server fed one
batch per step returns, in the same order, with bit-identical outputs,
the same fault counters and the same named errors.  The launched batch
stays in ``pending``; a batch that changed in the queue meanwhile is
dispatched afresh and its launch discarded.
"""

import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

import jax

from conftest import SWEEP_SEED, sweep_inputs
from repro.apps.paper_apps import make_app
from repro.backend import (
    DeadlineExceededError,
    PipelineServer,
    compile_pipeline,
    faults,
)

pytestmark = pytest.mark.serve

ROOT = Path(__file__).resolve().parents[1]

# (app, make_app arguments, compile arguments): one kernel, and camera's two
APPS = {
    "gaussian": ("gaussian", dict(size=13), dict(block_h=4)),
    "camera": ("camera", dict(size=16), {}),
}
SLOTS = 2


def _server(name, **kw):
    app_name, app_kw, ckw = APPS[name]
    app = make_app(app_name, **app_kw)
    return app, PipelineServer(app.pipeline, batch_slots=SLOTS, **ckw, **kw)


def _tiles(app, n, seed=SWEEP_SEED):
    return [sweep_inputs(app, seed + i, "u4") for i in range(n)]


def _one_batch_a_step(srv, tiles):
    """Each batch submitted alone and served by one ``step()``: nothing is
    ever queued behind the head, so nothing launches ahead."""
    steps = []
    for i in range(0, len(tiles), SLOTS):
        reqs = [srv.submit(t) for t in tiles[i:i + SLOTS]]
        left = srv.step()
        assert left == reqs
        steps.append(left)
    assert srv.dispatches_ahead == 0
    return steps


def _queued(srv, tiles):
    """Every tile queued first, then one ``step()`` at a time."""
    reqs = [srv.submit(t) for t in tiles]
    steps = []
    while srv.pending:
        steps.append(srv.step())
    return reqs, steps


@pytest.mark.parametrize("name", sorted(APPS))
def test_queued_steps_return_what_one_batch_a_step_returns(name):
    app, serial = _server(name)
    _app, srv = _server(name)
    tiles = _tiles(app, 3 * SLOTS)
    want = _one_batch_a_step(serial, tiles)
    reqs, got = _queued(srv, tiles)
    assert [len(s) for s in got] == [len(s) for s in want]
    assert [r for s in got for r in s] == reqs       # submission order
    for g, w in zip((r for s in got for r in s), (r for s in want for r in s)):
        assert g.ok and w.ok
        assert g.outputs.keys() == w.outputs.keys()
        for k in g.outputs:
            assert np.array_equal(g.outputs[k], w.outputs[k])
    s = srv.stats()
    assert s["dispatches_ahead"] == 2 and s["ahead_discarded"] == 0
    assert s["dispatches"] == serial.stats()["dispatches"] == 3
    for k in ("served", "failed", "bytes_to_device", "bytes_from_device"):
        assert s[k] == serial.stats()[k], k


def test_a_launched_batch_stays_queued():
    """The batch in flight still counts in ``pending`` (what a caller
    sizes its submissions by) until the step that returns it."""
    app, srv = _server("gaussian")
    for t in _tiles(app, 3 * SLOTS):
        srv.submit(t)
    assert len(srv.step()) == SLOTS
    assert srv.dispatches_ahead == 1
    assert len(srv.pending) == 2 * SLOTS
    assert srv.stats()["pending"] == 2 * SLOTS


@pytest.mark.parametrize("behind", ["partial", "mixed-shape"])
def test_no_launch_ahead_of_a_batch_that_is_not_full(behind):
    """A partial batch, or a run of two shapes, behind the head: the next
    step would not pop a full batch of one shape, so nothing launches."""
    app, srv = _server("gaussian")
    tiles = _tiles(app, SLOTS + 1)
    if behind == "mixed-shape":
        other = make_app("gaussian", size=21)
        srv.register(other.pipeline, block_h=4)
        tiles += _tiles(other, 1, seed=SWEEP_SEED + 50)
    for t in tiles:
        srv.submit(t)
    assert len(srv.step()) == SLOTS
    assert len(srv.pending) == len(tiles) - SLOTS
    assert srv.dispatches_ahead == 0
    while srv.pending:
        srv.step()
    assert srv.served == len(tiles) and srv.failed == 0
    assert srv.dispatches_ahead == 0


def test_no_launch_ahead_where_a_request_carries_a_deadline():
    """An ahead launch's time would count against the head batch's
    late-completion check, so a deadline in either batch keeps the step
    serial."""
    app, srv = _server("gaussian")
    tiles = _tiles(app, 2 * SLOTS)
    for i, t in enumerate(tiles):
        srv.submit(t, deadline=100.0 if i == SLOTS else None)
    srv.step()
    assert srv.dispatches_ahead == 0
    srv.step()
    assert srv.served == 2 * SLOTS and srv.failed == 0


def test_a_request_expiring_in_flight_discards_the_launch():
    """A queued request of the launched batch expires before its step:
    the step pops another batch, dispatches it afresh and throws the
    launch away; the expired request fails by name, the rest serve
    bit-exact."""
    clock = faults.FaultClock()
    app, srv = _server("gaussian", clock=clock)
    tiles = _tiles(app, 3 * SLOTS)
    reqs = [srv.submit(t) for t in tiles]
    assert srv.step() == reqs[:SLOTS]
    assert srv.dispatches_ahead == 1
    # a deadline set once its batch is in flight: the server launches no
    # batch ahead whose requests already carry one
    victim = reqs[SLOTS]
    victim.deadline = clock() + 1.0
    clock.advance(2.0)
    left = srv.step()
    assert left == [victim] + reqs[SLOTS + 1:2 * SLOTS + 1]
    assert srv.ahead_discarded == 1
    assert isinstance(victim.error, DeadlineExceededError)
    while srv.pending:
        srv.step()
    ref = compile_pipeline(app.pipeline, block_h=4)
    out = app.pipeline.output
    for req, tile in zip(reqs, tiles):
        if req is not victim:
            assert req.ok
            assert np.array_equal(req.outputs[out],
                                  np.asarray(ref.run(tile)[out]))
    assert srv.stats()["deadline_misses"] == 1


# each injector on a queue of two full batches, the second batch the
# faulty one: its fault is raised by the launch made ahead of its step
INJECTORS = {
    "kernel_raise-at-dispatch-2": lambda srv: faults.kernel_raise(
        srv, at_dispatch=2),
    "kernel_raise-on-marker": lambda srv: faults.kernel_raise(
        srv, on_marker=True),
    "poison_output": faults.poison_output,
}
MARKED = SLOTS + 1


def _faulted(inject, serve):
    app, srv = _server("gaussian")
    tiles = _tiles(app, 2 * SLOTS)
    faults.mark_poison(tiles[MARKED])
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        with inject(srv):
            reqs = serve(srv, tiles)
    assert "_run_pipeline" not in srv.__dict__
    return srv, reqs, [w.category for w in wlog]


@pytest.mark.parametrize("fault", sorted(INJECTORS))
def test_a_fault_is_charged_to_its_own_batch(fault):
    inject = INJECTORS[fault]
    serial, want, want_warned = _faulted(
        inject, lambda srv, t: [r for s in _one_batch_a_step(srv, t) for r in s])
    srv, got, warned = _faulted(inject, lambda srv, t: _queued(srv, t)[0])
    assert srv.dispatches_ahead == 1 and srv.ahead_discarded == 0
    assert srv.fault_counters == serial.fault_counters
    assert srv.failed == serial.failed and srv.served == serial.served
    assert warned == want_warned
    assert [type(r.error) for r in got] == [type(r.error) for r in want]
    assert [r.ok for r in got] == [r.ok for r in want]
    for g, w in zip(got, want):
        if w.ok:
            assert np.array_equal(g.outputs["gaussian"], w.outputs["gaussian"])
    if fault != "kernel_raise-at-dispatch-2":
        assert not got[MARKED].ok


def test_closed_loop_keeps_a_batch_in_flight():
    """The benchmark's own closed loop (``harness.serve_window``, 2 x
    ``batch_slots`` frames outstanding) on a small blur in interpret mode:
    every frame leaves the server through ``Loop.drain()``, correct, and
    the steady state launches ahead without discarding."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from chipbench import harness

    seed = 2**31 + 19
    _cell, config, mix = harness.find_cell(harness.load_spec(),
                                           "blur_1080p.offline")
    served = harness.build(config, seed, jax.devices()[0], mode="interpret",
                           make_app_overrides={"size": 18, "width": 34})
    assert served.server.dispatches_ahead == 0      # the warm-up is serial
    w = harness.serve_window(served, mix, 0.5, seed)
    assert w.run.frames and all(f.done and f.ok for f in w.run.frames)
    s = served.server.stats()
    assert s["pending"] == 0
    assert s["dispatches_ahead"] > 0 and s["ahead_discarded"] == 0
    checks = harness.compare(config, served.pool, w.port.sample, failed=0)
    assert harness.passes(checks), checks
