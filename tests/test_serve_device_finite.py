"""The serving layer's NaN/Inf check runs on the device.

``PipelineServer._dispatch`` launches ``ub_finite_slots`` on every
kernel's output (intermediates included) as the fault seam
``_run_pipeline`` returned them, before the copy-back, and reads back one
bool per slot; ``_poisoned_slots`` is a read of the first ``n_live`` of
them.  The flags must equal the host's per-slot ``np.isfinite(...).all()``
that they replace, so quarantine and its counters behave as before.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import SWEEP_SEED, sweep_inputs
from repro.apps.paper_apps import make_app
from repro.backend import PipelineServer, PoisonedTileError, faults
from repro.backend.serve_bridge import ub_finite_slots

pytestmark = pytest.mark.serve

SLOTS = 4
# slots on the leading axis; the rest mimic a frame, an interleaved
# (phase-on-lanes) intermediate and a channel-major tensor
SHAPES = [(SLOTS, 6, 10), (SLOTS, 2, 3, 8), (SLOTS, 5)]
BAD = {"nan": np.nan, "+inf": np.inf, "-inf": -np.inf}


def _outputs(seed):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(s).astype(np.float32) for s in SHAPES]


def _host_flags(outs):
    return np.array([all(np.isfinite(a[b]).all() for a in outs)
                     for b in range(SLOTS)])


def _tiles(app, n):
    return [sweep_inputs(app, SWEEP_SEED + i, "u4") for i in range(n)]


@pytest.mark.parametrize("value", sorted(BAD))
@pytest.mark.parametrize("slot", [0, 2, SLOTS - 1])
@pytest.mark.parametrize("out", range(len(SHAPES)))
def test_device_flags_equal_the_host_check(out, slot, value):
    outs = _outputs(SWEEP_SEED + 17 * out + slot)
    rng = np.random.default_rng(slot)
    at = tuple(int(rng.integers(n)) for n in SHAPES[out][1:])
    outs[out][(slot,) + at] = BAD[value]
    flags = np.asarray(ub_finite_slots(tuple(jnp.asarray(a) for a in outs)))
    assert flags.dtype == np.bool_ and flags.shape == (SLOTS,)
    want = _host_flags(outs)
    assert np.array_equal(flags, want)
    assert np.flatnonzero(~flags).tolist() == [slot]
    # the fault seam may hand back host arrays: the same flags
    assert np.array_equal(np.asarray(ub_finite_slots(tuple(outs))), want)


def test_clean_outputs_flag_every_slot_finite():
    outs = _outputs(SWEEP_SEED)
    assert np.asarray(ub_finite_slots(tuple(outs))).all()


def test_flags_reduce_in_place_without_a_relayout():
    args = tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in SHAPES)
    text = ub_finite_slots.lower(args).as_text()
    assert "module @jit_ub_finite_slots " in text
    assert "reshape" not in text and "transpose" not in text


def test_a_filler_slot_is_never_read():
    outs = _outputs(SWEEP_SEED)
    outs[0][SLOTS - 1, 0, 0] = np.nan
    flags = ub_finite_slots(tuple(outs))
    assert PipelineServer._poisoned_slots(flags, SLOTS - 1) == []
    assert PipelineServer._poisoned_slots(flags, SLOTS) == [SLOTS - 1]


def test_nan_in_a_served_filler_slot_is_ignored():
    """Two tiles in four slots; NaN splatted into the filler slots' every
    output after the kernels ran: both tiles complete, no quarantine."""
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=SLOTS, block_h=4)
    real = srv._run_pipeline

    def _filler_nan(pp, ins):
        bufs = dict(real(pp, ins))
        for ck in pp.kernels:
            arr = np.array(bufs[ck.name], copy=True)
            arr[2:] = np.nan
            bufs[ck.name] = arr
        return bufs

    srv._run_pipeline = _filler_nan
    try:
        done = srv.run(_tiles(app, 2))
    finally:
        srv.__dict__.pop("_run_pipeline", None)
    assert all(r.ok for r in done)
    assert srv.fault_counters["quarantine_dispatches"] == 0


@pytest.mark.parametrize("kernel", ["denoise", "camera"])
def test_nan_in_any_kernel_output_quarantines_the_tile(kernel):
    """Every kernel's output is checked, the intermediate too."""
    app = make_app("camera", size=16)
    srv = PipelineServer(app.pipeline, batch_slots=2)
    assert [ck.name for ck in srv.pipeline.kernels] == ["denoise", "camera"]
    tiles = _tiles(app, 2)
    faults.mark_poison(tiles[1])
    real = srv._run_pipeline

    def _one_output(pp, ins):
        bufs = dict(real(pp, ins))
        bad = faults._marked_slots(ins)
        arr = np.array(bufs[kernel], copy=True)
        arr[bad, 0] = np.nan
        bufs[kernel] = arr
        return bufs

    srv._run_pipeline = _one_output
    try:
        done = srv.run(tiles)
    finally:
        srv.__dict__.pop("_run_pipeline", None)
    assert done[0].ok
    assert isinstance(done[1].error, PoisonedTileError)
    assert done[1].error.kernel == kernel


# (poisoned tiles, fault_counters, dispatches) the host check gave: the
# device flags must give the same
_COUNTERS = dict(
    validation_rejects=0, backpressure_rejects=0, deadline_misses=0,
    dispatch_failures=0, recompiles=0, degraded_dispatches=0,
)
_BISECTED = dict(_COUNTERS, quarantine_dispatches=8, poisoned_tiles=2)
_CLEAN = dict(_COUNTERS, quarantine_dispatches=0, poisoned_tiles=0)


@pytest.mark.parametrize("kind", ["nan", "inf"])
def test_poison_output_quarantines_the_marked_tiles(kind):
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=SLOTS, block_h=4)
    tiles = _tiles(app, 6)
    for i in (1, 4):
        faults.mark_poison(tiles[i])
    with faults.poison_output(srv, kind=kind):
        done = srv.run(tiles)
    assert [i for i, r in enumerate(done) if not r.ok] == [1, 4]
    assert srv.fault_counters == _BISECTED
    assert srv.dispatches == 10


@pytest.mark.parametrize("kind, failed, counters, dispatches", [
    ("nan", [3, 4], _BISECTED, 10),
    # camera clamps hot pixels first, so an Inf input gives finite output
    ("inf", [], _CLEAN, 2),
])
def test_nan_input_quarantines_the_marked_tiles(kind, failed, counters,
                                                 dispatches):
    app = make_app("camera", size=16)
    srv = PipelineServer(app.pipeline, batch_slots=SLOTS, validate="shape")
    tiles = _tiles(app, 6)
    assert faults.nan_input(tiles, frac=0.3, seed=7, kind=kind) == [3, 4]
    done = srv.run(tiles)
    assert [i for i, r in enumerate(done) if not r.ok] == failed
    assert srv.fault_counters == counters
    assert srv.dispatches == dispatches


def test_a_second_dispatch_of_a_shape_compiles_nothing():
    app = make_app("camera", size=16)
    srv = PipelineServer(app.pipeline, batch_slots=2)
    tiles = _tiles(app, 2)
    srv.run(tiles)
    events = []

    def listen(event, duration=None, **kw):
        events.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        done = srv.run(tiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert all(r.ok for r in done)
    # one such event per jit trace, i.e. per lowering
    assert "/jax/core/compile/jaxpr_trace_duration" not in events
