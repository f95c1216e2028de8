"""Seeded fault-injection chaos suite for the serving stack.

``tests/test_verify.py`` proves the *static* side of robustness: seeded
plan corruptions are rejected by named ``UBxyz`` rules before emission.
This suite proves the *runtime* side: every fault class the serve path
has — corrupt schedule db, poisoned plan-cache entry, NaN/Inf inputs and
mid-pipeline outputs, kernel raises, slow dispatches, queue overload —
is injected deterministically (``backend.faults``) and asserted to either
**fully recover** (healthy requests complete bit-exact against the
per-tile pipeline) or **fail closed** with its specific named class from
``backend.errors``.  The one outcome that must never appear is a silent
wrong answer: a request with ``ok=True`` whose outputs came from a
poisoned dispatch.

The quarantine-bisection property is pinned across every serving
composition this backend supports — plain batched grids, lane-blocked
grids (``block_w``), carried line buffers (``line_buffer=True``), lane ×
carry column rings, and ragged final dispatches — because bisection
re-dispatches subsets padded to capacity, and each of those plan shapes
pads and discards differently.
"""

import os
import warnings

import numpy as np
import pytest

from conftest import SWEEP_SEED, sweep_inputs
from repro.apps.paper_apps import make_app
from repro.backend import (
    DeadlineExceededError,
    DegradedModeWarning,
    LaneCarryDegradeWarning,
    MissingInputError,
    NonFiniteInputError,
    PipelineServer,
    PoisonedTileError,
    QueueFullError,
    RequestError,
    ScheduleDB,
    ScheduleDBCorruptWarning,
    TunedModeMismatchWarning,
    autotune_search,
    clear_pipeline_cache,
    compile_pipeline,
    drop_pipeline_cache_entry,
    pipeline_cache_stats,
    schedule_db_key,
)
from repro.backend.autotune import lookup_schedule
from repro.backend.faults import (
    DB_CORRUPTIONS,
    FaultClock,
    InjectedFault,
    corrupt_schedule_db,
    kernel_raise,
    mark_poison,
    nan_input,
    poison_cache_entry,
    poison_output,
    slow_dispatch,
)

pytestmark = pytest.mark.faults


def _tiles(app, n, seed=SWEEP_SEED):
    return [sweep_inputs(app, seed + i, "u4") for i in range(n)]


def _assert_bit_exact(req, tile, ref_pp, out_name):
    assert req.ok, f"expected ok, got error: {req.error}"
    assert np.array_equal(
        req.outputs[out_name], np.asarray(ref_pp.run(tile)[out_name])
    )


# ---------------------------------------------------------------------------
# Admission validation: poison is rejected before it can enter a dispatch
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["nan", "inf"])
def test_nonfinite_input_rejected_at_submit(kind):
    """A seeded fraction of NaN/Inf tiles is rejected at submit with the
    named ``NonFiniteInputError`` (never queued), while every healthy
    tile drains bit-exact — request isolation at the admission gate."""
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=4, block_h=4)
    tiles = _tiles(app, 8)
    bad = nan_input(tiles, frac=0.25, seed=3, kind=kind)
    assert bad, "injector must poison at least one tile"
    accepted, rejected = [], []
    for i, t in enumerate(tiles):
        try:
            accepted.append((i, srv.submit(t)))
        except NonFiniteInputError as e:
            assert e.code == "REQ-NONFINITE"
            assert "[REQ-NONFINITE]" in str(e) and "first at" in str(e)
            assert isinstance(e, ValueError)      # back-compat contract
            rejected.append(i)
    assert rejected == bad
    while srv.pending:
        srv.step()
    ref = compile_pipeline(app.pipeline, block_h=4)
    out = app.pipeline.output
    for i, req in accepted:
        _assert_bit_exact(req, tiles[i], ref, out)
    s = srv.stats()
    assert s["validation_rejects"] == len(bad)
    assert s["poisoned_tiles"] == 0 and s["quarantine_dispatches"] == 0


def test_submit_rejects_bad_dtype_by_name():
    """Satellite: non-numeric dtypes fail at submit with a named
    ``RequestError`` listing expected vs got — not a deep Pallas error at
    drain time."""
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=2, block_h=4)
    shape = tuple(app.pipeline.buffer_boxes["input"].extents)
    for bad in (
        np.full(shape, "x", dtype="<U4"),
        np.zeros(shape, np.complex64),
        np.zeros(shape, "datetime64[s]"),
    ):
        with pytest.raises(RequestError, match="expected float32") as ei:
            srv.submit({"input": bad})
        assert ei.value.code == "REQ"
        assert str(bad.dtype) in str(ei.value)    # names what it got
        assert isinstance(ei.value, ValueError)
    with pytest.raises(MissingInputError, match="missing input") as ei:
        srv.submit({})
    assert ei.value.code == "REQ-MISSING"
    assert isinstance(ei.value, KeyError)
    assert srv.stats()["validation_rejects"] == 4
    assert srv.stats()["pending"] == 0            # nothing invalid queued


# ---------------------------------------------------------------------------
# Quarantine bisection: poisoned outputs isolated, healthy tiles bit-exact
# ---------------------------------------------------------------------------

# (app ctor args, compile kwargs, batch_slots, n tiles, marked indices) —
# one case per serving composition whose padding/discard behaviour differs
QUARANTINE_CASES = [
    pytest.param(
        ("gaussian", dict(size=13)), dict(block_h=4), 4, 6, [1],
        id="batched",
    ),
    pytest.param(
        ("gaussian", dict(size=13)), dict(block_h=4), 4, 6, [5],
        id="ragged-final-dispatch",
    ),
    pytest.param(
        ("gaussian", dict(size=21)), dict(block_w=8), 3, 4, [0],
        id="lane-blocked",
    ),
    pytest.param(
        ("unsharp", dict(size=15)),
        dict(fuse=True, block_h=5, line_buffer=True), 3, 5, [2],
        id="carried-line-buffer",
    ),
    pytest.param(
        ("harris", dict(schedule="sch3", size=20)),
        dict(block_w=8, line_buffer=True), 3, 4, [1, 3],
        id="lane-carry-rings-two-poisoned",
    ),
]


@pytest.mark.parametrize("mk, ckw, slots, n, marks", QUARANTINE_CASES)
def test_quarantine_isolates_poison_bit_exact(mk, ckw, slots, n, marks):
    """The core chaos property, across plan compositions: a mid-pipeline
    numeric fault that follows marked tile(s) is bisected down to exactly
    those tiles (``PoisonedTileError``), and every healthy tile's output
    is bit-equal to the per-tile pipeline — no value from a poisoned
    dispatch is ever returned."""
    name, kwargs = mk
    app = make_app(name, **kwargs)
    srv = PipelineServer(app.pipeline, batch_slots=slots, **ckw)
    tiles = _tiles(app, n)
    for i in marks:
        mark_poison(tiles[i])                 # finite: passes validation
    with poison_output(srv):
        done = srv.run(tiles)
    assert "_run_pipeline" not in srv.__dict__    # injector restored
    ref = compile_pipeline(app.pipeline, **ckw)
    out = app.pipeline.output
    for i, (req, tile) in enumerate(zip(done, tiles)):
        if i in marks:
            assert req.done and not req.ok and req.outputs is None
            assert isinstance(req.error, PoisonedTileError)
            assert req.error.code == "REQ-POISONED"
            assert "dispatched alone" in str(req.error)
        else:
            _assert_bit_exact(req, tile, ref, out)
    s = srv.stats()
    assert s["poisoned_tiles"] == len(marks)
    assert s["quarantine_dispatches"] >= 1
    assert s["failed"] == len(marks)
    # the fault is gone with the injector: the same marked tiles now serve
    redo = srv.run([tiles[i] for i in marks])
    for i, req in zip(marks, redo):
        _assert_bit_exact(req, tiles[i], ref, out)


def test_nan_admitted_under_shape_validation_is_quarantined():
    """Defense in depth: with ``validate="shape"`` the finite-values guard
    is off, so a NaN tile reaches a dispatch — and the output quarantine
    still isolates it while its batch neighbours stay bit-exact."""
    app = make_app("gaussian", size=13)
    srv = PipelineServer(
        app.pipeline, batch_slots=4, block_h=4, validate="shape"
    )
    tiles = _tiles(app, 4)
    bad = nan_input(tiles, frac=0.3, seed=7)
    done = srv.run(tiles)                     # no submit-time rejection
    ref = compile_pipeline(app.pipeline, block_h=4)
    out = app.pipeline.output
    for i, (req, tile) in enumerate(zip(done, tiles)):
        if i in bad:
            assert isinstance(req.error, PoisonedTileError)
            assert "non-finite" in str(req.error)
        else:
            _assert_bit_exact(req, tile, ref, out)
    assert srv.stats()["validation_rejects"] == 0
    assert srv.stats()["poisoned_tiles"] == len(bad)


# ---------------------------------------------------------------------------
# Retry-with-recompile ladder
# ---------------------------------------------------------------------------


def test_transient_kernel_raise_recovers_bit_exact():
    """A kernel raise at dispatch 1 and never again: the ladder drops the
    cache entry, recompiles the same schedule, retries — every request
    completes bit-exact, with one ``DegradedModeWarning`` naming the
    recovery."""
    app = make_app("gaussian", size=13)
    ckw = dict(block_h=4)
    srv = PipelineServer(app.pipeline, batch_slots=4, **ckw)
    tiles = _tiles(app, 6)
    with kernel_raise(srv, at_dispatch=1):
        with pytest.warns(DegradedModeWarning, match="recovered"):
            done = srv.run(tiles)
    assert "_run_pipeline" not in srv.__dict__
    ref = compile_pipeline(app.pipeline, **ckw)
    out = app.pipeline.output
    for req, tile in zip(done, tiles):
        _assert_bit_exact(req, tile, ref, out)
    s = srv.stats()
    assert s["dispatch_failures"] == 1
    assert s["recompiles"] == 1               # first rung was enough
    assert s["degraded_dispatches"] == 1
    assert s["quarantine_dispatches"] == 0 and s["poisoned_tiles"] == 0


def test_recovery_ladder_reaches_heuristic_schedule():
    """Two consecutive raises (initial dispatch + same-schedule retry)
    push the ladder to its heuristic rung — tunables stripped,
    ``tune=False`` — which serves correctly: matmul on integer tiles is
    exact under any schedule."""
    app = make_app("matmul", m=16, n=16, k=16)
    srv = PipelineServer(app.pipeline, batch_slots=2, block_h=4)
    tiles = _tiles(app, 2)
    real = srv._run_pipeline
    calls = {"n": 0}

    def flaky(pp, ins):
        calls["n"] += 1
        if calls["n"] <= 2:
            raise InjectedFault(f"flaky dispatch {calls['n']}")
        return real(pp, ins)

    srv._run_pipeline = flaky
    try:
        with pytest.warns(DegradedModeWarning, match="heuristic"):
            done = srv.run(tiles)
    finally:
        del srv.__dict__["_run_pipeline"]
    s = srv.stats()
    assert s["dispatch_failures"] == 1 and s["recompiles"] == 2
    assert s["degraded_dispatches"] == 1
    for req, tile in zip(done, tiles):
        assert req.ok
        want = tile["A"].astype(np.float64) @ tile["B"].astype(np.float64)
        assert np.array_equal(req.outputs["matmul"].astype(np.float64), want)


def test_poisoned_cache_entry_recovers():
    """The evicted-then-repopulated-broken scenario: the pipeline object a
    server (and the cache row) holds raises on every run.  Recovery drops
    the entry and recompiles — a *fresh* object the poison cannot follow —
    and serving continues bit-exact."""
    app = make_app("gaussian", size=13)
    ckw = dict(block_h=4)
    srv = PipelineServer(app.pipeline, batch_slots=3, **ckw)
    broken = srv.pipeline
    tiles = _tiles(app, 5)
    with poison_cache_entry(broken):
        with pytest.raises(InjectedFault):
            broken.run(tiles[0])              # the poison is live
        with pytest.warns(DegradedModeWarning, match="recovered"):
            done = srv.run(tiles)
    assert srv.pipeline is not broken         # the table moved off it
    assert srv.stats()["recompiles"] >= 1
    ref = compile_pipeline(app.pipeline, **ckw)
    out = app.pipeline.output
    for req, tile in zip(done, tiles):
        _assert_bit_exact(req, tile, ref, out)


def test_marker_raise_isolated_by_bisection():
    """A raise that follows the poisoned tile (every dispatch containing
    it raises, recompiles included): the ladder exhausts, bisection
    isolates the tile, the rest of its batch completes from clean
    dispatches."""
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=4, block_h=4)
    tiles = _tiles(app, 4)
    mark_poison(tiles[2])
    with kernel_raise(srv, on_marker=True):
        done = srv.run(tiles)
    ref = compile_pipeline(app.pipeline, block_h=4)
    out = app.pipeline.output
    for i, (req, tile) in enumerate(zip(done, tiles)):
        if i == 2:
            assert isinstance(req.error, PoisonedTileError)
            assert "dispatched alone" in str(req.error)
        else:
            _assert_bit_exact(req, tile, ref, out)
    s = srv.stats()
    assert s["dispatch_failures"] == 1 and s["recompiles"] == 2
    assert s["degraded_dispatches"] == 0      # no rung recovered
    assert s["poisoned_tiles"] == 1 and s["quarantine_dispatches"] >= 1


# ---------------------------------------------------------------------------
# Deadlines and backpressure
# ---------------------------------------------------------------------------


def test_deadline_expires_in_queue():
    app = make_app("gaussian", size=13)
    clock = FaultClock()
    srv = PipelineServer(app.pipeline, batch_slots=2, block_h=4, clock=clock)
    tiles = _tiles(app, 3)
    late = srv.submit(tiles[0], deadline=5.0)
    ok1 = srv.submit(tiles[1], deadline=50.0)
    ok2 = srv.submit(tiles[2])                # no deadline
    clock.advance(10.0)
    finished = srv.step()
    assert late in finished and late.outputs is None
    assert isinstance(late.error, DeadlineExceededError)
    assert late.error.code == "REQ-DEADLINE"
    assert "expired in queue" in str(late.error)
    while srv.pending:
        srv.step()
    assert ok1.ok and ok2.ok
    assert srv.stats()["deadline_misses"] == 1


def test_slow_dispatch_discards_late_results():
    """A dispatch slower than the deadline: the request *computed* but
    completed late — outputs are discarded, never returned as if on time;
    a request with enough budget on the same dispatch still completes."""
    app = make_app("gaussian", size=13)
    clock = FaultClock()
    srv = PipelineServer(
        app.pipeline, batch_slots=2, block_h=4,
        clock=clock, default_deadline=5.0,
    )
    tiles = _tiles(app, 2)
    tight = srv.submit(tiles[0])              # default 5s budget
    roomy = srv.submit(tiles[1], deadline=100.0)
    with slow_dispatch(srv, clock, dispatch_s=10.0):
        srv.step()
    assert tight.done and not tight.ok and tight.outputs is None
    assert isinstance(tight.error, DeadlineExceededError)
    assert "late results are discarded" in str(tight.error)
    assert roomy.ok
    assert srv.stats()["deadline_misses"] == 1


def test_backpressure_reject_and_block():
    app = make_app("gaussian", size=13)
    tiles = _tiles(app, 4)
    srv = PipelineServer(
        app.pipeline, batch_slots=2, block_h=4,
        max_pending=2, admission="reject",
    )
    srv.submit(tiles[0])
    srv.submit(tiles[1])
    with pytest.raises(QueueFullError, match="max_pending=2") as ei:
        srv.submit(tiles[2])
    assert ei.value.code == "SERVE-QUEUE-FULL"
    assert ei.value.witness == (2, 2)
    assert srv.stats()["backpressure_rejects"] == 1
    srv.step()                                # drain makes room
    srv.submit(tiles[2])                      # now admitted

    blk = PipelineServer(
        app.pipeline, batch_slots=2, block_h=4,
        max_pending=2, admission="block",
    )
    reqs = [blk.submit(t) for t in tiles]     # 3rd/4th submit self-service
    assert len(blk.pending) <= 2
    while blk.pending:
        blk.step()
    assert all(r.ok for r in reqs)
    assert blk.stats()["backpressure_rejects"] == 0


# ---------------------------------------------------------------------------
# Schedule-db corruption (satellite: tune="auto" degrades, never raises)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", DB_CORRUPTIONS)
def test_schedule_db_corruption_degrades_and_round_trips(tmp_path, mode):
    """Every corruption mode: the tuned compile degrades to the heuristic
    schedule with a named ``ScheduleDBCorruptWarning`` (bit-identical to a
    plain heuristic compile), and once the bytes are restored the stored
    winner serves again warning-free — the round trip."""
    app = make_app("gaussian", size=13)
    path = str(tmp_path / "schedule_db.json")
    res = autotune_search(
        app.pipeline, label="g13", db=path, measure=False
    )
    assert lookup_schedule(app.pipeline, db=path) == res.schedule
    ins = sweep_inputs(app, SWEEP_SEED)
    out = app.pipeline.output
    with corrupt_schedule_db(path, mode):
        with pytest.warns(ScheduleDBCorruptWarning):
            assert lookup_schedule(app.pipeline, db=path) is None
        with pytest.warns(ScheduleDBCorruptWarning, match="heuristic"):
            pp = compile_pipeline(app.pipeline, tune=path)
        heur = compile_pipeline(app.pipeline)
        assert np.array_equal(
            np.asarray(pp.run(ins)[out]), np.asarray(heur.run(ins)[out])
        )
    with warnings.catch_warnings():           # restored file: no warning
        warnings.simplefilter("error", ScheduleDBCorruptWarning)
        assert lookup_schedule(app.pipeline, db=path) == res.schedule
        compile_pipeline(app.pipeline, tune=path)


def test_truncated_db_on_disk_round_trip(tmp_path):
    """Satellite spelled out at the file level: a truncated
    ``schedule_db.json`` loads strict as the original error, non-strict as
    an empty db with the reason recorded, and a fresh ``search`` rewrites
    it into a servable db again."""
    app = make_app("gaussian", size=13)
    path = str(tmp_path / "schedule_db.json")
    autotune_search(app.pipeline, label="g13", db=path, measure=False)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[: len(blob) // 2])
    with pytest.raises(ValueError):
        ScheduleDB.load(path)                 # strict: loud for tools
    db = ScheduleDB.load(path, strict=False)
    assert db.entries == {} and db.corrupt and "JSONDecodeError" in db.corrupt
    with pytest.warns(ScheduleDBCorruptWarning, match="rewriting"):
        res = autotune_search(
            app.pipeline, label="g13", db=path, measure=False
        )
    assert lookup_schedule(app.pipeline, db=path) == res.schedule


def test_malformed_rows_degrade_by_name(tmp_path):
    """Unknown ``row_version`` and non-tunable schedule keys degrade to a
    heuristic miss with the reason in the warning — a future writer's rows
    never poison this reader's compile."""
    app = make_app("gaussian", size=13)
    key = schedule_db_key(app.pipeline)
    for row, reason in [
        ({"schedule": {"block_h": 4}, "row_version": 99}, "row_version"),
        ({"schedule": {"warp_speed": 9}}, "non-tunable"),
        ("not an object", "not an object"),
        ({"measurements": []}, "no 'schedule'"),
    ]:
        path = str(tmp_path / f"db_{reason[:4].strip()}.json")
        ScheduleDB(path=path, entries={key: row}).save()
        with pytest.warns(ScheduleDBCorruptWarning, match=reason):
            assert lookup_schedule(app.pipeline, db=path) is None


# ---------------------------------------------------------------------------
# Satellite: every named warning points at the caller (stacklevel audit)
# ---------------------------------------------------------------------------


def _only(record, category):
    msgs = [w for w in record if issubclass(w.category, category)]
    assert msgs, f"no {category.__name__} raised"
    return msgs


def test_warning_stacklevels_point_at_caller(tmp_path):
    """Each named warning's ``stacklevel`` walks its internal frames so
    the report names *this* file (the user's call site), not a frame
    inside the backend — the property that makes a degradation log
    actionable."""
    me = os.path.basename(__file__)
    app = make_app("gaussian", size=13)
    bad = str(tmp_path / "bad_db.json")
    with open(bad, "w") as f:
        f.write("not json")

    with pytest.warns(ScheduleDBCorruptWarning) as rec:
        lookup_schedule(app.pipeline, db=bad)       # stacklevel=3 chain
    assert all(
        os.path.basename(w.filename) == me
        for w in _only(rec, ScheduleDBCorruptWarning)
    )

    with pytest.warns(ScheduleDBCorruptWarning) as rec:
        compile_pipeline(app.pipeline, tune=bad)        # stacklevel=4 chain
    assert all(
        os.path.basename(w.filename) == me
        for w in _only(rec, ScheduleDBCorruptWarning)
    )

    tuned = str(tmp_path / "mode_db.json")
    ScheduleDB(
        path=tuned,
        entries={
            schedule_db_key(app.pipeline): {
                "schedule": {}, "mode": "compiled",
            }
        },
    ).save()
    with pytest.warns(TunedModeMismatchWarning) as rec:
        compile_pipeline(app.pipeline, tune=tuned)      # stacklevel=2
    assert all(
        os.path.basename(w.filename) == me
        for w in _only(rec, TunedModeMismatchWarning)
    )

    wide = make_app("gaussian", size=24, width=40)
    with pytest.warns(LaneCarryDegradeWarning) as rec:  # stacklevel=3
        compile_pipeline(wide.pipeline, block_w=1, line_buffer=True)
    assert all(
        os.path.basename(w.filename) == me
        for w in _only(rec, LaneCarryDegradeWarning)
    )

    srv = PipelineServer(app.pipeline, batch_slots=2, block_h=4)
    with kernel_raise(srv, at_dispatch=1):
        with pytest.warns(DegradedModeWarning) as rec:  # stacklevel=4
            srv.run(_tiles(app, 2))
    assert all(
        os.path.basename(w.filename) == me
        for w in _only(rec, DegradedModeWarning)
    )


# ---------------------------------------------------------------------------
# Satellite: cache-stats counters under eviction + clear with live servers
# ---------------------------------------------------------------------------


def test_cache_stats_across_eviction_and_clear(monkeypatch):
    """A server's bound pipeline outlives its cache row: LRU eviction and
    ``clear_pipeline_cache(reset_stats=False)`` drop the row but serving
    keeps working off the bound object with **zero** extra misses — and
    the counters stay exact through both."""
    from repro.backend import runner

    clear_pipeline_cache(reset_stats=True)
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=2, block_h=4)
    s0 = pipeline_cache_stats()
    assert s0 == {"hits": 0, "misses": 1, "evictions": 0, "entries": 1}

    monkeypatch.setattr(runner, "_PIPELINE_CACHE_MAX", 1)
    compile_pipeline(app.pipeline, block_h=2, cache=True)   # evicts srv row
    compile_pipeline(app.pipeline, block_h=8, cache=True)   # evicts again
    s1 = pipeline_cache_stats()
    assert s1 == {"hits": 0, "misses": 3, "evictions": 2, "entries": 1}
    # the server's row is gone (a deliberate drop now finds nothing — and
    # deliberate drops never count as evictions)
    assert drop_pipeline_cache_entry(srv.pipeline.cache_key) is False
    assert pipeline_cache_stats()["evictions"] == 2

    tiles = _tiles(app, 3)
    done = srv.run(tiles)                     # serves off the bound object
    ref = compile_pipeline(app.pipeline, block_h=4)          # uncached ref
    out = app.pipeline.output
    for req, tile in zip(done, tiles):
        _assert_bit_exact(req, tile, ref, out)
    s2 = pipeline_cache_stats()
    assert s2["misses"] == 3 and s2["hits"] == 0             # serving: 0 misses

    clear_pipeline_cache(reset_stats=False)
    s3 = pipeline_cache_stats()
    assert s3 == {"hits": 0, "misses": 3, "evictions": 2, "entries": 0}
    done2 = srv.run(_tiles(app, 2, seed=SWEEP_SEED + 9))
    assert all(r.ok for r in done2)
    assert pipeline_cache_stats()["misses"] == 3             # still none
    # full reset for whoever runs next
    clear_pipeline_cache(reset_stats=True)
    assert pipeline_cache_stats() == {
        "hits": 0, "misses": 0, "evictions": 0, "entries": 0
    }
