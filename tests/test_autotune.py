"""Schedule-autotuner suite (marker ``tune``): determinism, the schedule-db
round trip into ``compile_pipeline(tune=...)``, and the verifier gate — a
seeded-corrupted candidate is rejected by named rule and never emitted.

Run standalone with ``python -m pytest -q -m tune`` (scripts/ci.sh --tune).
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np
import pytest

from repro.apps.paper_apps import make_app
from repro.backend import clear_pipeline_cache, compile_pipeline
from repro.backend.autotune import (
    ScheduleDB,
    enumerate_candidates,
    lookup_schedule,
    lookup_schedule_entry,
    search,
)
from repro.backend.plan import PlanOptions
from repro.backend.runner import TUNABLE_KEYS, schedule_db_key

pytestmark = pytest.mark.tune


# ---------------------------------------------------------------------------
# Enumeration + determinism
# ---------------------------------------------------------------------------


def test_enumerate_candidates_spans_every_axis():
    """The heuristic {} leads; multi-stage apps get a fusion cut; big-K
    reductions get chunk candidates; rank-2 outputs get lane widths; every
    schedule names only tunable knobs and the list is deterministic."""
    uns = make_app("unsharp", size=18)
    cands = enumerate_candidates(uns.pipeline)
    assert cands[0] == {}
    assert cands == enumerate_candidates(uns.pipeline)
    keys = {k for s in cands for k in s}
    assert keys <= set(TUNABLE_KEYS)
    assert {"fuse": False} in cands
    assert any("block_h" in s and "line_buffer" in s for s in cands)

    mm = make_app("matmul", m=16, n=16, k=2048)
    mm_keys = {k for s in enumerate_candidates(mm.pipeline) for k in s}
    assert "red_chunk" in mm_keys
    # the cap truncates but always keeps the heuristic at index 0
    short = enumerate_candidates(uns.pipeline, max_candidates=5)
    assert len(short) == 5 and short[0] == {}


def test_enumerate_unflattens_lane_carry_axis():
    """The lane×carry fix un-flattened the search space: for every lane
    width in the (block_w, line_buffer) pairs, both carry modes coexist as
    candidates — the planner no longer collapses them to one plan, and the
    fingerprint dedup keeps them distinct (a carried lane plan holds rings
    the recompute twin lacks)."""
    from repro.backend.autotune import _plan_fingerprint
    from repro.backend.plan import build_pipeline_plan

    app = make_app("harris", schedule="sch3", size=20)
    cands = enumerate_candidates(app.pipeline)
    pairs = {
        (s["block_w"], s["line_buffer"])
        for s in cands if set(s) == {"block_w", "line_buffer"}
    }
    assert pairs, cands
    for bw in {bw for bw, _ in pairs}:
        assert (bw, True) in pairs and (bw, False) in pairs
    bw = sorted(pairs)[0][0]
    fp_lb = _plan_fingerprint(
        build_pipeline_plan(app.pipeline, block_w=bw, line_buffer=True)
    )
    fp_rc = _plan_fingerprint(
        build_pipeline_plan(app.pipeline, block_w=bw, line_buffer=False)
    )
    assert fp_lb != fp_rc


def test_search_is_deterministic_without_measurement():
    """Same pipeline + cost model => identical candidate list, winner, and
    db key (measure=False is the pure model path — nothing executes)."""
    app = make_app("unsharp", size=15)
    r1 = search(app.pipeline, label="unsharp", measure=False)
    r2 = search(app.pipeline, label="unsharp", measure=False)
    assert r1.schedule == r2.schedule
    assert r1.key == r2.key
    assert [c.schedule for c in r1.candidates] == [
        c.schedule for c in r2.candidates
    ]
    assert r1.model_cycles == r2.model_cycles
    assert not r1.measured and r1.warm_us is None
    # the model-path winner is the modeled-cheapest certified candidate
    assert r1.model_cycles == min(
        c.model_cycles for c in r1.candidates if c.model_cycles is not None
    )
    assert r1.model_cycles <= r1.heuristic_model_cycles


# ---------------------------------------------------------------------------
# Schedule-db round trip
# ---------------------------------------------------------------------------


def test_schedule_db_roundtrip_into_compile_pipeline(tmp_path):
    """search writes the db; a reload serves the stored schedule through
    compile_pipeline(tune=...): the tuned compile plans the winner's
    schedule, re-compiles hit the cache, and tuned vs heuristic compiles
    never collide on one cache entry."""
    dbp = str(tmp_path / "schedule_db.json")
    app = make_app("unsharp", size=15)
    clear_pipeline_cache(reset_stats=True)
    r = search(app.pipeline, label="unsharp", db=dbp, reps=2, measure_top=4)
    assert r.warm_us is not None and r.heuristic_warm_us is not None
    assert r.warm_us <= r.heuristic_warm_us      # heuristic always measured

    doc = json.loads(open(dbp).read())
    assert doc["version"] == 1 and len(doc["entries"]) == 1
    entry = doc["entries"][r.key]
    assert entry["schedule"] == r.schedule
    assert set(entry["schedule"]) <= set(TUNABLE_KEYS)
    assert entry["mode"] == "interpret"       # rows record how they measured

    reloaded = ScheduleDB.load(dbp)
    assert reloaded.lookup(r.key) == r.schedule
    assert lookup_schedule(app.pipeline, db=dbp) == r.schedule

    clear_pipeline_cache(reset_stats=True)
    tuned = compile_pipeline(app.pipeline, cache=True, tune=dbp)
    heur = compile_pipeline(app.pipeline, cache=True)
    for k, v in r.schedule.items():
        if k == "block_h":
            assert tuned.kernels[0].bh == min(
                v, tuned.kernels[0].nstage.pure_extents[0]
            )
    if r.schedule:
        assert tuned is not heur                 # distinct cache entries
    again = compile_pipeline(app.pipeline, cache=True, tune=dbp)
    assert again is tuned                        # tuned re-compile hits


def test_stored_schedule_applies_and_caller_overrides_win(tmp_path):
    """A hand-written db entry proves the lookup path end to end: the
    stored block_h plans, an explicit caller kwarg beats the db, and a
    db miss (different pipeline content) falls back to the heuristic."""
    app = make_app("gaussian", size=18)
    key = schedule_db_key(app.pipeline)
    db = ScheduleDB(path=str(tmp_path / "db.json"))
    db.store(key, {
        "app": "gaussian", "schedule": {"block_h": 2}, "warm_us": 1.0,
        "heuristic_warm_us": 2.0, "speedup": 2.0, "model_cycles": 1.0,
        "heuristic_model_cycles": 2.0, "mode": "interpret",
        "candidates": 1, "measured": 1, "rejected": 0,
    })
    db.save()

    tuned = compile_pipeline(app.pipeline, tune=db)
    assert tuned.kernels[0].bh == 2
    explicit = compile_pipeline(app.pipeline, tune=db, block_h=5)
    assert explicit.kernels[0].bh == 5           # caller beats the db
    other = make_app("gaussian", size=20)        # different content: db miss
    assert lookup_schedule(other.pipeline, db=db) is None
    heur = compile_pipeline(other.pipeline, tune=db)
    assert heur.kernels[0].bh != 2 or True       # heuristic planned

    # non-tunable keys are rejected at store time
    with pytest.raises(ValueError, match="non-tunable"):
        db.store(key, {"schedule": {"vmem_budget": 64}})


def test_interpret_measured_winner_warns_into_compiled_mode(tmp_path):
    """Stored rows record the execution mode they measured under; serving
    an interpret-measured winner to a ``mode="compiled"`` compile emits
    the one-line mismatch warning (interpret rankings may not transfer to
    TPU), while a same-mode serve stays silent."""
    import warnings

    from repro.backend.runner import TunedModeMismatchWarning

    app = make_app("gaussian", size=18)
    # compiled mode always plans TPU-aligned tiles, so the interpret run
    # that can stand in for it is an align_tpu=True one: same db key
    aligned = PlanOptions(align_tpu=True)
    key = schedule_db_key(app.pipeline, aligned)
    db = ScheduleDB(path=str(tmp_path / "db.json"))
    db.store(key, {
        "app": "gaussian", "schedule": {"block_h": 8}, "mode": "interpret",
    })
    entry = lookup_schedule_entry(app.pipeline, aligned, db=db)
    assert entry["mode"] == "interpret"

    # same mode: silent (errors would surface as test failures)
    with warnings.catch_warnings():
        warnings.simplefilter("error", TunedModeMismatchWarning)
        pp = compile_pipeline(app.pipeline, tune=db, align_tpu=True)
    assert pp.kernels[0].bh == 8               # the schedule still applies

    # mode="compiled": the warning fires when the schedule is looked up;
    # running the pipeline off-TPU is then refused by the compiled-mode gate
    with pytest.warns(TunedModeMismatchWarning, match="'interpret'.*'compiled'"):
        pp = compile_pipeline(app.pipeline, mode="compiled", tune=db)
    assert pp.kernels[0].bh == 8
    if jax.default_backend() != "tpu":
        inputs = {"input": np.zeros((18, 18), np.float32)}
        with pytest.raises(RuntimeError, match="TPU"):
            pp(inputs)


# the apps and sizes benchmarks/tune_bench.py tunes: the committed
# schedule_db.json holds one winner row for each
COMMITTED_DB = os.path.join(os.path.dirname(__file__), "..", "schedule_db.json")
COMMITTED_ROWS = {
    "harris": {"schedule": "sch3", "size": 20},
    "unsharp": {"size": 18},
    "matmul": {"m": 16, "n": 16, "k": 2048},
}


@pytest.mark.parametrize("name", sorted(COMMITTED_ROWS))
def test_committed_schedule_db_rows_are_found(name):
    """The schedule-db key of each tuned app still finds its committed
    row: a change to the key's inputs would orphan every stored winner
    and silently fall back to the heuristic planner."""
    app = make_app(name, **COMMITTED_ROWS[name])
    entry = lookup_schedule_entry(app.pipeline, db=COMMITTED_DB)
    assert entry is not None and entry["app"] == name
    stored = json.loads(open(COMMITTED_DB).read())["entries"]
    assert stored[schedule_db_key(app.pipeline)] == entry


def test_tuned_numerics_match_heuristic(tmp_path):
    """The tuned plan is the same function: bit-identical output to the
    heuristic plan on integer inputs."""
    dbp = str(tmp_path / "db.json")
    app = make_app("harris", schedule="sch3", size=20)
    search(app.pipeline, label="harris", db=dbp, reps=1, measure_top=4,
           max_candidates=16)
    rng = np.random.default_rng(0)
    inputs = {
        n: rng.integers(0, 16, tuple(app.pipeline.buffer_boxes[n].extents))
        .astype(np.float32)
        for n in app.pipeline.inputs
    }
    tuned = compile_pipeline(app.pipeline, tune=dbp)
    heur = compile_pipeline(app.pipeline)
    assert np.array_equal(
        np.asarray(tuned(inputs)), np.asarray(heur(inputs))
    )


# ---------------------------------------------------------------------------
# The verifier gate
# ---------------------------------------------------------------------------


def test_corrupted_candidate_is_rejected_and_never_emitted():
    """Seeded corruption: every non-heuristic survivor's plan gets its
    VMEM bookkeeping misstated (the UB403 seed from the verifier suite)
    before certification.  All of them must land in ``rejected`` with the
    named rule, none is measured (never emitted), and the winner is the
    untouched heuristic plan."""
    app = make_app("gaussian", size=18)
    corrupted = []

    def hook(schedule, plan):
        if schedule == {}:
            return plan                          # leave the heuristic alone
        kg = plan.kernels[0]
        kg.ws = (kg.ws[0] + 16, kg.ws[1])        # misstate the working set
        corrupted.append(schedule)
        return plan

    r = search(app.pipeline, label="gaussian", reps=1, measure_top=4,
               plan_hook=hook)
    assert corrupted, "hook never fired"
    assert len(r.rejected) == len(corrupted)
    for cand in r.rejected:
        assert cand.verified is False
        assert "UB403" in cand.rules
        assert cand.warm_us is None              # never emitted or run
    measured_scheds = [c.schedule for c in r.measured]
    assert measured_scheds == [{}]               # only the heuristic ran
    assert r.schedule == {}


def test_every_measured_candidate_was_certified(tmp_path):
    """The gate invariant on a clean search: everything measured passed
    verify_plan first, and rejected/measured partition the survivors."""
    app = make_app("matmul", m=16, n=16, k=2048)
    r = search(app.pipeline, label="matmul", db=str(tmp_path / "db.json"),
               reps=1, measure_top=4, max_candidates=16)
    assert r.measured and all(c.verified for c in r.measured)
    assert all(c.verified is False for c in r.rejected)
    assert r.warm_us <= r.heuristic_warm_us
    # audit counters survive into the db entry
    assert r.entry["measured"] == len(r.measured)
    assert r.entry["rejected"] == len(r.rejected)
