"""Tuned-vs-heuristic schedule benchmark (the ``"tune"`` rows of
BENCH_backend.json).

For each app the verifier-gated autotuner (``backend/autotune.search``)
enumerates candidate schedules, prunes with the scheduler cycle model,
certifies every survivor with ``verify_plan``, measures the certified
survivors through the plan-keyed compile cache, and stores the winner in
the schedule database.  Each row records the stored winner's warm time
against the heuristic plan's — the winner can never be slower (the
heuristic is always a measured candidate), and the speedup column is the
measured gain ``compile_pipeline(tune="auto")`` buys for that app.

    PYTHONPATH=src python -m benchmarks.tune_bench            # full rows
    PYTHONPATH=src python -m benchmarks.tune_bench --smoke    # schema check
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# (name, make_app kwargs, case label); the acceptance set — harris,
# unsharp, matmul — with matmul sized to engage the grid reduction so the
# red_chunk axis is searched, not just enumerated
TUNE_CASES = [
    ("harris", {"schedule": "sch3", "size": 20}, "20x20"),
    ("unsharp", {"size": 18}, "18x18"),
    ("matmul", {"m": 16, "n": 16, "k": 2048}, "16x16x2048"),
]


def tune_rows(smoke: bool = False, db_path: str | None = None) -> list[dict]:
    """One row per tuned app.  ``smoke=True`` bounds the search (2 apps,
    <= 16 candidates, fewer measured survivors) for the CI schema check;
    ``db_path`` overrides where winners are persisted (default: the repo
    schedule db)."""
    from repro.apps.paper_apps import make_app
    from repro.backend.autotune import default_db_path, search

    cases = TUNE_CASES[:2] if smoke else TUNE_CASES
    max_candidates = 16 if smoke else 32
    measure_top = 4 if smoke else 8
    reps = 2 if smoke else 3
    db = db_path or default_db_path()
    rows: list[dict] = []
    for name, kw, case in cases:
        app = make_app(name, **kw)
        r = search(
            app.pipeline, label=name, db=db,
            max_candidates=max_candidates, measure_top=measure_top,
            reps=reps,
        )
        rows.append({
            "kernel": f"{name}_tune",
            "case": case,
            "baseline": "heuristic-plan",
            "us_warm_tuned": round(r.warm_us, 1),
            "us_warm_heuristic": round(r.heuristic_warm_us, 1),
            "speedup": round(r.speedup, 3),
            "schedule": dict(r.schedule),
            "model_cycles_tuned": r.model_cycles,
            "model_cycles_heuristic": r.heuristic_model_cycles,
            "candidates": len(r.candidates),
            "measured": len(r.measured),
            "rejected": len(r.rejected),
        })
    return rows


def _check_db_schema(path: str) -> list[str]:
    """Schema-check one emitted schedule db: version, entry keys, and that
    every stored schedule names only tunable knobs."""
    import json

    from repro.backend.runner import TUNABLE_KEYS

    problems: list[str] = []
    if not os.path.exists(path):
        return [f"schedule db missing: {os.path.normpath(path)}"]
    with open(path) as f:
        doc = json.load(f)
    if doc.get("version") != 1:
        problems.append(f"schedule db version {doc.get('version')!r} != 1")
    entries = doc.get("entries")
    if not isinstance(entries, dict) or not entries:
        return problems + ["schedule db has no entries"]
    required = {
        "app", "schedule", "warm_us", "heuristic_warm_us", "speedup",
        "model_cycles", "candidates", "measured", "rejected",
    }
    for key, entry in entries.items():
        missing = sorted(required - set(entry))
        if missing:
            problems.append(f"db entry {key[:12]}…: missing keys {missing}")
        bad = sorted(set(entry.get("schedule", {})) - TUNABLE_KEYS)
        if bad:
            problems.append(
                f"db entry {key[:12]}…: non-tunable schedule keys {bad}"
            )
    return problems


def tune_smoke_check(path: str | None = None) -> int:
    """``--smoke``: run the bounded search (2 apps, <= 16 candidates) into
    a scratch db, schema-check the emitted db, and diff the fresh rows'
    key sets against the ``"tune"`` rows persisted in BENCH_backend.json —
    the same stale-schema gate as the kernel and serve benches."""
    import json
    import tempfile

    if path is None:
        path = os.path.join(
            os.path.dirname(__file__), "..", "BENCH_backend.json"
        )
    with open(path) as f:
        persisted = {r["kernel"]: r for r in json.load(f).get("tune", [])}
    problems: list[str] = []
    if not persisted:
        problems.append(
            f"no 'tune' rows persisted in {os.path.normpath(path)}"
        )
    with tempfile.TemporaryDirectory() as td:
        scratch_db = os.path.join(td, "schedule_db.json")
        fresh = tune_rows(smoke=True, db_path=scratch_db)
        problems += _check_db_schema(scratch_db)
    for row in fresh:
        old = persisted.get(row["kernel"])
        if old is None:
            problems.append(
                f"{row['kernel']}: tune row missing from "
                f"{os.path.normpath(path)}"
            )
            continue
        missing = sorted(set(row) - set(old))
        stale = sorted(set(old) - set(row))
        if missing or stale:
            problems.append(
                f"{row['kernel']}: tune schema drift — persisted lacks "
                f"{missing or '-'}, persisted has stale {stale or '-'}"
            )
        if row["us_warm_tuned"] > row["us_warm_heuristic"]:
            problems.append(
                f"{row['kernel']}: tuned warm time regressed past the "
                f"heuristic plan (structurally impossible — the heuristic "
                f"is always measured)"
            )
    # the committed schedule db must schema-check too
    problems += _check_db_schema(
        os.path.join(os.path.dirname(__file__), "..", "schedule_db.json")
    )
    for p in problems:
        print(f"tune-smoke: {p}", file=sys.stderr)
    if problems:
        print(
            "tune-smoke: regenerate with `python -m benchmarks.run`",
            file=sys.stderr,
        )
        return 1
    print(f"tune-smoke: {len(fresh)} rows match the persisted schema")
    return 0


def main() -> None:
    from repro.backend import enable_compile_cache

    enable_compile_cache()
    if "--smoke" in sys.argv[1:]:
        sys.exit(tune_smoke_check())
    for row in tune_rows():
        print(row)


if __name__ == "__main__":
    main()
