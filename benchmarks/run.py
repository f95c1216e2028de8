"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived...`` CSV rows.  Roofline/dry-run numbers
live in results/dryrun (produced by ``repro.launch.dryrun``) and are
summarized by ``python -m benchmarks.roofline_table``.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def main() -> None:
    from repro.backend import enable_compile_cache

    enable_compile_cache()
    if "--bench-smoke" in sys.argv[1:]:
        sys.exit(bench_smoke_check())
    if "--tune-smoke" in sys.argv[1:]:
        from benchmarks.tune_bench import tune_smoke_check

        sys.exit(tune_smoke_check())

    from benchmarks.paper_tables import ALL_TABLES

    for fn in ALL_TABLES:
        print(f"# --- {fn.__name__}: {fn.__doc__.strip().splitlines()[0]}")
        for row in fn():
            print(row)
        print()

    write_backend_bench()


def write_backend_bench(path: str | None = None) -> str:
    """Benchmark the generated backend kernels, the serve bridge, and the
    schedule autotuner, and persist BENCH_backend.json
    (``generated_kernels`` + ``serve`` + ``tune`` keys).  The tune pass
    also refreshes the repo schedule db (``schedule_db.json``) — the
    winners ``compile_pipeline(tune="auto")`` serves."""
    import json

    from benchmarks.kernel_bench import backend_rows
    from benchmarks.serve_bench import serve_rows
    from benchmarks.tune_bench import tune_rows

    if path is None:
        path = os.path.join(os.path.dirname(__file__), "..", "BENCH_backend.json")
    rows = backend_rows()
    srows = serve_rows()
    trows = tune_rows()
    with open(path, "w") as f:
        json.dump(
            {"generated_kernels": rows, "serve": srows, "tune": trows},
            f, indent=2,
        )
    print(
        f"# wrote {os.path.normpath(path)} ({len(rows)} generated-kernel "
        f"entries, {len(srows)} serve entries, {len(trows)} tune entries)"
    )
    return path


def bench_smoke_check(path: str | None = None) -> int:
    """``--bench-smoke``: regenerate the two fast benchmark rows (gaussian +
    matmul) and diff their key sets against the rows persisted in
    BENCH_backend.json.  A benchmark-schema change that was not
    re-persisted (stale-schema drift) fails here — in seconds, instead of
    being discovered after a full benchmark run or, worse, shipping a JSON
    whose columns no longer match the code that wrote it."""
    import json

    from benchmarks.kernel_bench import backend_rows

    if path is None:
        path = os.path.join(os.path.dirname(__file__), "..", "BENCH_backend.json")
    with open(path) as f:
        persisted = {r["kernel"]: r for r in json.load(f)["generated_kernels"]}
    problems = []
    fresh = backend_rows(smoke=True)
    for row in fresh:
        old = persisted.get(row["kernel"])
        if old is None:
            problems.append(
                f"{row['kernel']}: row missing from {os.path.normpath(path)} "
                f"(benchmark gained a row that was never persisted)"
            )
            continue
        missing = sorted(set(row) - set(old))
        stale = sorted(set(old) - set(row))
        if missing or stale:
            problems.append(
                f"{row['kernel']}: schema drift vs persisted row — "
                f"persisted lacks {missing or '-'}, "
                f"persisted has stale {stale or '-'}"
            )
    for p in problems:
        print(f"bench-smoke: {p}", file=sys.stderr)
    if problems:
        print(
            "bench-smoke: regenerate with `python -m benchmarks.run`",
            file=sys.stderr,
        )
        return 1
    print(f"bench-smoke: {len(fresh)} rows match the persisted schema")
    return 0


if __name__ == "__main__":
    main()
