"""Serve-path throughput: images/sec for batched pipeline dispatch.

Measures the serve bridge (``backend.serve_bridge.PipelineServer``) against
the per-tile loop it replaces: the same tile stream served one
``pallas_call`` sweep per batch versus one call per tile.  Interpret mode
on this CPU container, so the absolute numbers are dispatch-overhead
stories, not TPU wall-clock — but the *ratio* is exactly the per-call
overhead amortization the batch grid dimension buys, and the cold-vs-warm
split shows what the plan cache saves a serving process.

    PYTHONPATH=src python -m benchmarks.serve_bench            # full rows
    PYTHONPATH=src python -m benchmarks.serve_bench --smoke    # schema check

Rows persist under the ``"serve"`` key of BENCH_backend.json (written by
``python -m benchmarks.run``); ``--smoke`` regenerates cheap rows and
diffs their key sets against the persisted file, mirroring the
``--bench-smoke`` stale-schema guard for the kernel rows.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

# (app name, app kwargs, compile kwargs, batch slots): tiles are small on
# purpose — serving amortizes per-call dispatch overhead, which tiny tiles
# make visible; one fused stencil cascade and one DNN matmul tile
SERVE_CASES = [
    ("unsharp", dict(size=16), dict(fuse=True, block_h=8), 16),
    ("matmul", dict(m=16, n=16, k=16), dict(), 16),
]

# fraction of the degraded-mode stream that is marker-poisoned: the SLO
# question the degraded row answers is "what does serving look like with
# a few percent bad tiles", not "with a hostile majority"
DEGRADED_FRAC = 0.05


def _best_of(fn, reps: int):
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    return best


def serve_rows(smoke: bool = False) -> list:
    """One row per serve case: warm images/sec for the per-tile loop and
    the batched bridge, cold (compile + first dispatch) images/sec, the
    warm speedup, a bit-exactness bit (batched outputs vs the per-tile
    loop, ragged final dispatch included), the bridge's cache/dispatch
    counters, and the **degraded-mode** throughput — the same stream with
    ``DEGRADED_FRAC`` of its tiles marker-poisoned, served through
    quarantine bisection (poisoned tiles fail closed with
    ``PoisonedTileError``, healthy tiles stay bit-exact) — the price of
    fault isolation in images/sec.  ``smoke=True`` keeps the same schema
    but a single timing rep per measurement."""
    from repro.apps.paper_apps import make_app
    from repro.backend import (
        PipelineServer,
        PoisonedTileError,
        clear_pipeline_cache,
        compile_pipeline,
        pipeline_cache_stats,
    )
    from repro.backend.faults import mark_poison, poison_output

    reps = 1 if smoke else 5
    rng = np.random.default_rng(0)
    rows = []
    for name, akw, ckw, slots in SERVE_CASES:
        app = make_app(name, **akw)
        out_name = app.pipeline.output
        innames = list(app.input_extents)
        # steady-state timing on full batches; the ragged tail (a drain-time
        # case, not a throughput case) is exercised by the bit-exact check
        n_tiles = 2 * slots
        tiles = [
            {
                n: rng.standard_normal(
                    tuple(app.input_extents[n])
                ).astype(np.float32)
                for n in innames
            }
            for _ in range(n_tiles + 3)
        ]
        timed_tiles = tiles[:n_tiles]

        # -- per-tile loop baseline (warm: pipeline already traced) --------
        ptp = compile_pipeline(app.pipeline, **ckw)
        loop_out = [np.asarray(ptp.run(t)[out_name]) for t in tiles]  # warm
        t_loop = _best_of(
            lambda: [np.asarray(ptp.run(t)[out_name]) for t in timed_tiles],
            reps,
        )

        # -- batched bridge: cold = fresh cache, server build + first full
        # dispatch (plan + emit + trace); warm = steady-state dispatches --
        # (reset_stats: the per-case cache counters recorded in the row
        # below must start from zero, not accumulate across cases)
        clear_pipeline_cache(reset_stats=True)
        t0 = time.perf_counter()
        srv = PipelineServer(app.pipeline, batch_slots=slots, **ckw)
        for t in tiles[:slots]:
            srv.submit(t)
        srv.step()
        t_cold = time.perf_counter() - t0

        done = srv.run(tiles)  # incl. one ragged final dispatch
        bit_exact = all(
            np.array_equal(r.outputs[out_name], ref)
            for r, ref in zip(done, loop_out)
        )
        t_batch = _best_of(lambda: srv.run(timed_tiles), reps)
        stats = srv.stats()

        # -- degraded mode: the same stream with DEGRADED_FRAC of its tiles
        # marker-poisoned; every timed run pays the quarantine bisection
        # that isolates them, and the correctness pass asserts poisoned
        # tiles fail closed while healthy tiles match the per-tile loop
        # byte-for-byte
        n_bad = max(1, int(round(DEGRADED_FRAC * n_tiles)))
        bad_idx = sorted(
            int(i)
            for i in np.random.default_rng(1).choice(
                n_tiles, size=n_bad, replace=False
            )
        )
        degraded_tiles = [dict(t) for t in timed_tiles]  # arrays shared
        for i in bad_idx:
            mark_poison(degraded_tiles[i])
        with poison_output(srv):
            done_deg = srv.run(degraded_tiles)
            healthy_exact = all(
                np.array_equal(r.outputs[out_name], loop_out[i])
                for i, r in enumerate(done_deg)
                if i not in bad_idx
            )
            failed_closed = all(
                isinstance(done_deg[i].error, PoisonedTileError)
                for i in bad_idx
            )
            t_degraded = _best_of(lambda: srv.run(degraded_tiles), reps)
        deg_stats = srv.stats()

        rows.append({
            "kernel": name,
            "case": "x".join(
                str(e) for e in app.input_extents[innames[0]]
            ),
            "batch_slots": slots,
            "tiles": len(tiles),
            "images_sec_loop": round(n_tiles / t_loop, 1),
            "images_sec_batched_warm": round(n_tiles / t_batch, 1),
            "images_sec_batched_cold": round(slots / t_cold, 1),
            "speedup_warm": round(t_loop / t_batch, 2),
            "bit_exact": bool(bit_exact),
            "dispatches": stats["dispatches"],
            "cache_hits": stats["hits"],
            "cache_misses": stats["misses"],
            "cache_entries": stats["entries"],
            "degraded_frac": round(n_bad / n_tiles, 3),
            "images_sec_degraded_warm": round(n_tiles / t_degraded, 1),
            "degraded_vs_clean": round(t_batch / t_degraded, 2),
            "poisoned_failed_closed": bool(failed_closed),
            "healthy_bit_exact": bool(healthy_exact),
            "quarantine_dispatches": deg_stats["quarantine_dispatches"],
        })
    return rows


def serve_smoke_check(path: str | None = None) -> int:
    """``--smoke``: regenerate cheap serve rows and diff their key sets
    against the ``"serve"`` rows persisted in BENCH_backend.json."""
    import json

    if path is None:
        path = os.path.join(
            os.path.dirname(__file__), "..", "BENCH_backend.json"
        )
    with open(path) as f:
        persisted = {
            r["kernel"]: r for r in json.load(f).get("serve", [])
        }
    problems = []
    fresh = serve_rows(smoke=True)
    for row in fresh:
        old = persisted.get(row["kernel"])
        if old is None:
            problems.append(
                f"{row['kernel']}: serve row missing from "
                f"{os.path.normpath(path)}"
            )
            continue
        missing = sorted(set(row) - set(old))
        stale = sorted(set(old) - set(row))
        if missing or stale:
            problems.append(
                f"{row['kernel']}: serve schema drift — persisted lacks "
                f"{missing or '-'}, persisted has stale {stale or '-'}"
            )
        if not row["bit_exact"]:
            problems.append(
                f"{row['kernel']}: batched serve outputs diverged from the "
                f"per-tile loop"
            )
        if not row["healthy_bit_exact"]:
            problems.append(
                f"{row['kernel']}: degraded-mode healthy tiles diverged "
                f"from the per-tile loop (quarantine leaked a poisoned "
                f"dispatch)"
            )
        if not row["poisoned_failed_closed"]:
            problems.append(
                f"{row['kernel']}: a poisoned tile did not fail closed "
                f"with PoisonedTileError"
            )
    for p in problems:
        print(f"serve-smoke: {p}", file=sys.stderr)
    if problems:
        print(
            "serve-smoke: regenerate with `python -m benchmarks.run`",
            file=sys.stderr,
        )
        return 1
    print(f"serve-smoke: {len(fresh)} serve rows match the persisted schema")
    return 0


def main() -> None:
    from repro.backend import enable_compile_cache

    enable_compile_cache()
    if "--smoke" in sys.argv[1:]:
        sys.exit(serve_smoke_check())
    print(
        "kernel,case,batch_slots,tiles,images_sec_loop,"
        "images_sec_batched_warm,images_sec_batched_cold,speedup_warm,"
        "bit_exact,images_sec_degraded_warm,degraded_vs_clean,"
        "healthy_bit_exact"
    )
    for r in serve_rows():
        print(
            f"{r['kernel']},{r['case']},{r['batch_slots']},{r['tiles']},"
            f"{r['images_sec_loop']},{r['images_sec_batched_warm']},"
            f"{r['images_sec_batched_cold']},{r['speedup_warm']},"
            f"{r['bit_exact']},{r['images_sec_degraded_warm']},"
            f"{r['degraded_vs_clean']},{r['healthy_bit_exact']}"
        )
    print("# persist into BENCH_backend.json with `python -m benchmarks.run`")


if __name__ == "__main__":
    main()
