"""End-to-end backend demo: plan + compile paper apps to Pallas and validate.

    PYTHONPATH=src python -m repro.backend.demo [--apps a,b,c] [--smoke]
                                                [--no-fuse] [--mode m]

``--mode`` is the execution switch (interpret | compiled | auto); the
default interpret runs everywhere, auto upgrades to real Mosaic kernels on
a TPU host.  The table's ``run_us_warm`` column is the second invocation of
the same compiled pipeline — the emitted kernels are jit-bound closures, so
warm calls skip re-tracing entirely (the plan/emit/bind split).  Compiles
go through the plan-keyed pipeline cache: an identical re-compile per app
must hit (a miss is a MISMATCH note), and a stderr footer reports the
process-wide cache counters (``pipeline_cache_stats``).

For each app: lower -> plan (fusion / grid reductions / scheduler block
heights) -> generated Pallas kernels (interpret mode on CPU), run on random
inputs, and compare every materialized buffer against the von-Neumann
reference interpreter.  Also asserts the *plan shape*: multi-stage paper
apps must stay fused (fewer ``pallas_call``s than stages, intermediates in
VMEM scratch) and the large-K matmul must carry its reduction dim in the
grid — a regression from fused back to per-stage compilation fails the demo
even if the numerics still match.  Exits non-zero on any mismatch, so CI
uses it as the backend smoke test.
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# tolerance for f64 reference vs f32 kernels; stencil/DNN integer inputs are
# exact, division chains (harris response) accumulate ~1e-4
TOL = 1e-3

DEMO_APPS: List[Tuple[str, Dict]] = [
    ("gaussian", {}),
    ("harris", {"schedule": "sch3", "size": 20}),
    ("upsample", {"size": 16}),
    ("unsharp", {"size": 18}),
    # size 16 pins the strided-ring arbitration (GOLDEN_LINEBUF): at this
    # size "auto" must decline the demosaic kernel's stride-2 parity ring
    ("camera", {"size": 16}),
    ("resnet", {"img": 8, "cin": 4, "cout": 4}),
    ("mobilenet", {"img": 8, "cin": 4, "cout": 4}),
    ("matmul", {"m": 32, "n": 32, "k": 16}),
    ("matmul_bigk", {"m": 16, "n": 16, "k": 2048}),
    ("efficientnet_b0_mbconv", {"img": 6, "cin": 8}),
]

SMOKE_APPS = ["gaussian", "unsharp", "matmul", "matmul_bigk"]

# plan-shape expectations live in the golden table (backend/golden.py) so
# the demo and the pytest suite assert one contract; the demo looks up each
# app by (name, schedule) as configured in DEMO_APPS above.
def _expected_plan(name: str, kw: Dict) -> Optional[Tuple[int, int]]:
    from repro.backend.golden import expected_plan_shape

    return expected_plan_shape(name, kw.get("schedule"))


def _make(name: str, kw: Dict):
    from repro.apps.paper_apps import make_app

    if name == "matmul_bigk":
        return make_app("matmul", **kw)
    return make_app(name, **kw)


def run_demo(
    app_names=None, smoke: bool = False, fuse: bool = True,
    mode: str = "interpret", verify: bool = False,
) -> List[Dict]:
    from repro.backend import (
        build_pipeline_plan,
        clear_pipeline_cache,
        compile_pipeline,
        max_abs_error,
    )
    from repro.backend.golden import check_plan_verified

    # reset_stats: the footer main() prints reports only this demo run's
    # cache traffic, not counters inherited from the calling process
    clear_pipeline_cache(reset_stats=True)
    wanted = set(app_names) if app_names else None
    if wanted is not None:
        known = {name for name, _ in DEMO_APPS}
        unknown = wanted - known
        if unknown:
            raise SystemExit(
                f"unknown app(s) {sorted(unknown)}; choose from {sorted(known)}"
            )
    if smoke and wanted is None:
        wanted = set(SMOKE_APPS)
    rows: List[Dict] = []
    for name, kw in DEMO_APPS:
        if wanted is not None and name not in wanted:
            continue
        app = _make(name, kw)
        plan_us = None
        if verify:
            # cold plan wall-clock, measured without certification, so the
            # verifier's overhead share below is an honest ratio
            t0 = time.perf_counter()
            build_pipeline_plan(app.pipeline, fuse=fuse)
            plan_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        # verify=False here: the golden certification contract below reports
        # violations as plan_notes (a MISMATCH row + exit 1) instead of a
        # PlanVerificationError traceback mid-table
        pp = compile_pipeline(
            app.pipeline, fuse=fuse, mode=mode, verify=False, cache=True
        )
        compile_us = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        verify_notes = check_plan_verified(name, pp.plan)
        verify_us = (time.perf_counter() - t0) * 1e6
        rng = np.random.default_rng(0)
        inputs = {
            n: rng.integers(0, 16, s).astype(np.float32)
            for n, s in app.input_extents.items()
        }
        t0 = time.perf_counter()
        got = pp.run(inputs)
        got[pp.pipeline.output].block_until_ready()
        run_us = (time.perf_counter() - t0) * 1e6
        # second invocation of the same pipeline: jit-bound kernels reuse
        # the first call's trace, so this is the steady-state serve cost
        t0 = time.perf_counter()
        warm = pp.run(inputs)
        warm[pp.pipeline.output].block_until_ready()
        warm_us = (time.perf_counter() - t0) * 1e6

        plan_notes: List[str] = list(verify_notes)
        # cache observability smoke: an identical re-compile must hit the
        # plan-keyed pipeline cache (counted in the stats line main prints)
        again = compile_pipeline(
            app.pipeline, fuse=fuse, mode=mode, verify=False, cache=True
        )
        if again is not pp:
            plan_notes.append("identical re-compile missed the pipeline cache")
        if name == "matmul_bigk":
            # reference-interpreter tables are too slow at K=2048; the dense
            # f64 matmul is the same golden value
            a, b = inputs["A"].astype(np.float64), inputs["B"].astype(np.float64)
            err = float(np.max(np.abs(np.asarray(got[pp.pipeline.output]) - a @ b)))
            ck = pp.kernels[0]
            if fuse and (ck.red_grid is None or len(ck.grid) != 2):
                plan_notes.append("expected grid-level reduction for K=2048")
        else:
            errs = max_abs_error(pp, inputs, got=got)
            err = max(errs.values())
        expected = _expected_plan(name, kw) if fuse else None
        if expected is not None:
            want_stages, want_kernels = expected
            if (pp.plan.n_stages, pp.plan.n_kernels) != (want_stages, want_kernels):
                plan_notes.append(
                    f"plan regressed vs golden table: expected {want_stages} "
                    f"stages in {want_kernels} kernels, got {pp.plan.n_stages} "
                    f"in {pp.plan.n_kernels}"
                )
        # carry contract: the default plan's line-buffer decisions (and the
        # traffic/recompute drops they buy vs a line_buffer=False twin) must
        # match the golden table — a silent fallback to recompute fusion
        # fails the demo even though the numerics still match
        if fuse:
            from repro.backend.golden import check_linebuf_plan, expected_linebuf

            if expected_linebuf(name, kw.get("schedule")) is not None:
                plan_rc = build_pipeline_plan(app.pipeline, line_buffer=False)
                plan_notes.extend(
                    check_linebuf_plan(name, kw.get("schedule"), pp.plan, plan_rc)
                )
        lb_stages = sorted(
            n for names in pp.plan.line_buffered.values() for n in names
        )
        rows.append(
            {
                "app": name,
                "stages": pp.plan.n_stages,
                "kernels": pp.plan.n_kernels,
                "grids": {ck.name: list(ck.grid) for ck in pp.kernels},
                "streams": sum(len(ck.groups) + 1 for ck in pp.kernels),
                "linebuf": "+".join(lb_stages) if lb_stages else "-",
                "rings": pp.plan.n_rings,
                # contraction / block_reduce / unrolled stages (plan notes)
                "reductions": "/".join(
                    str(n) for n in pp.plan.notes["reductions"].values()
                ),
                "eval_rows": pp.plan.total_eval_rows(),
                "vmem_kib": sum(ck.plan.vmem_bytes for ck in pp.kernels) // 1024,
                "hbm_kib": pp.plan.hbm_bytes() // 1024,
                "compile_us": round(compile_us),
                "run_us_interp": round(run_us),
                "run_us_warm": round(warm_us),
                "max_err": err,
                "verified": "yes" if not verify_notes else "FAIL",
                "verify_us": round(verify_us),
                "plan_us": round(plan_us) if plan_us is not None else None,
                "plan_notes": plan_notes,
                "ok": err <= TOL and not plan_notes,
            }
        )
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--apps", help="comma-separated app subset")
    ap.add_argument("--smoke", action="store_true", help="fast 4-app subset")
    ap.add_argument(
        "--no-fuse", action="store_true",
        help="per-stage compilation (skips the plan-shape assertions)",
    )
    ap.add_argument(
        "--mode", default="interpret",
        choices=["interpret", "compiled", "auto"],
        help="execution path: interpret (portable), compiled (TPU Mosaic), "
             "auto (compiled on TPU, interpret elsewhere)",
    )
    ap.add_argument(
        "--verify", action="store_true",
        help="also report the static verifier's share of cold plan "
             "wall-clock (every plan is certified either way)",
    )
    args = ap.parse_args(argv)
    names = args.apps.split(",") if args.apps else None

    rows = run_demo(names, smoke=args.smoke, fuse=not args.no_fuse,
                    mode=args.mode, verify=args.verify)
    print(
        "app,stages,kernels,streams,linebuf,rings,reductions,eval_rows,"
        "vmem_kib,hbm_kib,compile_us,run_us_interp,run_us_warm,max_err,"
        "verified,status"
    )
    ok = True
    for r in rows:
        status = "OK" if r["ok"] else "MISMATCH"
        ok = ok and r["ok"]
        print(
            f"{r['app']},{r['stages']},{r['kernels']},{r['streams']},"
            f"{r['linebuf']},{r['rings']},{r['reductions']},{r['eval_rows']},"
            f"{r['vmem_kib']},{r['hbm_kib']},{r['compile_us']},"
            f"{r['run_us_interp']},{r['run_us_warm']},{r['max_err']:.2e},"
            f"{r['verified']},{status}"
        )
        for note in r["plan_notes"]:
            print(f"#   {r['app']}: {note}", file=sys.stderr)
    from repro.backend import pipeline_cache_stats

    cs = pipeline_cache_stats()
    print(
        f"# pipeline cache: {cs['misses']} cold compiles, {cs['hits']} hits, "
        f"{cs['evictions']} evictions, {cs['entries']} entries",
        file=sys.stderr,
    )
    if args.verify:
        plan_us = sum(r["plan_us"] for r in rows)
        verify_us = sum(r["verify_us"] for r in rows)
        pct = 100.0 * verify_us / max(plan_us, 1.0)
        print(
            f"# verify: {verify_us / 1e3:.1f}ms over {plan_us / 1e3:.1f}ms "
            f"cold plan wall-clock ({pct:.1f}% overhead)",
            file=sys.stderr,
        )
    if not ok:
        print("backend demo: MISMATCH against reference/plan", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
