"""Backend tests: generated Pallas kernels vs the reference interpreter,
plan-level properties (fusion, VMEM budgets, grid reductions, scheduler
block heights), and property tests tying BlockSpec delivery metadata to the
access maps."""

import numpy as np
import pytest

from repro.apps.paper_apps import make_app
from repro.backend import (
    FusionInfeasible,
    build_pipeline_plan,
    compile_pipeline,
    max_abs_error,
    reference_arrays,
    scheduler_cost,
)
from repro.backend.golden import GOLDEN_PLAN_SHAPES
from repro.core.scheduling import raster_cycles
from repro.core.ubplan import VMEM_BYTES, align_tpu_shape, plan_affine_stage
from repro.frontend.lower import normalize_pipeline

pytestmark = pytest.mark.backend

# f64 reference vs f32 kernels; integer inputs keep stencils/DNNs exact,
# division chains (harris response) accumulate ~1e-4
TOL = 1e-3

APP_CASES = [
    ("gaussian", {"size": 18}),
    ("harris", {"schedule": "sch3", "size": 20}),     # cascade, no recompute
    ("harris", {"schedule": "sch2", "size": 20}),     # cascade w/ recompute
    ("harris", {"schedule": "sch6", "size": 20}),     # host stage rides along
    ("upsample", {"size": 16}),
    ("unsharp", {"size": 18}),
    ("camera", {"size": 8}),
    ("resnet", {"img": 8, "cin": 4, "cout": 4}),
    ("mobilenet", {"img": 8, "cin": 4, "cout": 4}),
    ("matmul", {"m": 24, "n": 16, "k": 8}),
]

# multi-stage apps the planner must fuse — expectations come from the one
# golden table (backend/golden.py) that repro.backend.demo also enforces in
# CI, so plan-shape drift fails in a single place
FUSED_CASES = [
    ("harris", {"schedule": "sch3", "size": 20},
     *GOLDEN_PLAN_SHAPES[("harris", "sch3")]),
    ("harris", {"schedule": "sch2", "size": 20},
     *GOLDEN_PLAN_SHAPES[("harris", "sch2")]),
    ("unsharp", {"size": 18}, *GOLDEN_PLAN_SHAPES[("unsharp", None)]),
    ("camera", {"size": 8}, *GOLDEN_PLAN_SHAPES[("camera", None)]),
    ("mobilenet", {"img": 8, "cin": 4, "cout": 4},
     *GOLDEN_PLAN_SHAPES[("mobilenet", None)]),
]


def _inputs(app, seed=0):
    rng = np.random.default_rng(seed)
    return {
        n: rng.integers(0, 16, s).astype(np.float32)
        for n, s in app.input_extents.items()
    }


@pytest.mark.parametrize("name,kw", APP_CASES, ids=[f"{n}-{i}" for i, (n, _) in enumerate(APP_CASES)])
def test_generated_kernels_match_reference(name, kw):
    """Differential test: every buffer the fused plan materializes must
    match the von-Neumann reference interpreter."""
    app = make_app(name, **kw)
    pp = compile_pipeline(app.pipeline)
    errs = max_abs_error(pp, _inputs(app))
    assert max(errs.values()) <= TOL, errs


# power-of-two divisions / pure MACs on integer inputs: every intermediate
# is exactly f32-representable, so fused == unfused bit-for-bit; apps with
# inexact divisions (harris response, unsharp ratio, camera gamma) may
# differ by an ulp when XLA fuses across the former stage boundary
EXACT_APPS = {"gaussian", "upsample", "resnet", "mobilenet", "matmul"}


@pytest.mark.parametrize("name,kw", APP_CASES, ids=[f"{n}-{i}" for i, (n, _) in enumerate(APP_CASES)])
def test_fused_matches_unfused(name, kw):
    """The fused pipeline's output equals the per-stage pipeline's output:
    bit-for-bit where the unfused path was already exactly representable,
    to an ulp otherwise."""
    app = make_app(name, **kw)
    inputs = _inputs(app)
    got_f = np.asarray(compile_pipeline(app.pipeline)(inputs))
    got_u = np.asarray(compile_pipeline(app.pipeline, fuse=False)(inputs))
    if name in EXACT_APPS:
        assert np.array_equal(got_f, got_u), name
    else:
        np.testing.assert_allclose(got_f, got_u, rtol=1e-5, atol=1e-5)


def test_stencils_and_dnn_bit_exact():
    """Integer-input stencils and pure-MAC apps are exactly f32-representable:
    generated kernels must be *bit*-equal to the reference."""
    for name, kw in [
        ("gaussian", {"size": 18}),
        ("upsample", {"size": 16}),
        ("resnet", {"img": 8, "cin": 4, "cout": 4}),
        ("matmul", {"m": 16, "n": 16, "k": 8}),
    ]:
        app = make_app(name, **kw)
        pp = compile_pipeline(app.pipeline)
        inputs = _inputs(app)
        got = np.asarray(pp(inputs), np.float64)
        want = reference_arrays(app.pipeline, inputs)[app.pipeline.output]
        assert np.array_equal(got, want), name


def test_matmul_against_plain_jnp():
    app = make_app("matmul", m=24, n=16, k=8)
    rng = np.random.default_rng(1)
    a = rng.standard_normal((24, 8)).astype(np.float32)
    b = rng.standard_normal((8, 16)).astype(np.float32)
    out = np.asarray(compile_pipeline(app.pipeline)({"A": a, "B": b}))
    np.testing.assert_allclose(out, a @ b, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Fusion
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,kw,n_stages,n_kernels",
    FUSED_CASES,
    ids=[c[0] + ("-" + c[1].get("schedule", "")).rstrip("-") for c in FUSED_CASES],
)
def test_fusion_counts(name, kw, n_stages, n_kernels):
    """Multi-stage paper apps must compile to fewer pallas_calls than stages
    with the intermediates held in VMEM scratch."""
    app = make_app(name, **kw)
    pp = compile_pipeline(app.pipeline)
    assert pp.plan.n_stages == n_stages
    assert pp.plan.n_kernels == n_kernels
    fused_kernels = [k for k in pp.kernels if k.fused]
    assert fused_kernels, name
    # every fused intermediate has scratch panels, none is materialized
    for ck in fused_kernels:
        assert ck.kg.scratch_entries()
    got = pp.run(_inputs(app))
    for dropped in pp.plan.fused_away:
        assert dropped not in got


def test_fusion_shift_sets_cover_consumer_demand():
    """The producer rows materialized per panel (shift set) are exactly the
    rows the consumers' affine access maps demand."""
    app = make_app("unsharp", size=18)
    pp = compile_pipeline(app.pipeline)
    kg = pp.kernels[0].kg
    shifts = {sp.name: sp.shifts for sp in kg.stages}
    # unsharp: out<-sharpen<-blur_y<-blur_x; blur_y taps blur_x rows +0..2
    assert shifts["sharpen"] == (0,)
    assert shifts["blur_y"] == (0,)
    assert shifts["blur_x"] == (0, 1, 2)


def test_fusion_respects_vmem_budget():
    """Property: fusion never merges stages whose intermediate live range
    exceeds the VMEM budget.  Tight budgets no longer force a split: the
    planner narrows the lane dim (2-D lane-blocked grid) until the fused
    working set fits, so the chain keeps its VMEM intermediates at a
    fraction of the old minimum footprint."""
    app = make_app("unsharp", size=18)
    # generous budget -> single fused kernel whose working set fits
    for budget in (1 << 20, 8 << 20, 96 << 20):
        plan = build_pipeline_plan(app.pipeline, vmem_budget=budget)
        for kg in plan.kernels:
            if kg.fused:
                assert kg.vmem_bytes <= budget, (budget, kg.vmem_bytes)
    # a budget far below the full-width working set: the lane grid rescues
    # the fusion — one kernel, 2-D grid, still within budget
    for budget in (256, 1024):
        plan = build_pipeline_plan(app.pipeline, vmem_budget=budget)
        assert plan.n_kernels == 1
        kg = plan.kernels[0]
        assert kg.fused and kg.lane_grid is not None and len(kg.grid) == 2
        assert kg.vmem_bytes <= budget, (budget, kg.vmem_bytes)
    # with the lane grid disabled the old degradation applies: the 4-stage
    # chain no longer fits one kernel, but pairs do -> the planner splits
    plan = build_pipeline_plan(app.pipeline, vmem_budget=1024, lane_block=False)
    assert plan.n_kernels > 1
    for kg in plan.kernels:
        assert kg.lane_grid is None
        if kg.fused:
            assert kg.vmem_bytes <= 1024
    # budget below any fused pair's working set -> no fusion at all
    plan = build_pipeline_plan(app.pipeline, vmem_budget=256, lane_block=False)
    assert all(not kg.fused for kg in plan.kernels)
    assert plan.n_kernels == plan.n_stages


def test_fusion_budget_property_random():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    app = make_app("unsharp", size=18)

    @settings(max_examples=15, deadline=None)
    @given(budget=st.integers(min_value=1024, max_value=1 << 22))
    def prop(budget):
        plan = build_pipeline_plan(app.pipeline, vmem_budget=budget)
        for kg in plan.kernels:
            if kg.fused:
                assert kg.vmem_bytes <= budget

    prop()


def test_fusion_reduces_hbm_traffic_estimate():
    for name, kw in [("unsharp", {"size": 18}), ("harris", {"schedule": "sch3", "size": 20})]:
        app = make_app(name, **kw)
        fused = build_pipeline_plan(app.pipeline).hbm_bytes()
        unfused = build_pipeline_plan(app.pipeline, fuse=False).hbm_bytes()
        assert fused < unfused, (name, fused, unfused)


def test_host_stage_not_fused():
    """harris sch6 puts the threshold stage on the host: its input must stay
    materialized in HBM, so `response` cannot fuse into the host stage."""
    app = make_app("harris", schedule="sch6", size=20)
    pp = compile_pipeline(app.pipeline)
    names = [k.name for k in pp.kernels]
    assert "response" in names and "harris" in names
    got = pp.run(_inputs(app))
    assert "response" in got


# ---------------------------------------------------------------------------
# Grid-level reductions
# ---------------------------------------------------------------------------


def test_grid_reduction_matmul_matches_reference():
    """A large-K matmul puts K into the grid (no full in-kernel unroll) and
    stays bit-exact on integer inputs (exactly representable sums)."""
    app = make_app("matmul", m=16, n=16, k=512)
    pp = compile_pipeline(app.pipeline, red_grid_threshold=128)
    ck = pp.kernels[0]
    assert ck.red_grid is not None and ck.red_grid.dim == "k0"
    assert len(ck.grid) == 2 and ck.grid[1] == 512 // ck.red_grid.chunk
    assert ck.red_grid.chunk < 512          # not fully unrolled in-kernel
    rng = np.random.default_rng(0)
    a = rng.integers(0, 8, (16, 512)).astype(np.float32)
    b = rng.integers(0, 8, (512, 16)).astype(np.float32)
    out = np.asarray(pp({"A": a, "B": b}), np.float64)
    want = a.astype(np.float64) @ b.astype(np.float64)
    assert np.array_equal(out, want)


def test_grid_reduction_float_tolerance():
    app = make_app("matmul", m=16, n=16, k=512)
    pp = compile_pipeline(app.pipeline, red_grid_threshold=128)
    rng = np.random.default_rng(2)
    a = rng.standard_normal((16, 512)).astype(np.float32)
    b = rng.standard_normal((512, 16)).astype(np.float32)
    out = np.asarray(pp({"A": a, "B": b}))
    want = a.astype(np.float64) @ b.astype(np.float64)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-3)


def test_grid_reduction_below_threshold_unrolled():
    app = make_app("matmul", m=16, n=16, k=64)
    pp = compile_pipeline(app.pipeline)     # default threshold 256
    assert pp.kernels[0].red_grid is None
    assert len(pp.kernels[0].grid) == 1


def test_grid_reduction_delivery_metadata():
    """element_for / delivered_interval remain exact under chunked delivery."""
    app = make_app("matmul", m=8, n=8, k=64)
    pp = compile_pipeline(app.pipeline, red_grid_threshold=32)
    ck = pp.kernels[0]
    assert ck.red_grid is not None
    ns = normalize_pipeline(app.pipeline)[0]
    rng = np.random.default_rng(0)
    dims = ns.pure_dims + ns.red_dims
    extents = ns.pure_extents + ns.red_extents
    for _ in range(25):
        point = {d: int(rng.integers(0, e)) for d, e in zip(dims, extents)}
        for k, (buf, acc) in enumerate(ns.loads):
            want = acc.eval(point)
            assert ck.element_for(k, point) == want, (buf, point)


# ---------------------------------------------------------------------------
# Scheduler-driven block heights + TPU alignment
# ---------------------------------------------------------------------------


def _cdiv(a, b):
    return -(-a // b)


def _bh_candidates(e0, max_bh=256):
    """Mirror of plan_affine_stage's candidate set (any block up to the
    streaming cap; padded grids make every height legal)."""
    cap = min(max_bh, e0)
    if e0 > 8:
        cap = min(cap, max(e0 // 4, 8))
    return range(1, max(cap, 1) + 1)


def test_plan_affine_stage_padded_selection():
    """Default (no cost hook) choice: fewest grid steps the budget allows,
    then minimal padding waste — which collapses to the old 'largest
    fitting divisor' rule whenever a divisor can match the step count."""
    for e0 in [1, 2, 8, 30, 60, 62, 64, 96, 128, 191, 253, 1000]:
        bh = plan_affine_stage(e0, 1024, 0)
        fitting = [c for c in _bh_candidates(e0) if 2 * 1024 * c <= VMEM_BYTES]
        steps = _cdiv(e0, bh)
        assert steps == min(_cdiv(e0, c) for c in fitting), (e0, bh)
        same_steps = [c for c in fitting if _cdiv(e0, c) == steps]
        assert steps * bh - e0 == min(
            _cdiv(e0, c) * c - e0 for c in same_steps
        ), (e0, bh)
        # streaming preference: multi-step grids whenever the extent allows
        if e0 > 8:
            assert steps >= 2, (e0, bh)
        # divisor-only mode restores exact tiling for callers that need it
        assert e0 % plan_affine_stage(e0, 1024, 0, allow_padding=False) == 0


def test_plan_affine_stage_respects_budget():
    # 1 MiB budget, 64 KiB/row double-buffered -> at most 8 rows
    bh = plan_affine_stage(1024, 64 * 1024, 0, vmem_budget=2 * 1024 * 1024)
    assert 2 * 64 * 1024 * bh <= 2 * 1024 * 1024


def test_plan_affine_stage_budget_and_padding_property():
    """Property sweep (seeded, no hypothesis needed): the chosen block never
    exceeds the VMEM budget when any candidate fits, stays within [1, e0],
    and under align_tpu is sublane-aligned whenever an aligned candidate
    fits.  Among equal-step candidates the padding waste is minimal."""
    rng = np.random.default_rng(0)
    for _ in range(300):
        e0 = int(rng.integers(1, 1500))
        bpr = int(rng.integers(1, 1 << 14))
        fixed = int(rng.integers(0, 1 << 18))
        budget = int(rng.integers(1 << 12, 1 << 23))
        for align in (False, True):
            bh = plan_affine_stage(
                e0, bpr, fixed, vmem_budget=budget, align_tpu=align
            )
            assert 1 <= bh <= max(e0, 1)
            fitting = [
                c for c in _bh_candidates(e0)
                if 2 * bpr * c + fixed <= budget
            ]
            if not fitting:
                assert bh == 1       # degenerate escape hatch
                continue
            assert 2 * bpr * bh + fixed <= budget, (e0, bpr, budget, bh)
            aligned = [c for c in fitting if c % 8 == 0]
            pool = aligned if (align and aligned) else fitting
            steps = _cdiv(e0, bh)
            same = [c for c in pool if _cdiv(e0, c) == steps]
            assert steps * bh - e0 == min(_cdiv(e0, c) * c - e0 for c in same)
            if align and aligned:
                assert bh % 8 == 0, (e0, bh)


def test_plan_affine_stage_cost_hook():
    """The cost hook picks the cheapest fitting candidate (not simply the
    largest), and with the scheduler model the choice is the cycle-count
    argmin over every candidate block height (divisor or padded)."""
    e0 = 1024
    heuristic = plan_affine_stage(e0, 256, 0)
    assert heuristic == 256
    # an arbitrary cost steers the choice anywhere in the candidate range —
    # including non-divisors, now legal via padded grids
    chosen = plan_affine_stage(e0, 256, 0, cost=lambda bh: abs(bh - 12))
    assert chosen == 12 and chosen != heuristic
    # the scheduler model: chosen block is the modeled-cycles argmin
    cost = scheduler_cost(e0, stmts_per_row=1, latency=4,
                          bytes_per_row=1 << 16, fixed_bytes=0)
    chosen = plan_affine_stage(e0, 256, 0, cost=cost)
    assert cost(chosen) == min(cost(c) for c in _bh_candidates(e0))
    # the scheduler model prices padding: on a prime extent the argmin holds
    # over every candidate, and cost ties break toward less tail waste
    e0 = 191
    cost = scheduler_cost(e0, stmts_per_row=1, latency=4,
                          bytes_per_row=1 << 12, fixed_bytes=0)
    chosen = plan_affine_stage(e0, 256, 0, cost=cost)
    cands = list(_bh_candidates(e0))
    assert cost(chosen) == min(cost(c) for c in cands)
    tied = [c for c in cands if cost(c) == cost(chosen)]
    assert _cdiv(e0, chosen) * chosen - e0 == min(
        _cdiv(e0, c) * c - e0 for c in tied
    )


def test_raster_cycles_matches_scheduler_and_simulator():
    """Cross-check the cost hook's cycle model against the full scheduler
    and the cycle-accurate simulator on a single-stage pipeline."""
    from repro.core.scheduling import schedule_pipeline
    from repro.core.simulator import simulate

    # matmul schedules under the DNN policy: every stage rasters its own
    # domain, which is exactly the panel model the cost hook prices with
    app = make_app("matmul", m=4, n=4, k=4)
    sched = schedule_pipeline(app.pipeline)
    st = app.pipeline.stages[0]
    assert sched.stage(st.name).cycles() == raster_cycles(st.domain.extents, st.latency)
    rng = np.random.default_rng(0)
    inputs = {
        "A": rng.integers(0, 8, (4, 4)).astype(np.float32),
        "B": rng.integers(0, 8, (4, 4)).astype(np.float32),
    }
    sim = simulate(app.pipeline, sched, inputs)
    assert not sim.hazards
    assert sim.cycles == sched.completion


def test_align_tpu():
    # a sublane-multiple divisor exists -> it is chosen
    bh = plan_affine_stage(64, 1024, 0, align_tpu=True)
    assert bh % 8 == 0 and 64 % bh == 0
    # no aligned *divisor* (62 = 2 * 31): padded grids make an aligned
    # block legal anyway — 8-row panels on a ceil(62/8)=8-step masked grid
    bh = plan_affine_stage(62, 1024, 0, align_tpu=True)
    assert bh == 8 and 62 % bh != 0
    # aligned blocks exist but none fits the budget -> the VMEM guarantee
    # wins: the unaligned fitting block is returned, not an oversized panel
    bh = plan_affine_stage(64, 8 << 20, 0, vmem_budget=64 << 20, align_tpu=True)
    assert bh == 4 and 2 * (8 << 20) * bh <= 64 << 20
    # shape rounding: (sublane, lane) quanta for f32
    assert align_tpu_shape((2, 62)) == (8, 128)
    assert align_tpu_shape((8, 128)) == (8, 128)
    assert align_tpu_shape((17, 200)) == (24, 256)
    assert align_tpu_shape((5, 3, 62)) == (5, 8, 128)
    assert align_tpu_shape((62,)) == (128,)


def test_align_tpu_threads_through_pipeline():
    app = make_app("gaussian")               # 62 rows: no aligned divisor
    pp = compile_pipeline(app.pipeline, align_tpu=True)
    ck = pp.kernels[0]
    # padded grids let alignment win even without an aligned divisor: the
    # sublane-multiple panel runs on a masked ceil-division grid
    assert ck.bh % 8 == 0
    assert ck.padded_grid is not None and ck.grid[0] * ck.bh >= 62
    assert max(max_abs_error(pp, _inputs(app)).values()) == 0.0
    app64 = make_app("upsample", size=64)     # 64 rows: aligned divisor exists
    pp64 = compile_pipeline(app64.pipeline, align_tpu=True)
    assert pp64.kernels[0].bh % 8 == 0
    aligned = pp64.kernels[0].kg.aligned_blocks()
    assert all(s[-1] % 128 == 0 for s in aligned.values())


# ---------------------------------------------------------------------------
# Delivery metadata (unfused path)
# ---------------------------------------------------------------------------


def test_gaussian_generates_row_shifted_streams():
    """The recompute-delivery 3x3 stencil must have the hand-written
    structure of kernels/stencil.py: one row-shifted input view per vertical
    tap (the shift-register chain lifted to rows), streamed over a >1-step
    grid.  The default (line-buffered) plan collapses that class into one
    streaming view at the leading tap plus a pinned 2-row warm-up view,
    with a VMEM ring carrying the halo across grid steps."""
    app = make_app("gaussian")          # 64 input -> 62 output rows
    pp = compile_pipeline(app.pipeline, line_buffer=False)
    cs = pp.stage("gaussian")
    assert cs.streamed and cs.grid[0] > 1
    assert len(cs.groups) == 3
    assert sorted(g.k0 for g in cs.groups) == [0, 1, 2]
    assert all(g.blocked_axis == 0 for g in cs.groups)
    # column taps hulled into the view width: W + 2 halo columns
    assert all(g.span[1] == 64 for g in cs.groups)

    # line-buffered delivery: the three shifted views become one ring
    pp = compile_pipeline(app.pipeline)
    cs = pp.stage("gaussian")
    assert len(cs.rings) == 1
    ring = cs.rings[0]
    assert (ring.lo, ring.hi, ring.halo) == (0, 2, 2)
    steady, prefix = cs.groups[ring.steady], cs.groups[ring.prefix]
    assert steady.k0 == 2 and not steady.pinned
    assert prefix.k0 == 0 and prefix.pinned and prefix.rows0 == 2
    assert len(cs.groups) == 2
    # the ring delivers each input row once: 1 streaming view instead of 3
    lb_bytes = pp.plan.hbm_bytes()
    rc_bytes = compile_pipeline(app.pipeline, line_buffer=False).plan.hbm_bytes()
    assert lb_bytes < rc_bytes


def test_matmul_broadcast_stream():
    """B does not depend on the blocked dim -> delivered whole every step."""
    app = make_app("matmul", m=24, n=16, k=8)
    cs = compile_pipeline(app.pipeline).stage("matmul")
    kinds = {g.buffer: g.blocked_axis for g in cs.groups}
    assert kinds["A"] == 0 and kinds["B"] is None


@pytest.mark.parametrize(
    "name,kw",
    [
        ("gaussian", {"size": 18}),
        ("camera", {"size": 8}),
        ("resnet", {"img": 8, "cin": 4, "cout": 4}),
        ("mobilenet", {"img": 8, "cin": 4, "cout": 4}),
        ("matmul", {"m": 24, "n": 16, "k": 8}),
    ],
)
def test_delivery_agrees_with_access_maps(name, kw):
    """Property test: on sampled iteration points, the element the generated
    kernel reads (reconstructed purely from view/BlockSpec/tap metadata)
    equals the stage's zero-based access map, and lies inside the block the
    BlockSpec delivers at that grid step.  Runs on the per-stage (unfused)
    plan, whose delivery metadata covers every stage."""
    app = make_app(name, **kw)
    pp = compile_pipeline(app.pipeline, fuse=False, grid_reduction=False)
    nstages = {ns.name: ns for ns in normalize_pipeline(app.pipeline)}
    rng = np.random.default_rng(0)
    for cs in pp.kernels:
        ns = nstages[cs.name]
        dims = ns.pure_dims + ns.red_dims
        extents = ns.pure_extents + ns.red_extents
        for _ in range(25):
            point = {d: int(rng.integers(0, e)) for d, e in zip(dims, extents)}
            grid_step = point[ns.pure_dims[0]] // cs.bh
            for k, (buf, acc) in enumerate(ns.loads):
                want = acc.eval(point)
                got = cs.element_for(k, point)
                assert got == want, (cs.name, buf, point, got, want)
                rho = {r: point[r] for r in ns.red_dims}
                for j, e in enumerate(want):
                    lo, hi, step = cs.delivered_interval(k, j, grid_step, rho)
                    assert lo <= e <= hi and (e - lo) % step == 0, (
                        cs.name, buf, j, e, (lo, hi, step),
                    )


def test_block_h_override():
    app = make_app("gaussian", size=18)     # 16 output rows
    pp = compile_pipeline(app.pipeline, block_h=4)
    cs = pp.stage("gaussian")
    assert cs.bh == 4 and cs.grid == (4,)
    errs = max_abs_error(pp, _inputs(app))
    assert max(errs.values()) == 0.0


# ---------------------------------------------------------------------------
# Padded grids (arbitrary extents / non-divisor blocks)
# ---------------------------------------------------------------------------

# one padded-grid plan per paper app (plus matmul): prime-ish extents or a
# non-divisor block_h force grid = ceil(e0/bh) with a masked tail block
PADDED_CASES = [
    ("gaussian", {"size": 13}, {}),                    # 11 rows (prime)
    ("harris", {"schedule": "sch3", "size": 17}, {}),  # 13 rows, fused x6
    ("harris", {"schedule": "sch6", "size": 17}, {}),  # host stage rides along
    ("upsample", {"size": 11}, {}),
    ("unsharp", {"size": 15}, {}),                     # 13 rows, fused x4
    ("camera", {"size": 7}, {"block_h": 3}),           # force the ragged edge
    # resnet's blocked dim is the channel dim co (extent 3): 2-channel
    # panels leave a 1-channel masked tail
    ("resnet", {"img": 7, "cin": 3, "cout": 3}, {"block_h": 2}),
    ("mobilenet", {"img": 7, "cin": 4, "cout": 4}, {"block_h": 3}),
    ("matmul", {"m": 19, "n": 13, "k": 11}, {}),
]


@pytest.mark.parametrize(
    "name,kw,ckw", PADDED_CASES,
    ids=[f"{c[0]}-{i}" for i, c in enumerate(PADDED_CASES)],
)
def test_padded_grid_matches_reference(name, kw, ckw):
    """Every paper app compiles and validates on a padded-grid plan: at
    least one kernel's grid dim 0 is a ceil-division over the extent, with
    the masked tail keeping every materialized buffer correct."""
    app = make_app(name, **kw)
    pp = compile_pipeline(app.pipeline, **ckw)
    padded = [ck for ck in pp.kernels if ck.padded_grid is not None]
    assert padded, [(ck.name, ck.bh, ck.grid) for ck in pp.kernels]
    for ck in padded:
        pg = ck.padded_grid
        assert ck.grid[0] == -(-pg.extent // pg.block) == pg.steps
        assert 0 < pg.pad < pg.block
        assert ck.kg.e0 == pg.extent
    errs = max_abs_error(pp, _inputs(app))
    assert max(errs.values()) <= TOL, errs


def test_padded_grid_bit_exact_on_integer_inputs():
    """Masking is exact, not approximate: padded plans of dyadic-exact apps
    stay *bit*-equal to the f64 reference on integer inputs."""
    for name, kw, ckw in [
        ("gaussian", {"size": 13}, {}),
        ("gaussian", {"size": 18}, {"block_h": 5}),   # 16 rows, 4x5 panels
        ("upsample", {"size": 11}, {}),
        ("matmul", {"m": 19, "n": 13, "k": 11}, {}),
        ("resnet", {"img": 7, "cin": 3, "cout": 3}, {"block_h": 2}),
    ]:
        app = make_app(name, **kw)
        pp = compile_pipeline(app.pipeline, **ckw)
        assert any(ck.padded_grid is not None for ck in pp.kernels), name
        inputs = _inputs(app)
        got = np.asarray(pp(inputs), np.float64)
        want = reference_arrays(app.pipeline, inputs)[app.pipeline.output]
        assert np.array_equal(got, want), name


def test_padded_grid_fused_scratch():
    """Fusion survives padding: the unsharp chain stays one kernel on a
    prime extent, VMEM scratch intermediates and all."""
    app = make_app("unsharp", size=15)      # 13 output rows
    pp = compile_pipeline(app.pipeline)
    assert pp.plan.n_kernels == 1
    ck = pp.kernels[0]
    assert ck.fused and ck.padded_grid is not None
    assert ck.kg.scratch_entries()
    errs = max_abs_error(pp, _inputs(app))
    assert max(errs.values()) <= TOL, errs


def test_padded_grid_metadata_threaded():
    """Valid-extent metadata rides the plan: view groups record the valid
    blocked-axis span, stage plans expose per-step valid rows, and the
    unified-buffer notes carry the padded-grid decision."""
    app = make_app("gaussian", size=13)     # 11 rows
    pp = compile_pipeline(app.pipeline)
    ck = pp.stage("gaussian")
    pg = ck.padded_grid
    assert pg is not None and pg.extent == 11
    for g in ck.groups:
        if g.pinned:
            continue                        # warm-up views are not gridded
        assert g.blocked_axis is not None and g.valid0 == 11
    sp = ck.kg.output
    assert sp.valid_e0 == 11
    rows = [sp.valid_rows(ck.bh, s) for s in range(pg.steps)]
    assert sum(rows) == 11 and rows[-1] == ck.bh - pg.pad
    assert ck.plan.notes.get("padded_grid") == (pg.extent, pg.block, pg.steps)


def test_grid_reduction_masked_tail_k1000():
    """Regression: non-power-of-two K chunks as ceil(K/128) grid steps with
    a masked tail (K=1000 -> 7x128 + 104), bit-exact on integer inputs —
    the padded tail terms contribute exactly zero to the accumulator."""
    app = make_app("matmul", m=16, n=16, k=1000)
    pp = compile_pipeline(app.pipeline)     # default threshold 256
    ck = pp.kernels[0]
    rg = ck.red_grid
    assert rg is not None and rg.chunk == 128
    assert rg.steps == 8 and rg.extent == 1000
    assert rg.padded and rg.tail == 104
    assert ck.grid[1] == 8
    rng = np.random.default_rng(0)
    a = rng.integers(0, 8, (16, 1000)).astype(np.float32)
    b = rng.integers(0, 8, (1000, 16)).astype(np.float32)
    out = np.asarray(pp({"A": a, "B": b}), np.float64)
    want = a.astype(np.float64) @ b.astype(np.float64)
    assert np.array_equal(out, want)


def test_grid_reduction_masked_tail_with_padded_rows():
    """Both ragged edges at once: prime M (padded row panels) and
    non-multiple K (masked reduction tail) in one kernel."""
    app = make_app("matmul", m=19, n=13, k=300)
    pp = compile_pipeline(app.pipeline, red_grid_threshold=128, block_h=4)
    ck = pp.kernels[0]
    assert ck.padded_grid is not None and ck.red_grid is not None
    assert ck.red_grid.padded
    rng = np.random.default_rng(3)
    a = rng.integers(0, 8, (19, 300)).astype(np.float32)
    b = rng.integers(0, 8, (300, 13)).astype(np.float32)
    out = np.asarray(pp({"A": a, "B": b}), np.float64)
    want = a.astype(np.float64) @ b.astype(np.float64)
    assert np.array_equal(out, want)


# ---------------------------------------------------------------------------
# Lane-blocked 2-D grids
# ---------------------------------------------------------------------------


def test_lane_width_candidates():
    """Widest-first lane candidates: every 128-multiple below the extent
    leads (so budget-driven engagement lands lane-tileable whenever one
    fits), then the narrower widths; never the full extent (that's the
    flat plan)."""
    from repro.core.ubplan import lane_width_candidates

    c = lane_width_candidates(2046)
    assert c[0] == 1920 and all(w % 128 == 0 for w in c[: c.index(1023)])
    assert all(w < 2046 for w in c)
    assert sorted(set(c), reverse=True) == c           # strictly descending
    assert lane_width_candidates(300)[:2] == [256, 150]
    # small extents: no 128-multiple exists
    assert max(lane_width_candidates(100)) == 64
    assert lane_width_candidates(1) == [1]


def test_lane_width_candidates_joint_order():
    """The candidate pool of the joint (bh, bw) pricer: the 128-multiples,
    the power-of-two widths below 128, and the low-padding ceil-division
    widths a narrow extent wants, in descending order."""
    from repro.core.ubplan import lane_width_candidates

    joint = lane_width_candidates(300)
    assert {256, 128, 64, 32, 16, 8, 4, 2, 1} <= set(joint)
    assert sorted(set(joint), reverse=True) == joint    # still descending
    # ceil-division splits: ceil(300/2)=150, /3=100, /4=75 — none of which
    # the 128-multiple / power-of-two pools can express
    assert {150, 100, 75} <= set(joint)
    assert all(w < 300 for w in joint)
    # sub-128 widths exist for narrow extents
    assert {50, 34, 64} <= set(lane_width_candidates(100))
    assert lane_width_candidates(1) == [1]


def test_joint_lane_pricing_beats_or_matches_greedy():
    """Budget-driven lane engagement prices every fitting (bh, bw) pair
    with the scheduler model (model_cycles scales with the lane-step
    count) and keeps the modeled-cheapest: no fitting candidate width,
    forced with block_w, prices lower; the plan stays budget-clean and
    bit-exact."""
    from repro.core.ubplan import lane_width_candidates

    app = make_app("unsharp", size=18)
    budget = 1024
    joint = build_pipeline_plan(app.pipeline, vmem_budget=budget)
    kj = joint.kernels[0]
    assert kj.lane_grid is not None
    assert kj.vmem_bytes <= budget
    assert kj.notes["lane"] == "auto-vmem"
    cj = kj.notes["model_cycles"]
    e1 = kj.stages[-1].nstage.pure_extents[-1]
    priced = 0
    for bw in lane_width_candidates(e1):
        if kj.bw % 128 == 0 and bw % 128:
            continue            # 128-multiples form the pool when one fits
        try:
            forced = build_pipeline_plan(
                app.pipeline, vmem_budget=budget, block_w=bw
            ).kernels[0]
        except FusionInfeasible:
            continue
        bpr, fixed = forced.ws
        if 2 * bpr * forced.bh + fixed > budget:
            continue
        priced += 1
        assert cj <= forced.notes["model_cycles"], (kj.bw, cj, bw)
    assert priced >= 2
    pp = compile_pipeline(app.pipeline, vmem_budget=budget)
    errs = max_abs_error(pp, _inputs(app))
    assert max(errs.values()) <= TOL, errs


def test_explicit_block_h_records_model_cycles():
    """Explicit block heights still record model_cycles (the autotuner's
    uniform pruning signal) but mark the height as not model-chosen, so
    the carry-vs-recompute arbitration keeps its carry-unpriced
    preference."""
    app = make_app("gaussian", size=18)
    plan = build_pipeline_plan(app.pipeline, block_h=4)
    kg = plan.kernels[0]
    assert kg.notes["model_cycles"] > 0
    assert kg.notes["bh_priced"] is False
    auto = build_pipeline_plan(app.pipeline)
    assert auto.kernels[0].notes["bh_priced"] is True


def test_red_chunk_override():
    """red_chunk overrides the grid-reduction chunk size: the grid's
    reduction steps re-divide accordingly, the plan verifies clean, and
    the accumulation stays bit-exact (leading-dim chunking preserves the
    reference's lexicographic order)."""
    from repro.backend.verify import verify_plan

    app = make_app("matmul", m=16, n=16, k=2048)
    plan = build_pipeline_plan(app.pipeline, red_chunk=64)
    kg = plan.kernels[0]
    assert kg.red_grid is not None and kg.red_grid.chunk == 64
    assert kg.red_grid.steps == 32 and kg.grid[-1] == 32
    assert verify_plan(plan) == []
    assert plan.notes["red_chunk"] == 64
    # a chunk of 1 declines the grid reduction (pure overhead)
    flat = build_pipeline_plan(app.pipeline, red_chunk=1)
    assert flat.kernels[0].red_grid is None
    pp = compile_pipeline(app.pipeline, red_chunk=64)
    inputs = _inputs(app)
    got = np.asarray(pp(inputs), np.float64)
    a = inputs["A"].astype(np.float64)
    b = inputs["B"].astype(np.float64)
    default = compile_pipeline(app.pipeline)
    assert np.array_equal(got, np.asarray(default(inputs), np.float64))
    assert float(np.max(np.abs(got - a @ b))) <= 1e-3


def test_lane_blocked_grid_bit_exact():
    """Explicit block_w tiles the trailing dim: grid (ceil(e0/bh),
    ceil(e1/bw)), lane-tail masks on non-divisor widths, bit-exact on
    integer inputs — including a fused cascade whose in-group column
    offsets become per-lane-shift recompute panels."""
    for name, kw, ckw in [
        ("gaussian", {"size": 18}, {"block_w": 5}),       # 16 = 3x5 + tail 1
        ("gaussian", {"size": 13}, {"block_w": 4, "block_h": 3}),
        ("matmul", {"m": 19, "n": 13, "k": 11}, {"block_w": 4}),
        ("upsample", {"size": 11}, {"block_w": 1}),
        ("resnet", {"img": 7, "cin": 3, "cout": 3}, {"block_w": 3, "block_h": 2}),
    ]:
        app = make_app(name, **kw)
        pp = compile_pipeline(app.pipeline, **ckw)
        lane_kernels = [ck for ck in pp.kernels if ck.lane_grid is not None]
        assert lane_kernels, name
        for ck in lane_kernels:
            lg = ck.lane_grid
            assert ck.grid[1] == -(-lg.extent // lg.block) == lg.steps
            assert ck.bw == lg.block
        inputs = _inputs(app)
        got = np.asarray(pp(inputs), np.float64)
        want = reference_arrays(app.pipeline, inputs)[app.pipeline.output]
        assert np.array_equal(got, want), name


def test_lane_blocked_fused_chain_with_lane_shifts():
    """harris reads its fused intermediates at column offsets 0..2: under a
    lane grid those become lane shift sets — per-(row, lane)-shift scratch
    panels — and the fusion survives with the plan still matching the
    reference within tolerance."""
    app = make_app("harris", schedule="sch3", size=20)
    pp = compile_pipeline(app.pipeline, block_w=5)
    assert pp.plan.n_kernels == 1
    ck = pp.kernels[0]
    assert ck.fused and ck.lane_grid is not None
    lane_shifted = [
        sp.name for sp in ck.kg.stages[:-1] if len(sp.lane_shifts) > 1
    ]
    assert lane_shifted, "expected in-group column offsets to widen lane shifts"
    keys = {key for _, key in ck.kg.scratch_entries()}
    assert all(isinstance(k, tuple) for k in keys)
    errs = max_abs_error(pp, _inputs(app))
    assert max(errs.values()) <= TOL, errs


def test_lane_metadata_and_element_for():
    """Delivery metadata stays exact under lane blocking: element_for
    reconstructs each read from view/BlockSpec/lane metadata and matches
    the access map, and delivered_interval covers it at the right (row,
    lane) step."""
    from repro.frontend.lower import normalize_pipeline

    app = make_app("gaussian", size=18)
    pp = compile_pipeline(app.pipeline, fuse=False, block_w=5,
                          line_buffer=False)
    cs = pp.kernels[0]
    assert cs.lane_grid is not None
    ns = normalize_pipeline(app.pipeline)[0]
    rng = np.random.default_rng(0)
    dims = ns.pure_dims + ns.red_dims
    extents = ns.pure_extents + ns.red_extents
    for _ in range(30):
        point = {d: int(rng.integers(0, e)) for d, e in zip(dims, extents)}
        grid_step = point[ns.pure_dims[0]] // cs.bh
        lane_step = point[ns.pure_dims[-1]] // cs.kg.bw
        for k, (buf, acc) in enumerate(ns.loads):
            want = acc.eval(point)
            got = cs.element_for(k, point)
            assert got == want, (buf, point, got, want)
            rho = {r: point[r] for r in ns.red_dims}
            for j, e in enumerate(want):
                lo, hi, step = cs.delivered_interval(
                    k, j, grid_step, rho, lane_step
                )
                assert lo <= e <= hi and (e - lo) % step == 0, (buf, j, e)


def test_align_tpu_rounds_bw_at_emission():
    """Under align_tpu a lane-blocked kernel's emitted lane width is a
    128-lane multiple — the blocks themselves, not just the
    aligned_blocks() report — with the ragged lane tail masked."""
    app = make_app("gaussian", size=18)           # 16 columns
    pp = compile_pipeline(app.pipeline, block_w=5, align_tpu=True)
    ck = pp.kernels[0]
    assert ck.bw == 128 and ck.lane_grid.steps == 1
    assert ck.lane_grid.pad == 128 - 16
    assert ck.kg.output.panel_shape(ck.bh)[-1] == 128
    for g in ck.groups:
        assert g.block_shape(ck.bh, ck.bw)[g.lane_axis] == 128
    inputs = _inputs(app)
    got = np.asarray(pp(inputs), np.float64)
    want = reference_arrays(app.pipeline, inputs)[app.pipeline.output]
    assert np.array_equal(got, want)


def test_wide_extent_auto_lane_engagement():
    """The acceptance shape: a width-2048 tile under a budget where today's
    planner either fails or must hold the full width resident.  The lane
    grid engages automatically, the per-kernel VMEM estimate lands under
    the budget, and the result stays bit-exact on integer inputs."""
    app = make_app("gaussian", size=16, width=2048)   # 14 x 2046 output
    budget = 48 * 1024

    # today's (flat) planner: even a one-row full-width panel overflows
    flat = build_pipeline_plan(app.pipeline, vmem_budget=budget,
                               lane_block=False)
    kg = flat.kernels[0]
    bpr, fixed = kg.ws
    assert kg.bh == 1 and 2 * bpr + fixed > budget

    plan = build_pipeline_plan(app.pipeline, vmem_budget=budget)
    kg = plan.kernels[0]
    assert kg.lane_grid is not None and len(kg.grid) == 2
    assert kg.lane_grid.extent == 2046 and kg.bw % 128 == 0
    assert kg.vmem_bytes <= budget, (kg.vmem_bytes, budget)

    pp = compile_pipeline(app.pipeline, vmem_budget=budget)
    inputs = _inputs(app)
    got = np.asarray(pp(inputs), np.float64)
    want = reference_arrays(app.pipeline, inputs)[app.pipeline.output]
    assert np.array_equal(got, want)


def test_lane_rescued_fusion_stays_budgeted():
    """Fusion survives budgets far below the full-width working set by
    narrowing the lane dim (see test_fusion_respects_vmem_budget); the
    numeric contract holds on the rescued plan."""
    app = make_app("unsharp", size=18)
    pp = compile_pipeline(app.pipeline, vmem_budget=1024)
    ck = pp.kernels[0]
    assert ck.fused and ck.lane_grid is not None
    errs = max_abs_error(pp, _inputs(app))
    assert max(errs.values()) <= TOL, errs


# ---------------------------------------------------------------------------
# Runner input validation
# ---------------------------------------------------------------------------


def test_runner_validates_input_shapes():
    """Mis-shaped inputs raise a clear, named error at the runner boundary
    instead of a cryptic BlockSpec/slice failure inside pallas_call."""
    app = make_app("gaussian", size=18)
    pp = compile_pipeline(app.pipeline)
    inputs = _inputs(app)

    with pytest.raises(KeyError, match="missing input 'input'"):
        pp.run({})

    bad = {"input": inputs["input"][:-1]}          # 17x18 instead of 18x18
    with pytest.raises(ValueError, match=r"input 'input'.*declared extents"):
        pp.run(bad)

    with pytest.raises(ValueError, match=r"rank"):
        pp.run({"input": inputs["input"][0]})      # 1-D instead of 2-D


def test_kernel_validates_view_extents():
    """Direct CompiledKernel calls validate every view's backing buffer
    against the plan's required extents (buffer, axis, and need named)."""
    app = make_app("gaussian", size=18)
    pp = compile_pipeline(app.pipeline)
    ck = pp.stage("gaussian")
    need = ck.kg.required_extents()
    assert need == {"input": (18, 18)}
    with pytest.raises(ValueError, match=r"buffer 'input' axis 0.*>= 18"):
        ck({"input": np.zeros((17, 18), np.float32)})
    with pytest.raises(KeyError, match="missing input buffer 'input'"):
        ck({})
