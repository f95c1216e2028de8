"""Execution-mode switch, plan-keyed pipeline cache, and bind reuse
(marker ``backend``).

The backend grew a three-way execution switch — ``mode="interpret"`` (the
portable Pallas interpreter), ``"compiled"`` (real Mosaic kernels; needs a
TPU jax backend), ``"auto"`` (compiled on TPU, interpret elsewhere) —
plus two layers of reuse:

* **bind reuse** — every emitted kernel is a ``jax.jit``-wrapped closure,
  so repeated ``__call__``s of one compiled pipeline skip re-tracing;
* **the plan-keyed cache** — ``compile_pipeline(..., cache=True)`` keys
  whole pipelines on a content hash of the lowered pipeline + plan
  parameters + mode (``plan_cache_key``), so repeat compilations skip
  re-planning and re-emitting too.

Interpret-vs-compiled *parity* can only run where a compiled backend
exists, so those tests are gated on ``jax.default_backend()``; everything
else runs everywhere.
"""

import time
from dataclasses import fields

import jax
import numpy as np
import pytest

from repro.apps.paper_apps import make_app
from repro.backend import (
    PipelineServer,
    PlanOptions,
    clear_pipeline_cache,
    compile_pipeline,
    pipeline_cache_size,
    pipeline_cache_stats,
    plan_cache_key,
    resolve_mode,
)

pytestmark = pytest.mark.backend

ON_TPU = jax.default_backend() == "tpu"


def _inputs(app, seed=0):
    rng = np.random.default_rng(seed)
    return {
        n: rng.integers(0, 16, s).astype(np.float32)
        for n, s in app.input_extents.items()
    }


# ---------------------------------------------------------------------------
# Mode switch
# ---------------------------------------------------------------------------


def test_mode_resolution():
    assert resolve_mode("interpret") == "interpret"
    assert resolve_mode("compiled") == "compiled"
    want = "compiled" if jax.default_backend() == "tpu" else "interpret"
    assert resolve_mode("auto") == want
    with pytest.raises(ValueError, match="unknown backend mode"):
        resolve_mode("fast")


def test_auto_mode_falls_back_cleanly():
    """mode="auto" always compiles and runs: on CPU it lands on interpret
    (recorded on the pipeline and each kernel), on TPU it would land on
    compiled — same call site either way."""
    app = make_app("gaussian", size=18)
    pp = compile_pipeline(app.pipeline, mode="auto")
    expected = "compiled" if jax.default_backend() == "tpu" else "interpret"
    assert pp.mode == expected
    assert all(ck.mode == expected for ck in pp.kernels)
    out = np.asarray(pp(_inputs(app)))
    assert out.shape == (16, 16)


@pytest.mark.skipif(ON_TPU, reason="explicit compiled mode is legal here")
def test_compiled_mode_on_cpu_raises_clearly():
    """A compiled pipeline plans and emits anywhere (so its kernels can be
    lowered for a described TPU), but running it off-TPU raises a named
    error instead of silently falling back to the interpreter."""
    app = make_app("gaussian", size=18)
    inputs = _inputs(app)
    pp = compile_pipeline(app.pipeline, mode="compiled")
    assert pp.mode == "compiled"
    assert all(ck.mode == "compiled" for ck in pp.kernels)
    with pytest.raises(RuntimeError, match="TPU jax backend"):
        pp(inputs)
    # ``mode`` is the one execution switch: a name that is no plan option
    # is refused
    with pytest.raises(TypeError, match="interpret"):
        compile_pipeline(app.pipeline, interpret=False)


def test_compiled_mode_plans_tpu_aligned_tiles():
    """Mosaic refuses blocks whose last two dims are not (8, 128)-tileable:
    at 1080 output rows the unaligned planner picks 10-row blocks, while a
    compiled pipeline always plans with align_tpu — and an explicit
    align_tpu=True names the same plan, so it shares the cache entry."""
    app = make_app("gaussian", size=1082, width=1922)
    assert compile_pipeline(app.pipeline).kernels[0].bh % 8 != 0
    clear_pipeline_cache(reset_stats=True)
    try:
        pp = compile_pipeline(app.pipeline, mode="compiled", cache=True)
        assert all(ck.bh % 8 == 0 for ck in pp.kernels)
        assert pp.plan.notes["align_tpu"] is True
        again = compile_pipeline(
            app.pipeline, mode="compiled", cache=True, align_tpu=True
        )
        assert again is pp
        assert pp.cache_key == plan_cache_key(
            app.pipeline, "compiled", PlanOptions(align_tpu=True)
        )
    finally:
        clear_pipeline_cache(reset_stats=True)


@pytest.mark.skipif(not ON_TPU, reason="needs a TPU backend for compiled mode")
@pytest.mark.parametrize(
    "name,kw,ckw",
    [
        ("gaussian", {"size": 18}, {}),
        ("gaussian", {"size": 18}, {"block_w": 5, "align_tpu": True}),
        ("unsharp", {"size": 18}, {}),
        ("matmul", {"m": 16, "n": 16, "k": 512}, {"red_grid_threshold": 128}),
    ],
)
def test_interpret_vs_compiled_parity(name, kw, ckw):
    """Where a compiled backend exists, the same plan emitted in both modes
    must agree on integer inputs (compiled math is still f32; dyadic-exact
    apps must match bit-for-bit)."""
    app = make_app(name, **kw)
    inputs = _inputs(app)
    got_i = np.asarray(compile_pipeline(app.pipeline, mode="interpret", **ckw)(inputs))
    got_c = np.asarray(compile_pipeline(app.pipeline, mode="compiled", **ckw)(inputs))
    np.testing.assert_allclose(got_c, got_i, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Bind reuse (plan/emit/bind split)
# ---------------------------------------------------------------------------


def test_repeated_calls_reuse_emitted_closures():
    """Second and later calls of one compiled pipeline hit the jit cache:
    no re-trace, so the warm call is orders of magnitude faster than the
    first — and bit-identical."""
    app = make_app("unsharp", size=18)
    pp = compile_pipeline(app.pipeline)
    inputs = _inputs(app)
    t0 = time.perf_counter()
    first = np.asarray(pp(inputs))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    second = np.asarray(pp(inputs))
    warm = time.perf_counter() - t0
    assert np.array_equal(first, second)
    assert warm < cold / 10, (cold, warm)
    # new buffers, same shapes: still the warm path, different data
    other = _inputs(app, seed=1)
    t0 = time.perf_counter()
    np.asarray(pp(other))
    rebind = time.perf_counter() - t0
    assert rebind < cold / 10, (cold, rebind)


# ---------------------------------------------------------------------------
# Plan-keyed pipeline cache
# ---------------------------------------------------------------------------


def test_pipeline_cache_hit_and_key_contract():
    """cache=True returns the same PallasPipeline for identical (pipeline
    content, plan kwargs, mode); any extent, parameter, or mode change is a
    miss.  Two structurally identical app builds share one entry — the key
    is content, not object identity."""
    clear_pipeline_cache()
    try:
        app = make_app("gaussian", size=18)
        pp1 = compile_pipeline(app.pipeline, cache=True)
        assert pipeline_cache_size() == 1 and pp1.cache_key is not None
        assert compile_pipeline(app.pipeline, cache=True) is pp1

        # a *fresh build* of the same app hits the same entry
        app_again = make_app("gaussian", size=18)
        assert compile_pipeline(app_again.pipeline, cache=True) is pp1

        # parameter, extent, and mode changes all miss
        pp_bh = compile_pipeline(app.pipeline, cache=True, block_h=4)
        assert pp_bh is not pp1
        app32 = make_app("gaussian", size=32)
        pp32 = compile_pipeline(app32.pipeline, cache=True)
        assert pp32 is not pp1
        assert pipeline_cache_size() == 3

        # uncached compiles never touch the cache
        pp_raw = compile_pipeline(app.pipeline)
        assert pp_raw is not pp1 and pp_raw.cache_key is None
        assert pipeline_cache_size() == 3
    finally:
        clear_pipeline_cache()


def test_plan_cache_key_is_deterministic_and_content_keyed():
    opts = PlanOptions(block_h=None, fuse=True)
    a1 = make_app("gaussian", size=18)
    a2 = make_app("gaussian", size=18)
    a3 = make_app("gaussian", size=20)
    k1 = plan_cache_key(a1.pipeline, "interpret", opts)
    assert k1 == plan_cache_key(a1.pipeline, "interpret", opts)
    assert k1 == plan_cache_key(a2.pipeline, "interpret", opts)
    assert k1 != plan_cache_key(a3.pipeline, "interpret", opts)
    assert k1 != plan_cache_key(a1.pipeline, "compiled", opts)
    assert k1 != plan_cache_key(
        a1.pipeline, "interpret", PlanOptions(block_h=4)
    )


# a value away from the default for every plan option
NON_DEFAULT = dict(
    block_h=4, block_w=4, lane_block=False, fuse=False, grid_reduction=False,
    red_grid_threshold=512, vmem_budget=1 << 20, align_tpu=True,
    line_buffer=True, red_resident=False, batch=2, batch_capacity=4,
    red_chunk=64,
)


@pytest.mark.parametrize("option", [f.name for f in fields(PlanOptions)])
def test_plan_cache_key_normalizes_default_kwargs(option):
    """Options are normalized against their defaults before hashing, so an
    explicitly passed default and an omitted option produce one key —
    compile_pipeline(app) and compile_pipeline(app, block_w=None) share a
    single cache entry instead of silently missing.  A non-default value
    still misses."""
    app = make_app("gaussian", size=18)
    default = PlanOptions.__dataclass_fields__[option].default
    k_bare = plan_cache_key(app.pipeline, "interpret", PlanOptions())
    assert k_bare == plan_cache_key(
        app.pipeline, "interpret", PlanOptions(**{option: default})
    )
    assert k_bare != plan_cache_key(
        app.pipeline, "interpret", PlanOptions(**{option: NON_DEFAULT[option]})
    )

    clear_pipeline_cache(reset_stats=True)
    try:
        pp1 = compile_pipeline(app.pipeline, cache=True)
        pp2 = compile_pipeline(app.pipeline, cache=True, **{option: default})
        assert pp2 is pp1 and pipeline_cache_size() == 1
        stats = pipeline_cache_stats()
        assert stats["misses"] == 1 and stats["hits"] == 1
    finally:
        clear_pipeline_cache(reset_stats=True)


# the plan cache key of each benchmark cell's served compile
# (chipbench/configs/<cell>.json: make_app arguments and batch_slots),
# recorded before PlanOptions replaced the keyword dicts (the EfficientNet
# block's when its cell was added): the same key names the same plan, so a
# change here changes what the cells run
SERVED_KEYS = {
    "blur_1080p": (
        "gaussian", dict(size=1082, width=1922), 4,
        "61300c92d983b1b3be3aaefc61de109261dd420dbd8732e74508e93b9e81fc85",
    ),
    "camera_isp_1080": (
        "camera", dict(size=540), 4,
        "b177e708eb53d35adcea61051d432861dd91eedd29206d924f6762a033ac538a",
    ),
    "resnet50_conv2x_block": (
        "resnet50_block",
        dict(img=56, cin=256, mid=64, weight_seed=1512, act_scale=0.015625),
        8,
        "cc24fea2b20c978973e75b147a4adc9dc42acc0b78da19aa035f911de843f2b1",
    ),
    "efficientnet_b0_mbconv6_56": (
        "efficientnet_b0_mbconv",
        dict(img=56, cin=24, expand=6, se_ratio=0.25, kernel=3,
             weight_seed=1905, act_scale=0.03125),
        64,
        "f0328b394a491f1c7671cebf5ce76d77732375ffabad87c907ccb6ea2c477014",
    ),
}


@pytest.mark.parametrize("cell", sorted(SERVED_KEYS))
def test_served_cell_plan_cache_keys_are_stable(cell):
    """A compiled-mode server plans with align_tpu and batch = capacity =
    batch_slots; its cache key is the recorded one."""
    name, kw, slots, want = SERVED_KEYS[cell]
    app = make_app(name, **kw)
    opts = PlanOptions(align_tpu=True, batch=slots, batch_capacity=slots)
    assert plan_cache_key(app.pipeline, "compiled", opts) == want
    clear_pipeline_cache(reset_stats=True)
    try:
        srv = PipelineServer(app.pipeline, batch_slots=slots, mode="compiled")
        assert srv.pipeline.cache_key == want
    finally:
        clear_pipeline_cache(reset_stats=True)


def test_clear_pipeline_cache_preserves_stats_by_default():
    """clear_pipeline_cache() evicts entries but keeps the hit/miss
    counters (a measuring harness that clears between candidates retains
    its observability); reset_stats=True restores the old zeroing."""
    clear_pipeline_cache(reset_stats=True)
    try:
        app = make_app("gaussian", size=18)
        compile_pipeline(app.pipeline, cache=True)
        compile_pipeline(app.pipeline, cache=True)
        clear_pipeline_cache()
        stats = pipeline_cache_stats()
        assert stats["entries"] == 0
        assert stats["misses"] == 1 and stats["hits"] == 1
        clear_pipeline_cache(reset_stats=True)
        assert pipeline_cache_stats() == {
            "hits": 0, "misses": 0, "evictions": 0, "entries": 0,
        }
    finally:
        clear_pipeline_cache(reset_stats=True)


def test_cached_pipeline_warm_invocation_is_10x_faster():
    """The acceptance bar: a warm-cache invocation (cache hit + jit-warm
    kernels) beats the cold plan+emit+trace path by >= 10x."""
    clear_pipeline_cache()
    try:
        app = make_app("gaussian", size=18)
        inputs = _inputs(app)
        t0 = time.perf_counter()
        pp = compile_pipeline(app.pipeline, cache=True)
        np.asarray(pp(inputs))
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        pp2 = compile_pipeline(app.pipeline, cache=True)
        np.asarray(pp2(inputs))
        warm = time.perf_counter() - t0
        assert pp2 is pp
        assert warm * 10 < cold, (cold, warm)
    finally:
        clear_pipeline_cache()


# ---------------------------------------------------------------------------
# Persistent compile cache location
# ---------------------------------------------------------------------------


def _cache_child(env_dir, compile_one: bool):
    """Run enable_compile_cache() in a fresh CPU process; return the path
    it reports and the one JAX is configured with."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parents[1]
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.backend import enable_compile_cache\n"
        "print(enable_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        + ("jax.jit(lambda x: x * 3)(jnp.ones(4)).block_until_ready()\n"
           if compile_one else "")
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(root / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split(), root


def test_compile_cache_follows_env_then_checkout(tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and receives the
    compiled entries; otherwise the cache is the fixed <checkout>/.jax_cache
    (never a temp, pid or timestamp path)."""
    got, _root = _cache_child(tmp_path / "cc", compile_one=True)
    assert got == [str(tmp_path / "cc")] * 2
    assert any((tmp_path / "cc").iterdir())
    got, root = _cache_child(None, compile_one=False)
    assert got == [str(root / ".jax_cache")] * 2
