"""Whole-pipeline compilation and execution against the golden reference.

``compile_pipeline`` plans a lowered :class:`~repro.frontend.lower.Pipeline`
(``backend/plan.build_pipeline_plan``: fusion, grid reductions, scheduler-
driven block heights) and emits one generated Pallas kernel per planned
:class:`~repro.backend.plan.KernelGroup`, executed in topological order.
Only kernel *outputs* are materialized in HBM — fused intermediates live and
die in VMEM scratch, which is the point of the plan/emit split.

The split is really plan/emit/**bind**: every emitted kernel is a
``jax.jit``-wrapped closure, so calling an already-compiled pipeline with
new same-shaped buffers reuses the first call's trace.  On top of that,
``compile_pipeline(..., cache=True)`` keys whole compiled pipelines on a
content hash of the lowered pipeline + every plan-affecting parameter + the
execution mode (see :func:`plan_cache_key`), so the serve path, benchmarks
and sweeps skip re-planning *and* re-tracing on repeat invocations.

``mode`` selects the execution path: ``"interpret"`` (portable Pallas
interpreter, the CPU default), ``"compiled"`` (real Mosaic kernels; needs a
TPU backend), ``"auto"`` (compiled on TPU, interpret elsewhere).

``reference_arrays`` converts the von-Neumann reference interpreter's value
tables (absolute coordinates) into the same zero-based dense layout so
differential tests can compare bit-for-bit element-wise.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import warnings
from collections import OrderedDict
from dataclasses import dataclass, field, replace
from functools import partial
from pathlib import Path
from typing import Dict, Iterable, List, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.frontend.lower import Pipeline, execute_pipeline, normalize_pipeline

from . import tracing
from .codegen import CompiledKernel, emit_kernel, resolve_mode
from .errors import (
    EmitError,
    LaneCarryDegradeWarning,
    TunedModeMismatchWarning,
)
from .plan import TUNABLE_KEYS, PipelinePlan, PlanOptions, build_pipeline_plan
from .verify import assert_plan_verified


def _warn_lane_carry_degrades(plan: PipelinePlan) -> None:
    """Satellite of the lane×carry fix: an explicit ``line_buffer=True``
    that the planner cannot honor on a lane-blocked kernel must not pass
    silently.  The planner records its reason in
    ``KernelGroup.notes["lane_carry"]`` (and partial sheds in
    ``notes["lane_carry_shed"]``); surface each one as a named warning."""
    for kg in plan.kernels:
        if kg.lane_grid is None:
            continue
        reason = kg.notes.get("lane_carry")
        shed = kg.notes.get("lane_carry_shed")
        out = kg.stages[-1].name
        if reason not in (None, "carried"):
            warnings.warn(
                f"kernel {out!r}: line_buffer=True requested but the "
                f"lane-blocked plan degraded to recompute mode "
                f"(reason: {reason})",
                LaneCarryDegradeWarning,
                stacklevel=3,
            )
        elif shed:
            stages = ", ".join(shed.get("stages", ())) or "<none>"
            warnings.warn(
                f"kernel {out!r}: line_buffer=True requested but the "
                f"lane-blocked plan shed part of the carry "
                f"(stages: {stages}; ring classes dropped: "
                f"{shed.get('ring_classes', 0)}) — halo exceeds the lane "
                f"block width for the shed members",
                LaneCarryDegradeWarning,
                stacklevel=3,
            )


def stage_dtype(dtypes: Iterable) -> np.dtype:
    """The dtype inputs of ``dtypes`` cross to the device at: their common
    dtype where float32 holds every value of it exactly (uint8, uint16,
    int8, int16, bool, float16, float32), else float32, cast on the host
    (int32, int64, float64 round there as they always have).  The kernels
    read float32, so a narrower input is widened on the device
    (:func:`ub_widen`); the widening is exact."""
    common = np.result_type(*dtypes)
    if np.can_cast(common, np.float32, "safe"):
        return common
    return np.dtype(np.float32)


# glibc's malloc hands a freed block above its mmap threshold back to the
# kernel, and trims the top of its heap once more than its trim threshold
# lies free there.  Unless set, both follow the largest mapped block the
# process has freed so far (trim = 2 x mmap, mmap at most 32 MiB), so they
# depend on what ran before.  A dispatch's host buffers (the stacked
# inputs, every kernel's copied-back output) are freed together from the
# top of the heap: where they outweigh the trim threshold, each dispatch
# hands them back and faults them in afresh.
_M_TRIM_THRESHOLD, _M_MMAP_THRESHOLD = -1, -3       # mallopt parameters
HOST_MMAP_THRESHOLD = 32 << 20                      # glibc's largest
_HOST_TRIM_FLOOR = 2 * HOST_MMAP_THRESHOLD          # glibc's largest trim
_host_trim_pinned = 0


def pin_host_allocator(dispatch_bytes: int) -> bool:
    """Keep two dispatches' host buffers of ``dispatch_bytes`` each on
    glibc's heap: pin the mmap threshold at 32 MiB and the trim threshold
    at twice ``dispatch_bytes`` (at least 64 MiB), neither below what glibc
    would set itself.  Two cover what a pipelined server holds at once:
    the head batch's copied-back outputs beside the next batch's staged
    inputs and the host buffers of its copy, already started.  The trim threshold only rises.  It is process-wide:
    from then on glibc no longer moves either threshold, so every block up
    to 32 MiB comes from the heap and the process keeps up to the trim
    threshold of freed heap.  Returns whether glibc holds the pin (False
    without glibc).  Unpinned, a server that started from a warm compile
    cache served camera frames 30% slower on a TPU v5e host, its heap
    trimmed and faulted in again on every dispatch."""
    global _host_trim_pinned
    want = max(_HOST_TRIM_FLOOR, 2 * int(dispatch_bytes))
    if _host_trim_pinned >= want:
        return True
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):      # no C library or not glibc
        return False
    if not (mallopt(_M_MMAP_THRESHOLD, HOST_MMAP_THRESHOLD)
            and mallopt(_M_TRIM_THRESHOLD, want)):
        return False
    _host_trim_pinned = want
    return True


@partial(jax.jit, static_argnums=1)
def ub_widen(flat: jax.Array, shape: tuple) -> jax.Array:
    """Widen a narrow input, shipped flat, to the kernels' float32 of
    ``shape`` on the device (module ``jit_ub_widen``).  The runtime relays
    an 8-bit array of the frame's shape out into the device's tiles on the
    host before it crosses, at about half the link rate of the same bytes
    shipped flat (TPU v5e)."""
    return flat.reshape(shape).astype(jnp.float32)


@dataclass
class PallasPipeline:
    """Executable pipeline: generated kernels in dependency order."""

    pipeline: Pipeline
    kernels: List[CompiledKernel]
    plan: PipelinePlan
    mode: str = "interpret"
    cache_key: Optional[str] = None
    # each parameter's device array, in the plan's layout, uploaded once
    params: Dict[str, jax.Array] = field(default_factory=dict)

    @property
    def param_bytes(self) -> int:
        """Bytes of parameters this pipeline holds on the device."""
        return sum(int(a.nbytes) for a in self.params.values())

    def stage(self, name: str) -> CompiledKernel:
        """Kernel writing buffer ``name`` (or containing the fused stage)."""
        for k in self.kernels:
            if k.name == name:
                return k
        for k in self.kernels:
            if name in k.stage_names:
                return k
        raise KeyError(name)

    def run(self, inputs: Mapping[str, np.ndarray]) -> Dict[str, jax.Array]:
        """Execute every kernel; returns all *materialized* buffers
        (zero-based): pipeline inputs plus one buffer per kernel.  Fused
        intermediates stay in VMEM and are deliberately absent.

        Inputs are validated against the plan's declared extents up front
        (and again per kernel by ``KernelGroup.validate_buffers``), so a
        mis-shaped array raises a clear error naming the buffer and the
        expected box instead of a cryptic BlockSpec/slice failure inside
        ``pallas_call``.

        A batched pipeline (``compile_pipeline(..., batch=N)``) takes every
        input with one extra leading dim of exactly ``N`` independent
        tiles.  When the plan's slot capacity exceeds ``N`` (a ragged final
        batch) the inputs are zero-padded up to capacity before the sweep
        and every returned buffer is sliced back to the ``N`` valid tiles —
        callers never see the padded slots.

        An input whose dtype float32 holds exactly (:func:`stage_dtype`)
        crosses to the device flat, at that dtype, and is widened there;
        any other is cast to float32 on the host first."""
        batch = self.plan.notes.get("batch")
        cap = self.plan.notes.get("batch_capacity", batch)
        buffers: Dict[str, jax.Array] = {}
        with tracing.span(tracing.TO_DEVICE):
            for name in self.pipeline.inputs:
                if name not in inputs:
                    raise KeyError(
                        f"missing input {name!r}; the plan requires "
                        f"{sorted(self.pipeline.inputs)}"
                    )
                x = inputs[name]
                if not hasattr(x, "dtype"):
                    x = np.asarray(x)
                want = tuple(self.pipeline.buffer_boxes[name].extents)
                if batch is not None:
                    want = (batch,) + want
                if x.ndim != len(want):
                    raise ValueError(
                        f"input {name!r}: rank {x.ndim} (shape "
                        f"{tuple(x.shape)}) != plan's declared rank "
                        f"{len(want)} (extents {want}"
                        + (f", leading dim = batch {batch})" if batch else ")")
                    )
                if tuple(x.shape) != want:
                    raise ValueError(
                        f"input {name!r}: shape {tuple(x.shape)} != the "
                        f"plan's declared extents {want}"
                        + (f" (leading dim = batch {batch})" if batch else "")
                    )
                arr = (
                    ub_widen(jax.device_put(x.reshape(-1)), want)
                    if stage_dtype([x.dtype]) != np.float32
                    else jnp.asarray(x, jnp.float32)
                )
                if batch is not None and cap > batch:
                    arr = jnp.concatenate(
                        [arr, jnp.zeros((cap - batch,) + want[1:], jnp.float32)]
                    )
                buffers[name] = arr
        for ck in self.kernels:
            with tracing.span(ck.span):
                buffers[ck.name] = ck(buffers)
        if batch is not None and cap > batch:
            buffers = {name: arr[:batch] for name, arr in buffers.items()}
        return buffers

    def __call__(self, inputs: Mapping[str, np.ndarray]) -> jax.Array:
        return self.run(inputs)[self.pipeline.output]


# ---------------------------------------------------------------------------
# Plan-keyed pipeline cache
# ---------------------------------------------------------------------------

_PIPELINE_CACHE: "OrderedDict[str, PallasPipeline]" = OrderedDict()
_PIPELINE_CACHE_MAX = 128
# cache observability: cumulative counters over every ``cache=True``
# compile (uncached compiles are not cache traffic and are not counted).
# ``clear_pipeline_cache(reset_stats=True)`` resets them together with the
# entries; by default clearing evicts entries but *keeps* the counters, so
# a harness that clears between candidates retains its observability.
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def _hash_pipeline_content(h, pipe: Pipeline) -> None:
    """Feed the lowered pipeline's content — every normalized stage
    (zero-based access maps, value expressions, extents), the buffer
    boxes, the stream element dtype — into ``h``.  Frozen-dataclass
    ``repr``s make the serialization deterministic."""
    h.update(repr(pipe.output).encode())
    h.update(repr(sorted(pipe.inputs)).encode())
    for name, value in sorted(pipe.params.items()):
        arr = np.ascontiguousarray(value, np.float32)
        h.update(f"param {name}:{arr.shape};".encode())
        h.update(arr.tobytes())
    for name, box in sorted(pipe.buffer_boxes.items()):
        h.update(f"{name}:{box.dims}:{box.intervals};".encode())
    for ns in normalize_pipeline(pipe):
        h.update(repr((
            ns.name, ns.pure_dims, ns.pure_extents, ns.red_dims,
            ns.red_extents, ns.value, ns.init, ns.loads, ns.dim_lower,
            ns.on_host,
        )).encode())
    h.update(b"elem:f32")


def plan_cache_key(pipe: Pipeline, mode: str, options: PlanOptions) -> str:
    """Content hash identifying a compiled pipeline: the *inputs* of
    planning — the lowered pipeline content (see
    ``_hash_pipeline_content``) — plus every plan option away from its
    default (:meth:`PlanOptions.items`, so an explicit default and an
    omitted option hash identically) and the resolved execution mode.
    Two pipelines with identical lowered content and parameters share one
    cache entry; changing any extent, expression, non-default option, or
    the mode produces a different key.  Planning itself is *not* run to
    compute the key, which is what lets a cache hit skip re-planning
    entirely."""
    h = hashlib.sha256()
    h.update(mode.encode())
    h.update(repr(options.items()).encode())
    _hash_pipeline_content(h, pipe)
    return h.hexdigest()


def schedule_db_key(pipe: Pipeline, options: PlanOptions = PlanOptions()) -> str:
    """Key a pipeline into the autotuner's schedule database: the same
    content hash as :func:`plan_cache_key` minus the *tunable* options
    (``TUNABLE_KEYS`` — the schedule itself) and minus the execution
    mode.  Two compiles that pose the same planning problem — identical
    lowered content, budget, batching — look up the same stored schedule
    regardless of which schedule options or mode they currently run
    with."""
    h = hashlib.sha256()
    h.update(b"schedule-db:")
    h.update(repr(options.heuristic().items()).encode())
    _hash_pipeline_content(h, pipe)
    return h.hexdigest()


def clear_pipeline_cache(reset_stats: bool = False) -> None:
    """Evict every cached pipeline.  The hit/miss/eviction counters are
    *kept* by default — a harness that clears between measurement
    candidates (cold-compile timing, the autotuner) retains its
    observability; pass ``reset_stats=True`` to zero them too (the old
    behavior, used by phase-scoped reporters like the serve bench)."""
    _PIPELINE_CACHE.clear()
    if reset_stats:
        _CACHE_STATS.update(hits=0, misses=0, evictions=0)


def drop_pipeline_cache_entry(key: Optional[str]) -> bool:
    """Evict one cache entry by its :func:`plan_cache_key` (the serve
    bridge's retry-with-recompile path: a dispatch failure drops the
    possibly-poisoned entry before recompiling, so the fresh compile can
    never be served the broken pipeline back as a cache hit).  Returns
    whether an entry was present.  Deliberate drops are not LRU pressure
    and do not count as ``evictions`` in :func:`pipeline_cache_stats`."""
    if key is None:
        return False
    return _PIPELINE_CACHE.pop(key, None) is not None


def pipeline_cache_size() -> int:
    return len(_PIPELINE_CACHE)


def pipeline_cache_stats() -> Dict[str, int]:
    """Hit/miss/eviction counters of the plan-keyed pipeline cache since
    the last :func:`clear_pipeline_cache`, plus the live entry count.  A
    miss is a ``cache=True`` compile that had to plan+emit; an eviction is
    an LRU drop past the ``_PIPELINE_CACHE_MAX``-entry capacity — under
    mixed serve traffic ``hits / (hits + misses)`` is the
    compile-amortization rate the batch bridge depends on."""
    return {**_CACHE_STATS, "entries": len(_PIPELINE_CACHE)}


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other path is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``, so the next run in the same checkout finds
    what this one compiled.  Every compile is cached, however short: a
    served pipeline compiles one kernel per plan group, most in about a
    second.  Call it before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def compile_pipeline(
    pipe: Pipeline,
    *,
    mode: str = "interpret",
    cache: bool = False,
    verify: object = "auto",
    tune: object = False,
    **options,
) -> PallasPipeline:
    """Plan, verify and emit ``pipe``; ``options`` are the keywords of
    :class:`~repro.backend.plan.PlanOptions` (an unknown one raises
    ``TypeError``).

    ``mode`` is the execution switch (``"interpret"`` | ``"compiled"`` |
    ``"auto"``).  A compiled pipeline always plans with ``align_tpu``.
    ``cache=True`` consults the plan-keyed pipeline cache: a hit returns
    the previously compiled :class:`PallasPipeline` (its jit-warmed kernels
    included) without re-planning or re-emitting.  Every option, batching
    included, is part of the cache key: a batched and an unbatched compile
    of the same pipeline — or two different capacities — can never
    collide on one cache entry.

    ``verify`` gates static plan certification (``backend.verify``): every
    freshly built plan is checked before emission and a violation raises
    :class:`~repro.backend.verify.PlanVerificationError` instead of emitting
    a kernel from a broken plan.  ``"auto"`` (default) verifies fresh plans
    only (cache hits were certified when first built), ``True`` also
    re-verifies on cache hits, ``False`` skips verification.  The knob does
    not affect the plan itself, so it is deliberately *not* part of the
    plan cache key.

    ``tune`` consults the autotuner's schedule database
    (``backend/autotune``) before planning: ``"auto"`` (or ``True``) looks
    up the default on-disk db, a path string/`ScheduleDB` uses that db,
    ``False`` (default) skips the lookup.  A stored winning schedule
    overrides only the tunable options the caller left at their defaults —
    an explicit ``block_h=...`` always beats the db — and the overridden
    options *do* enter the plan cache key, so tuned and heuristic compiles
    of one pipeline never collide on a cache entry.  A miss (no stored
    schedule for this pipeline) falls back to the heuristic planner
    silently; a hit whose stored row was *measured* in a different
    execution mode than this compile emits a one-line
    :class:`TunedModeMismatchWarning` (interpret rankings may not
    transfer to TPU).

    An explicit ``line_buffer=True`` the planner cannot honor on a
    lane-blocked kernel (halo wider than the lane block, carry
    bookkeeping over budget, ...) emits a :class:`LaneCarryDegradeWarning`
    naming the planner's reason instead of degrading silently."""
    mode = resolve_mode(mode)
    opts = PlanOptions(**options)
    if mode == "compiled":
        # Mosaic refuses blocks whose last two dims are not
        # (8, 128)-tileable, so a compiled pipeline always plans aligned
        opts = replace(opts, align_tpu=True)
    if verify not in (True, False, "auto"):
        raise ValueError(f"verify must be True, False, or 'auto': {verify!r}")
    if tune is not False and tune is not None:
        from .autotune import lookup_schedule_entry

        entry = lookup_schedule_entry(pipe, opts, db=tune)
        if entry:
            stored_mode = entry.get("mode")
            if stored_mode is not None and stored_mode != mode:
                warnings.warn(
                    f"serving a schedule measured in {stored_mode!r} mode "
                    f"to a {mode!r}-mode compile; {stored_mode}-mode "
                    f"rankings may not transfer — re-tune with "
                    f"mode={mode!r}",
                    TunedModeMismatchWarning,
                    stacklevel=2,
                )
            opts = opts.tuned(entry.get("schedule", {}))
    key: Optional[str] = None
    if cache:
        key = plan_cache_key(pipe, mode, opts)
        hit = _PIPELINE_CACHE.get(key)
        if hit is not None:
            _CACHE_STATS["hits"] += 1
            _PIPELINE_CACHE.move_to_end(key)
            if verify is True:
                assert_plan_verified(hit.plan)
            return hit
        _CACHE_STATS["misses"] += 1
    with tracing.span(tracing.PLAN) as span:
        plan = build_pipeline_plan(pipe, options=opts)
        if opts.line_buffer is True:
            _warn_lane_carry_degrades(plan)
        if verify is not False:
            assert_plan_verified(plan)
        span.set_metadata(**plan.notes["reductions"])
    params: Dict[str, jax.Array] = {}
    if plan.params:
        with tracing.span(tracing.PARAMS):
            params = {
                name: jax.device_put(layout.apply(pipe.params[name]))
                for name, layout in plan.params.items()
            }
    kernels = []
    for kg in plan.kernels:
        try:
            kernels.append(emit_kernel(
                kg, mode=mode, vmem_budget=opts.vmem_budget, params=params,
            ))
        except Exception as e:
            # a certified plan failing to lower is an emitter (or Pallas)
            # defect, not a caller error: name the kernel group instead of
            # surfacing a bare Pallas traceback
            raise EmitError(
                f"emission failed in {mode!r} mode: {e}",
                kernel=kg.stages[-1].name,
                stage=kg.stage_names[-1] if kg.stage_names else None,
            ) from e
    pp = PallasPipeline(
        pipe, kernels, plan, mode=mode, cache_key=key, params=params
    )
    if cache:
        _PIPELINE_CACHE[key] = pp
        while len(_PIPELINE_CACHE) > _PIPELINE_CACHE_MAX:
            _PIPELINE_CACHE.popitem(last=False)
            _CACHE_STATS["evictions"] += 1
    return pp


def reference_arrays(
    pipe: Pipeline, inputs: Mapping[str, np.ndarray]
) -> Dict[str, np.ndarray]:
    """Reference interpreter results as zero-based dense arrays."""
    values = execute_pipeline(pipe, inputs)
    out: Dict[str, np.ndarray] = {}
    for name, tbl in values.items():
        box = pipe.buffer_boxes[name]
        lo = tuple(l for l, _ in box.intervals)
        arr = np.zeros(box.extents, np.float64)
        for idx, v in tbl.items():
            arr[tuple(i - l for i, l in zip(idx, lo))] = v
        out[name] = arr
    return out


def max_abs_error(
    pp: PallasPipeline,
    inputs: Mapping[str, np.ndarray],
    got: Optional[Mapping[str, jax.Array]] = None,
) -> Dict[str, float]:
    """Per-kernel max |generated - reference| over every buffer the pipeline
    materializes (differential validation; fused intermediates have no HBM
    realization to compare).  Pass ``got`` (the result of ``pp.run``) to
    reuse already-computed buffers instead of re-executing the pipeline.

    For a batched pipeline the reference interpreter (which is per-tile)
    runs once per batch slot and the reported error is the max over
    slots — so a ring carried across a batch boundary, which corrupts
    every slot after the first, cannot hide behind slot 0 being right."""
    if got is None:
        got = pp.run(inputs)
    batch = pp.plan.notes.get("batch")
    if batch is not None:
        errs = {ck.name: 0.0 for ck in pp.kernels}
        for b in range(batch):
            tile_in = {n: np.asarray(a)[b] for n, a in inputs.items()}
            want = reference_arrays(pp.pipeline, tile_in)
            for ck in pp.kernels:
                w = want[ck.name]
                if w.size:
                    e = float(np.max(np.abs(np.asarray(got[ck.name][b]) - w)))
                    errs[ck.name] = max(errs[ck.name], e)
        return errs
    want = reference_arrays(pp.pipeline, inputs)
    return {
        ck.name: float(np.max(np.abs(np.asarray(got[ck.name]) - want[ck.name])))
        if want[ck.name].size
        else 0.0
        for ck in pp.kernels
    }


__all__ = [
    "LaneCarryDegradeWarning",
    "PallasPipeline",
    "TunedModeMismatchWarning",
    "compile_pipeline",
    "enable_compile_cache",
    "plan_cache_key",
    "schedule_db_key",
    "stage_dtype",
    "TUNABLE_KEYS",
    "clear_pipeline_cache",
    "drop_pipeline_cache_entry",
    "pipeline_cache_size",
    "pipeline_cache_stats",
    "pin_host_allocator",
    "reference_arrays",
    "max_abs_error",
]
