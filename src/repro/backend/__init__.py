"""Pallas code generator for lowered Halide pipelines (plan/emit).

Bridges the paper's compiler front half (``frontend.lower`` -> ``Stage`` IR,
the input of unified-buffer extraction) to an executable push-memory target
in two phases: ``plan.build_pipeline_plan`` makes every memory decision
(view streams, stage fusion into VMEM scratch, grid-level reductions,
scheduler-driven block heights) symbolically, and ``codegen.emit_kernel``
lowers each planned kernel group to a ``pallas_call``.  See README.md in
this package for the Stage -> plan -> grid/BlockSpec correspondence.
"""

from .access import AxisAccess, LoadAccess, UnsupportedAccessError, decompose_stage
from .autotune import ScheduleDB, TuneResult, lookup_schedule
from .autotune import search as autotune_search
from .errors import (
    BackendError,
    BackendWarning,
    DeadlineExceededError,
    DegradedModeWarning,
    EmitError,
    LaneCarryDegradeWarning,
    MissingInputError,
    NonFiniteInputError,
    PlanError,
    PoisonedTileError,
    QueueFullError,
    RequestError,
    ScheduleDBCorruptWarning,
    ServeError,
    TunedModeMismatchWarning,
)
from .codegen import (
    CompiledKernel,
    CompiledStage,
    compile_stage,
    emit_kernel,
    resolve_mode,
)
from .plan import (
    FusionInfeasible,
    KernelGroup,
    LineBuffer,
    PaddedGrid,
    PipelinePlan,
    RedGrid,
    RingStream,
    StagePlan,
    ViewGroup,
    build_pipeline_plan,
    scheduler_cost,
)
from .runner import (
    TUNABLE_KEYS,
    PallasPipeline,
    clear_pipeline_cache,
    compile_pipeline,
    drop_pipeline_cache_entry,
    enable_compile_cache,
    max_abs_error,
    pipeline_cache_size,
    pipeline_cache_stats,
    plan_cache_key,
    reference_arrays,
    schedule_db_key,
)
from .serve_bridge import PipelineServer, TileRequest
from .verify import (
    RULES,
    PlanVerificationError,
    PlanViolation,
    assert_plan_verified,
    verify_plan,
)

__all__ = [
    "AxisAccess",
    "LoadAccess",
    "UnsupportedAccessError",
    "decompose_stage",
    "CompiledKernel",
    "CompiledStage",
    "ViewGroup",
    "compile_stage",
    "emit_kernel",
    "FusionInfeasible",
    "KernelGroup",
    "LineBuffer",
    "PaddedGrid",
    "PipelinePlan",
    "RedGrid",
    "RingStream",
    "StagePlan",
    "build_pipeline_plan",
    "scheduler_cost",
    "PallasPipeline",
    "compile_pipeline",
    "enable_compile_cache",
    "plan_cache_key",
    "schedule_db_key",
    "TUNABLE_KEYS",
    "ScheduleDB",
    "TuneResult",
    "autotune_search",
    "lookup_schedule",
    "clear_pipeline_cache",
    "pipeline_cache_size",
    "pipeline_cache_stats",
    "resolve_mode",
    "max_abs_error",
    "reference_arrays",
    "PipelineServer",
    "TileRequest",
    "BackendError",
    "BackendWarning",
    "PlanError",
    "EmitError",
    "RequestError",
    "MissingInputError",
    "NonFiniteInputError",
    "DeadlineExceededError",
    "PoisonedTileError",
    "ServeError",
    "QueueFullError",
    "DegradedModeWarning",
    "ScheduleDBCorruptWarning",
    "LaneCarryDegradeWarning",
    "TunedModeMismatchWarning",
    "drop_pipeline_cache_entry",
    "RULES",
    "PlanViolation",
    "PlanVerificationError",
    "verify_plan",
    "assert_plan_verified",
]
