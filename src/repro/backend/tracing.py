"""Names of the serving path's profiler spans, on the profiler's own clock.

Every span is a ``jax.profiler.TraceAnnotation``: it costs about a
microsecond with the profiler off and lands on the host plane of a
``jax.profiler`` trace with it on.  One span per dispatch and per phase,
never per slot; the per-kernel launch span is named once, at emit time
(``CompiledKernel.span``).  Two spans belong to a compile, not a dispatch:
``ub.plan`` (with the plan's count of each reduction kind as arguments)
and ``ub.params``.
"""

import jax

span = jax.profiler.TraceAnnotation

PREFIX = "ub."
STEP = "ub.step"                  # PipelineServer.step; argument dispatch=n
STACK = "ub.stack"                # pad to slots + np.stack at the staged dtype
TO_DEVICE = "ub.to_device"        # PallasPipeline.run: inputs onto the device
KERNEL = "ub.kernel."             # + kernel name: one host-side launch
AHEAD = "ub.ahead"                # launch of the next batch before the head's copy-back
COPY_BACK = "ub.copy_back"        # np.asarray of every kernel's output
FINITE_CHECK = "ub.finite_check"  # read of the device's per-slot NaN/Inf flags
RECOMPILE = "ub.recompile"        # recovery-ladder recompile
QUARANTINE = "ub.quarantine"      # one bisection probe dispatch
PARAMS = "ub.params"              # compile_pipeline: parameters onto the device, once
PLAN = "ub.plan"                  # compile_pipeline: plan and verify; reduction-kind counts
