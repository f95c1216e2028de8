"""Names of the serving path's profiler spans, on the profiler's own clock.

Every span is a ``jax.profiler.TraceAnnotation``: it costs about a
microsecond with the profiler off and lands on the host plane of a
``jax.profiler`` trace with it on.  One span per dispatch and per phase,
never per slot; the per-kernel launch span is named once, at emit time
(``CompiledKernel.span``).
"""

import jax

span = jax.profiler.TraceAnnotation

PREFIX = "ub."
STEP = "ub.step"                  # PipelineServer.step; argument dispatch=n
STACK = "ub.stack"                # pad to slots + np.stack at the staged dtype
TO_DEVICE = "ub.to_device"        # PallasPipeline.run: inputs onto the device
KERNEL = "ub.kernel."             # + kernel name: one host-side launch
COPY_BACK = "ub.copy_back"        # np.asarray of every kernel's output
FINITE_CHECK = "ub.finite_check"  # read of the device's per-slot NaN/Inf flags
RECOMPILE = "ub.recompile"        # recovery-ladder recompile
QUARANTINE = "ub.quarantine"      # one bisection probe dispatch
PARAMS = "ub.params"              # compile_pipeline: parameters onto the device, once
