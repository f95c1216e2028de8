"""Continuous-batching serve bridge for compiled batched pipelines.

Requests pack into a fixed number of batch slots, and the ragged tail is
padded with filler requests whose results are discarded
(:func:`pad_to_slots`).  A :class:`PipelineServer` owns one
pipeline compiled at full slot capacity (``batch = batch_capacity =
batch_slots``, so every service step reuses the same cached kernels — the
batch options are part of the plan cache key), queues :class:`TileRequest`\\ s,
and each ``step()`` packs up to ``batch_slots`` pending tiles into a single
batched dispatch: one ``pallas_call`` grid sweep per kernel group instead of
one call per tile.

Raggedness is handled by the serve layer, not the kernel: a short final
batch is padded to capacity with zero tiles via :func:`pad_to_slots`
and the filler slots' outputs are discarded, which keeps the valid slots'
emission identical to the unbatched path (see ``_StageCtx.panel_mask`` on
why an in-kernel batch mask would break bit-exactness).

One server can juggle *several* tile shapes: :meth:`PipelineServer.register`
adds another pipeline (same serving contract, different extents) to a
per-shape dispatch table, :meth:`~PipelineServer.submit` routes each request
to its registered shape (anything unregistered is rejected with the tile
shapes it *could* have matched), and :meth:`~PipelineServer.step` dispatches
the longest same-shape run at the head of the FIFO queue — drain order is
preserved across shapes, and the batch-keyed plan cache amortizes the extra
compiles exactly as it does across servers.

Fault tolerance (the serving analogue of the static plan verifier): every
failure surfaces as a named class from :mod:`backend.errors`, and no fault
in one request can corrupt another's result.

* **Admission validation.**  ``submit()`` checks each request's inputs for
  presence (:class:`MissingInputError`), real numeric dtype
  (:class:`RequestError` listing expected vs got), registered tile shape,
  and — under ``validate=True`` — finite values
  (:class:`NonFiniteInputError` with the first bad coordinate), so poison
  is rejected before it can enter a batched dispatch.
* **Backpressure.**  ``max_pending`` bounds the queue; a full queue either
  rejects new work (:class:`QueueFullError`, ``admission="reject"``) or
  services batches synchronously until there is room
  (``admission="block"``).
* **Deadlines.**  A per-request deadline (``submit(..., deadline=s)`` or
  the server-wide ``default_deadline``) fails the request with
  :class:`DeadlineExceededError` whether it expires waiting in the queue
  or completes late — late results are discarded, never returned as if on
  time.  The clock is injectable (``clock=``) so the fault harness can
  advance time deterministically.
* **Retry-with-recompile.**  A dispatch that *raises* climbs a recovery
  ladder: drop the (possibly poisoned) plan-cache entry and recompile
  fresh; then recompile on the heuristic schedule (tunable options
  stripped, ``tune=False``); each recovered rung emits a
  :class:`DegradedModeWarning`.  Only when the ladder is exhausted does
  the batch enter quarantine.
* **Quarantine by bisection.**  A dispatch that still fails — or whose
  output contains NaN/Inf in any live slot — is bisected: halves are
  re-dispatched (padded to capacity) until the poisoned tile(s) are
  isolated down to single-tile dispatches and failed individually with
  :class:`PoisonedTileError`, while every healthy tile completes from a
  clean dispatch and is therefore bit-exact vs the per-tile pipeline.

Pipelining: a dispatch is a launch (stage, kernels, finite flags, and the
start of every device→host copy) and a finish (the copy-back).  When a
full same-shape batch is queued behind the head batch, ``step()``
launches it before it finishes the head, so the device runs batch n+1
while the host copies batch n back.  The launched batch stays in the
queue until the next ``step()`` pops it and finishes that launch; if the
batch it pops is not exactly those requests (one expired meanwhile), the
launch is discarded and the batch dispatched afresh.  A fault raised by
such a launch is held and charged to its own batch, in the next step.
No batch launches ahead where a request of it or of the head batch
carries a deadline, nor from a recovery-ladder or quarantine dispatch.

``stats()`` reports the serving counters, the per-fault-class counters,
and the process-wide pipeline-cache counters in one dict.
"""

from __future__ import annotations

import functools
import itertools
import time
import warnings
from collections import deque
from dataclasses import dataclass
from typing import (
    Callable,
    Deque,
    Dict,
    List,
    Mapping,
    Optional,
    Sequence,
    Tuple,
    Union,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.frontend.lower import Pipeline

from . import tracing
from .errors import (
    BackendError,
    DeadlineExceededError,
    DegradedModeWarning,
    MissingInputError,
    NonFiniteInputError,
    PoisonedTileError,
    QueueFullError,
    RequestError,
)
from .plan import TUNABLE_KEYS
from .runner import (
    PallasPipeline,
    compile_pipeline,
    drop_pipeline_cache_entry,
    pin_host_allocator,
    pipeline_cache_stats,
    stage_dtype,
)

# dtypes a tile may arrive in: anything real-numeric reaches the kernels'
# float32 element type; everything else (object, strings, complex,
# datetimes) would surface as a deep BlockSpec/Pallas error at drain time
# and is rejected at submit instead.  A batch crosses to the device at its
# live slots' common dtype where float32 holds it exactly (uint8, uint16,
# int8, int16, bool, float16, float32) and is widened there; int32, int64
# and float64 batches are cast to float32 on the host (runner.stage_dtype)
_NUMERIC_KINDS = frozenset("fiub")


def pad_to_slots(
    requests: List, slots: int, make_filler: Callable[[], object]
) -> List:
    """Pad a ragged request list up to ``slots`` with filler requests
    (pad-and-discard: fillers do the slot's work on dummy data and their
    results are thrown away)."""
    if len(requests) > slots:
        raise ValueError(
            f"{len(requests)} requests exceed the {slots} batch slots"
        )
    return list(requests) + [make_filler() for _ in range(slots - len(requests))]


@jax.jit
def ub_finite_slots(outs: Sequence[jax.Array]) -> jax.Array:
    """Per slot, whether every element of every array of ``outs`` (slots
    on the leading axis) is finite, on the device (module
    ``jit_ub_finite_slots``).  Each array reduces over its other axes in
    the layout it has: a reshape first could cost a relayout copy."""
    flags = [jnp.isfinite(a).all(axis=tuple(range(1, a.ndim))) for a in outs]
    return functools.reduce(jnp.logical_and, flags)


@dataclass
class TileRequest:
    """One tile of work: per-tile input arrays in, per-tile outputs out.

    ``done`` flips once the request leaves the system — successfully
    (``outputs`` set, ``error`` None) or failed closed (``outputs`` None,
    ``error`` a named :class:`~repro.backend.errors.BackendError`).
    ``deadline`` is an absolute server-clock time; ``None`` means no
    deadline."""

    inputs: Dict[str, np.ndarray]
    outputs: Optional[Dict[str, np.ndarray]] = None
    done: bool = False
    filler: bool = False              # capacity padding; outputs discarded
    error: Optional[BackendError] = None
    deadline: Optional[float] = None
    submitted_at: Optional[float] = None

    @property
    def ok(self) -> bool:
        """Completed successfully (serviced and not failed)."""
        return self.done and self.error is None


@dataclass
class _Launch:
    """A launched dispatch: every kernel's output (device arrays, or the
    host arrays a fault injector put in their place) with its copy to the
    host started, the per-slot finite flags, and the bytes it moves."""

    bufs: Dict[str, Union[jax.Array, np.ndarray]]
    finite: jax.Array
    nbytes: int                       # staged inputs + outputs to copy back


@dataclass
class _Ahead:
    """The batch launched ahead of its step: its requests (still queued)
    and its launch, or the exception the launch raised."""

    reqs: List[TileRequest]
    launch: Union[_Launch, Exception]


def _fault_counter_zeros() -> Dict[str, int]:
    return {
        "validation_rejects": 0,       # submit() refused the request
        "backpressure_rejects": 0,     # QueueFullError under admission=reject
        "deadline_misses": 0,          # expired in queue or completed late
        "dispatch_failures": 0,        # a batched dispatch raised
        "recompiles": 0,               # recovery-ladder recompiles
        "degraded_dispatches": 0,      # dispatches served off the ladder
        "quarantine_dispatches": 0,    # bisection probe dispatches
        "poisoned_tiles": 0,           # requests failed as poisoned
    }


class PipelineServer:
    """Fixed-slot batched pipeline execution (continuous-batching lite).

    Submit tiles with :meth:`submit`; :meth:`step` services one batch —
    up to ``batch_slots`` pending requests in a single batched pipeline
    dispatch — and :meth:`run` drains the queue.  Completed requests carry
    ``outputs`` (one array per pipeline kernel) and ``done=True``; a
    request that failed carries a named ``error`` instead (see the module
    docstring for the full fault-tolerance contract).

    :meth:`register` adds further pipelines (other tile shapes) to the
    server's per-shape dispatch table; ``submit`` routes each request by
    its input tile shapes and rejects anything unregistered.  ``step``
    always dispatches the longest consecutive same-shape run at the head
    of the queue, so completion order stays submission order even under
    mixed-shape traffic.

    ``max_pending`` bounds the queue (``None`` = unbounded);
    ``admission`` picks the full-queue policy (``"reject"`` raises
    :class:`QueueFullError`, ``"block"`` services batches until there is
    room).  ``default_deadline`` (seconds) applies to every request that
    does not carry its own.  ``validate`` controls admission checks:
    ``True`` (default) = shape + dtype + finite values, ``"shape"`` =
    skip only the finite-values guard (poison is then caught by output
    quarantine instead — defense in depth), ``False`` = shape routing
    only.  ``clock`` injects a time source (default
    ``time.monotonic``)."""

    def __init__(
        self,
        pipe: Pipeline,
        batch_slots: int,
        *,
        max_pending: Optional[int] = None,
        admission: str = "reject",
        default_deadline: Optional[float] = None,
        validate: object = True,
        clock: Optional[Callable[[], float]] = None,
        **compile_kwargs,
    ) -> None:
        if batch_slots < 1:
            raise ValueError(f"batch_slots must be >= 1, got {batch_slots}")
        if admission not in ("reject", "block"):
            raise ValueError(
                f"admission must be 'reject' or 'block', got {admission!r}"
            )
        if max_pending is not None and max_pending < 1:
            raise ValueError(f"max_pending must be >= 1, got {max_pending}")
        if validate not in (True, False, "shape"):
            raise ValueError(
                f"validate must be True, False, or 'shape': {validate!r}"
            )
        self.pipe = pipe
        self.batch_slots = batch_slots
        self.max_pending = max_pending
        self.admission = admission
        self.default_deadline = default_deadline
        self.validate = validate
        self._clock = clock if clock is not None else time.monotonic
        # per-shape dispatch table: shape signature -> (pipeline source,
        # compiled full-capacity batched pipeline, its compile kwargs —
        # kept so the recovery ladder can recompile the same problem)
        self._table: Dict[
            Tuple, Tuple[Pipeline, PallasPipeline, Dict]
        ] = {}
        self.pipeline: PallasPipeline = self.register(pipe, **compile_kwargs)
        self.pending: Deque[Tuple[Tuple, TileRequest]] = deque()
        self.served = 0
        self.failed = 0
        self.dispatches = 0
        # dispatches whose inputs crossed narrower than float32
        self.narrow_dispatches = 0
        # host<->device traffic of every dispatch, filler slots included
        self.bytes_to_device = 0
        self.bytes_from_device = 0
        # batches launched before their step (a launch that raised
        # included), and those of them thrown away unfinished
        self.dispatches_ahead = 0
        self.ahead_discarded = 0
        self._ahead: Optional[_Ahead] = None
        self.fault_counters: Dict[str, int] = _fault_counter_zeros()

    # -- request lifecycle --------------------------------------------------

    @staticmethod
    def _tile_shape(pipe: Pipeline, name: str) -> tuple:
        return tuple(pipe.buffer_boxes[name].extents)

    @classmethod
    def _shape_key(cls, pipe: Pipeline) -> Tuple:
        """A pipeline's serving signature: its sorted (input, shape) pairs."""
        return tuple(sorted(
            (n, cls._tile_shape(pipe, n)) for n in pipe.inputs
        ))

    def register(self, pipe: Pipeline, **compile_kwargs) -> PallasPipeline:
        """Add ``pipe`` (another tile shape of the serving contract) to the
        dispatch table, compiled at full slot capacity.  Returns the
        compiled pipeline; the batch-keyed plan cache (on by default) makes
        re-registering a shape — here or on another server — a cache hit
        instead of a recompile."""
        # full-capacity plan: ragged service steps pad to capacity instead
        # of recompiling at a smaller batch, so the warm path is one cache
        # hit per dispatch
        compile_kwargs.setdefault("cache", True)
        pp = compile_pipeline(
            pipe,
            batch=self.batch_slots,
            batch_capacity=self.batch_slots,
            **compile_kwargs,
        )
        self._table[self._shape_key(pipe)] = (pipe, pp, dict(compile_kwargs))
        return pp

    @staticmethod
    def _zero_request(
        pipe: Pipeline, dtypes: Mapping[str, np.dtype]
    ) -> TileRequest:
        """A filler tile at the batch's staged ``dtypes``: a float32
        filler would promote a narrow batch back to float32."""
        return TileRequest(
            inputs={
                n: np.zeros(PipelineServer._tile_shape(pipe, n), dtypes[n])
                for n in pipe.inputs
            },
            filler=True,
        )

    def _validate_request(self, req: TileRequest) -> Tuple:
        """Admission checks; returns the routed shape key or raises a
        named :class:`RequestError` subclass.  Nothing invalid is ever
        queued, so a bad request can only fail itself."""
        for n in self.pipe.inputs:
            if n not in req.inputs:
                raise MissingInputError(
                    f"request is missing input {n!r}; the pipeline requires "
                    f"{sorted(self.pipe.inputs)}",
                    stage=n,
                )
        if self.validate is not False:
            for n in sorted(self.pipe.inputs):
                arr = np.asarray(req.inputs[n])
                if arr.dtype.kind not in _NUMERIC_KINDS:
                    raise RequestError(
                        f"input {n!r}: dtype {arr.dtype} is not castable to "
                        f"the pipeline element type; expected float32 (or "
                        f"any real numeric dtype), got {arr.dtype}",
                        stage=n,
                    )
        key = self._route(req)
        if self.validate is True:
            for n in sorted(self.pipe.inputs):
                arr = np.asarray(req.inputs[n])
                if arr.dtype.kind == "f":
                    finite = np.isfinite(arr)
                    if not finite.all():
                        bad = int(arr.size - int(finite.sum()))
                        first = tuple(
                            int(i)
                            for i in np.unravel_index(
                                int(np.argmin(finite)), arr.shape
                            )
                        )
                        raise NonFiniteInputError(
                            f"input {n!r}: {bad} non-finite value(s) "
                            f"(first at {first}); rejecting at submit so "
                            f"the poison never enters a batched dispatch",
                            stage=n,
                            witness=first,
                        )
        return key

    def _route(self, req: TileRequest) -> Tuple:
        """Dispatch-table routing by input tile shapes."""
        for key, (pipe, _pp, _kw) in self._table.items():
            want = dict(key)
            if all(
                n in req.inputs
                and tuple(np.shape(req.inputs[n])) == want[n]
                for n in pipe.inputs
            ):
                return key
        got = {
            n: tuple(np.shape(req.inputs[n]))
            for n in sorted(self.pipe.inputs)
            if n in req.inputs
        }
        raise RequestError(
            f"request input tile shape {got} matches no registered "
            f"pipeline; registered shapes: "
            f"{[dict(k) for k in self._table]}"
        )

    def submit(
        self,
        request: Union[TileRequest, Mapping[str, np.ndarray]],
        *,
        deadline: Optional[float] = None,
    ) -> TileRequest:
        """Queue one tile; returns the (possibly wrapped) request object.
        The request is routed by its input tile shapes; admission
        validation and the bounded-queue policy run first (see the class
        docstring).  ``deadline`` is seconds from now (overrides the
        server's ``default_deadline``)."""
        req = (
            request
            if isinstance(request, TileRequest)
            else TileRequest(inputs=dict(request))
        )
        try:
            key = self._validate_request(req)
        except RequestError:
            self.fault_counters["validation_rejects"] += 1
            raise
        if self.max_pending is not None:
            if self.admission == "reject":
                if len(self.pending) >= self.max_pending:
                    self.fault_counters["backpressure_rejects"] += 1
                    raise QueueFullError(
                        f"queue is full ({len(self.pending)} pending >= "
                        f"max_pending={self.max_pending}); resubmit after a "
                        f"step() or use admission='block'",
                        witness=(len(self.pending), self.max_pending),
                    )
            else:                                # admission == "block"
                while len(self.pending) >= self.max_pending:
                    self.step()
        now = self._clock()
        req.submitted_at = now
        budget = deadline if deadline is not None else self.default_deadline
        if budget is not None:
            req.deadline = now + budget
        self.pending.append((key, req))
        return req

    # -- dispatch + fault handling ------------------------------------------

    def _run_pipeline(
        self, pp: PallasPipeline, ins: Dict[str, np.ndarray]
    ) -> Mapping[str, object]:
        """The single seam every batched execution goes through — the
        fault-injection harness (``backend.faults``) wraps this bound
        method to simulate kernel raises, poisoned outputs, and slow
        dispatches without touching kernel code."""
        return pp.run(ins)

    def _launch(
        self, pipe: Pipeline, pp: PallasPipeline, reqs: List[TileRequest]
    ) -> _Launch:
        """Launch one padded-to-capacity batched execution: stage, run the
        kernels, launch the device's per-slot finite flags
        (:func:`ub_finite_slots`) and start every device→host copy, so
        the host waits on none of it until :meth:`_finish`.  Raises
        whatever the kernels raise — fault handling is the caller's
        (``_service``) job."""
        with tracing.span(tracing.STACK):
            dtypes = {
                n: stage_dtype([np.asarray(r.inputs[n]).dtype for r in reqs])
                for n in pipe.inputs
            }
            slots = pad_to_slots(
                reqs, self.batch_slots,
                lambda: self._zero_request(pipe, dtypes),
            )
            # one pass into the staged stack: no per-frame float32 copy
            ins = {
                n: np.stack(
                    [np.asarray(r.inputs[n]) for r in slots],
                    dtype=dtypes[n], casting="unsafe",
                )
                for n in pipe.inputs
            }
        staged = sum(a.nbytes for a in ins.values())
        self.bytes_to_device += staged
        ran = self._run_pipeline(pp, ins)
        self.dispatches += 1
        if any(a.dtype != np.float32 for a in ins.values()):
            self.narrow_dispatches += 1
        bufs = {ck.name: ran[ck.name] for ck in pp.kernels}
        # flagged on what the seam returned (the fault injectors' arrays
        # too)
        finite = ub_finite_slots(tuple(bufs.values()))
        copied = sum(a.nbytes for a in bufs.values())
        self.bytes_from_device += copied
        for a in (*bufs.values(), finite):
            if isinstance(a, jax.Array):
                a.copy_to_host_async()
        return _Launch(bufs, finite, staged + copied)

    def _finish(
        self, launch: _Launch
    ) -> Tuple[Dict[str, np.ndarray], jax.Array]:
        """Wait out a launch's copies; returns per-kernel stacked host
        arrays and the per-slot finite flags."""
        # one host conversion per kernel per dispatch — slicing per slot on
        # the jax array would pay a separate device sync per tile
        with tracing.span(tracing.COPY_BACK):
            outs = {name: np.asarray(a) for name, a in launch.bufs.items()}
        # keep this dispatch's host buffers on the heap for the next one
        pin_host_allocator(launch.nbytes)
        return outs, launch.finite

    def _dispatch(
        self, pipe: Pipeline, pp: PallasPipeline, reqs: List[TileRequest]
    ) -> Tuple[Dict[str, np.ndarray], jax.Array]:
        """One batched execution, launched and finished."""
        return self._finish(self._launch(pipe, pp, reqs))

    def _launch_ahead(self, head: List[TileRequest]) -> None:
        """Launch the next batch while the head batch's copies run, where
        the queue holds a full same-shape batch behind the head and no
        request of either batch carries a deadline: the ahead launch's
        time would count against the head batch's late-completion check
        (an injected clock, as ``faults.slow_dispatch`` advances, would
        charge it there outright).  A launch that raises is held, to be
        raised in its own batch's step."""
        if len(self.pending) < self.batch_slots:
            return
        batch = list(itertools.islice(self.pending, self.batch_slots))
        key = batch[0][0]
        reqs = [r for _k, r in batch]
        if any(k != key for k, _r in batch) or any(
            r.deadline is not None for r in head + reqs
        ):
            return
        pipe, pp, _kw = self._table[key]
        self.dispatches_ahead += 1
        with tracing.span(tracing.AHEAD):
            try:
                launch: Union[_Launch, Exception] = self._launch(
                    pipe, pp, reqs
                )
            except Exception as e:
                launch = e
        self._ahead = _Ahead(reqs, launch)

    @staticmethod
    def _poisoned_slots(finite: jax.Array, n_live: int) -> List[int]:
        """Live slot indices whose outputs contain NaN/Inf, read from the
        dispatch's per-slot device flags (filler slots run on zero inputs
        and are never read back)."""
        with tracing.span(tracing.FINITE_CHECK):
            ok = np.asarray(finite)[:n_live]
        return [int(b) for b in np.flatnonzero(~ok)]

    def _complete(
        self, reqs: List[TileRequest], outs: Dict[str, np.ndarray]
    ) -> None:
        for b, req in enumerate(reqs):  # filler slots are never read back
            req.outputs = {name: a[b] for name, a in outs.items()}
            req.error = None
            req.done = True

    def _fail(self, req: TileRequest, err: BackendError) -> None:
        req.outputs = None
        req.error = err
        req.done = True
        self.failed += 1

    def _recompile(self, key: Tuple, heuristic: bool = False) -> PallasPipeline:
        """Recovery-ladder recompile: drop the (possibly poisoned) cache
        entry first so the fresh compile can never be handed the broken
        pipeline back as a cache hit.  ``heuristic=True`` strips every
        tunable plan option and disables the schedule db — the most
        conservative plan the heuristic planner produces for this
        problem."""
        pipe, pp, ckw = self._table[key]
        drop_pipeline_cache_entry(pp.cache_key)
        kw = dict(ckw)
        if heuristic:
            kw = {k: v for k, v in kw.items() if k not in TUNABLE_KEYS}
            kw["tune"] = False
        self.fault_counters["recompiles"] += 1
        with tracing.span(tracing.RECOMPILE):
            fresh = compile_pipeline(
                pipe,
                batch=self.batch_slots,
                batch_capacity=self.batch_slots,
                **kw,
            )
        self._table[key] = (pipe, fresh, ckw)
        if pipe is self.pipe:
            self.pipeline = fresh
        return fresh

    def _quarantine(self, key: Tuple, reqs: List[TileRequest]) -> None:
        """Bisect a failing/poisoned batch down to the poisoned tile(s).

        Every subset is re-dispatched padded to capacity; a clean subset
        completes from *its own clean dispatch* (so healthy tiles are
        bit-exact vs the per-tile pipeline — no value from a poisoned
        dispatch is ever returned), a dirty subset splits and recurses,
        and a single tile that still fails or produces non-finite output
        is failed closed with :class:`PoisonedTileError`."""
        pipe, pp, _kw = self._table[key]
        self.fault_counters["quarantine_dispatches"] += 1
        try:
            with tracing.span(tracing.QUARANTINE):
                outs, finite = self._dispatch(pipe, pp, reqs)
        except Exception as e:
            if len(reqs) == 1:
                self.fault_counters["poisoned_tiles"] += 1
                self._fail(reqs[0], PoisonedTileError(
                    f"tile fails even dispatched alone "
                    f"({type(e).__name__}: {e})",
                    kernel=pipe.output,
                ))
                return
            mid = len(reqs) // 2
            self._quarantine(key, reqs[:mid])
            self._quarantine(key, reqs[mid:])
            return
        if not self._poisoned_slots(finite, len(reqs)):
            self._complete(reqs, outs)
            return
        if len(reqs) == 1:
            name, first = self._first_nonfinite(outs, 0)
            self.fault_counters["poisoned_tiles"] += 1
            self._fail(reqs[0], PoisonedTileError(
                f"output {name!r} is non-finite even dispatched alone "
                f"(first at {first}); the fault travels with the tile",
                kernel=name,
                witness=first,
            ))
            return
        mid = len(reqs) // 2
        self._quarantine(key, reqs[:mid])
        self._quarantine(key, reqs[mid:])

    @staticmethod
    def _first_nonfinite(
        outs: Dict[str, np.ndarray], b: int
    ) -> Tuple[str, Tuple[int, ...]]:
        for name, arr in outs.items():
            finite = np.isfinite(arr[b])
            if not finite.all():
                first = tuple(
                    int(i)
                    for i in np.unravel_index(
                        int(np.argmin(finite)), finite.shape
                    )
                )
                return name, first
        return next(iter(outs)), ()

    def _service(
        self,
        key: Tuple,
        reqs: List[TileRequest],
        launch: Union[_Launch, Exception, None] = None,
    ) -> None:
        """Service one same-shape batch with the full recovery ladder:
        dispatch → (on raise) recompile fresh → recompile heuristic →
        quarantine bisection.  On return every request in ``reqs`` is
        ``done`` — completed or failed closed with a named error.
        ``launch`` is the batch's launch made ahead in the previous step
        (or what it raised); ``None`` launches it here.  Between the
        batch's launch and its finish the next batch may launch ahead
        (:meth:`_launch_ahead`); a ladder rung never does."""
        pipe, pp, _kw = self._table[key]
        done: Optional[Tuple[Dict[str, np.ndarray], jax.Array]] = None
        try:
            if isinstance(launch, Exception):
                raise launch
            if launch is None:
                launch = self._launch(pipe, pp, reqs)
            self._launch_ahead(reqs)
            done = self._finish(launch)
        except Exception as first_err:
            # the next batch is dispatched afresh, after the recompiles
            if self._ahead is not None:
                self._ahead = None
                self.ahead_discarded += 1
            self.fault_counters["dispatch_failures"] += 1
            for heuristic in (False, True):
                try:
                    fresh = self._recompile(key, heuristic=heuristic)
                    done = self._dispatch(pipe, fresh, reqs)
                except Exception:
                    continue
                self.fault_counters["degraded_dispatches"] += 1
                warnings.warn(
                    f"dispatch of {len(reqs)} tile(s) failed "
                    f"({type(first_err).__name__}: {first_err}); recovered "
                    f"after dropping the cache entry and recompiling"
                    + (" on the heuristic schedule" if heuristic else ""),
                    DegradedModeWarning,
                    stacklevel=4,
                )
                break
        if done is None:
            # ladder exhausted: isolate the poison per tile
            self._quarantine(key, reqs)
            return
        outs, finite = done
        if self._poisoned_slots(finite, len(reqs)):
            # non-finite output in a live slot: nothing from this dispatch
            # is trustworthy — re-serve every tile from clean bisection
            # dispatches so healthy tiles stay bit-exact
            self._quarantine(key, reqs)
            return
        self._complete(reqs, outs)

    def _expire(self, now: float) -> List[TileRequest]:
        """Fail every queued request whose deadline has passed."""
        expired: List[TileRequest] = []
        if not any(r.deadline is not None for _k, r in self.pending):
            return expired
        keep: Deque[Tuple[Tuple, TileRequest]] = deque()
        for key, req in self.pending:
            if req.deadline is not None and now > req.deadline:
                self.fault_counters["deadline_misses"] += 1
                self._fail(req, DeadlineExceededError(
                    f"deadline expired in queue ({now - req.deadline:.3f}s "
                    f"past; waited {now - (req.submitted_at or now):.3f}s)",
                    witness=(),
                ))
                expired.append(req)
            else:
                keep.append((key, req))
        self.pending = keep
        return expired

    def step(self) -> List[TileRequest]:
        """Service one batch; returns the requests that *left the system*
        this step — completed, failed closed, or expired (empty when the
        queue is empty).  One dispatch serves one shape: the longest
        consecutive same-shape run at the head of the queue (up to
        ``batch_slots``), so mixed-shape traffic completes in submission
        order.

        The batch may have been launched by the previous step; this step
        then finishes that launch (see the module docstring), and
        ``dispatch`` on its span numbers the batch's own launch."""
        ahead, self._ahead = self._ahead, None
        launched = ahead is not None and isinstance(ahead.launch, _Launch)
        with tracing.span(tracing.STEP, dispatch=self.dispatches - launched):
            now = self._clock()
            finished: List[TileRequest] = list(self._expire(now))
            if not self.pending:
                if ahead is not None:
                    self.ahead_discarded += 1
                return finished
            key = self.pending[0][0]
            reqs: List[TileRequest] = []
            while (
                self.pending
                and len(reqs) < self.batch_slots
                and self.pending[0][0] == key
            ):
                reqs.append(self.pending.popleft()[1])
            launch = None
            if ahead is not None:
                if len(ahead.reqs) == len(reqs) and all(
                    a is r for a, r in zip(ahead.reqs, reqs)
                ):
                    launch = ahead.launch
                else:
                    self.ahead_discarded += 1
            self._service(key, reqs, launch)
            # completed-late check: a request whose deadline passed during the
            # dispatch fails closed — its computed outputs are discarded, not
            # returned late as if on time
            end = self._clock()
            for req in reqs:
                if req.ok and req.deadline is not None and end > req.deadline:
                    self.fault_counters["deadline_misses"] += 1
                    self._fail(req, DeadlineExceededError(
                        f"completed {end - req.deadline:.3f}s past the "
                        f"deadline; late results are discarded",
                    ))
            self.served += len(reqs)
            finished.extend(reqs)
            return finished

    def run(
        self, requests: List[Union[TileRequest, Mapping[str, np.ndarray]]]
    ) -> List[TileRequest]:
        """Submit ``requests`` and drain the queue; returns them completed
        (or failed closed), in submission order."""
        out = [self.submit(r) for r in requests]
        while self.pending:
            self.step()
        return out

    # -- observability ------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        """Serving counters, per-fault-class health counters, plus the
        process-wide pipeline-cache stats (hits/misses/evictions/entries)
        the warm path depends on."""
        held = {id(pp): pp.param_bytes for _p, pp, _kw in self._table.values()}
        return {
            "served": self.served,
            "failed": self.failed,
            "dispatches": self.dispatches,
            "narrow_dispatches": self.narrow_dispatches,
            "bytes_to_device": self.bytes_to_device,
            "bytes_from_device": self.bytes_from_device,
            "dispatches_ahead": self.dispatches_ahead,
            "ahead_discarded": self.ahead_discarded,
            # parameters held on the device (uploaded at registration,
            # never per dispatch; bytes_to_device counts requests alone)
            "param_bytes": sum(held.values()),
            "batch_slots": self.batch_slots,
            "shapes": len(self._table),
            "pending": len(self.pending),
            **self.fault_counters,
            **pipeline_cache_stats(),
        }


__all__ = ["TileRequest", "PipelineServer", "pad_to_slots", "ub_finite_slots"]
