"""Plan -> Pallas kernel emission (the *emit* half of plan/emit).

All placement decisions — view groups, fusion, scratch residency, grid
reductions, block heights — are made by ``backend/plan.py``; this module is
a pure emitter from a :class:`~repro.backend.plan.KernelGroup` to an
executable ``pallas_call``:

  * each **view group** becomes one input stream: an offset/strided view of
    a producer buffer plus a ``BlockSpec`` index map advancing in lock-step
    with the output panel (and, under a grid reduction, with the reduction
    chunk),
  * each fused **non-output stage** is evaluated once per panel shift into
    a VMEM scratch buffer (``scratch_shapes``); consumers tap the scratch
    panels exactly as they would tap a delivered block — the intermediate
    never round-trips HBM (the paper's coarse pipeline, Fig. 7),
  * a **grid reduction** appends the chunked reduction dim to the grid and
    accumulates into the revisited output block (``@pl.when`` init on chunk
    0), preserving the reference interpreter's accumulation order
    bit-for-bit in f32,
  * the value expression (``frontend.expr`` AST) is compiled to jnp ops;
    in-kernel reduction loops are unrolled in lexicographic order, matching
    the reference interpreter's accumulation order.

Column taps and reduction offsets stay *inside* the kernel as static slices
of the delivered block or scratch panel (register-level shifts within a
panel, the paper's Fig. 8a chain lifted from pixels to rows); strided and
traced-position taps are read through the ref instead, which is what the
TPU compiler (Mosaic) can lower.
"""

from __future__ import annotations

import itertools
import re
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ubplan import LANE, KernelPlan, VMEM_BYTES
from repro.frontend.expr import BinOp, Const, Expr, FuncRef, IterVal, Select
from repro.frontend.lower import NormalizedStage

from . import tracing
from .access import UnsupportedAccessError, decompose_stage
from .plan import (
    KernelGroup,
    RED_GRID_THRESHOLD,
    StagePlan,
    ViewGroup,
    _build_kernel_group,
    _stream_ok,
)

# sentinel scratch-dict key space for input ring buffers (cannot collide
# with (stage name, shift) keys)
_RING = object()

# operand precision of a contraction's matrix products: float32 operands
# (several bfloat16 MXU passes), accumulated in float32
CONTRACT_PRECISION = jax.lax.Precision.HIGHEST

# test instrumentation: every panel/warm-up evaluation site records
# {kernel, stage, shift, rows, when} as the kernel function is traced — the
# eval counter behind the computed-exactly-once property tests.  Scopes are
# opened with the ``eval_trace()`` context manager and nest (each scope gets
# its own list, so parametrized/parallel tests cannot clobber each other's
# counters).
_EVAL_SCOPES: List[List[Dict]] = []


@contextmanager
def eval_trace() -> Iterator[List[Dict]]:
    """Collect eval-site records for kernels *traced* inside the scope::

        with codegen.eval_trace() as trace:
            pp.run(inputs)
        assert trace  # [{kernel, stage, shift, lane_shift, rows, when}, ...]

    Sites fire at jit-trace time, so re-running an already-warm pipeline
    records nothing — arm the scope around the first invocation.  Scopes
    nest: records go to the innermost active scope, so a helper tracing its
    own compile does not pollute an enclosing test's counter."""
    trace: List[Dict] = []
    _EVAL_SCOPES.append(trace)
    try:
        yield trace
    finally:
        _EVAL_SCOPES.remove(trace)


def _record_eval(record: Dict) -> None:
    if _EVAL_SCOPES:
        _EVAL_SCOPES[-1].append(record)


# ---------------------------------------------------------------------------
# Per-stage emission context
# ---------------------------------------------------------------------------


class _StageCtx:
    """Emission context for one stage inside a kernel.

    ``rows`` is the leading (blocked-dim) extent of the evaluation: the
    full panel height by default, or the halo row count for a line-buffer
    warm-up evaluation (``with_rows``), which evaluates only the first
    ``rows`` rows of a shift's panel.  ``cols`` is the trailing (lane-dim)
    extent under lane blocking: the full block width by default, or the
    lane-halo column count for a *lane* line-buffer warm-up
    (``with_cols``), which evaluates only the first ``cols`` columns of a
    lane shift's panel."""

    def __init__(self, kg: KernelGroup, sp: StagePlan):
        self.kg = kg
        self.sp = sp
        self.nstage = sp.nstage
        self.bh = kg.bh
        self.streamed = kg.streamed and sp.streamed
        self.d0 = sp.d0
        self.pure_pos = {d: i for i, d in enumerate(sp.nstage.pure_dims)}
        self.block_shape = sp.panel_shape(kg.bh)
        self.rows = self.block_shape[0] if self.streamed else None
        self.lower = dict(sp.nstage.dim_lower)
        # lane blocking: the trailing pure dim is tiled over grid dim 1
        self.lane = kg.lane_grid is not None and self.streamed
        self.bw = kg.bw
        self.cols = kg.bw if self.lane else None
        self.lane_dim = sp.nstage.pure_dims[-1] if self.lane else None
        # grid positions, assigned once at the top of the kernel body: in
        # interpret mode ``pl.program_id`` cannot be bound inside a
        # ``pl.when`` branch, so every use site reads these hoisted values
        # (which also keeps the emitted kernel legal in compiled mode, where
        # the same hoisting is simply redundant)
        self.step0 = 0
        self.stepk = 0
        self.stepj = 0
        # view group -> (lane stride, tap starts) of a segmented view
        self.lane_segments: Dict[int, Tuple[int, Tuple[int, ...]]] = {}

    def with_rows(self, rows: int) -> "_StageCtx":
        """A copy evaluating only the first ``rows`` rows of the panel."""
        import copy

        out = copy.copy(self)
        out.rows = rows
        out.block_shape = (rows,) + tuple(self.block_shape[1:])
        return out

    def with_cols(self, cols: int) -> "_StageCtx":
        """A copy evaluating only the first ``cols`` columns of the panel
        (the lane-halo warm-up of a lane line buffer)."""
        import copy

        out = copy.copy(self)
        out.cols = cols
        out.block_shape = tuple(self.block_shape[:-1]) + (cols,)
        return out

    def extent(self, dim: str) -> int:
        if dim == self.d0 and self.streamed:
            return self.rows
        if self.lane and dim == self.lane_dim:
            return self.cols
        return self.nstage.extent(dim)

    def panel_mask(self):
        """Valid-element mask of this stage's panel at the current grid
        step, or None when no grid dim is padded.  Under a padded row grid
        the tail block hangs past the extent; under a padded lane grid the
        tail lane block does the same on the trailing dim.  Delivered
        out-of-range elements are undefined (NaN in interpret mode), so
        every stored or accumulated panel is masked to exact zeros on rows
        (and lanes) at or above the stage's valid extent."""
        mask = None
        pg = self.kg.padded_grid
        if pg is not None and self.streamed:
            # every view stream (and hence every scratch panel derived from
            # it) delivers pg.extent valid blocked-axis elements — the
            # kernel output's extent, which also bounds each fused stage's
            # demand
            rows = jax.lax.broadcasted_iota(jnp.int32, self.block_shape, 0)
            mask = rows + self.step0 * self.bh < pg.extent
        lg = self.kg.lane_grid
        if self.lane and lg is not None and lg.pad > 0:
            lanes = jax.lax.broadcasted_iota(
                jnp.int32, self.block_shape, len(self.block_shape) - 1
            )
            lmask = lanes + self.stepj * self.bw < lg.extent
            mask = lmask if mask is None else jnp.logical_and(mask, lmask)
        # A ragged batch tail (batch_grid.pad > 0) is deliberately NOT
        # value-masked here: a where() wrapped around the accumulate path
        # blocks XLA's multiply-add contraction, so even the all-valid
        # slots would round differently from the unbatched emission.
        # Padded slots instead run on zero-filled input tiles (well-defined
        # values, never NaN deliveries) and the runner slices them off
        # before anything downstream can observe them.
        return mask

    # pre-lane name, kept for introspection/tests
    row_mask = panel_mask

    def red_ranges(self) -> List[range]:
        rg = self.kg.red_grid
        out = []
        for rd, ex in zip(self.nstage.red_dims, self.nstage.red_extents):
            out.append(range(rg.chunk if rg is not None and rd == rg.dim else ex))
        return out


def _lane_segments(kg: KernelGroup) -> Dict[int, Tuple[int, Tuple[int, ...]]]:
    """View groups whose last axis every tap reads at one stride ``s > 1``
    from a static start, mapped to ``(s, starts)``.  Mosaic has no strided
    load along lanes, so such a view is delivered as one segment per
    start — the elements ``start, start + s, ...`` packed contiguously
    from a lane-aligned offset (see :func:`_segment_width`) — and each tap
    reads its segment whole.  Ring-fed views are left alone: their rings
    keep the delivered layout."""
    ring_fed = {i for r in kg.rings for i in (r.steady, r.prefix)}
    seen: Dict[int, set] = {}
    for sp in kg.stages:
        for li, binding in enumerate(sp.view_binding):
            if sp.load_kind[li] != "view":
                continue
            ax = sp.accesses[li].axes[-1]
            for gi in set(binding.values()):
                g = kg.groups[gi]
                last = g.ndim - 1
                spanned = (
                    ax.pure_dim is not None
                    and not ax.red_coeffs
                    and last not in (g.blocked_axis, g.lane_axis, g.red_axis)
                )
                seen.setdefault(gi, set()).add(
                    (ax.stride, ax.const - g.base[last]) if spanned else None
                )
    out: Dict[int, Tuple[int, Tuple[int, ...]]] = {}
    for gi, taps in seen.items():
        strides = {t[0] for t in taps if t is not None}
        if gi in ring_fed or None in taps or len(strides) != 1:
            continue
        stride = strides.pop()
        if stride > 1:
            out[gi] = (stride, tuple(sorted(t[1] for t in taps)))
    return out


def _segment_width(span: int, stride: int) -> int:
    """Lane width of one segment: the longest strided read of a ``span``
    wide view, rounded up to whole 128-lane vregs so every segment starts
    lane-aligned."""
    longest = -(-span // stride)
    return -(-longest // LANE) * LANE


def _segmented(view: jax.Array, stride: int, starts: Tuple[int, ...]) -> jax.Array:
    """``view`` with its last axis regrouped into one zero-padded segment
    per start (see :func:`_lane_segments`)."""
    w = _segment_width(view.shape[-1], stride)
    lead = [(0, 0)] * (view.ndim - 1)
    segs = [view[..., t::stride] for t in starts]
    return jnp.concatenate(
        [jnp.pad(v, lead + [(0, w - v.shape[-1])]) for v in segs], axis=-1
    )


def _span(start: int, n: int, stride: int) -> Tuple[object, object]:
    """(ref index, value index) reading ``n`` elements from ``start`` at
    ``stride``.  A unit-stride span slices the loaded block; a strided one
    is read through the ref (``pl.ds`` with a stride), because Mosaic
    cannot lower a strided slice of a loaded value."""
    if stride == 1:
        return slice(None), slice(start, start + n)
    return pl.ds(start, n, stride), slice(None)


def _tap(
    ctx: _StageCtx,
    refs,
    scratch: Mapping[Tuple[str, int], object],
    load_idx: int,
    rho: Mapping[str, int],
    shift: int,
    lshift: int = 0,
):
    """Extract one load's value lattice (:func:`_tap_raw`) and align it
    with the stage's output block (transpose + broadcast axes)."""
    tap, tags = _tap_raw(ctx, refs, scratch, load_idx, rho, shift, lshift)
    # order the kept pure-dim axes as the stage's dims, leaving unit axes
    # in place, then reshape to the broadcastable block shape
    pos = [t for t, d in enumerate(tags) if d is not None]
    want = sorted(pos, key=lambda t: ctx.pure_pos[tags[t]])
    if want != pos:
        perm = list(range(len(tags)))
        for p, w in zip(pos, want):
            perm[p] = w
        tap = jnp.transpose(tap, perm)
    newshape = tuple(
        ctx.block_shape[i] if d in tags else 1
        for i, d in enumerate(ctx.nstage.pure_dims)
    )
    return tap.reshape(newshape)


def _tap_raw(
    ctx: _StageCtx,
    refs,
    scratch: Mapping[Tuple[str, int], object],
    load_idx: int,
    rho: Mapping[str, int],
    shift: int,
    lshift: int = 0,
    keep: Optional[str] = None,
) -> Tuple[jax.Array, List[Optional[str]]]:
    """One load's value lattice — from a delivered view block, a
    cross-grid-step ring (input delivery or line-buffered intermediate), or
    an in-kernel scratch panel — with ``tags`` naming the dim of each axis
    it keeps, in the source's axis order.

    Each source axis gets a ref index (applied by the load) and a value
    index (applied to the loaded block): strided spans and the traced
    global reduction position of a resident operand are read through the
    ref, every other offset slices the loaded value.  A tag is the pure
    dim of a kept axis, None for a kept unit axis, or ``keep``: the one
    reduction dim whose axis is kept whole (a contraction's channel dim,
    absent from ``rho``)."""
    sp = ctx.sp
    la = sp.accesses[load_idx]
    ref_idx: List[object] = [slice(None)] * len(la.axes)
    idx: List[object] = []
    tags: List[Optional[str]] = []

    def span(j: int, start: int, ep: int, stride: int, dim: str) -> None:
        ref_idx[j], v = _span(start, ep, stride)
        idx.append(v)
        tags.append(dim)

    def plain(j: int, ax, base: int, unit: bool = False) -> None:
        """An axis no grid dim tiles: the kept reduction axis, a pure-dim
        span, a kept unit axis (``unit``), or a squeezed static index."""
        if keep is not None and keep in dict(ax.red_coeffs):
            k0 = ax.const - base
            idx.append(slice(k0, k0 + ctx.nstage.extent(keep)))
            tags.append(keep)
        elif ax.pure_dim is not None:
            span(j, ax.offset_at(rho) - base, ctx.extent(ax.pure_dim),
                 ax.stride, ax.pure_dim)
        elif unit:
            idx.append(slice(None))
            tags.append(None)
        else:
            idx.append(ax.offset_at(rho) - base)

    if sp.load_kind[load_idx] == "scratch" and not ctx.streamed:
        # a whole kernel holds each producer as one whole panel: every
        # axis is addressed from zero, as a view's untiled axes are
        src = scratch[(sp.scratch_producer[load_idx], 0)]
        for j, ax in enumerate(la.axes):
            plain(j, ax, 0)
    elif sp.load_kind[load_idx] == "scratch":
        pname = sp.scratch_producer[load_idx]
        slot = la.axes[0].offset_at(rho) + shift
        plb = ctx.kg.stage_plan(pname).line_buffer
        lane_sl: object = slice(None)
        if plb is not None and plb.lane:
            # lane-line-buffered producer: this row shift's panels live in
            # one column ring; the lane-shift panel starts ``lslot - lo``
            # columns in (the column analog of the row-ring tap below)
            lslot = la.axes[-1].offset_at(rho) + lshift
            src = scratch[(pname, (slot, None))]
            lead: object = (
                slice(None) if ctx.rows == ctx.bh else slice(0, ctx.rows)
            )
            lane_sl = slice(lslot - plb.lo, lslot - plb.lo + ctx.cols)
        elif plb is not None:
            # line-buffered producer: the per-shift panel lives at rows
            # [slot - lo, slot - lo + bh) of the persistent ring
            src = scratch[(pname, None)]
            lead = slice(slot - plb.lo, slot - plb.lo + ctx.rows)
        elif ctx.lane:
            # lane-blocked producer: the (row, lane)-shift panel holds the
            # tap's bw columns exactly (lane offset baked into the slot);
            # a partial-width (warm-up) consumer takes the leading columns
            lslot = la.axes[-1].offset_at(rho) + lshift
            src = scratch[(pname, (slot, lslot))]
            lead = slice(None) if ctx.rows == ctx.bh else slice(0, ctx.rows)
            if ctx.cols != ctx.bw:
                lane_sl = slice(0, ctx.cols)
        else:
            src = scratch[(pname, slot)]
            lead = slice(None) if ctx.rows == ctx.bh else slice(0, ctx.rows)
        last = len(la.axes) - 1
        for j, ax in enumerate(la.axes):
            if j == 0:
                idx.append(lead)                    # the blocked dim
                tags.append(ctx.d0)
            elif ctx.lane and j == last:
                idx.append(lane_sl)                 # the lane-blocked dim
                tags.append(ax.pure_dim)
            elif ax.pure_dim is not None:
                # scratch axes are zero-based
                span(j, ax.offset_at(rho), ctx.extent(ax.pure_dim),
                     ax.stride, ax.pure_dim)
            else:
                idx.append(ax.offset_at(rho))       # squeezed static index
    else:
        j0 = sp.blocked_axis_of[load_idx]
        jL = sp.lane_axis_of[load_idx] if sp.lane_axis_of else None
        roff = la.axes[j0].offset_at(rho) if j0 is not None else None
        if ctx.lane:
            loff = la.axes[jL].offset_at(rho) if jL is not None else None
            key: Tuple = (shift, roff, lshift, loff)
        else:
            key = (shift, roff)
        ring_hit = sp.ring_binding[load_idx].get(key) if sp.ring_binding else None
        if ring_hit is not None and ctx.kg.rings[ring_hit[0]].lane:
            # column-ring-delivered input: this tap's window starts t0
            # lattice *columns* into the ring (rotated per lane step); the
            # row axis holds exactly this row step's bh delivered rows
            r_idx, t0 = ring_hit
            ring = ctx.kg.rings[r_idx]
            src = scratch[(_RING, r_idx)]
            for j, ax in enumerate(la.axes):
                if j == ring.axis:
                    idx.append(slice(t0, t0 + ctx.cols))
                    tags.append(ax.pure_dim)
                elif j == ring.row_axis:
                    idx.append(slice(0, ctx.rows))
                    tags.append(ctx.d0)
                elif ax.pure_dim is not None:
                    span(j, ax.offset_at(rho) - ring.base[j],
                         ctx.extent(ax.pure_dim), ax.stride, ax.pure_dim)
                else:
                    idx.append(ax.offset_at(rho) - ring.base[j])
        elif ring_hit is not None:
            # ring-delivered input: this tap's window starts t0 lattice rows
            # into the ring, which the emitter keeps aligned with the grid
            r_idx, t0 = ring_hit
            ring = ctx.kg.rings[r_idx]
            src = scratch[(_RING, r_idx)]
            for j, ax in enumerate(la.axes):
                if j == j0:
                    idx.append(slice(t0, t0 + ctx.rows))
                    tags.append(ctx.d0)
                elif ax.pure_dim is not None:
                    span(j, ax.offset_at(rho) - ring.base[j],
                         ctx.extent(ax.pure_dim), ax.stride, ax.pure_dim)
                else:
                    idx.append(ax.offset_at(rho) - ring.base[j])
        else:
            gi = sp.view_binding[load_idx][key]
            g = ctx.kg.groups[gi]
            src = refs[gi]
            seg = ctx.lane_segments.get(gi)
            last = len(la.axes) - 1
            for j, ax in enumerate(la.axes):
                if j0 is not None and j == j0:
                    idx.append(slice(None) if ctx.rows == ctx.bh else slice(0, ctx.rows))
                    tags.append(ctx.d0)
                elif ctx.lane and jL is not None and j == jL:
                    # lane-blocked axis: the delivered block is the tap's
                    # bw columns (lane offset baked into the view start); a
                    # partial-width warm-up takes its leading columns
                    idx.append(
                        slice(None) if ctx.cols == ctx.bw else slice(0, ctx.cols)
                    )
                    tags.append(ax.pure_dim)
                elif j == g.red_axis and g.resident:
                    # whole operand resident in VMEM: read the global
                    # reduction position (grid chunk * chunk + in-chunk
                    # rho) through the ref, keeping a unit axis
                    rg = ctx.kg.red_grid
                    ref_idx[j] = pl.ds(
                        ctx.stepk * rg.chunk + ax.offset_at(rho) - g.base[j], 1
                    )
                    idx.append(slice(None))
                    tags.append(None)
                elif j == last and seg is not None:
                    # segmented view: this tap's elements are one segment,
                    # read from its lane-aligned start
                    stride, starts = seg
                    k = starts.index(ax.offset_at(rho) - g.base[j])
                    ref_idx[j] = pl.ds(
                        k * _segment_width(g.span[j], stride),
                        ctx.extent(ax.pure_dim),
                    )
                    idx.append(slice(None))
                    tags.append(ax.pure_dim)
                else:
                    # a parameter keeps its unit axes: its layout already
                    # puts every axis where the stage's block has it
                    plain(j, ax, g.base[j], unit=g.param and g.span[j] == 1)
    return src[tuple(ref_idx)][tuple(idx)], tags


def _contract(
    ctx: _StageCtx, refs, scratch, rho: Mapping[str, int], shift: int,
    lshift: int,
):
    """One tap of a :class:`~repro.backend.plan.Contraction`: the
    activation's lattice with its channel axis kept, times the
    parameter's ``(K, N)`` matrix at the tap, as one MXU matrix product in
    float32 at float32 operand precision, ordered as the stage's block."""
    cn = ctx.sp.contraction
    a, at = _tap_raw(ctx, refs, scratch, cn.lhs, rho, shift, lshift, cn.dim)
    w, wt = _tap_raw(ctx, refs, scratch, cn.rhs, rho, shift, lshift, cn.dim)
    prod = jax.lax.dot_general(
        a, w, (((at.index(cn.dim),), (wt.index(cn.dim),)), ((), ())),
        precision=CONTRACT_PRECISION, preferred_element_type=jnp.float32,
    )
    tags = [t for t in at if t != cn.dim] + [t for t in wt if t != cn.dim]
    perm = sorted(range(len(tags)), key=lambda t: ctx.pure_pos[tags[t]])
    if perm != list(range(len(tags))):
        prod = jnp.transpose(prod, perm)
    return prod.reshape(ctx.block_shape)


def _emit(
    e: Expr,
    ctx: _StageCtx,
    refs,
    scratch,
    rho: Mapping[str, int],
    shift: int,
    counter: List[int],
    lshift: int = 0,
):
    if isinstance(e, Const):
        return float(e.value)
    if isinstance(e, IterVal):
        lo = ctx.lower.get(e.name, 0)
        if e.name in ctx.nstage.red_dims:
            rg = ctx.kg.red_grid
            if rg is not None and e.name == rg.dim:
                k = ctx.stepk
                return (k * rg.chunk + rho[e.name] + lo).astype(jnp.float32)
            return float(rho[e.name] + lo)
        ax = ctx.pure_pos[e.name]
        iota = jax.lax.broadcasted_iota(jnp.int32, ctx.block_shape, ax)
        if ctx.streamed and ax == 0:
            iota = iota + ctx.step0 * ctx.bh + shift
        elif ctx.lane and e.name == ctx.lane_dim:
            iota = iota + ctx.stepj * ctx.bw + lshift
        return (iota + lo).astype(jnp.float32)
    if isinstance(e, FuncRef):
        k = counter[0]
        counter[0] += 1
        return _tap(ctx, refs, scratch, k, rho, shift, lshift)
    if isinstance(e, BinOp):
        a = _emit(e.a, ctx, refs, scratch, rho, shift, counter, lshift)
        b = _emit(e.b, ctx, refs, scratch, rho, shift, counter, lshift)
        if e.op == "add":
            return a + b
        if e.op == "sub":
            return a - b
        if e.op == "mul":
            return a * b
        if e.op == "div":
            # reference semantics: x / 0 == 0
            zero = jnp.asarray(b) == 0
            return jnp.where(zero, 0.0, a / jnp.where(zero, 1.0, b))
        if e.op == "min":
            return jnp.minimum(a, b)
        if e.op == "max":
            return jnp.maximum(a, b)
        if e.op == "shr":
            ai = jnp.asarray(a).astype(jnp.int32)
            bi = jnp.asarray(b).astype(jnp.int32)
            return jnp.right_shift(ai, bi).astype(jnp.float32)
        if e.op == "lt":
            return jnp.where(jnp.asarray(a) < b, 1.0, 0.0)
        if e.op == "gt":
            return jnp.where(jnp.asarray(a) > b, 1.0, 0.0)
        raise UnsupportedAccessError(f"binop {e.op} not supported by codegen")
    if isinstance(e, Select):
        c = _emit(e.cond, ctx, refs, scratch, rho, shift, counter, lshift)
        t = _emit(e.if_true, ctx, refs, scratch, rho, shift, counter, lshift)
        f = _emit(e.if_false, ctx, refs, scratch, rho, shift, counter, lshift)
        return jnp.where(jnp.asarray(c) != 0, t, f)
    raise UnsupportedAccessError(f"cannot compile {e!r}")


def _stage_panel(
    ctx: _StageCtx, refs, scratch, shift: int, lshift: int = 0,
    when: str = "every",
):
    """One stage's panel value at row shift ``shift`` and lane shift
    ``lshift`` (in-kernel reductions unrolled).  ``when`` tags which grid
    steps execute this evaluation site ("every" or "step0") for the
    eval-trace instrumentation."""
    if _EVAL_SCOPES:
        _record_eval({
            "kernel": ctx.kg.name,
            "stage": ctx.sp.name,
            "shift": shift,
            "lane_shift": lshift,
            "rows": ctx.rows if ctx.rows is not None else ctx.block_shape[0],
            "when": when,
        })
    ns = ctx.nstage
    cn = ctx.sp.contraction
    if cn is not None:
        # one matrix product per spatial tap, the channels on the MXU
        acc = _emit(ns.init, ctx, refs, scratch, {}, shift, [0], lshift)
        acc = jnp.broadcast_to(
            jnp.asarray(acc, jnp.float32), ctx.block_shape
        ).astype(jnp.float32)
        for combo in itertools.product(*(range(ns.extent(d)) for d in cn.taps)):
            rho = dict(zip(cn.taps, combo))
            acc = acc + _contract(ctx, refs, scratch, rho, shift, lshift)
    elif ns.red_dims:
        acc = _emit(ns.init, ctx, refs, scratch, {}, shift, [0], lshift)
        acc = jnp.broadcast_to(
            jnp.asarray(acc, jnp.float32), ctx.block_shape
        ).astype(jnp.float32)
        for combo in itertools.product(*ctx.red_ranges()):
            rho = dict(zip(ns.red_dims, combo))
            acc = acc + _emit(ns.value, ctx, refs, scratch, rho, shift, [0], lshift)
    else:
        acc = _emit(ns.value, ctx, refs, scratch, {}, shift, [0], lshift)
    panel = jnp.broadcast_to(jnp.asarray(acc, jnp.float32), ctx.block_shape)
    mask = ctx.panel_mask()
    if mask is not None:
        panel = jnp.where(mask, panel, 0.0)
    return panel


# ---------------------------------------------------------------------------
# Kernel emission
# ---------------------------------------------------------------------------


def resolve_mode(mode: str) -> str:
    """Resolve the execution-mode switch: ``"interpret"`` runs every
    ``pallas_call`` through the Pallas interpreter (portable, slow),
    ``"compiled"`` emits real Mosaic kernels (requires a TPU jax backend —
    the emitted kernels use TPU VMEM scratch, which the GPU/Triton path
    cannot lower), and ``"auto"`` picks compiled when the default jax
    backend is a TPU and falls back cleanly to interpret everywhere else
    (CPU and GPU alike)."""
    if mode == "auto":
        return "compiled" if jax.default_backend() == "tpu" else "interpret"
    if mode in ("interpret", "compiled"):
        return mode
    raise ValueError(
        f"unknown backend mode {mode!r}; use 'interpret' | 'compiled' | 'auto'"
    )


@dataclass
class CompiledKernel:
    """An executable Pallas kernel for one plan group (1..N fused stages)."""

    name: str                         # output stage / buffer written
    kg: KernelGroup
    nstage: NormalizedStage           # output stage
    plan: KernelPlan                  # unified-buffer introspection
    _call: Callable
    mode: str = "interpret"
    # the jitted program behind ``__call__``: takes one tuple of backing
    # arrays, ordered as ``buffer_order``, so callers can ``.lower()`` it
    # against shapes placed on a described (not attached) device
    jitted: Optional[Callable] = None
    buffer_order: Tuple[str, ...] = ()
    # the host-side launch span (``tracing.KERNEL`` + kernel name), named
    # once here so the serving path builds no string per call
    span: str = ""

    def __call__(self, buffers: Mapping[str, jax.Array]) -> jax.Array:
        return self._call(buffers)

    # -- introspection (plan passthrough) -------------------------------------
    @property
    def stage_names(self) -> List[str]:
        return self.kg.stage_names

    @property
    def fused(self) -> bool:
        return self.kg.fused

    @property
    def groups(self) -> List[ViewGroup]:
        return self.kg.groups

    @property
    def bh(self) -> int:
        return self.kg.bh

    @property
    def grid(self) -> Tuple[int, ...]:
        return self.kg.grid

    @property
    def streamed(self) -> bool:
        return self.kg.streamed

    @property
    def red_grid(self):
        return self.kg.red_grid

    @property
    def padded_grid(self):
        return self.kg.padded_grid

    @property
    def rings(self):
        return self.kg.rings

    @property
    def line_buffered(self) -> Tuple[str, ...]:
        return self.kg.line_buffered

    @property
    def block(self) -> Tuple[int, ...]:
        return self.kg.output.panel_shape(self.kg.bh)

    @property
    def accesses(self):
        return self.kg.output.accesses

    @property
    def blocked_axis_of(self):
        return self.kg.output.blocked_axis_of

    @property
    def bindings(self) -> List[Dict[Optional[int], int]]:
        """Pre-refactor binding view (offset -> group) of the output stage."""
        return [
            {k[1]: g for k, g in vb.items() if k[0] == 0}
            for vb in self.kg.output.view_binding
        ]

    @property
    def lane_grid(self):
        return self.kg.lane_grid

    @property
    def bw(self):
        return self.kg.bw

    # -- delivery arithmetic (mirrors the kernel; used by property tests) -----
    def _bind_key(self, load_idx: int, rho: Mapping[str, int]) -> Tuple:
        sp = self.kg.output
        la = sp.accesses[load_idx]
        j0 = sp.blocked_axis_of[load_idx]
        roff = la.axes[j0].offset_at(rho) if j0 is not None else None
        if self.kg.lane_grid is None:
            return (0, roff)
        jL = sp.lane_axis_of[load_idx]
        loff = la.axes[jL].offset_at(rho) if jL is not None else None
        return (0, roff, 0, loff)

    def _group_of(self, load_idx: int, rho: Mapping[str, int]) -> ViewGroup:
        sp = self.kg.output
        return self.kg.groups[
            sp.view_binding[load_idx][self._bind_key(load_idx, rho)]
        ]

    def element_for(self, load_idx: int, point: Mapping[str, int]) -> Tuple[int, ...]:
        """Producer element the generated kernel reads for load ``load_idx``
        at zero-based iteration ``point``, reconstructed by composing the
        stored delivery objects exactly as the runtime does: in-kernel tap
        coordinate -> BlockSpec block offset -> view slice.  A bookkeeping
        bug in the group binding, ``k0``/stride, block shape, or index map
        shows up as a mismatch against the stage's access map.  (Fused
        kernels expose only their output stage here.)"""
        if self.kg.fused:
            raise NotImplementedError("element_for covers unfused kernels only")
        if self.kg.batch_grid is not None:
            raise NotImplementedError(
                "element_for addresses per-tile elements; batched kernels "
                "replicate the per-tile delivery per slot"
            )
        sp = self.kg.output
        ns = self.nstage
        la = sp.accesses[load_idx]
        d0 = ns.pure_dims[0]
        rg = self.kg.red_grid
        rho = {r: point[r] for r in ns.red_dims}
        if rg is not None:
            rho = dict(rho)
            rho[rg.dim] = point[rg.dim] % rg.chunk
        ring_hit = self._ring_of(load_idx, rho)
        if ring_hit is not None:
            # ring-delivered tap: ring lattice row c maps to buffer element
            # lo + stride0 * c, and this tap starts t0 rows into the ring.
            # For a column ring the lattice runs along the lane axis —
            # lane step j's window starts j*bw lattice units in — and the
            # shared row binding delivers rows in grid lock-step.
            r_idx, t0 = ring_hit
            ring = self.kg.rings[r_idx]
            elem = []
            if ring.lane:
                dL = ns.pure_dims[-1]
                for j, ax in enumerate(la.axes):
                    if j == ring.axis:
                        jlane = point[dL] // self.kg.bw
                        elem.append(ring.lo + ring.stride0 * (
                            jlane * self.kg.bw + t0 + point[dL] % self.kg.bw
                        ))
                    elif j == ring.row_axis:
                        elem.append(
                            ring.row_k0 + ring.row_stride * point[d0]
                        )
                    else:
                        e = ax.offset_at(rho)
                        if ax.pure_dim is not None:
                            e += ax.stride * point[ax.pure_dim]
                        elem.append(e)
                return tuple(elem)
            for j, ax in enumerate(la.axes):
                if j == ring.axis:
                    elem.append(ring.lo + ring.stride0 * (t0 + point[d0]))
                else:
                    e = ax.offset_at(rho)
                    if ax.pure_dim is not None:
                        e += ax.stride * point[ax.pure_dim]
                    elem.append(e)
            return tuple(elem)
        g = self._group_of(load_idx, rho)
        slices = g.view_slices(self.kg.e0, self.kg.e1)
        block_shape = g.block_shape(self.bh, self.kg.bw)
        dL = ns.pure_dims[-1] if self.kg.lane_grid is not None else None
        step0 = point[d0] // self.bh if g.blocked_axis is not None else 0
        if g.lane_axis is not None:
            step1 = point[dL] // self.kg.bw
        elif g.red_axis is not None:
            step1 = point[rg.dim] // rg.chunk
        else:
            step1 = 0
        dim1 = "lane" if self.kg.lane_grid is not None else "red"
        block_idx = (
            g.index_map(len(self.grid), dim1)(step0, step1)
            if len(self.grid) > 1
            else g.index_map(1)(step0)
        )
        elem = []
        for j, ax in enumerate(la.axes):
            if j == g.blocked_axis:
                local = point[d0] % self.bh            # full-panel tap
            elif j == g.lane_axis:
                local = point[dL] % self.kg.bw         # lane offset in view l0
            elif j == g.red_axis and g.resident:
                # resident operand: the kernel indexes the global reduction
                # position, not the in-chunk offset
                local = ax.offset_at({**rho, rg.dim: point[rg.dim]}) - g.base[j]
            elif ax.pure_dim is not None:
                local = (ax.offset_at(rho) - g.base[j]) + ax.stride * point[ax.pure_dim]
            else:
                local = ax.offset_at(rho) - g.base[j]  # squeezed static index
            t = block_idx[j] * block_shape[j] + local  # block -> view coordinate
            elem.append(slices[j].start + (slices[j].step or 1) * t)
        return tuple(elem)

    def _ring_of(
        self, load_idx: int, rho: Mapping[str, int]
    ) -> Optional[Tuple[int, int]]:
        sp = self.kg.output
        if not sp.ring_binding:
            return None
        if self.kg.lane_grid is not None:
            return sp.ring_binding[load_idx].get(
                self._bind_key(load_idx, rho)
            )
        la = sp.accesses[load_idx]
        j0 = sp.blocked_axis_of[load_idx]
        key = (0, la.axes[j0].offset_at(rho)) if j0 is not None else (0, None)
        return sp.ring_binding[load_idx].get(key)

    def delivered_interval(
        self, load_idx: int, axis_j: int, grid_step: int,
        rho: Mapping[str, int], lane_step: int = 0,
    ) -> Tuple[int, int, int]:
        """(lo, hi, step) of producer elements available in VMEM on
        ``axis_j`` at ``grid_step`` (and, for lane-blocked kernels,
        ``lane_step``) for this load: the BlockSpec's delivered block, or
        the ring's coverage for ring-delivered taps."""
        if self.kg.fused:
            raise NotImplementedError("delivered_interval covers unfused kernels only")
        if self.kg.batch_grid is not None:
            raise NotImplementedError(
                "delivered_interval addresses per-tile delivery; batched "
                "kernels replicate it per slot"
            )
        rg = self.kg.red_grid
        rho_l = dict(rho)
        if rg is not None and rg.dim in rho_l:
            rho_l[rg.dim] = rho[rg.dim] % rg.chunk
        ring_hit = self._ring_of(load_idx, rho_l)
        if ring_hit is not None:
            ring = self.kg.rings[ring_hit[0]]
            if ring.lane:
                if axis_j == ring.axis:
                    lo = ring.lo + ring.stride0 * lane_step * self.kg.bw
                    hi = ring.lo + ring.stride0 * (
                        lane_step * self.kg.bw + self.kg.bw + ring.halo - 1
                    )
                    return lo, hi, ring.stride0
                if axis_j == ring.row_axis:
                    lo = ring.row_k0 + ring.row_stride * grid_step * self.bh
                    return (
                        lo, lo + ring.row_stride * (self.bh - 1),
                        ring.row_stride,
                    )
                return (
                    ring.base[axis_j],
                    ring.base[axis_j] + ring.span[axis_j] - 1, 1,
                )
            if axis_j == ring.axis:
                lo = ring.lo + ring.stride0 * grid_step * self.bh
                hi = ring.lo + ring.stride0 * (
                    grid_step * self.bh + self.bh + ring.halo - 1
                )
                return lo, hi, ring.stride0
            return ring.base[axis_j], ring.base[axis_j] + ring.span[axis_j] - 1, 1
        g = self._group_of(load_idx, rho_l)
        if axis_j == g.blocked_axis:
            lo = g.k0 + g.stride0 * grid_step * self.bh
            return lo, lo + g.stride0 * (self.bh - 1), g.stride0
        if axis_j == g.lane_axis:
            lo = g.l0 + g.lane_stride * lane_step * self.kg.bw
            return lo, lo + g.lane_stride * (self.kg.bw - 1), g.lane_stride
        if axis_j == g.red_axis:
            if g.resident:
                return g.base[axis_j], g.base[axis_j] + g.span[axis_j] - 1, 1
            lo = (rho[rg.dim] // rg.chunk) * rg.chunk
            return lo, lo + rg.chunk - 1, 1
        return g.base[axis_j], g.base[axis_j] + g.span[axis_j] - 1, 1


class _BoundParams:
    """A jitted kernel with its parameters bound: called, or lowered, with
    the per-request buffers alone, as a kernel without parameters is.  The
    parameters are the device arrays the pipeline uploaded once; lowering
    describes them on the per-request buffers' placement, so
    ``lower(buffers)`` lowers the executable that calls run."""

    def __init__(self, jitted, params: Tuple[jax.Array, ...]):
        self.jitted = jitted
        self.params = params

    def __call__(self, arrays):
        return self.jitted(arrays, self.params)

    def lower(self, arrays):
        where = getattr(arrays[0], "sharding", None) if arrays else None
        params = tuple(
            jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=where)
            for p in self.params
        )
        return self.jitted.lower(arrays, params)


def emit_kernel(
    kg: KernelGroup,
    *,
    interpret: bool = True,
    mode: Optional[str] = None,
    vmem_budget: int = VMEM_BYTES,
    params: Optional[Mapping[str, jax.Array]] = None,
) -> CompiledKernel:
    """Emit one executable ``pallas_call`` from a planned kernel group.
    All shape information (and its bounds validation) lives in the plan.

    ``mode`` (when given) supersedes ``interpret``: ``"interpret"`` |
    ``"compiled"`` | ``"auto"`` (see :func:`resolve_mode`).  The emitted
    closure is wrapped in ``jax.jit``, so repeated calls with same-shaped
    buffers reuse the first call's trace — binding new buffers to an
    already-emitted kernel is cheap (the plan/emit/bind split).

    ``vmem_budget`` is the budget the plan was made under; a compiled
    kernel is granted exactly that much VMEM (``vmem_limit_bytes``), so
    the budget rule the verifier certifies (UB402) is the limit Mosaic
    enforces, not the chip's smaller default scoped limit.

    ``params`` maps each parameter the kernel reads to its device array
    (in the plan's layout); the jitted program binds them, so it is called
    and lowered with the per-request buffers of ``buffer_order`` alone."""
    if mode is not None:
        mode = resolve_mode(mode)
        interpret = mode != "compiled"
    else:
        mode = "interpret" if interpret else "compiled"
    ctxs = {sp.name: _StageCtx(kg, sp) for sp in kg.stages}
    segments = {} if kg.lane_grid is not None else _lane_segments(kg)
    for ctx in ctxs.values():
        ctx.lane_segments = segments
    scratch_entries = kg.scratch_entries()
    n_groups = len(kg.groups)
    n_grid = len(kg.grid)
    out_sp = kg.output
    out_ctx = ctxs[out_sp.name]
    rg = kg.red_grid
    lane = kg.lane_grid is not None
    # batch grid: dim 0 sweeps batch slots (slowest-varying), the per-tile
    # structural dims shift right by bofs.  Because the row step cycles
    # once per slot, every ``i0 == 0`` warm-up below re-fires at each batch
    # boundary — the ring-reset rule falls out of the grid ordering
    bg = kg.batch_grid
    bofs = kg.bofs
    n_base = n_grid - bofs

    def kernel(*args):
        refs = args[:n_groups]
        out_ref = args[n_groups]
        pos = n_groups + 1
        scratch: Dict[object, object] = {}
        for (sp, key), ref in zip(scratch_entries, args[pos:pos + len(scratch_entries)]):
            scratch[(sp.name, key)] = ref
        pos += len(scratch_entries)
        for r_idx, ref in enumerate(args[pos:pos + len(kg.rings)]):
            scratch[(_RING, r_idx)] = ref
        bh = kg.bh
        i0 = pl.program_id(bofs)
        # grid dim 1+bofs is the reduction chunk *or* the lane block, never
        # both (the reduction chunk stays the last — fastest-varying — dim)
        kprog = pl.program_id(n_grid - 1) if rg is not None else 0
        jprog = pl.program_id(1 + bofs) if lane else 0
        stepb = pl.program_id(0) if bg is not None else 0
        for ctx in ctxs.values():
            ctx.step0 = i0
            ctx.stepk = kprog
            ctx.stepj = jprog
        # under a grid reduction the reduction chunk (last grid dim) varies
        # fastest: ring maintenance must run once per row panel, on chunk 0
        kfirst = kprog == 0 if rg is not None else None

        def _guard(cond):
            return cond if kfirst is None else jnp.logical_and(cond, kfirst)

        def _carry_guards(reset: bool):
            """(rotate, warm-up) conditions for a cross-grid-step ring.

            ``reset=True`` (the only planned value): the bare row step —
            with the batch dim leading, ``i0`` cycles per slot, so the
            warm-up re-fires at every batch boundary and no carried rows
            cross it.  ``reset=False`` exists only for seeded corruption
            plans: it emits the genuinely wrong global variant (one warm-up
            on the very first grid step, rotation everywhere else), which
            carries the previous tile's rows into the next slot — the bug
            verify rule UB502 rejects statically."""
            if bg is None or reset:
                return i0 > 0, i0 == 0
            return (
                jnp.logical_or(i0 > 0, stepb > 0),
                jnp.logical_and(i0 == 0, stepb == 0),
            )

        def _lane_carry_guards(reset: bool):
            """(rotate, warm-up) conditions for a *column* ring.  The lane
            dim varies fastest, so ``jprog == 0`` recurs at the first lane
            step of every row step — and hence of every batch slot: the
            per-row-sweep warm-up subsumes the batch reset.  ``reset=False``
            (seeded corruption only) emits the genuinely wrong global
            variant — one warm-up on the very first grid step, rotation
            everywhere else — which carries the previous row sweep's (and
            previous tile's) columns forward; rejected statically by rules
            UB205/UB502."""
            if reset:
                return jprog > 0, jprog == 0
            first = jnp.logical_and(i0 == 0, jprog == 0)
            if bg is not None:
                first = jnp.logical_and(first, stepb == 0)
            return jnp.logical_not(first), first

        def _lane_slice(ndim: int, axis: int, lo: int, hi: int):
            return tuple(
                slice(lo, hi) if j == axis else slice(None)
                for j in range(ndim)
            )

        # input delivery rings: rotate the carried halo, land the new block
        for r_idx, ring in enumerate(kg.rings):
            ref = scratch[(_RING, r_idx)]
            halo = ring.halo
            if ring.lane:
                # column ring: rotate/warm on the *lane* axis once per lane
                # step, land the steady bw-wide block unconditionally (lane
                # grids exclude reduction grids, so no chunk guard applies)
                rot_c, warm_c = _lane_carry_guards(ring.batch_reset)
                bw = kg.bw
                head = _lane_slice(ring.ndim, ring.axis, 0, halo)
                tail = _lane_slice(ring.ndim, ring.axis, bw, bw + halo)
                body = _lane_slice(ring.ndim, ring.axis, halo, halo + bw)

                @pl.when(rot_c)
                def _lcarry(ref=ref, head=head, tail=tail):
                    ref[head] = ref[tail]

                @pl.when(warm_c)
                def _lwarmup(ref=ref, head=head, pi=ring.prefix):
                    ref[head] = refs[pi][...]

                ref[body] = refs[ring.steady][...]
                continue
            rot_c, warm_c = _carry_guards(ring.batch_reset)

            @pl.when(_guard(rot_c))
            def _carry(ref=ref, halo=halo):
                ref[0:halo] = ref[bh:bh + halo]

            @pl.when(_guard(warm_c))
            def _warmup(ref=ref, halo=halo, pi=ring.prefix):
                ref[0:halo] = refs[pi][...]

            if kfirst is None:
                ref[halo:halo + bh] = refs[ring.steady][...]
            else:
                @pl.when(kfirst)
                def _steady(ref=ref, halo=halo, si=ring.steady):
                    ref[halo:halo + bh] = refs[si][...]

        # fused intermediates, topo order: a line-buffered stage rotates its
        # ring and computes exactly bh new rows (the shift-hi panel), with a
        # one-time halo warm-up on step 0; a recompute-mode stage evaluates
        # one panel per demanded shift
        for sp, key in scratch_entries:
            ctx = ctxs[sp.name]
            if isinstance(key, tuple) and key[1] is None:
                # lane line buffer: one column ring per demanded row shift,
                # rotated per lane step; lane step 0 of every row step
                # warm-fills the halo columns (a partial-*width* panel at
                # the lane shift ``lo``), every lane step computes the
                # bw-wide leading-edge panel at lane shift ``hi``
                lb = sp.line_buffer
                halo = lb.halo
                ref = scratch[(sp.name, key)]
                nd = len(ctx.block_shape)
                rot_c, warm_c = _lane_carry_guards(lb.batch_reset)
                bw = kg.bw
                head = _lane_slice(nd, nd - 1, 0, halo)
                tail = _lane_slice(nd, nd - 1, bw, bw + halo)
                body = _lane_slice(nd, nd - 1, halo, halo + bw)

                @pl.when(rot_c)
                def _lrotate(ref=ref, head=head, tail=tail):
                    ref[head] = ref[tail]

                pctx = ctx.with_cols(halo)

                @pl.when(warm_c)
                def _lwarm(
                    ref=ref, pctx=pctx, s=key[0], lo=lb.lo, head=head
                ):
                    ref[head] = _stage_panel(
                        pctx, refs, scratch, s, lo, when="lane0"
                    )

                ref[body] = _stage_panel(ctx, refs, scratch, key[0], lb.hi)
            elif isinstance(key, tuple):
                # lane-blocked recompute panel at (row shift, lane shift)
                scratch[(sp.name, key)][...] = _stage_panel(
                    ctx, refs, scratch, key[0], key[1]
                )
            elif key is None:
                lb = sp.line_buffer
                halo = lb.halo
                ref = scratch[(sp.name, None)]
                rot_c, warm_c = _carry_guards(lb.batch_reset)

                @pl.when(rot_c)
                def _rotate(ref=ref, halo=halo):
                    ref[0:halo] = ref[bh:bh + halo]

                pctx = ctx.with_rows(halo)

                @pl.when(warm_c)
                def _warm(ref=ref, pctx=pctx, lo=lb.lo, halo=halo):
                    ref[0:halo] = _stage_panel(
                        pctx, refs, scratch, lo, when="step0"
                    )

                ref[halo:halo + bh] = _stage_panel(ctx, refs, scratch, lb.hi)
            else:
                scratch[(sp.name, key)][...] = _stage_panel(ctx, refs, scratch, key)
        ns = out_sp.nstage
        if rg is not None:
            # grid-level reduction: accumulate into the revisited output
            # block, element update order identical to the unrolled path
            k = kprog
            init = _emit(ns.init, out_ctx, refs, scratch, {}, 0, [0])
            mask = out_ctx.panel_mask()

            @pl.when(k == 0)
            def _init():
                blk = jnp.broadcast_to(
                    jnp.asarray(init, jnp.float32), out_ctx.block_shape
                )
                if mask is not None:
                    blk = jnp.where(mask, blk, 0.0)
                out_ref[...] = blk.astype(out_ref.dtype)

            for combo in itertools.product(*out_ctx.red_ranges()):
                rho = dict(zip(ns.red_dims, combo))
                term = _emit(ns.value, out_ctx, refs, scratch, rho, 0, [0])
                term = jnp.broadcast_to(
                    jnp.asarray(term, jnp.float32), out_ctx.block_shape
                )
                if rg.padded:
                    # masked K-tail: a term whose global reduction index
                    # reaches the true extent reads padded (undefined)
                    # chunk elements — force it to contribute exactly zero
                    term = jnp.where(
                        k * rg.chunk + rho[rg.dim] < rg.extent, term, 0.0
                    )
                if mask is not None:
                    term = jnp.where(mask, term, 0.0)
                out_ref[...] += term
        else:
            out_ref[...] = _stage_panel(out_ctx, refs, scratch, 0).astype(
                out_ref.dtype
            )
        # drop the hoisted grid-position tracers: the ctxs outlive the trace
        # (they hang off the CompiledKernel), and retaining tracers would
        # pin the trace's object graph and leak into later introspection
        for ctx in ctxs.values():
            ctx.step0 = 0
            ctx.stepk = 0
            ctx.stepj = 0

    # under a batch grid every spec gains a leading size-None batch block:
    # Pallas squeezes the unit batch dim away, so the kernel body sees
    # refs shaped exactly as in the unbatched plan — the whole batched
    # emission reduces to program-id offsets plus these spec wrappers
    def _batch_spec(block_shape, index_map):
        if bg is None:
            return pl.BlockSpec(block_shape, index_map)
        return pl.BlockSpec(
            (None,) + tuple(block_shape),
            lambda b, *idx, f=index_map: (b,) + tuple(f(*idx)),
        )

    def _block(gi: int, g: ViewGroup) -> Tuple[int, ...]:
        blk = g.block_shape(kg.bh, kg.bw)
        if gi in segments:
            stride, starts = segments[gi]
            blk = blk[:-1] + (len(starts) * _segment_width(blk[-1], stride),)
        return blk

    # a parameter has no batch dim: the kernel sees its whole block
    in_specs = [
        pl.BlockSpec(
            ((None,) if bg is not None and not g.param else ())
            + tuple(_block(gi, g)),
            kg.view_index_map(gi),
        )
        for gi, g in enumerate(kg.groups)
    ]
    out_nd = len(out_ctx.block_shape)
    if n_base == 1:
        out_index = lambda i, nd=out_nd: (i,) + (0,) * (nd - 1)
    elif lane:
        out_index = lambda i, j, nd=out_nd: (i,) + (0,) * (nd - 2) + (j,)
    else:
        out_index = lambda i, k, nd=out_nd: (i,) + (0,) * (nd - 1)
    out_spec = _batch_spec(out_ctx.block_shape, out_index)
    out_extents = tuple(out_sp.nstage.pure_extents)
    if bg is not None:
        out_extents = (bg.steps,) + out_extents
    out_shape = jax.ShapeDtypeStruct(out_extents, jnp.float32)
    call_kwargs: Dict[str, object] = {}
    if not interpret:
        call_kwargs["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=vmem_budget
        )
    if scratch_entries or kg.rings:
        call_kwargs["scratch_shapes"] = [
            pltpu.VMEM(sp.scratch_shape(kg.bh, key), jnp.float32)
            for sp, key in scratch_entries
        ] + [
            pltpu.VMEM(r.ring_shape(kg.bh, kg.bw), jnp.float32)
            for r in kg.rings
        ]
    e0 = kg.e0
    e1 = kg.e1

    # one buffer slot per distinct producer: the jitted closure takes the
    # backing arrays positionally and carves every planned view inside the
    # trace, so re-binding new buffers hits the jit cache (no re-trace)
    buffer_order: List[str] = []
    param_order: List[str] = []
    for g in kg.groups:
        order = param_order if g.param else buffer_order
        if g.buffer not in order:
            order.append(g.buffer)
    slot_of = {b: i for i, b in enumerate(buffer_order)}
    param_slot = {b: i for i, b in enumerate(param_order)}
    missing = set(param_order) - set(params or {})
    if missing:
        raise ValueError(
            f"kernel {out_sp.name!r}: no device array for parameter(s) "
            f"{sorted(missing)}"
        )

    # batched arrays are stacked (capacity, *buffer); the per-tile view
    # slices apply past the untouched batch dim
    lead = (slice(None),) if bg is not None else ()

    # a stable name for the trace: the module reads ``jit_ub_<kernel>``
    # and the Mosaic kernel ``ub_<kernel>``, whatever the code's fingerprint
    safe = re.sub(r"[^A-Za-z0-9_]", "_", out_sp.name)
    stable = "ub_" + safe

    def _invoke(arrays, bound=()):
        views = [
            bound[param_slot[g.buffer]] if g.param
            else jnp.asarray(arrays[slot_of[g.buffer]], jnp.float32)[
                lead + g.view_slices(e0, e1)
            ]
            for g in kg.groups
        ]
        views = [
            _segmented(v, *segments[gi]) if gi in segments else v
            for gi, v in enumerate(views)
        ]
        return pl.pallas_call(
            kernel,
            grid=kg.grid,
            in_specs=in_specs,
            out_specs=out_spec,
            out_shape=out_shape,
            interpret=interpret,
            name=stable,
            **call_kwargs,
        )(*views)

    _invoke.__name__ = _invoke.__qualname__ = stable
    jitted = jax.jit(_invoke)
    if param_order:
        jitted = _BoundParams(jitted, tuple(params[b] for b in param_order))

    def call(buffers: Mapping[str, jax.Array]) -> jax.Array:
        # emission and lowering work anywhere (a compiled kernel can be
        # lowered for a described TPU); only execution needs the chip
        if not interpret and jax.default_backend() != "tpu":
            raise RuntimeError(
                f"kernel {out_sp.name!r}: backend mode 'compiled' runs real "
                f"Mosaic kernels and needs a TPU jax backend; "
                f"default_backend() is {jax.default_backend()!r}.  Use "
                f"mode='auto' to fall back to interpret mode off-TPU."
            )
        kg.validate_buffers(buffers)
        return jitted(tuple(buffers[b] for b in buffer_order))

    return CompiledKernel(
        name=out_sp.name,
        kg=kg,
        nstage=out_sp.nstage,
        plan=kg.ub_plan(),
        _call=call,
        mode=mode,
        jitted=jitted,
        buffer_order=tuple(buffer_order),
        span=tracing.KERNEL + safe,
    )


def compile_stage(
    nstage: NormalizedStage,
    buffer_shapes: Mapping[str, Tuple[int, ...]],
    *,
    interpret: bool = True,
    mode: Optional[str] = None,
    block_h: Optional[int] = None,
    block_w: Optional[int] = None,
    vmem_budget: int = VMEM_BYTES,
    grid_reduction: bool = False,
    red_grid_threshold: int = RED_GRID_THRESHOLD,
    cost_model: str = "scheduler",
    line_buffer: object = "auto",
    red_resident: bool = True,
) -> CompiledKernel:
    """Compile one normalized stage to a Pallas kernel (plan + emit)."""
    from repro.frontend.expr import refs_in

    if nstage.init is not None and refs_in(nstage.init):
        raise UnsupportedAccessError(
            f"{nstage.name}: reduction init with buffer reads is not supported"
        )
    accesses = decompose_stage(nstage)
    streamed = _stream_ok(accesses, nstage.pure_dims[0])
    kg = _build_kernel_group(
        [(nstage, accesses, streamed)],
        buffer_shapes,
        block_h=block_h,
        block_w=block_w,
        vmem_budget=vmem_budget,
        cost_model=cost_model,
        grid_reduction=grid_reduction,
        red_grid_threshold=red_grid_threshold,
        line_buffer=line_buffer,
        red_resident=red_resident,
    )
    return emit_kernel(
        kg, interpret=interpret, mode=mode, vmem_budget=vmem_budget
    )


# pre-refactor name: a single-stage CompiledKernel is the old CompiledStage
CompiledStage = CompiledKernel

__all__ = [
    "CompiledKernel",
    "CompiledStage",
    "ViewGroup",
    "compile_stage",
    "emit_kernel",
    "eval_trace",
    "resolve_mode",
]
