"""Serve-bridge suite: batched pipelines behind fixed serve slots.

``PipelineServer`` packs queued tiles into full-capacity batched dispatches
(one ``pallas_call`` sweep per kernel group per batch) and pads the ragged
tail with zero tiles it discards (``serve_bridge.pad_to_slots``, the
pad-and-discard slot discipline ``ServeEngine`` also applies to decode
requests).  These tests pin the bridge's contract: slot
packing and drain order, bit-exactness of every served tile against the
per-tile loop (ragged final dispatch included), request validation, and the
cache/dispatch observability counters the serve benchmark reports.
"""

import numpy as np
import pytest

from conftest import SWEEP_SEED, sweep_inputs
from repro.apps.paper_apps import make_app
from repro.backend import (
    PipelineServer,
    TileRequest,
    clear_pipeline_cache,
    compile_pipeline,
    pipeline_cache_stats,
)
from repro.backend.serve_bridge import pad_to_slots

pytestmark = pytest.mark.serve


def _tiles(app, n, seed=SWEEP_SEED):
    return [
        sweep_inputs(app, seed + i, "u4") for i in range(n)
    ]


def test_ragged_queue_bit_exact_and_in_order():
    """Seven tiles through four slots: two dispatches (4 + ragged 3),
    every tile's every materialized buffer bit-equal to the per-tile
    pipeline, results returned in submission order."""
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=4, block_h=4)
    tiles = _tiles(app, 7)
    done = srv.run(tiles)
    assert len(done) == 7 and all(r.done for r in done)
    assert srv.dispatches == 2 and srv.served == 7
    ptp = compile_pipeline(app.pipeline, block_h=4)
    for req, tile in zip(done, tiles):
        ref = ptp.run(tile)
        for ck in ptp.kernels:
            assert np.array_equal(req.outputs[ck.name], np.asarray(ref[ck.name]))


def test_carried_line_buffer_across_dispatches():
    """A line-buffered (carried) pipeline served batched: ring warm-ups
    reset per slot, so no request's output depends on its slot neighbours
    or on earlier dispatches."""
    app = make_app("unsharp", size=15)
    ckw = dict(fuse=True, block_h=5, line_buffer=True)
    srv = PipelineServer(app.pipeline, batch_slots=3, **ckw)
    tiles = _tiles(app, 8)
    done = srv.run(tiles)
    ptp = compile_pipeline(app.pipeline, **ckw)
    out = app.pipeline.output
    for req, tile in zip(done, tiles):
        assert np.array_equal(
            req.outputs[out], np.asarray(ptp.run(tile)[out])
        )
    # serve the same tiles again in a different order: identical outputs
    # (no cross-dispatch state)
    redo = srv.run(list(reversed(tiles)))
    for req, prev in zip(redo, reversed(done)):
        assert np.array_equal(req.outputs[out], prev.outputs[out])


def test_step_packs_up_to_capacity():
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=4, block_h=4)
    for t in _tiles(app, 6):
        srv.submit(t)
    first = srv.step()
    assert len(first) == 4 and len(srv.pending) == 2
    second = srv.step()
    assert len(second) == 2
    assert srv.step() == []              # empty queue: no dispatch
    assert srv.dispatches == 2


def test_submit_validates_inputs():
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=2, block_h=4)
    with pytest.raises(KeyError, match="missing input"):
        srv.submit({})
    with pytest.raises(ValueError, match="tile shape"):
        srv.submit({"input": np.zeros((3, 3), np.float32)})
    with pytest.raises(ValueError, match="batch_slots"):
        PipelineServer(app.pipeline, batch_slots=0)


def test_mixed_shape_dispatch_preserves_drain_order():
    """One server, two registered tile shapes: submit() routes each request
    by its input shapes, step() dispatches the longest same-shape run at
    the head of the queue, and the drain completes every request in
    submission order — bit-exact against each shape's own per-tile
    pipeline."""
    small = make_app("gaussian", size=13)
    large = make_app("gaussian", size=21)
    srv = PipelineServer(small.pipeline, batch_slots=3, block_h=4)
    srv.register(large.pipeline, block_h=4)
    assert srv.stats()["shapes"] == 2

    # interleaved traffic: S S L L S  (runs: [S,S], [L,L], [S])
    tiles = _tiles(small, 2) + _tiles(large, 2, seed=SWEEP_SEED + 50) \
        + _tiles(small, 1, seed=SWEEP_SEED + 90)
    submitted = [srv.submit(t) for t in tiles]

    order = []
    while srv.pending:
        for req in srv.step():
            order.append(req)
    assert order == submitted          # completion order == submission order
    assert srv.dispatches == 3         # [S,S], [L,L], [S] — no shape mixing
    assert srv.served == 5

    ref_small = compile_pipeline(small.pipeline, block_h=4)
    ref_large = compile_pipeline(large.pipeline, block_h=4)
    out = small.pipeline.output
    for req, tile, ref in zip(
        submitted, tiles,
        [ref_small, ref_small, ref_large, ref_large, ref_small],
    ):
        assert np.array_equal(req.outputs[out], np.asarray(ref.run(tile)[out]))

    # an unregistered third shape is still rejected by name
    other = make_app("gaussian", size=17)
    with pytest.raises(ValueError, match="tile shape"):
        srv.submit(_tiles(other, 1)[0])


def test_pad_to_slots_contract():
    fillers = []

    def filler():
        fillers.append(object())
        return fillers[-1]

    reqs = ["a", "b"]
    padded = pad_to_slots(reqs, 4, filler)
    assert padded[:2] == reqs and padded[2:] == fillers
    assert pad_to_slots(reqs, 2, filler) == reqs
    with pytest.raises(ValueError, match="exceed"):
        pad_to_slots(["a", "b", "c"], 2, filler)


def test_server_reports_cache_stats():
    """The bridge's stats() merges its own serving counters with the
    process-wide pipeline-cache counters — one miss for the server's own
    full-capacity compile, hits for later same-capacity servers."""
    clear_pipeline_cache(reset_stats=True)
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=3, block_h=4)
    srv.run(_tiles(app, 4))
    s = srv.stats()
    assert s["served"] == 4 and s["dispatches"] == 2
    assert s["batch_slots"] == 3
    assert s["misses"] == 1 and s["entries"] == 1 and s["hits"] == 0
    # a second server at the same capacity reuses the cached pipeline
    srv2 = PipelineServer(app.pipeline, batch_slots=3, block_h=4)
    assert srv2.pipeline is srv.pipeline
    assert pipeline_cache_stats()["hits"] == 1


def test_cache_key_includes_batch_kwargs():
    """The bugfix this PR carries: batch/batch_capacity are part of the
    plan cache key, so per-tile and batched compiles (or two capacities)
    never collide in the cache."""
    clear_pipeline_cache(reset_stats=True)
    app = make_app("gaussian", size=13)
    a = compile_pipeline(app.pipeline, block_h=4, cache=True)
    b = compile_pipeline(app.pipeline, block_h=4, cache=True, batch=3)
    c = compile_pipeline(
        app.pipeline, block_h=4, cache=True, batch=3, batch_capacity=4
    )
    assert len({a.cache_key, b.cache_key, c.cache_key}) == 3
    stats = pipeline_cache_stats()
    assert stats["misses"] == 3 and stats["entries"] == 3
    again = compile_pipeline(app.pipeline, block_h=4, cache=True, batch=3)
    assert again is b
    assert pipeline_cache_stats()["hits"] == 1
    clear_pipeline_cache(reset_stats=True)
    stats = pipeline_cache_stats()
    assert stats == {"hits": 0, "misses": 0, "evictions": 0, "entries": 0}


def test_filler_slots_never_escape():
    """Filler requests exist only inside a dispatch: callers get exactly
    their own requests back, and a TileRequest row marked filler is never
    among them."""
    app = make_app("matmul", m=16, n=16, k=16)
    srv = PipelineServer(app.pipeline, batch_slots=4)
    tiles = _tiles(app, 5)
    done = srv.run(tiles)
    assert len(done) == 5
    assert not any(r.filler for r in done)
    assert all(isinstance(r, TileRequest) for r in done)
    a0, b0 = tiles[0]["A"], tiles[0]["B"]
    want = a0.astype(np.float64) @ b0.astype(np.float64)
    assert np.array_equal(done[0].outputs["matmul"].astype(np.float64), want)
