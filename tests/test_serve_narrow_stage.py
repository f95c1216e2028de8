"""Inputs cross to the device at the dtype they arrived in.

``PipelineServer._dispatch`` stacks a batch at its live slots' common
dtype where float32 holds it exactly (``runner.stage_dtype``), filler
slots included, and ``PallasPipeline.run`` widens it to float32 on the
device (``ub_widen``) before the first kernel, shipped flat.  Widening
is exact, so a
uint8 frame gives the same output bits as the same frame sent as float32.
Wider dtypes (int32, int64, float64) are cast on the host as before.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import SWEEP_SEED, sweep_inputs
from repro.apps.paper_apps import make_app
from repro.backend import (
    PipelineServer,
    PoisonedTileError,
    compile_pipeline,
    faults,
    serve_bridge,
)
from repro.backend.runner import stage_dtype, ub_widen

pytestmark = pytest.mark.serve

SLOTS = 4


def _u8(app, seed):
    rng = np.random.default_rng(seed)
    return {n: rng.integers(0, 256, size=shape, dtype=np.uint8)
            for n, shape in app.input_extents.items()}


def _as(tile, dtype):
    return {n: np.asarray(a, dtype) for n, a in tile.items()}


@pytest.fixture(scope="module")
def gaussian():
    app = make_app("gaussian", size=13)
    return app, PipelineServer(app.pipeline, batch_slots=SLOTS, block_h=4)


@pytest.fixture(scope="module")
def ref(gaussian):
    app, _srv = gaussian
    return compile_pipeline(app.pipeline, block_h=4)


def _capture(srv):
    """Wrap the dispatch seam so each dispatch's stacked inputs are kept."""
    seen = []
    real = srv._run_pipeline

    def _wrapped(pp, ins):
        seen.append({n: np.array(a, copy=True) for n, a in ins.items()})
        return real(pp, ins)

    srv._run_pipeline = _wrapped
    return seen


def _restore(srv):
    srv.__dict__.pop("_run_pipeline", None)


@pytest.mark.parametrize("dtype, want", [
    (np.uint8, np.uint8), (np.uint16, np.uint16), (np.int8, np.int8),
    (np.int16, np.int16), (np.bool_, np.bool_), (np.float16, np.float16),
    (np.float32, np.float32), (np.int32, np.float32),
    (np.int64, np.float32), (np.float64, np.float32),
])
def test_stage_dtype_keeps_what_float32_holds_exactly(dtype, want):
    assert stage_dtype([np.dtype(dtype)]) == np.dtype(want)


def test_stage_dtype_takes_the_common_dtype_of_a_batch():
    assert stage_dtype([np.dtype(np.uint8)] * 3) == np.uint8
    assert stage_dtype([np.dtype(np.uint8), np.dtype(np.int8)]) == np.int16
    assert stage_dtype([np.dtype(np.uint8), np.dtype(np.float32)]) == np.float32
    assert stage_dtype([np.dtype(np.uint8), np.dtype(np.int32)]) == np.float32


@pytest.mark.parametrize("name, kwargs, ckw, slots", [
    ("gaussian", dict(size=13), dict(block_h=4), 4),
    ("camera", dict(size=16), {}, 2),
])
def test_uint8_frames_serve_bit_equal_to_float32(name, kwargs, ckw, slots):
    app = make_app(name, **kwargs)
    srv = PipelineServer(app.pipeline, batch_slots=slots, **ckw)
    tiles = [_u8(app, SWEEP_SEED + i) for i in range(slots + 1)]
    narrow = srv.run(tiles)
    assert srv.stats()["narrow_dispatches"] == srv.stats()["dispatches"] == 2
    wide = srv.run([_as(t, np.float32) for t in tiles])
    assert srv.stats()["narrow_dispatches"] == 2
    for a, b in zip(narrow, wide):
        assert a.ok and b.ok
        assert a.outputs.keys() == b.outputs.keys()
        for k in a.outputs:
            assert a.outputs[k].dtype == np.float32
            assert np.array_equal(a.outputs[k], b.outputs[k]), k


def test_bytes_to_device_count_uint8_filler_included(gaussian):
    """Six tiles through four slots: two dispatches, the second padded
    with three uint8 filler tiles, which cross at one byte a pixel."""
    app, srv = gaussian
    tiles = [_u8(app, SWEEP_SEED + i) for i in range(6)]
    before = srv.stats()
    srv.run(tiles)
    after = srv.stats()
    pixels = sum(int(np.prod(s)) for s in app.input_extents.values())
    assert after["bytes_to_device"] - before["bytes_to_device"] == 2 * SLOTS * pixels
    assert after["narrow_dispatches"] - before["narrow_dispatches"] == 2
    assert after["dispatches"] - before["dispatches"] == 2


def test_short_batch_filler_takes_the_live_dtype(gaussian):
    app, srv = gaussian
    seen = _capture(srv)
    try:
        tile = _u8(app, SWEEP_SEED)
        [req] = srv.run([tile])
    finally:
        _restore(srv)
    [ins] = seen
    for n, a in ins.items():
        assert a.dtype == np.uint8 and a.shape[0] == SLOTS
        assert np.array_equal(a[0], tile[n])
        assert not a[1:].any()
    assert req.ok


def test_uint8_and_float32_batch_stages_at_float32(gaussian, ref):
    app, srv = gaussian
    tiles = [_u8(app, SWEEP_SEED + i) for i in range(SLOTS)]
    tiles[2] = sweep_inputs(app, SWEEP_SEED + 2, "f32")
    seen = _capture(srv)
    before = srv.stats()["narrow_dispatches"]
    try:
        done = srv.run(tiles)
    finally:
        _restore(srv)
    [ins] = seen
    assert all(a.dtype == np.float32 for a in ins.values())
    assert srv.stats()["narrow_dispatches"] == before
    out = app.pipeline.output
    for req, tile in zip(done, tiles):
        assert np.array_equal(req.outputs[out],
                              np.asarray(ref.run(_as(tile, np.float32))[out]))


@pytest.mark.parametrize("dtype", [np.int32, np.float64])
def test_wide_requests_are_cast_on_the_host(gaussian, dtype):
    """int32 past 2**24 and float64 round on the host as they always did:
    the stack is float32, equal to the per-frame float32 cast."""
    app, srv = gaussian
    rng = np.random.default_rng(SWEEP_SEED)
    tiles = []
    for _ in range(SLOTS):
        if dtype is np.int32:
            t = {n: rng.integers(2**24, 2**24 + 64, size=s).astype(np.int32)
                 for n, s in app.input_extents.items()}
        else:
            t = {n: rng.uniform(-4.0, 4.0, size=s)
                 for n, s in app.input_extents.items()}
        tiles.append(t)
    seen = _capture(srv)
    before = srv.stats()["narrow_dispatches"]
    try:
        wide = srv.run(tiles)
    finally:
        _restore(srv)
    [ins] = seen
    for n, a in ins.items():
        assert a.dtype == np.float32
        assert np.array_equal(
            a, np.stack([np.asarray(t[n], np.float32) for t in tiles]))
    assert srv.stats()["narrow_dispatches"] == before
    cast = srv.run([_as(t, np.float32) for t in tiles])
    out = app.pipeline.output
    for a, b in zip(wide, cast):
        assert np.array_equal(a.outputs[out], b.outputs[out])


def test_marked_tile_is_isolated_among_uint8_tiles(gaussian, ref):
    """``mark_poison`` turns one tile float32 with a 2**60 marker: the
    batch stages at float32, the marker survives into the stack, and
    quarantine fails that tile alone."""
    app, srv = gaussian
    tiles = [_u8(app, SWEEP_SEED + i) for i in range(SLOTS)]
    faults.mark_poison(tiles[1])
    with faults.poison_output(srv):
        done = srv.run(tiles)
    out = app.pipeline.output
    for i, (req, tile) in enumerate(zip(done, tiles)):
        if i == 1:
            assert isinstance(req.error, PoisonedTileError)
        else:
            assert req.ok
            assert np.array_equal(
                req.outputs[out],
                np.asarray(ref.run(_as(tile, np.float32))[out]))


def test_nan_tile_is_isolated_among_uint8_tiles(ref):
    app = make_app("gaussian", size=13)
    srv = PipelineServer(app.pipeline, batch_slots=SLOTS, block_h=4,
                         validate="shape")
    tiles = [_u8(app, SWEEP_SEED + i) for i in range(SLOTS)]
    bad = faults.nan_input(tiles, frac=0.25, seed=7)
    assert len(bad) == 1
    assert all(a.dtype == np.float32 for a in tiles[bad[0]].values())
    done = srv.run(tiles)
    out = app.pipeline.output
    for i, (req, tile) in enumerate(zip(done, tiles)):
        if i in bad:
            assert isinstance(req.error, PoisonedTileError)
            assert "non-finite" in str(req.error)
        else:
            assert np.array_equal(
                req.outputs[out],
                np.asarray(ref.run(_as(tile, np.float32))[out]))
    assert srv.stats()["poisoned_tiles"] == 1


def test_float32_serving_counts_no_narrow_dispatch(gaussian):
    app, srv = gaussian
    before = srv.stats()
    srv.run([sweep_inputs(app, SWEEP_SEED + i, "u4") for i in range(SLOTS)])
    after = srv.stats()
    assert after["dispatches"] - before["dispatches"] == 1
    assert after["narrow_dispatches"] == before["narrow_dispatches"]


@pytest.mark.parametrize("batch, capacity", [(None, None), (2, 2), (2, 4)])
def test_pipeline_run_on_uint8_equals_the_float32_call(batch, capacity):
    """``PallasPipeline.run`` widens a uint8 input on the device, before
    any capacity padding, and returns the float32 call's bits."""
    app = make_app("gaussian", size=13)
    pp = compile_pipeline(app.pipeline, block_h=4, batch=batch,
                          batch_capacity=capacity)
    rng = np.random.default_rng(SWEEP_SEED)
    lead = (batch,) if batch else ()
    x = {n: rng.integers(0, 256, size=lead + tuple(s), dtype=np.uint8)
         for n, s in app.input_extents.items()}
    narrow = pp.run(x)
    wide = pp.run(_as(x, np.float32))
    assert narrow.keys() == wide.keys()
    for k in narrow:
        assert narrow[k].dtype == jnp.float32
        assert np.array_equal(np.asarray(narrow[k]), np.asarray(wide[k])), k


def test_pipeline_run_checks_the_shape_of_a_narrow_input():
    app = make_app("gaussian", size=13)
    pp = compile_pipeline(app.pipeline, block_h=4)
    [(n, s)] = app.input_extents.items()
    with pytest.raises(ValueError, match="declared extents"):
        pp.run({n: np.zeros(tuple(d + 1 for d in s), np.uint8)})


@pytest.mark.parametrize("dtype", [
    np.uint8, np.int8, np.uint16, np.int16, np.float16, np.bool_,
])
@pytest.mark.parametrize("shape", [(3, 8, 6), (3, 5)])
def test_widen_restores_every_narrow_dtype_exactly(dtype, shape):
    """Shipped flat and widened on the device, every narrow dtype equals
    the host's float32 cast."""
    rng = np.random.default_rng(SWEEP_SEED)
    if dtype is np.bool_:
        x = rng.integers(0, 2, size=shape).astype(np.bool_)
    elif dtype is np.float16:
        x = rng.uniform(-6e4, 6e4, size=shape).astype(np.float16)
    else:
        info = np.iinfo(dtype)
        x = rng.integers(info.min, info.max, size=shape, endpoint=True,
                         dtype=dtype)
    out = ub_widen(jax.device_put(x.reshape(-1)), x.shape)
    assert out.dtype == jnp.float32 and out.shape == x.shape
    assert np.array_equal(np.asarray(out), x.astype(np.float32))


def test_widen_is_its_own_module():
    """The widen compiles as ``jit_ub_widen``, so a trace names it."""
    flat = jnp.zeros((2 * 8 * 128,), jnp.uint8)
    text = ub_widen.lower(flat, (2, 8, 128)).as_text()
    assert "module @jit_ub_widen " in text


def test_dispatch_pins_room_for_its_host_buffers(gaussian, monkeypatch):
    """Each dispatch asks for heap room for what it staged and copied back."""
    app, srv = gaussian
    asked = []
    monkeypatch.setattr(serve_bridge, "pin_host_allocator",
                        lambda n: asked.append(n) or True)
    before = srv.stats()
    srv.run([_u8(app, 60 + i) for i in range(SLOTS)])
    after = srv.stats()
    assert asked == [
        after["bytes_to_device"] - before["bytes_to_device"]
        + after["bytes_from_device"] - before["bytes_from_device"]
    ]


def test_server_serves_where_the_pin_is_refused(gaussian, ref, monkeypatch):
    """Without glibc the pin returns False and serving goes on unchanged."""
    monkeypatch.setattr(serve_bridge, "pin_host_allocator", lambda n: False)
    app, srv = gaussian
    out = app.pipeline.output
    tiles = [_u8(app, 70 + i) for i in range(SLOTS)]
    for tile, req in zip(tiles, srv.run(tiles)):
        assert req.ok
        assert np.array_equal(
            req.outputs[out],
            np.asarray(ref.run(_as(tile, np.float32))[out]))


_TRIM_PROBE = """
import ctypes, json, platform
import numpy as np
from repro.backend.runner import pin_host_allocator

class Info(ctypes.Structure):
    _fields_ = [(f, ctypes.c_size_t) for f in (
        "arena ordblks smblks hblks hblkhd usmblks fsmblks uordblks "
        "fordblks keepcost").split()]

mallinfo2 = ctypes.CDLL(None).mallinfo2
mallinfo2.restype = Info
SIZES = (8 << 20,) + (20 << 20,) * 6    # 128 MiB, each below 32 MiB

def trims(n):
    # a dispatch: a stack and its outputs, written, then freed together
    count = 0
    for _ in range(n):
        bufs = [np.ones(k, np.uint8) for k in SIZES]
        top = mallinfo2().arena
        del bufs
        count += mallinfo2().arena < top
    return count

trims(2)                 # the first frees raise glibc's own thresholds
before = trims(20)
pinned = pin_host_allocator(sum(SIZES))
print(json.dumps({"glibc": platform.libc_ver()[0] == "glibc",
                  "before": before, "pinned": pinned, "after": trims(20)}))
"""


def test_pin_keeps_a_dispatchs_buffers_on_the_heap():
    """Unpinned, glibc's trim threshold is at most 64 MiB, so freeing a
    dispatch's 128 MiB of buffers hands memory back to the kernel every
    time; pinned for them, the heap keeps it.  Run in a fresh process: the
    pin is process-wide."""
    import json
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [src] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
    done = subprocess.run([sys.executable, "-c", _TRIM_PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr
    got = json.loads(done.stdout.strip().splitlines()[-1])
    assert got["glibc"], "the pin is glibc's; this host has another libc"
    assert got["pinned"] is True
    assert got["before"] == 20
    assert got["after"] == 0
