"""Serve four apps through ``compile_pipeline`` -> ``PipelineServer`` on one TPU.

The quickest proof that the compiler's served path still runs on the chip.
Each app is compiled in Mosaic (``mode="compiled"``) mode at frame size and
served by a 4-slot ``PipelineServer``: six integer requests, i.e. one full
dispatch and one ragged one.  Before serving, every emitted kernel is lowered
(its text must hold a ``tpu_custom_call``) and compiled, so a refusal by the
TPU compiler fails the run here instead of being quarantined by the server.
Every output is checked against a plain float64 numpy reference written in
this file; camera is also served at size 16 and checked against the repo's
reference interpreter (``execute_pipeline``).

    python chip_smoke.py [--seed N]

It fails, printing no result, where JAX finds no TPU.  The last line of a
passing run is one JSON object naming the device.  The frames/s it prints
come from one short warm pass: smoke readings, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings
from pathlib import Path
from typing import Dict, List

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BATCH_SLOTS = 4
N_REQUESTS = 6          # one full 4-slot dispatch, one ragged 2-slot one


# ---------------------------------------------------------------------------
# Plain references (float64, vectorised).  Arrays are in loop order: an
# image is indexed [y, x]; f[x, y] in the app DSL reads a[y, x].
# ---------------------------------------------------------------------------


def _window(a: np.ndarray, dy: int, dx: int, h: int, w: int) -> np.ndarray:
    return a[dy:dy + h, dx:dx + w]


def ref_gaussian(inp: np.ndarray) -> np.ndarray:
    """3x3 [1 2 1] x [1 2 1] blur over 16."""
    a = inp.astype(np.float64)
    h, w = a.shape[0] - 2, a.shape[1] - 2
    wts = [[1, 2, 1], [2, 4, 2], [1, 2, 1]]
    acc = sum(
        wts[dy][dx] * _window(a, dy, dx, h, w)
        for dy in range(3) for dx in range(3)
    )
    return acc / 16


def ref_harris(inp: np.ndarray) -> np.ndarray:
    """Sobel gradients, 3x3 box-summed structure tensor, response kept
    where it exceeds 100."""
    a = inp.astype(np.float64)
    n = a.shape[0] - 2                          # gradient extent

    def tap(dy, dx):
        return _window(a, dy, dx, n, n)

    gx = (-tap(0, 0) + tap(0, 2) - 2 * tap(1, 0) + 2 * tap(1, 2)
          - tap(2, 0) + tap(2, 2))
    gy = (-tap(0, 0) - 2 * tap(0, 1) - tap(0, 2)
          + tap(2, 0) + 2 * tap(2, 1) + tap(2, 2))
    m = n - 2

    def box(v):
        return sum(_window(v, dy, dx, m, m) for dy in range(3) for dx in range(3))

    sxx = box(gx * gx / 64)
    syy = box(gy * gy / 64)
    sxy = box(gx * gy / 64)
    resp = (sxx * syy - sxy * sxy) - (sxx + syy) ** 2 / 16
    return np.where(resp > 100, resp, 0.0)


def ref_camera(raw: np.ndarray) -> np.ndarray:
    """Hot-pixel clamp, GRBG demosaic, colour matrix and gamma; the output
    is indexed [y, yi, x, xi] (pixel (2y + yi, 2x + xi))."""
    a = raw.astype(np.float64)
    size = (a.shape[0] - 4) // 2
    e = a.shape[0] - 2                          # denoise extent
    c = _window(a, 1, 1, e, e)
    nbrs = [_window(a, 1, 0, e, e), _window(a, 1, 2, e, e),
            _window(a, 0, 1, e, e), _window(a, 2, 1, e, e)]
    dn = np.minimum(np.maximum(c, np.minimum.reduce(nbrs)), np.maximum.reduce(nbrs))

    def at(dx, dy):                             # dn[2x + dx, 2y + dy]
        return dn[dy:dy + 2 * size:2, dx:dx + 2 * size:2][:, None, :, None]

    yi = np.arange(2).reshape(1, 2, 1, 1)
    xi = np.arange(2).reshape(1, 1, 1, 2)

    def phase(px, py):
        tx = xi if px == 1 else 1 - xi
        ty = yi if py == 1 else 1 - yi
        return tx * ty

    g = (phase(0, 0) * at(0, 0) + phase(1, 1) * at(1, 1)
         + (phase(1, 0) + phase(0, 1)) * ((at(0, 0) + at(1, 1)) / 2))
    r = phase(1, 0) * at(1, 0) + (1 - phase(1, 0)) * ((at(1, 0) + at(3, 0)) / 2)
    b = phase(0, 1) * at(0, 1) + (1 - phase(0, 1)) * ((at(0, 1) + at(0, 3)) / 2)
    ccm_r = (r * 14 + g * 2 - b) / 16
    ccm_g = (r * -1 + g * 14 + b * 2) / 16
    ccm_b = (r * 2 - g + b * 14) / 16
    lum = (ccm_r * 5 + ccm_g * 9 + ccm_b * 2) / 16
    return np.minimum(np.maximum(lum + lum * lum / 256, 0), 255)


def ref_matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return a.astype(np.float64) @ b.astype(np.float64)


# app name -> (make_app kwargs, input value bound, reference, error bound
# as a function of the reference).  Gaussian is exact in f32 (integer MACs,
# a power-of-two divide).  Matmul on inputs below 16 is too (every partial
# sum is an integer below 2**24); it gets a bound only because the order
# of accumulation is the kernel's.  Harris cancels in the determinant, so
# its bound scales with the largest response; camera's gamma squares a
# non-integer, so it gets a small absolute bound on a 0..255 output.
APPS: Dict[str, tuple] = {
    "gaussian": (dict(size=1082, width=1922), 256,
                 lambda ins: ref_gaussian(ins["input"]), lambda ref: 0.0),
    "harris": (dict(schedule="sch3", size=1084), 256,
               lambda ins: ref_harris(ins["input"]),
               lambda ref: 1e-5 * float(np.max(np.abs(ref)))),
    "camera": (dict(size=540), 256, lambda ins: ref_camera(ins["raw"]),
               lambda ref: 1e-3),
    "matmul": (dict(m=1024, n=1024, k=1024), 16,
               lambda ins: ref_matmul(ins["A"], ins["B"]),
               lambda ref: 1e-6 * float(np.max(np.abs(ref)))),
}


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


class SmokeFailure(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAIL: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def make_requests(app, seed: int, bound: int) -> List[Dict[str, np.ndarray]]:
    rng = np.random.default_rng(seed)
    dtype = np.uint8 if bound <= 256 else np.uint16
    return [
        {n: rng.integers(0, bound, shp).astype(dtype)
         for n, shp in sorted(app.input_extents.items())}
        for _ in range(N_REQUESTS)
    ]


def aot_compile(pp, device) -> None:
    """Lower and compile every kernel of ``pp`` for ``device`` ahead of
    serving: the lowered text must hold the Mosaic kernel, and a compiler
    refusal raises here."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    cap = pp.plan.notes["batch_capacity"]
    on_chip = SingleDeviceSharding(device)
    for ck in pp.kernels:
        args = tuple(
            jax.ShapeDtypeStruct(
                (cap,) + tuple(pp.pipeline.buffer_boxes[b].extents),
                jnp.float32, sharding=on_chip,
            )
            for b in ck.buffer_order
        )
        lowered = ck.jitted.lower(args)
        check("tpu_custom_call" in lowered.as_text(),
              f"kernel {ck.name!r}: lowered text holds no tpu_custom_call")
        lowered.compile()


def serve(server, requests) -> tuple:
    t0 = time.perf_counter()
    done = server.run(requests)
    secs = time.perf_counter() - t0
    for i, req in enumerate(done):
        check(req.ok, f"request {i} failed: {req.error}")
    return done, secs


def check_server(name: str, server) -> None:
    stats = server.stats()
    check(stats["failed"] == 0, f"{name}: {stats['failed']} request(s) failed")
    faults = {k: v for k, v in server.fault_counters.items() if v}
    check(not faults, f"{name}: fault counters {faults}")
    modes = {server.pipeline.mode} | {ck.mode for ck in server.pipeline.kernels}
    check(modes == {"compiled"}, f"{name}: modes {sorted(modes)}")


def smoke_app(name: str, seed: int, device) -> None:
    from repro.apps.paper_apps import make_app
    from repro.backend import PipelineServer

    app_kw, bound, reference, err_bound = APPS[name]
    app = make_app(name, **app_kw)
    t0 = time.perf_counter()
    server = PipelineServer(app.pipeline, batch_slots=BATCH_SLOTS, mode="compiled")
    plan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    aot_compile(server.pipeline, device)
    compile_s = time.perf_counter() - t0
    requests = make_requests(app, seed, bound)
    done, cold_s = serve(server, requests)
    max_err = 0.0
    for i, (req, ins) in enumerate(zip(done, requests)):
        ref = reference(ins)
        got = req.outputs[app.pipeline.output]
        check(got.shape == ref.shape,
              f"{name} request {i}: shape {got.shape} != {ref.shape}")
        check(bool(np.isfinite(got).all()), f"{name} request {i}: non-finite")
        err = float(np.max(np.abs(got.astype(np.float64) - ref)))
        bound_i = err_bound(ref)
        check(err <= bound_i, f"{name} request {i}: max error {err} > {bound_i}")
        max_err = max(max_err, err)
    # warm pass: same requests, outputs must repeat bit for bit
    again, warm_s = serve(server, requests)
    for i, (a, b) in enumerate(zip(done, again)):
        check(np.array_equal(a.outputs[app.pipeline.output],
                             b.outputs[app.pipeline.output]),
              f"{name} request {i}: warm output differs from cold")
    check_server(name, server)
    kernels = ", ".join(
        f"{ck.name}(grid={ck.grid}, bh={ck.bh})" for ck in server.pipeline.kernels
    )
    print(f"{name}: {kernels}")
    print(f"{name}: plan_s={plan_s:.3f} compile_s={compile_s:.3f} "
          f"cold_serve_s={cold_s:.3f} max_err={max_err!r} "
          f"smoke_frames_per_s={N_REQUESTS / warm_s:.2f} (one warm pass, "
          f"not a benchmark)")


def smoke_camera_vs_interpreter(seed: int) -> None:
    """Camera at size 16 served on the chip against the repo's reference
    interpreter, and the numpy camera reference against it too."""
    from repro.apps.paper_apps import make_app
    from repro.backend import PipelineServer, reference_arrays

    app = make_app("camera", size=16)
    server = PipelineServer(app.pipeline, batch_slots=BATCH_SLOTS, mode="compiled")
    requests = make_requests(app, seed, 256)
    done, _ = serve(server, requests)
    max_err = 0.0
    for i, (req, ins) in enumerate(zip(done, requests)):
        want = reference_arrays(
            app.pipeline, {n: a.astype(np.float64) for n, a in ins.items()}
        )["camera"]
        check(float(np.max(np.abs(ref_camera(ins["raw"]) - want))) < 1e-9,
              f"camera size 16 request {i}: numpy reference disagrees with "
              f"execute_pipeline")
        err = float(np.max(np.abs(req.outputs["camera"] - want)))
        check(err <= 1e-3, f"camera size 16 request {i}: max error {err}")
        max_err = max(max_err, err)
    check_server("camera size 16", server)
    print(f"camera size 16 vs execute_pipeline: max_err={max_err!r}")


def cache_entries(path: str) -> int:
    p = Path(path)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.backend import DegradedModeWarning, enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{device.platform!r} ({device.device_kind}); nothing was run",
              file=sys.stderr)
        return 2
    print(f"device: platform={device.platform} kind={device.device_kind} "
          f"count={len(devices)} jax={jax.__version__}")
    print(f"compile cache: {cache_dir} ({cache_entries(cache_dir)} entries "
          f"at start)")
    warnings.simplefilter("error", DegradedModeWarning)
    for i, name in enumerate(APPS):
        smoke_app(name, args.seed + i, device)
    smoke_camera_vs_interpreter(args.seed + len(APPS))
    print(f"compile cache: {cache_entries(cache_dir)} entries at end")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform,
        "kind": device.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
