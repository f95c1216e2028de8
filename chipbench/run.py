"""Run one cell of the on-chip benchmark once, on the chip it starts on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout.  The cell, its configuration, traffic
mix and metrics come from ``BENCHMARK.json``; see ``harness.py``.  It
serves in compiled (Mosaic) mode only: where JAX finds no TPU, fewer chips
than the cell asks for, or a device missing from ``peaks.json``, it exits
non-zero and prints no result.  The last line of standard output is the
result, one JSON object; the last lines of standard error give each number
the correctness check compared, beside its limit.  JAX's compilation cache
is kept in ``<checkout>/.jax_cache``, so only a checkout's first run
compiles.
"""

import time

T_START = time.perf_counter()           # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from chipbench import harness, work

    cell, _config, _mix = harness.find_cell(harness.load_spec(), args.workload)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chipbench: needs a TPU, but JAX found {dev.platform!r} "
              f"({dev.device_kind}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"chipbench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}; nothing was run", file=sys.stderr)
        return 2
    try:
        peaks = work.peaks_for(dev.device_kind, dev.platform)
    except work.UnknownDevice as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 2
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}; found "
          f"{time.perf_counter() - T_START:.3f} s after start", file=sys.stderr)

    def log(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    result, check_lines = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        t_start=T_START, peaks=peaks, log=log)
    for line in check_lines:
        log(line)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
