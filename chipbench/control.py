"""The controls: runs that must come out not correct.

* The lower-precision control: the reference in the program's place,
  computed in bfloat16, the precision below the float32 the configuration
  states (the program has no such switch of its own).  For each seed it
  makes the pool of frames a run with that seed serves, computes every
  frame in bfloat16 (each eager ``jax.numpy`` operation rounds its result
  on the default device, the chip where there is one) and hands them to
  ``harness.compare`` as the sample, as a run hands its served frames.
* Planted faults: a whole run of a cell (``harness.run_cell``, compiled
  mode, the cell's own sizes) with the served pipeline broken underneath
  ``PipelineServer.step``.

    python3 chipbench/control.py --cell camera_isp_1080.offline --seeds 1,2,3 \\
        [--faults --seconds 2]

prints one JSON line per seed (and per fault), each with the numbers
compared beside their limits and ``correct``, and a last line that says
whether every control run came out not correct, as it must.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def bfloat16_checks(config: Dict, seed: int,
                    input_shapes: Optional[Dict[str, List[int]]] = None) -> Dict:
    """``harness.compare`` of the bfloat16 reference's output for every
    frame of the seed's pool."""
    import jax.numpy as jnp

    from chipbench import harness, references

    ref = references.load(config["reference"]).reference
    pool = harness.make_pool(config, input_shapes or config["input_shapes"], seed)
    sample = [(i, np.asarray(ref(frame, jnp, jnp.bfloat16), np.float32))
              for i, frame in enumerate(pool)]
    return harness.compare(config, pool, sample, failed=0)


# -- faults planted under the server ------------------------------------------


def answer_altered(pp, bufs):
    """One element of every slot's output off by one unit."""
    out = bufs[pp.pipeline.output]
    first = (slice(None),) + (0,) * (out.ndim - 1)
    return {**bufs, pp.pipeline.output: out.at[first].add(1.0)}


def half_the_batch_left_out(pp, bufs):
    """The second half of the slots gets the first half's results."""
    import jax.numpy as jnp

    out = bufs[pp.pipeline.output]
    h = out.shape[0] // 2
    return {**bufs, pp.pipeline.output: jnp.concatenate([out[:h], out[:h]])}


FAULTS = {"answer_altered": answer_altered,
          "half_the_batch_left_out": half_the_batch_left_out}


def faults_for(mix: Dict) -> List[str]:
    """The faults a cell with this mix can have: an open loop of one
    stream carries one live frame per dispatch, so it has no half batch."""
    one_live = mix["loop"] == "open" and int(mix["streams"]) == 1
    return [f for f in FAULTS if not (one_live and f == "half_the_batch_left_out")]


@contextlib.contextmanager
def planted(fault):
    """Run ``fault(pipeline, buffers) -> buffers`` on what every
    ``PallasPipeline.run`` returns, while the context is open."""
    from repro.backend.runner import PallasPipeline

    orig = PallasPipeline.run

    def run_broken(self, inputs):
        return fault(self, orig(self, inputs))

    PallasPipeline.run = run_broken
    try:
        yield
    finally:
        PallasPipeline.run = orig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--faults", action="store_true",
                    help="also run the cell with each fault it can have planted")
    ap.add_argument("--seconds", type=float, default=2.0,
                    help="window of each fault run")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from chipbench import harness, work

    _cell, config, mix = harness.find_cell(harness.load_spec(), args.cell)
    dev = jax.devices()[0]
    failed_all = True

    def report(line: Dict, checks: Dict) -> None:
        nonlocal failed_all
        correct = harness.passes(checks)
        failed_all = failed_all and not correct
        print(json.dumps({**line, "device": dev.device_kind, "correct": correct,
                          "checks": checks}), flush=True)

    seeds = [int(s) for s in args.seeds.split(",")]
    for seed in seeds:
        report({"cell": args.cell, "seed": seed, "control": "bfloat16"},
               bfloat16_checks(config, seed))
    if args.faults:
        peaks = work.peaks_for(dev.device_kind, dev.platform)
        for name in faults_for(mix):
            for seed in seeds:
                with planted(FAULTS[name]):
                    result, _ = harness.run_cell(
                        args.cell, seed, args.seconds, False,
                        t_start=time.perf_counter(), peaks=peaks,
                        log=lambda s: None)
                report({"cell": args.cell, "seed": seed, "control": name},
                       result["checks"])
    print(json.dumps({"cell": args.cell, "every_control_not_correct": failed_all}))
    return 0 if failed_all else 1


if __name__ == "__main__":
    sys.exit(main())
