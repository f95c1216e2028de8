"""A run with the timed path broken underneath must come out not correct.

Each test drives ``harness.run_cell`` -- set-up, the window, the drain,
the check -- at a small size in Pallas interpret mode on the CPU, skipping
only ``run.py``'s look for a chip.  Faults are planted in the served
pipeline (``PallasPipeline.run``), under ``PipelineServer.step``."""

import contextlib
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

from chipbench import control, harness
from chipbench.harness import ROOT

SMALL = {"camera_isp_1080": {"size": 16}, "blur_1080p": {"size": 18, "width": 34}}
SEED = 2**31 + 5
CELLS = [w["name"] for w in harness.load_spec()["workloads"]]


def run(cell, seconds=1.0):
    small = SMALL[cell.split(".")[0]]
    result, lines = harness.run_cell(
        cell, SEED, seconds, False, t_start=time.perf_counter(),
        mode="interpret", make_app_overrides=small, log=lambda s: None)
    return result, lines


@pytest.fixture
def broken():
    """Plant ``fault(pipeline, buffers) -> buffers`` under the server."""
    with contextlib.ExitStack() as stack:
        yield lambda fault: stack.enter_context(control.planted(fault))


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result, lines = run(cell)
    assert result["correct"], lines
    assert result["failed"] == 0
    assert result["checks"]["frames_compared"]["value"] > 0
    assert list(result["checks"]) == ["max_abs_err", "failed_frames",
                                      "bad_frames", "frames_compared"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_answer_altered_where_produced_is_caught(cell, broken):
    broken(control.answer_altered)
    result, lines = run(cell)
    assert not result["correct"]
    assert result["checks"]["max_abs_err"]["value"] >= 1.0 - 1e-6, lines


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out_is_caught(cell, broken):
    assert "half_the_batch_left_out" in control.faults_for(harness.find_cell(
        harness.load_spec(), cell)[2])
    broken(control.half_the_batch_left_out)
    result, _ = run(cell)
    assert not result["correct"]


def test_non_finite_output_fails_the_frames(broken):
    # from the window on (after the warm-up dispatches), every output is
    # NaN: the server quarantines each dispatch and fails its frames
    calls = []

    def poison(pp, bufs):
        calls.append(1)
        if len(calls) <= harness.WARM_DISPATCHES:
            return bufs
        return {**bufs, pp.pipeline.output: bufs[pp.pipeline.output] * jnp.nan}

    broken(poison)
    result, _ = run("camera_isp_1080.offline", seconds=0.5)
    assert not result["correct"]
    assert result["failed"] > 0


def run_cli(args, cwd, env_extra=None):
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run([sys.executable, "chipbench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


ARGS = ["--workload", "camera_isp_1080.offline", "--seed", str(SEED),
        "--seconds", "1", "--trace", "0"]


def test_no_chip_no_result():
    p = run_cli(ARGS, ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_alone_fails(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(ARGS, tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
