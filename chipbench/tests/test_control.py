"""The lower-precision control must fail the comparison, and the float64
references must agree with the repository's reference interpreter."""

import json

import numpy as np
import pytest

from chipbench import control, harness, references
from chipbench.harness import ROOT

SMALL = {"camera_isp_1080": {"raw": [36, 36]}, "blur_1080p": {"input": [18, 34]}}


def config(name):
    entry = {c["name"]: c for c in harness.load_spec()["configs"]}[name]
    return json.loads((ROOT / entry["file"]).read_text())


@pytest.mark.parametrize("name", sorted(SMALL))
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 11])
def test_bfloat16_control_fails_the_limit(name, seed):
    c = config(name)
    checks = control.bfloat16_checks(c, seed, SMALL[name])
    assert not harness.passes(checks)     # the run's own decision
    gap = checks["max_abs_err"]
    assert gap["value"] > gap["limit"]
    assert gap["value"] > 0.25  # whole units on a 0..255 output, not rounding
    assert checks["frames_compared"]["value"] == c["pool_frames"]


def test_one_stream_has_no_half_batch_fault():
    mix = json.loads((harness.BENCH_DIR / "traffic" / "stream1.json").read_text())
    assert control.faults_for(mix) == ["answer_altered"]
    rig = json.loads((harness.BENCH_DIR / "traffic" / "rig4.json").read_text())
    assert control.faults_for(rig) == list(control.FAULTS)


@pytest.mark.parametrize("name, app_kw", [
    ("camera_isp_1080", {"size": 16}),
    ("blur_1080p", {"size": 18, "width": 34}),
])
def test_reference_matches_the_reference_interpreter(name, app_kw):
    from repro.apps.paper_apps import make_app
    from repro.backend import reference_arrays

    c = config(name)
    app = make_app(c["app"], **app_kw)
    ref = references.load(c["reference"]).reference
    for frame in harness.make_pool(c, app.input_extents, 5)[:2]:
        want = reference_arrays(
            app.pipeline, {n: a.astype(np.float64) for n, a in frame.items()}
        )[app.pipeline.output]
        np.testing.assert_allclose(ref(frame), want, rtol=0, atol=1e-9)


def test_pool_frames_are_seeded_and_distinct():
    c = config("camera_isp_1080")
    a = harness.make_pool(c, {"raw": (36, 36)}, 2**33 + 1)
    b = harness.make_pool(c, {"raw": (36, 36)}, 2**33 + 1)
    assert len(a) == c["pool_frames"]
    assert all(np.array_equal(x["raw"], y["raw"]) for x, y in zip(a, b))
    assert len({x["raw"].tobytes() for x in a}) == len(a)
    assert a[0]["raw"].dtype == np.uint8
