"""Bytes and operations per frame of each configuration, the least time
per frame, and the peaks table."""

import json
import math

import pytest

from chipbench import harness, work
from chipbench.harness import ROOT

V5E = work.peaks_for("TPU v5 lite", "tpu")


def config(name):
    entry = {c["name"]: c for c in harness.load_spec()["configs"]}[name]
    return json.loads((ROOT / entry["file"]).read_text())


@pytest.mark.parametrize("name, nbytes, nops, seconds", [
    # 1084^2 uint8 in + 1080^2 float32 out; 8 ops x 1082^2 + 63 x 1080^2
    ("camera_isp_1080", 1084 * 1084 + 4 * 1080 * 1080,
     8 * 1082 * 1082 + 63 * 1080 * 1080, 7.131448107448108e-06),
    # 1082x1922 uint8 in + 1080x1920 float32 out; 18 ops per output pixel
    ("blur_1080p", 1082 * 1922 + 4 * 1080 * 1920, 18 * 1080 * 1920,
     1.2666671550671551e-05),
])
def test_work_per_frame(name, nbytes, nops, seconds):
    least = work.least_time(config(name)["work"], V5E)
    assert least["bytes"] == nbytes
    assert least["ops"] == nops
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(seconds, rel=1e-12)
    assert least["seconds"] == pytest.approx(nbytes / 819e9, rel=1e-12)


@pytest.mark.parametrize("name", ["camera_isp_1080", "blur_1080p"])
def test_counted_elements_match_the_app(name):
    from repro.apps.paper_apps import make_app

    c = config(name)
    app = make_app(c["app"], **c["make_app"])
    boxes = {b: math.prod(box.extents) for b, box in app.pipeline.buffer_boxes.items()}
    w = c["work"]
    assert {k: list(v) for k, v in app.input_extents.items()} == c["input_shapes"]
    assert w["input_elements"] == sum(boxes[n] for n in app.pipeline.inputs)
    assert w["output_elements"] == boxes[app.pipeline.output]
    assert list(app.pipeline.buffer_boxes[app.pipeline.output].extents) == c["output_shape"]
    assert w["output_elements"] == c["frame_rows"] * c["frame_cols"]
    for stage in w["ops"]:
        assert stage["elements"] == boxes[stage["stage"]]


def test_unknown_device_is_an_error():
    with pytest.raises(work.UnknownDevice):
        work.peaks_for("TPU v9 imaginary", "tpu")


def test_device_on_another_platform_is_an_error():
    with pytest.raises(work.UnknownDevice):
        work.peaks_for("TPU v5 lite", "cpu")
