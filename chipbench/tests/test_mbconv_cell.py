"""The EfficientNet-B0 MBConv block's cell
(``efficientnet_b0_mbconv6_56.offline``): its entries in
``BENCHMARK.json``, the work its configuration counts, and whole runs
through ``harness.run_cell`` at a small size in interpret mode, sound and
with each planted fault."""

import json
import math
import time

import pytest

from chipbench import control, harness, work
from chipbench.harness import ROOT

CELL = "efficientnet_b0_mbconv6_56.offline"
CONFIG = "efficientnet_b0_mbconv6_56"
SPEC = harness.load_spec()
V5E = work.peaks_for("TPU v5 lite", "tpu")
# 6 x 6 pixels and 8 channels (48 expanded, 2 squeezed); the reference
# keeps the configuration's expansion, squeeze ratio and weight seed
SMALL = {"img": 6, "cin": 8}
SEED = 2**31 + 20


def config():
    entry = {c["name"]: c for c in SPEC["configs"]}[CONFIG]
    return json.loads((ROOT / entry["file"]).read_text())


def test_the_spec_holds_the_cell():
    cell, cfg, mix = harness.find_cell(SPEC, CELL)
    assert cell["chips"] == 1 and mix["loop"] == "closed"
    assert cfg["app"] == "efficientnet_b0_mbconv" and cfg["reduced"] == ["depth"]
    entry = {c["name"]: c for c in SPEC["configs"]}[CONFIG]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert (ROOT / "chipbench" / "references" / "mbconv_block.py").is_file()
    e2e = {m["name"] for m in harness.metrics_for(SPEC, CELL, False)}
    assert e2e == {"frames_per_s", "setup_s"}
    per_layer = {m["name"] for m in harness.metrics_for(SPEC, CELL, True)}
    assert per_layer == {m["name"] for m in SPEC["per_layer"]}
    # the cell came last, and only appended to each list
    assert SPEC["workloads"][-1]["name"] == CELL
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        if "workloads" in m:
            assert m["workloads"][-1] == CELL


def test_work_per_image():
    """The block's stages over 56 x 56 as the published block defines
    them; uint8 in, float32 out; bytes bind."""
    from repro.apps.paper_apps import make_app

    cfg = config()
    w = cfg["work"]
    least = work.least_time(w, V5E)
    ops = {s["stage"]: s["elements"] * s["ops_per_element"] for s in w["ops"]}
    pixels, mid = 56 * 56, 144
    assert ops["expand"] == ops["project"] == 2 * pixels * 24 * mid
    assert ops["depthwise"] == 2 * pixels * 9 * mid
    assert ops["squeeze"] == pixels * mid
    assert ops["reduce"] == 6 * (2 * mid + 6)
    assert least["ops"] == 58_108_020
    assert least["bytes"] == 381_792 == 24 * 58 * 58 + 4 * 24 * pixels
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(381_792 / 819e9, rel=1e-12)
    app = make_app(cfg["app"], **cfg["make_app"])
    boxes = {b: math.prod(box.extents) for b, box in app.pipeline.buffer_boxes.items()}
    assert {k: list(v) for k, v in app.input_extents.items()} == cfg["input_shapes"]
    assert w["input_elements"] == sum(boxes[n] for n in app.pipeline.inputs)
    assert w["output_elements"] == boxes[app.pipeline.output]
    assert list(app.pipeline.buffer_boxes[app.pipeline.output].extents) == cfg["output_shape"]


def run():
    return harness.run_cell(
        CELL, SEED, 1.0, False, t_start=time.perf_counter(),
        mode="interpret", make_app_overrides=SMALL, log=lambda s: None)


def test_sound_run_is_correct():
    result, lines = run()
    assert result["correct"], lines
    assert result["failed"] == 0 and result["compiles_in_window"] == 0
    assert result["checks"]["frames_compared"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_a_planted_fault_is_caught(fault):
    with control.planted(control.FAULTS[fault]):
        result, lines = run()
    assert not result["correct"], lines


def test_bfloat16_control_is_not_correct():
    checks = control.bfloat16_checks(config(), SEED, {"ifmap": [8, 8, 8]})
    assert not harness.passes(checks), checks
