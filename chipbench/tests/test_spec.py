"""``BENCHMARK.json`` against the files the harness finds by name."""

import json
import re

import pytest

from chipbench import harness
from chipbench.harness import BENCH_DIR, ROOT

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_names_and_units():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    assert callable(harness.reader(metric["name"]))


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_every_cell_has_its_files_and_metrics(cell):
    _cell, config, mix = harness.find_cell(SPEC, cell["name"])
    assert mix["loop"] in ("open", "closed")
    assert (BENCH_DIR / "references" / f"{config['reference']}.py").is_file()
    assert cell["chips"] == 1
    e2e = {m["name"] for m in harness.metrics_for(SPEC, cell["name"], False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = harness.metrics_for(SPEC, cell["name"], True)
    assert per_layer
    for m in per_layer:
        assert m["moves"] in e2e


def test_configs_are_files_under_the_benchmark():
    for c in SPEC["configs"]:
        path = ROOT / c["file"]
        assert path.resolve().is_relative_to(BENCH_DIR)
        data = json.loads(path.read_text())
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        assert data["source"] == c["source"]
