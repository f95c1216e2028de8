"""The program's spans and kernel modules in a trace: innermost-span
attribution and the readers built on it, against hand-worked values; and
on a trace from a program without them, nothing to read and every older
number unchanged."""

import pytest

from chipbench import phases
from chipbench import tracereader as tr
from chipbench.harness import reader
from chipbench.tracereader import Trace

DEV = "/device:TPU:0"
MOD = "jit_ub_k(123)"


class Rec:
    """What a reader sees of a run: its trace and counter deltas."""

    def __init__(self, trace, dispatches=2, served=2, least=None):
        self.trace = trace
        self.compile_s = 0.25
        self.least = least
        self._d = {"dispatches": dispatches, "served": served}

    def delta(self, key):
        return self._d[key]


def nested() -> Trace:
    # window 0..100.  One dispatch: chipbench.step 10-90 holds ub.step
    # 12-88, which holds stack 14-20, to_device 20-24, the launch 24-26,
    # copy_back 26-60 and finite_check 60-70; a submit 92-95 follows.
    # Device: the kernel 30-40, a copy 40-45, a loop 45-55 whose body
    # fusion 47-50 is an event of its own; busy 30-55.
    t = Trace()
    t.ops[DEV] = [(f"{MOD}/ub_k.1 custom-call", 30, 40),
                  (f"{MOD}/copy.1 copy", 40, 45),
                  (f"{MOD}/while.1 while", 45, 55),
                  (f"{MOD}/fusion.1 fusion", 47, 50)]
    t.spans = sorted([
        ("chipbench.window", 0, 100),
        ("chipbench.step", 10, 90),
        ("ub.step", 12, 88),
        ("ub.stack", 14, 20),
        ("ub.to_device", 20, 24),
        ("ub.kernel.k", 24, 26),
        ("ub.copy_back", 26, 60),
        ("ub.finite_check", 60, 70),
        ("chipbench.submit", 92, 95),
    ], key=lambda s: s[1])
    return t


def test_idle_goes_to_the_innermost_span():
    idle = phases.idle_by_innermost_span(nested(), DEV)
    assert idle == {
        tr.NO_SPAN: 10 + 2 + 5,          # 0-10, 90-92, 95-100
        "chipbench.step": 2 + 2,         # 10-12, 88-90
        "ub.step": 2 + 18,               # 12-14, 70-88
        "ub.stack": 6,
        "ub.to_device": 4,
        "ub.kernel.k": 2,
        "ub.copy_back": 4 + 5,           # 26-30, 55-60
        "ub.finite_check": 10,
        "chipbench.submit": 3,
    }
    assert sum(idle.values()) == 100 - 25


def test_a_span_that_starts_with_its_parent_is_still_inner():
    t = Trace()
    t.ops[DEV] = []
    t.spans = [("chipbench.window", 0, 10), ("chipbench.step", 0, 10),
               ("ub.step", 0, 8)]
    assert phases.idle_by_innermost_span(t, DEV) == {"ub.step": 8, "chipbench.step": 2}


def test_host_phase_readers_by_hand():
    rec = Rec(nested(), dispatches=2)
    assert reader("stage_ms_per_dispatch.throughput")(rec) == (6 + 4) / 2 / 1e6
    assert reader("copy_back_ms_per_dispatch.throughput")(rec) == 9 / 2 / 1e6
    assert reader("finite_check_ms_per_dispatch.throughput")(rec) == 10 / 2 / 1e6


def test_kernel_and_view_readers_by_hand():
    rec = Rec(nested(), dispatches=2)
    kernel = reader("kernel_ms_per_dispatch.throughput")(rec)
    view = reader("view_ms_per_dispatch.throughput")(rec)
    assert kernel == 10 / 2 / 1e6
    # 40-55 once: the fusion inside the loop is not counted again
    assert view == 15 / 2 / 1e6
    assert (kernel + view) * 2 * 1e6 == tr.busy_ns(rec.trace)


def test_ops_outside_the_program_modules_are_not_read():
    t = nested()
    t.ops[DEV] = [(n.replace("jit_ub_k", "jit__invoke"), s, e) for n, s, e in t.ops[DEV]]
    rec = Rec(t)
    assert reader("kernel_ms_per_dispatch.throughput")(rec) is None
    assert reader("view_ms_per_dispatch.throughput")(rec) is None


def test_without_program_spans_innermost_is_the_flat_charge():
    from chipbench.tests.test_tracereader import synthetic

    t = synthetic()
    assert phases.idle_by_innermost_span(t, DEV) == tr.idle_by_span(t, DEV)
    for name in ("stage_ms_per_dispatch", "copy_back_ms_per_dispatch",
                 "finite_check_ms_per_dispatch"):
        assert reader(name)(Rec(t)) is None


def test_span_count_inside_the_window():
    t = nested()
    t.spans.append(("ub.step", 99, 120))
    t.spans.append(("ub.step", 120, 130))
    assert phases.count_spans(t, "ub.step") == 2


# -- the recorded trace (a program without spans or stable names) ------------


@pytest.fixture(scope="module")
def recorded_path(tmp_path_factory):
    import gzip
    from pathlib import Path

    src = Path(__file__).parent / "data" / "camera_stream1.xplane.pb.gz"
    dst = tmp_path_factory.mktemp("trace") / "camera_stream1.xplane.pb"
    dst.write_bytes(gzip.decompress(src.read_bytes()))
    return dst


@pytest.fixture(scope="module")
def recorded(recorded_path):
    return phases.load(recorded_path)


def test_recorded_load_adds_nothing(recorded, recorded_path):
    plain = tr.load(recorded_path)
    assert recorded.spans == plain.spans
    assert recorded.ops == plain.ops


def test_recorded_numbers_are_unchanged(recorded):
    assert tr.busy_ns(recorded) == 52598498.0
    flat = tr.idle_by_span(recorded, DEV)
    assert flat == {
        "chipbench.submit": 188491.0,
        "chipbench.step": 155159694.0,
        tr.NO_SPAN: 151540.0,
        "chipbench.check": 20089.0,
        "chipbench.wait_arrival": 7161410.0,
    }
    assert phases.idle_by_innermost_span(recorded, DEV) == flat


def test_recorded_older_readers_are_unchanged(recorded):
    import json
    from pathlib import Path

    from chipbench import work

    config = json.loads((Path(__file__).parents[1] / "configs"
                         / "camera_isp_1080.json").read_text())
    least = work.least_time(config["work"], work.peaks_for("TPU v5 lite", "tpu"))
    rec = Rec(recorded, dispatches=6, served=6, least=least)
    assert reader("host_ms_per_dispatch.throughput")(rec) == 25.859949
    assert reader("roofline_pct.throughput")(rec) == 0.0813496397647869
    assert reader("device_idle_pct.throughput")(rec) == 75.56736997272786
    assert reader("compile_s")(rec) == 0.25


@pytest.mark.parametrize("name", [
    "stage_ms_per_dispatch.throughput", "copy_back_ms_per_dispatch.throughput",
    "finite_check_ms_per_dispatch.throughput", "kernel_ms_per_dispatch.throughput",
    "view_ms_per_dispatch.throughput"])
def test_recorded_new_readers_find_nothing(recorded, name):
    assert reader(name)(Rec(recorded, dispatches=6)) is None


# -- a real trace of the program ------------------------------------------------


def test_a_traced_dispatch_nests_the_program_spans(tmp_path):
    """One interpret-mode dispatch under the profiler on the CPU: one
    ``ub.step`` holding the phases in order, read back by ``phases.load``."""
    import jax
    import numpy as np

    from repro.apps.paper_apps import make_app
    from repro.backend import PipelineServer

    app = make_app("camera", size=16)
    srv = PipelineServer(app.pipeline, batch_slots=2)
    tile = {n: np.ones(app.input_extents[n], np.float32) for n in app.pipeline.inputs}
    srv.run([tile])                        # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            srv.run([tile])
    [path] = tmp_path.rglob("*.xplane.pb")
    trace = phases.load(path)
    ub = [(n, s, e) for n, s, e in trace.spans if n.startswith(phases.PREFIX)]
    assert [n for n, _s, _e in ub] == [
        "ub.step", "ub.stack", "ub.to_device", "ub.kernel.denoise",
        "ub.kernel.camera", "ub.copy_back", "ub.finite_check"]
    _n, lo, hi = ub[0]
    assert all(lo <= s <= e <= hi for _n, s, e in ub[1:])
    assert phases.count_spans(trace, phases.STEP) == 1
