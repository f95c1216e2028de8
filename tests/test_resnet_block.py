"""The ResNet-50 v1.5 identity bottleneck block with parameters (marker
``backend``), in interpret mode at small sizes.

``make_app("resnet50_block")`` binds its weights and biases as parameters:
the compiled pipeline uploads them once and every request carries only the
uint8 ``ifmap``.  Each channel reduction is planned as a contraction (one
matrix product per spatial tap) inside one whole-image kernel.  The served
outputs are checked against the benchmark's independent reference
(``chipbench/references/resnet_block.py``), which draws the same weights
from the same seed.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.apps import paper_apps
from repro.apps.paper_apps import make_app, resnet50_block_weights
from repro.backend import PipelineServer, compile_pipeline, tracing
from repro.backend.runner import max_abs_error
from repro.backend.verify import verify_plan
from repro.frontend.lower import execute_pipeline

pytestmark = pytest.mark.backend

SEED = 20161604
REF_PATH = (Path(__file__).resolve().parents[1]
            / "chipbench" / "references" / "resnet_block.py")


def _reference_module():
    spec = importlib.util.spec_from_file_location("resnet_block_ref", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference_module()


def _codes(shape, seed, ring=None):
    x = np.random.default_rng(seed).integers(0, 256, size=shape).astype(np.uint8)
    if ring is not None:
        x[:, 0, :] = x[:, -1, :] = x[:, :, 0] = x[:, :, -1] = ring
    return x


def _reference(app_kw, x):
    return REF.reference({"ifmap": x}, mid=app_kw["mid"],
                         weight_seed=app_kw["weight_seed"],
                         act_scale=app_kw["act_scale"])


@pytest.mark.parametrize("cin", [8, 16])
def test_served_block_matches_the_reference(cin):
    kw = dict(img=6, cin=cin, mid=4, weight_seed=SEED + cin, act_scale=1 / 64)
    app = make_app("resnet50_block", **kw)
    srv = PipelineServer(app.pipeline, batch_slots=2)
    tiles = [_codes(app.input_extents["ifmap"], SEED + i) for i in range(3)]
    done = srv.run([{"ifmap": t} for t in tiles])
    assert all(r.ok for r in done)
    for req, t in zip(done, tiles):
        got = req.outputs["resnet50_block"]
        assert got.shape == (cin, 6, 6)
        np.testing.assert_allclose(got, _reference(kw, t), rtol=0, atol=2e-5)


def test_channel_reductions_are_contractions_in_one_whole_kernel():
    app = make_app("resnet50_block", img=6, cin=8, mid=4)
    pp = compile_pipeline(app.pipeline)
    [kg] = pp.plan.kernels
    assert kg.stage_names == ["conv1", "h1", "conv2", "h2", "conv3",
                              "resnet50_block"]
    assert not kg.streamed and kg.grid == (1,)
    cn = {sp.name: sp.contraction for sp in kg.stages}
    assert cn["conv1"].taps == () and cn["conv3"].taps == ()
    assert len(cn["conv2"].taps) == 2           # one product per 3x3 tap
    assert cn["h1"] is None and cn["resnet50_block"] is None
    # a contraction's weights: taps, then (in, out) on the last two axes
    assert pp.plan.params["w2"].shape == (3, 3, 4, 4)
    assert pp.plan.params["b3"].shape == (8, 1, 1)
    assert pp.plan.params["b1"].shape == (1, 1, 4)


def test_h1_ring_is_zero():
    kw = dict(img=6, cin=8, mid=4, weight_seed=SEED, act_scale=1 / 64)
    app = make_app("resnet50_block", **kw)
    x = _codes(app.input_extents["ifmap"], SEED, ring=255)
    h1 = execute_pipeline(app.pipeline, {"ifmap": x})["h1"]   # [y, x, m]
    assert all(h1[(0, 3, m)] == 0.0 and h1[(7, 7, m)] == 0.0 for m in range(4))
    assert any(h1[(1, 1, m)] != 0.0 for m in range(4))
    # the served border pixels do not see the input's ring: the padding is
    # h1's zero ring, whatever codes the ring holds
    pp = compile_pipeline(app.pipeline)
    lit = np.asarray(pp({"ifmap": x}))
    dark = np.asarray(pp({"ifmap": _codes(x.shape, SEED, ring=0)}))
    np.testing.assert_array_equal(lit, dark)
    np.testing.assert_allclose(lit[:, 0, 0], _reference(kw, x)[:, 0, 0],
                               rtol=0, atol=2e-5)


def test_zero_weights_leave_the_skip(monkeypatch):
    def zero_convs(cin, mid, seed):
        w1, b1, w2, b2, w3, b3 = resnet50_block_weights(cin, mid, seed)
        return (np.zeros_like(w1), b1, np.zeros_like(w2), b2,
                np.zeros_like(w3), b3)

    monkeypatch.setattr(paper_apps, "resnet50_block_weights", zero_convs)
    app = make_app("resnet50_block", img=6, cin=8, mid=4, weight_seed=SEED)
    x = _codes(app.input_extents["ifmap"], SEED + 1)
    got = np.asarray(compile_pipeline(app.pipeline)({"ifmap": x}))
    b3 = resnet50_block_weights(8, 4, SEED)[5]
    want = np.maximum(b3[:, None, None] + x[:, 1:7, 1:7] / 64.0, 0)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)


@pytest.mark.parametrize("cin,mid,seed", [(8, 4, 1), (256, 64, 1512)])
def test_app_and_reference_draw_the_same_weights(cin, mid, seed):
    for a, b in zip(resnet50_block_weights(cin, mid, seed),
                    REF.weights(cin, mid, seed)):
        assert a.dtype == b.dtype == np.float32
        np.testing.assert_array_equal(a, b)


def test_parameters_cross_once_and_requests_carry_the_ifmap_alone():
    app = make_app("resnet50_block", img=6, cin=8, mid=4, weight_seed=SEED + 7)
    assert app.pipeline.inputs == ["ifmap"]
    assert set(app.pipeline.params) == {"w1", "b1", "w2", "b2", "w3", "b3"}
    srv = PipelineServer(app.pipeline, batch_slots=4)
    nbytes = int(np.prod(app.input_extents["ifmap"]))           # uint8
    held = sum(v.nbytes for v in app.pipeline.params.values())
    assert held == 4 * (8 * 4 + 4 + 9 * 4 * 4 + 4 + 4 * 8 + 8)
    assert srv.stats()["param_bytes"] == held
    tiles = [{"ifmap": _codes(app.input_extents["ifmap"], i)} for i in range(7)]
    srv.run(tiles)                               # 4 + 3 with one filler
    st = srv.stats()
    assert st["dispatches"] == 2 and st["served"] == 7
    assert st["bytes_to_device"] == 2 * 4 * nbytes
    srv.run(tiles[:4])
    st2 = srv.stats()
    assert (st2["bytes_to_device"] - st["bytes_to_device"]) / 4 == nbytes
    assert st2["param_bytes"] == held


def test_params_upload_at_registration_only(tmp_path):
    from jax.profiler import ProfileData

    def names(path):
        return [ev.name for plane in ProfileData.from_file(str(path)).planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name.startswith(tracing.PREFIX)]

    app = make_app("resnet50_block", img=6, cin=8, mid=4, weight_seed=SEED + 9)
    tiles = [{"ifmap": _codes(app.input_extents["ifmap"], i)} for i in range(3)]
    with jax.profiler.trace(str(tmp_path / "register")):
        srv = PipelineServer(app.pipeline, batch_slots=2)
    [path] = (tmp_path / "register").rglob("*.xplane.pb")
    assert names(path).count(tracing.PARAMS) == 1
    srv.run(tiles[:1])                           # compile outside the trace
    with jax.profiler.trace(str(tmp_path / "serve")):
        srv.run(tiles)
    [path] = (tmp_path / "serve").rglob("*.xplane.pb")
    spans = names(path)
    assert spans.count(tracing.STEP) == 2 and tracing.PARAMS not in spans


def test_verifier_rejects_a_parameter_that_follows_the_batch():
    app = make_app("resnet50_block", img=6, cin=8, mid=4)
    pp = compile_pipeline(app.pipeline, batch=2)
    assert verify_plan(pp.plan) == []
    [kg] = pp.plan.kernels
    g = next(g for g in kg.groups if g.buffer == "w1")
    g.param = False                 # its block would follow the batch index
    hits = [v for v in verify_plan(pp.plan) if v.rule == "UB504"]
    assert hits and "batch" in hits[0].message


def test_resnet_weights_bound_as_a_parameter():
    w = np.random.default_rng(SEED).integers(-3, 4, (4, 3, 3, 3)).astype(np.float32)
    app = make_app("resnet", img=6, cin=3, cout=4, weights=w)
    assert app.pipeline.inputs == ["ifmap"]
    assert set(app.input_extents) == {"ifmap"}
    x = np.random.default_rng(SEED + 1).integers(0, 16, (3, 8, 8)).astype(np.float32)
    pp = compile_pipeline(app.pipeline)
    assert pp.plan.kernels[0].output.contraction is not None
    assert max_abs_error(pp, {"ifmap": x}) == {"resnet": 0.0}
    want = sum(
        np.einsum("oc,cyx->oyx", w[:, :, ky, kx], x[:, ky:ky + 6, kx:kx + 6])
        for ky in range(3) for kx in range(3)
    )
    np.testing.assert_array_equal(np.asarray(pp({"ifmap": x})), want)
