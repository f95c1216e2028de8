"""The EfficientNet-B0 MBConv6 block with squeeze-and-excitation (marker
``backend``), in interpret mode at small sizes.

``make_app("efficientnet_b0_mbconv")`` binds its weights and biases as
parameters; every request carries only the uint8 ``ifmap``.  The block
plans into one whole-image kernel: its 1x1 convolutions and the
excitation's two fully-connected layers are contractions (the latter two
on vectors, one unit row each), the depthwise stage is unrolled over its
nine taps, and the squeeze is one block reduction over every pixel of
``h2``.  The served outputs are checked against a plain ``jax.numpy``
forward (below) and against the benchmark's float64 reference
(``chipbench/references/mbconv_block.py``); both draw the weights from the
seed on their own.
"""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import paper_apps
from repro.apps.paper_apps import efficientnet_mbconv_weights, make_app
from repro.backend import PipelineServer, compile_pipeline, tracing
from repro.backend.codegen import eval_trace
from repro.backend.plan import BlockReduce
from repro.backend.runner import max_abs_error
from repro.backend.verify import verify_plan
from repro.frontend import Func, RDom, Var, count_ops, exp, expr_depth, lower_pipeline

pytestmark = pytest.mark.backend

SEED = 19051194
REF_PATH = (Path(__file__).resolve().parents[1]
            / "chipbench" / "references" / "mbconv_block.py")
# float32 kernels against float64 or float32 references on outputs of
# magnitude up to ~10: each output sums 24-144 products after two swishes
# and a gate, so rounding stays near 1e-6; 2e-5 leaves 10x room
ATOL = 2e-5


def _reference_module():
    spec = importlib.util.spec_from_file_location("mbconv_block_ref", REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference_module()


def _codes(shape, seed, ring=None):
    x = np.random.default_rng(seed).integers(0, 256, size=shape).astype(np.uint8)
    if ring is not None:
        x[:, 0, :] = x[:, -1, :] = x[:, :, 0] = x[:, :, -1] = ring
    return x


def jnp_block(codes, weights, act_scale, se=True):
    """The block in plain ``jax.numpy`` float32, at the highest matmul
    precision: no kernels, no batching; the depthwise stage is a grouped
    convolution and the activations are ``jax.nn``'s."""
    w1, b1, w2, b2, wr, br, we, be, w3, b3 = (jnp.asarray(a) for a in weights)
    with jax.default_matmul_precision("highest"):
        x = (jnp.asarray(codes, jnp.float32) - 128) * act_scale
        x = x[:, 1:-1, 1:-1]
        h1 = jax.nn.silu(jnp.einsum("cyx,cm->myx", x, w1) + b1[:, None, None])
        h1 = jnp.pad(h1, ((0, 0), (1, 1), (1, 1)))
        dw = jax.lax.conv_general_dilated(
            h1[None], w2[:, :, None, :], (1, 1), "VALID",
            dimension_numbers=("NCHW", "HWIO", "NCHW"),
            feature_group_count=h1.shape[0],
        )[0]
        h2 = jax.nn.silu(dw + b2[:, None, None])
        g = jnp.ones(h2.shape[0], jnp.float32)
        if se:
            s = jnp.mean(h2, axis=(1, 2))
            g = jax.nn.sigmoid(jax.nn.silu(s @ wr + br) @ we + be)
        y = jnp.einsum("myx,mc->cyx", h2 * g[:, None, None], w3)
        return np.asarray(y + b3[:, None, None] + x)


def _kw(cin, **over):
    return {**dict(img=6, cin=cin, expand=6, se_ratio=0.25, kernel=3,
                   weight_seed=SEED + cin, act_scale=1 / 32), **over}


@pytest.mark.parametrize("cin", [8, 16])
def test_served_block_matches_both_references(cin):
    kw = _kw(cin, weight_seed=REF.WEIGHT_SEED)
    app = make_app("efficientnet_b0_mbconv", **kw)
    srv = PipelineServer(app.pipeline, batch_slots=2)
    tiles = [_codes(app.input_extents["ifmap"], SEED + i) for i in range(3)]
    done = srv.run([{"ifmap": t} for t in tiles])
    assert all(r.ok for r in done)
    weights = efficientnet_mbconv_weights(cin, 6, 0.25, 3, REF.WEIGHT_SEED)
    for req, t in zip(done, tiles):
        got = req.outputs["efficientnet_b0_mbconv"]
        assert got.shape == (cin, 6, 6)
        want = REF.reference({"ifmap": t})
        assert np.abs(want).max() > 1.0          # not a vacuous comparison
        np.testing.assert_allclose(got, want, rtol=0, atol=ATOL)
        np.testing.assert_allclose(got, jnp_block(t, weights, 1 / 32),
                                   rtol=0, atol=ATOL)


def test_app_and_reference_draw_the_same_weights():
    for cin in (8, 24):
        app = efficientnet_mbconv_weights(cin, 6, 0.25, 3, REF.WEIGHT_SEED)
        ref = REF.weights(cin, REF.WEIGHT_SEED)
        assert [a.shape for a in app] == [b.shape for b in ref]
        for a, b in zip(app, ref):
            assert a.dtype == b.dtype == np.float32
            np.testing.assert_array_equal(a, b)
    w = efficientnet_mbconv_weights(24, 6, 0.25, 3, 1905)
    assert w[4].shape == (144, 6) and w[6].shape == (6, 144)


def test_exp_in_the_interpreter_matches_the_emitted_kernel():
    x, y = Var("x"), Var("y")
    inp = Func.input("input", 2)
    f = Func("soft")
    f[x, y] = exp(inp[x, y] / 16 - 8) + exp(0 - inp[x + 1, y] / 32)
    f.hw_accelerate()
    pipe = lower_pipeline(f, [inp, f], {"x": 12, "y": 10})
    pp = compile_pipeline(pipe)
    img = np.random.default_rng(SEED).integers(0, 256, (10, 13)).astype(np.float32)
    got = pp.run({"input": img})
    # values up to e**8 ~ 3e3 in float32 against Python's float64 exp
    assert max_abs_error(pp, {"input": img}, got=got)["soft"] <= 1e-3
    want = np.exp(img[:, :12] / 16 - 8) + np.exp(-img[:, 1:] / 32)
    np.testing.assert_allclose(np.asarray(got["soft"]), want, rtol=2e-6)


def test_exp_counts_as_one_op():
    inp = Func.input("input", 1)
    e = exp(inp[Var("x")] + 1)
    assert count_ops(e) == 2 and expr_depth(e) == 2
    assert count_ops(paper_apps.swish(inp[Var("x")])) == 5


def test_squeeze_is_one_block_reduction():
    app = make_app("efficientnet_b0_mbconv", **_kw(8))
    with eval_trace() as trace:
        pp = compile_pipeline(app.pipeline, batch=2)
        pp.run({"ifmap": np.stack([_codes((8, 8, 8), i) for i in range(2)])})
    [kg] = pp.plan.kernels
    assert not kg.streamed and len(kg.stage_names) == 13
    kinds = {sp.name: sp.reduction_kind for sp in kg.stages if sp.reduction_kind}
    assert kinds == {"expand": "contraction", "depthwise": "unrolled",
                     "squeeze": "block_reduce", "reduce": "contraction",
                     "excite": "contraction", "project": "contraction"}
    assert pp.plan.notes["reductions"] == {
        "contraction": 4, "block_reduce": 1, "unrolled": 1}
    sq = kg.stage_plan("squeeze")
    assert sq.block_reduce == BlockReduce(0, 1 / 36)
    assert sq.scratch_producer[0] == "h2"
    # evaluated once (whole) per kernel trace, not once per pixel
    assert [r["stage"] for r in trace].count("squeeze") == 1
    assert verify_plan(pp.plan) == []


def test_verifier_rejects_a_block_reduction_over_part_of_its_member():
    app = make_app("efficientnet_b0_mbconv", **_kw(8))
    pp = compile_pipeline(app.pipeline)
    [kg] = pp.plan.kernels
    # the depthwise taps read h1 at x + kx, y + ky: no axis is read whole
    kg.stage_plan("depthwise").block_reduce = BlockReduce(0)
    hits = [v for v in verify_plan(pp.plan) if v.rule == "UB303"]
    assert hits and all(v.stage == "depthwise" for v in hits)
    kg.stage_plan("depthwise").block_reduce = None
    kg.stage_plan("squeeze").load_kind[0] = "view"
    hits = [v for v in verify_plan(pp.plan) if v.rule == "UB303"]
    assert hits and "scratch" in hits[0].message


def test_excitation_is_a_vector_contraction():
    app = make_app("efficientnet_b0_mbconv", **_kw(8))
    [kg] = compile_pipeline(app.pipeline).plan.kernels
    for name, k in (("reduce", 48), ("excite", 2)):
        sp = kg.stage_plan(name)
        cn = sp.contraction
        assert cn is not None and cn.taps == ()
        # the activation has no axis but the contracted one
        assert len(sp.accesses[cn.lhs].axes) == 1
        assert sp.nstage.red_extents == (k,)


def test_a_vector_contraction_matches_numpy():
    j, rc = Var("j"), RDom(40, name="c")
    w = np.random.default_rng(SEED).standard_normal((40, 6)).astype(np.float32)
    a = Func.input("a", 1)
    wp = Func.param("w", w)                      # [j, c]
    v = Func("v")
    v[j] = 0
    v.update((j,), v[j] + a[rc[0]] * wp[j, rc[0]], rc)
    v.hw_accelerate()
    pipe = lower_pipeline(v, [a, wp, v], {"j": 6})
    pp = compile_pipeline(pipe)
    assert pp.plan.kernels[0].output.contraction is not None
    x = np.random.default_rng(SEED + 1).standard_normal(40).astype(np.float32)
    np.testing.assert_allclose(np.asarray(pp.run({"a": x})["v"]),
                               x.astype(np.float64) @ w, rtol=0, atol=1e-5)


def test_zero_weights_leave_the_skip(monkeypatch):
    def zero_convs(*args):
        w = efficientnet_mbconv_weights(*args)
        return w[:8] + (np.zeros_like(w[8]), np.zeros_like(w[9]))

    monkeypatch.setattr(paper_apps, "efficientnet_mbconv_weights", zero_convs)
    app = make_app("efficientnet_b0_mbconv", **_kw(8))
    x = _codes(app.input_extents["ifmap"], SEED + 1)
    got = np.asarray(compile_pipeline(app.pipeline)({"ifmap": x}))
    want = (x[:, 1:7, 1:7].astype(np.float32) - 128) / 32
    np.testing.assert_array_equal(got, want)


def test_ring_codes_do_not_reach_the_output():
    app = make_app("efficientnet_b0_mbconv", **_kw(8))
    pp = compile_pipeline(app.pipeline)
    lit = np.asarray(pp({"ifmap": _codes((8, 8, 8), SEED, ring=255)}))
    dark = np.asarray(pp({"ifmap": _codes((8, 8, 8), SEED, ring=0)}))
    # the squeeze sees only h2, whose padding is h1's zero ring
    np.testing.assert_array_equal(lit, dark)


def test_without_squeeze_and_excitation_the_gate_is_one():
    kw = _kw(8, se_ratio=0)
    app = make_app("efficientnet_b0_mbconv", **kw)
    assert set(app.pipeline.params) == {"w1", "b1", "w2", "b2", "w3", "b3"}
    pp = compile_pipeline(app.pipeline)
    assert pp.plan.notes["reductions"] == {
        "contraction": 2, "block_reduce": 0, "unrolled": 1}
    x = _codes((8, 8, 8), SEED + 2)
    weights = efficientnet_mbconv_weights(8, 6, 0, 3, kw["weight_seed"])
    np.testing.assert_allclose(np.asarray(pp({"ifmap": x})),
                               jnp_block(x, weights, 1 / 32, se=False),
                               rtol=0, atol=ATOL)


def test_requests_carry_the_ifmap_alone():
    app = make_app("efficientnet_b0_mbconv", **_kw(8))
    assert app.pipeline.inputs == ["ifmap"]
    assert set(app.input_extents) == {"ifmap"}
    srv = PipelineServer(app.pipeline, batch_slots=2)
    [ck] = srv.pipeline.kernels
    assert ck.buffer_order == ("ifmap",)
    held = sum(v.nbytes for v in app.pipeline.params.values())
    assert held == 4 * (8 * 48 + 48 + 9 * 48 + 48 + 48 * 2 + 2 + 2 * 48
                        + 48 + 48 * 8 + 8)
    assert srv.stats()["param_bytes"] == held
    srv.run([{"ifmap": _codes((8, 8, 8), i)} for i in range(2)])
    assert srv.stats()["bytes_to_device"] == 2 * 8 * 8 * 8     # uint8 codes


def test_plan_span_carries_the_reduction_kinds(tmp_path):
    from jax.profiler import ProfileData

    app = make_app("efficientnet_b0_mbconv", **_kw(8))
    with jax.profiler.trace(str(tmp_path)):
        compile_pipeline(app.pipeline)
    [path] = tmp_path.rglob("*.xplane.pb")
    spans = [ev for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name == tracing.PLAN]
    assert len(spans) == 1
    assert dict(spans[0].stats) == {
        "contraction": 4, "block_reduce": 1, "unrolled": 1}


def test_mobilenet_weights_bound_as_parameters():
    rng = np.random.default_rng(SEED)
    dw = rng.integers(-3, 4, (4, 3, 3)).astype(np.float32)      # (c, ky, kx)
    pw = rng.integers(-3, 4, (5, 4)).astype(np.float32)         # (co, c)
    app = make_app("mobilenet", img=6, cin=4, cout=5, weights=(dw, pw))
    assert app.pipeline.inputs == ["ifmap"]
    assert set(app.input_extents) == {"ifmap"}
    pp = compile_pipeline(app.pipeline)
    assert pp.plan.notes["reductions"] == {
        "contraction": 1, "block_reduce": 0, "unrolled": 1}
    x = rng.integers(0, 16, (8, 8, 4)).astype(np.float32)        # (y, x, c)
    assert max_abs_error(pp, {"ifmap": x}) == {"mobilenet": 0.0}
    depthwise = sum(dw[:, ky, kx] * x[ky:ky + 6, kx:kx + 6]
                    for ky in range(3) for kx in range(3))
    want = np.einsum("oc,yxc->yxo", pw, depthwise)
    np.testing.assert_array_equal(np.asarray(pp({"ifmap": x})), want)
    with pytest.raises(ValueError, match="shapes"):
        make_app("mobilenet", img=6, cin=4, cout=5, weights=(pw, dw))
