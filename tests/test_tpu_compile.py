"""Compile rehearsal: the served kernels compile for a described TPU v5e
(marker ``backend``).

Each case builds its pipeline exactly as ``chip_smoke.py`` serves it
(``PipelineServer(..., batch_slots=4, mode="compiled")``) or as a plain
compiled ``compile_pipeline``, then lowers every emitted kernel against
shapes placed on a described — not attached — ``v5e`` chip and compiles it
with the TPU compiler.  Nothing runs: a pass says Mosaic accepts the
kernels (tiling, strided and resident taps, VMEM), not that they are right
or fast.  The topology is described inside a module-scoped fixture only,
so the TPU runtime is loaded by the one worker that runs this file.
"""

import base64
import os
import re

import jax
import numpy as np
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps.paper_apps import make_app
from repro.backend import PipelineServer, compile_pipeline

pytestmark = pytest.mark.backend


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # keep the compiler's logs out of /tmp; a described-device compile
        # can be written to a persistent cache but never read back, so the
        # cache stays off around these compiles
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler to describe one with
            jax.config.update("jax_enable_compilation_cache", was)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _compile_all(pp, sharding):
    """Lower and compile every kernel of ``pp`` on ``sharding``'s device;
    return the compiled executables."""
    cap = pp.plan.notes.get("batch_capacity")
    lead = (cap,) if cap else ()
    out = []
    for ck in pp.kernels:
        assert ck.mode == "compiled"
        args = tuple(
            jax.ShapeDtypeStruct(
                lead + tuple(pp.pipeline.buffer_boxes[b].extents),
                jnp.float32, sharding=sharding,
            )
            for b in ck.buffer_order
        )
        lowered = ck.jitted.lower(args)
        assert "tpu_custom_call" in lowered.as_text(), ck.name
        out.append(lowered.compile())
    return out


# the four chip_smoke.py apps at its sizes, served at 4 batch slots
SERVED = {
    "gaussian": dict(size=1082, width=1922),
    "harris": dict(schedule="sch3", size=1084),
    "camera": dict(size=540),
    "matmul": dict(m=1024, n=1024, k=1024),
}


@pytest.mark.parametrize("name", sorted(SERVED))
def test_served_kernels_compile_for_v5e(name, one_chip):
    app = make_app(name, **SERVED[name])
    server = PipelineServer(app.pipeline, batch_slots=4, mode="compiled")
    pp = server.pipeline
    assert pp.plan.notes["align_tpu"] is True
    assert all(ck.bh % 8 == 0 for ck in pp.kernels if ck.streamed)
    _compile_all(pp, one_chip)


@pytest.mark.parametrize(
    "ckw",
    [
        {},                                       # unbatched frame
        {"block_w": 256, "line_buffer": True},    # column rings
        # 23.6 MiB of scoped VMEM: over the chip's default 16 MiB limit,
        # under the plan's budget, which the kernel is granted
        {"block_h": 512},
    ],
    ids=["plain", "lane-carry", "bh512"],
)
def test_gaussian_frame_compiles_for_v5e(ckw, one_chip):
    app = make_app("gaussian", size=1082, width=1922)
    pp = compile_pipeline(app.pipeline, mode="compiled", **ckw)
    if "block_w" in ckw:
        assert pp.kernels[0].kg.notes.get("lane_carry") == "carried"
        assert pp.kernels[0].rings
    _compile_all(pp, one_chip)


def _mosaic_bodies(lowered_text):
    """The serialized Mosaic module of every ``tpu_custom_call`` in a
    lowering (MLIR bytecode, whose op names are plain strings)."""
    return [
        base64.b64decode(b)
        for b in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                            lowered_text)
    ]


def _compile_contractions(pp, sharding):
    """Every kernel of ``pp`` compiles, and its Mosaic module holds the
    matmul op the channel contractions lower to."""
    cap = pp.plan.notes.get("batch_capacity")
    lead = (cap,) if cap else ()
    for ck in pp.kernels:
        args = tuple(
            jax.ShapeDtypeStruct(
                lead + tuple(pp.pipeline.buffer_boxes[b].extents),
                jnp.float32, sharding=sharding,
            )
            for b in ck.buffer_order
        )
        lowered = ck.jitted.lower(args)
        bodies = _mosaic_bodies(lowered.as_text())
        assert bodies and all(b"matmul" in b for b in bodies), ck.name
        lowered.compile()


def test_resnet50_block_compiles_for_v5e(one_chip):
    """The identity bottleneck at its published widths (56 x 56, 256 -> 64
    -> 64 -> 256), served at 8 batch slots: one whole-image kernel whose
    channel reductions are MXU matrix products (about 35 s here)."""
    app = make_app("resnet50_block")
    server = PipelineServer(app.pipeline, batch_slots=8, mode="compiled")
    pp = server.pipeline
    [ck] = pp.kernels
    assert ck.buffer_order == ("ifmap",)            # weights are bound
    _compile_contractions(pp, one_chip)


def test_resnet_layer_with_bound_weights_compiles_for_v5e(one_chip):
    """The paper's resnet layer at 56 x 56, 64 -> 64 channels, its weights
    a parameter: nine matrix products, one per 3 x 3 tap, where the
    unrolled path (576 outer products) was refused (about 40 s here)."""
    w = np.random.default_rng(0).standard_normal((64, 64, 3, 3))
    app = make_app("resnet", img=56, cin=64, cout=64, weights=w)
    pp = compile_pipeline(app.pipeline, mode="compiled")
    assert pp.kernels[0].kg.output.contraction is not None
    _compile_contractions(pp, one_chip)
