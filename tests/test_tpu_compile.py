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

import os

import jax
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
