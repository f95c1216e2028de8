"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, and the
plain references it judges the chip's outputs by agree with the repo's
reference interpreter (``execute_pipeline``) on small instances."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.apps.paper_apps import make_app
from repro.backend import reference_arrays

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _smoke_module():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_refuses_cpu():
    """With JAX held to the CPU the script exits non-zero, names the
    platform it found, and never claims a result.  The child never loads
    the TPU runtime (JAX_PLATFORMS=cpu)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(SMOKE)], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300,
    )
    out = proc.stdout + proc.stderr
    assert proc.returncode != 0, out
    assert "platform 'cpu'" in proc.stderr, out
    assert '"ok": true' not in out


# small instances of the smoke apps (same builders, same references)
SMALL = {
    "gaussian": dict(size=10, width=13),
    "harris": dict(schedule="sch3", size=12),
    "camera": dict(size=6),
    "matmul": dict(m=5, n=7, k=9),
}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_smoke_reference_matches_interpreter(name):
    smoke = _smoke_module()
    assert set(SMALL) == set(smoke.APPS)
    _kw, bound, reference, _err = smoke.APPS[name]
    app = make_app(name, **SMALL[name])
    ins = smoke.make_requests(app, seed=3, bound=bound)[0]
    want = reference_arrays(
        app.pipeline, {n: a.astype(np.float64) for n, a in ins.items()}
    )[app.pipeline.output]
    got = reference(ins)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-9)
