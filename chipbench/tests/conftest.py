"""The benchmark's own tests: CPU only, run with

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

from the checkout's root."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
