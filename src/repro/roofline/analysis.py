"""Three-term roofline analysis from a compiled dry-run artifact.

    compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
    memory term     = HLO_bytes / (chips x HBM_bw)
    collective term = collective_bytes / (chips x link_bw)

``cost_analysis`` provides FLOPs/bytes; collective bytes are parsed from the
compiled HLO text by summing operand sizes of all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute ops.

Hardware constants (TPU v5e-class, per the brief): 197 bf16 TFLOP/s per
chip, 819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s/link (sum over a ring's share)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

# shapes like  bf16[16,512,128]{2,1,0}  possibly inside tuples
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?[%\w.-]+\s*=\s*((?:\([^)]*\)|[^=(]+?))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum result-shape bytes per collective kind.  ``-start`` ops are
    counted; their ``-done`` twins are skipped to avoid double counting."""
    out: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLL_RE.match(line)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        out[kind] = out.get(kind, 0) + _shape_bytes(shape_str)
    return out


@dataclass
class RooflineReport:
    name: str
    chips: int
    flops: float                    # per-chip HLO dot-flops (trip-count aware)
    hbm_bytes: float                # per-chip HBM bytes (trip-count aware)
    collective_bytes: Dict[str, int]
    model_flops: float = 0.0        # 6*N*D analytical (global)
    peak_flops: float = PEAK_FLOPS
    hbm_bw: float = HBM_BW
    ici_bw: float = ICI_BW
    xla_cost: Optional[Dict] = None

    @property
    def t_compute(self) -> float:
        # cost_analysis flops are whole-program when lowered SPMD: they are
        # reported per-device by XLA's analysis on the partitioned module
        return self.flops / self.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def t_collective(self) -> float:
        return sum(self.collective_bytes.values()) / self.ici_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (per chip): catches remat/redundancy."""
        per_chip_model = self.model_flops / self.chips
        return per_chip_model / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / bound time (how close to the roofline)."""
        bound = max(self.t_compute, self.t_memory, self.t_collective)
        useful = (self.model_flops / self.chips) / self.peak_flops
        return useful / bound if bound > 0 else 0.0

    def as_dict(self) -> Dict:
        return {
            "name": self.name,
            "chips": self.chips,
            "flops": self.flops,
            "hbm_bytes": self.hbm_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "xla_cost_unscaled": self.xla_cost,
        }


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty when XLA gives none)."""
    return compiled.cost_analysis() or {}


def analyze_compiled(
    name: str,
    compiled,
    chips: int,
    model_flops: float = 0.0,
) -> RooflineReport:
    """XLA's cost_analysis counts while-loop bodies once (verified; see
    EXPERIMENTS.md), so FLOPs/bytes/collectives come from the trip-count-
    aware HLO cost model; raw cost_analysis numbers are kept for reference
    in ``xla_cost``."""
    from .hlo_cost import HloCostModel

    text = compiled.as_text()
    cost = HloCostModel(text).cost()
    ca = cost_analysis_dict(compiled)
    rep = RooflineReport(
        name, chips, cost.flops, cost.bytes,
        {k: int(v) for k, v in cost.collectives.items()}, model_flops,
    )
    rep.xla_cost = {
        "flops_unscaled": float(ca.get("flops", 0.0)),
        "bytes_unscaled": float(ca.get("bytes accessed", 0.0)),
    }
    return rep


__all__ = ["RooflineReport", "analyze_compiled", "cost_analysis_dict", "collective_bytes_from_hlo", "PEAK_FLOPS", "HBM_BW", "ICI_BW"]
