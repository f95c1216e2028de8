"""The least time one frame can take on a chip, from the work the
algorithm needs and the chip's published peaks.

Bytes are a frame's input elements at the dtype the request arrives in
plus its output elements at the dtype users receive; intermediates are not
counted, so an implementation that keeps them on chip is not charged for
them.  Operations are the arithmetic of the app's definition, counted by
hand in the configuration's file.  The compute peak is the published MXU
peak (no VPU float32 peak is published), so the compute bound is a lower
bound on time and a share of the roofline never overstates.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class UnknownDevice(LookupError):
    pass


def peaks_for(device_kind: str, platform: str) -> Dict:
    """The peaks of ``device_kind``; an unknown device or a platform other
    than the table's is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is not in {PEAKS_FILE.name} "
            f"(known: {sorted(table)})")
    row = table[device_kind]
    if row["platform"] != platform:
        raise UnknownDevice(
            f"device_kind {device_kind!r} is listed for platform "
            f"{row['platform']!r}, not {platform!r}")
    return row


def frame_bytes(work: Dict) -> int:
    return (work["input_elements"] * work["input_bytes_per_element"]
            + work["output_elements"] * work["output_bytes_per_element"])


def frame_ops(work: Dict) -> int:
    return sum(s["elements"] * s["ops_per_element"] for s in work["ops"])


def least_time(work: Dict, peaks: Dict) -> Dict:
    """``{"seconds", "bound", "bytes", "ops"}``: the larger of bytes over
    HBM bandwidth and operations over the compute peak, and which binds."""
    nbytes, nops = frame_bytes(work), frame_ops(work)
    t_bytes = nbytes / float(peaks["hbm_bytes_per_s"])
    t_ops = nops / float(peaks["flops_per_s"])
    return {
        "seconds": max(t_bytes, t_ops),
        "bound": "bytes" if t_bytes >= t_ops else "ops",
        "bytes": nbytes,
        "ops": nops,
    }
