"""The chip's published peaks and the roofline arithmetic over them.

The operations and bytes a model needs are its family's
(``families/<family>.py``), counted from shapes alone.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {PEAKS_FILE}")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """Least time on the chip: the larger of compute and memory time."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
