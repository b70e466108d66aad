"""Work counted from shapes: model FLOPs of the local solves, and the
chips' published peaks.  Kept with the benchmark so that every later
change is measured against the same arithmetic.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; an unknown device is
    an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add them with their source")
    return table[device_kind]


def mclr_flops_per_example_step(n_features: int, n_classes: int) -> float:
    """Forward plus backward FLOPs of one example in one local step of
    multinomial logistic regression (3x the forward matrix product)."""
    return 3.0 * 2.0 * n_features * n_classes
