"""FLOP counts of ``bench.core.counts`` against hand-worked small shapes,
and the peaks table."""
from __future__ import annotations

import pytest

from bench.core import counts


def test_mclr_flops():
    assert counts.mclr_flops_per_example_step(60, 10) == 3600.0


def test_peaks_table():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")
