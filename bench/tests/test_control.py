"""The control of each cell at a test size on the CPU: the plain reference
computed in bfloat16, put in the program's place, fails the cell's
comparison (at least one number over its limit), while the program itself
passes it.  ``bench/calibrate.py`` reads the same on the chip at the
cells' own sizes.

  python -m pytest bench/tests
"""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench.tests import small

CELLS = ["mclr-deadline-1m", "mclr-sync"]
LIMITS = Path(__file__).resolve().parents[1] / "limits"


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_program_passes(workload):
    import jax
    from bench import calibrate
    jax.clear_caches()
    config, traffic = small.cell(workload)
    limits = json.loads((LIMITS / f"{workload}.json").read_text())
    out, _ = calibrate.readings(
        workload, [31337], [31338], require_tpu=False,
        overrides={"config": config, "traffic": traffic})
    by = {r["kind"]: r for r in out}
    assert all(by["program"][k] <= v for k, v in limits.items()), by
    assert any(by["control"][k] > v for k, v in limits.items()), by
