"""``plan_transfers_per_round``: the device round trips of the program's
plan building (``h2d_transfers`` + ``d2h_fetches`` under ``plan_build/``)
per round, read from the program's span recorder.

  python -m pytest bench/tests
"""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import run
from bench.core import recorded
from bench.tests.test_spans import CELLS, traced_run


def reader():
    return run.load_module(run.BENCH / "metrics"
                           / "plan_transfers_per_round.py", "t_plan_moves")


def snapshot(counters):
    return {"spans": {name: {"seconds": 1e-3, "self_seconds": 1e-3,
                             "count": 1} for name in counters if name},
            "counters": counters, "calls": 1}


def test_none_without_the_transfer_counter(monkeypatch):
    """A program that counts reads and bytes but not transfers, as the
    recorder did before ``h2d_transfers``, reports nothing."""
    monkeypatch.setattr(recorded, "snapshot", lambda: snapshot({
        "plan_build/key_chain": {"d2h_fetches": 2},
        "plan_build/step_draws": {"h2d_bytes": 4000},
        "eval/fetch": {"d2h_fetches": 300}}))
    assert reader().read(SimpleNamespace(work={"rounds": 100})) is None


def test_none_without_a_recorder(monkeypatch):
    monkeypatch.setattr(recorded, "snapshot", lambda: None)
    assert reader().read(SimpleNamespace(work={"rounds": 100})) is None


def test_counts_plan_build_spans_only(monkeypatch):
    monkeypatch.setattr(recorded, "snapshot", lambda: snapshot({
        "plan_build/key_chain": {"d2h_fetches": 4},
        "plan_build/step_draws": {"h2d_transfers": 2, "h2d_bytes": 8000},
        "plan_build": {"h2d_transfers": 7},
        "gather/to_device": {"h2d_transfers": 6, "h2d_bytes": 9000},
        "eval/fetch": {"d2h_fetches": 600},
        "": {"h2d_transfers": 14, "d2h_fetches": 2}}))
    assert reader().read(SimpleNamespace(work={"rounds": 200})) == \
        pytest.approx(6 / 200)


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_plan_transfers(workload, capsys):
    """Sync: the step table's one transfer a call.  Deadline: the key
    chain's two reads (ids and keys) a call; its step draws never leave
    the host."""
    res, traffic = traced_run(workload, capsys)
    per_call = 2 if workload == "mclr-deadline-1m" else 1
    assert res["metrics"]["plan_transfers_per_round"]["value"] == \
        pytest.approx(per_call / traffic["rounds"])
