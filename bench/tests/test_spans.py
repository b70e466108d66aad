"""The metrics read from the program's own spans and counters: a traced
run of each cell at a test size on the CPU reports every one of them
(``gather_synth_ms`` in the deadline cell only), with the counts the
cell's shape gives.  The device metrics read nothing on the CPU.

  python -m pytest bench/tests
"""
from __future__ import annotations

import json

import pytest

from bench.tests import small

CELLS = ["mclr-deadline-1m", "mclr-sync"]
NEW = ("gather_synth_ms", "eval_fetch_ms", "eval_fetches_per_round",
       "h2d_kb_per_round")


def count_reads(monkeypatch, prefix):
    """Device arrays read to the host while the program's innermost
    recorded span's name starts with ``prefix``, counted where jax hands
    the data over (``np.asarray`` takes the buffer protocol, ``float()``
    ``_value``): independent of the program's own counter."""
    from jax._src.array import ArrayImpl
    from repro.telemetry import profiler
    reads = [0]

    def inside():
        stack = profiler._THREAD.stack
        return bool(stack) and stack[-1].name.startswith(prefix)

    buffer, value = ArrayImpl.__buffer__, ArrayImpl._value

    def counted_buffer(self, flags):
        reads[0] += inside()
        return buffer(self, flags)

    def counted_value(self):
        reads[0] += inside()
        return value.fget(self)

    monkeypatch.setattr(ArrayImpl, "__buffer__", counted_buffer)
    monkeypatch.setattr(ArrayImpl, "_value", property(counted_value))
    return reads


def traced_run(workload, capsys, seed=2718281828459):
    import jax
    from bench import run
    from repro.telemetry import profiler
    jax.clear_caches()
    profiler.reset()
    config, traffic = small.cell(workload)
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.3", "--trace", "1"],
                  overrides={"config": config, "traffic": traffic},
                  require_tpu=False)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1]), traffic


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_span_metrics(workload, capsys, monkeypatch):
    from repro.telemetry import profiler
    reads = count_reads(monkeypatch, "eval/")
    res, traffic = traced_run(workload, capsys)
    calls = profiler.snapshot()["calls"]
    got = res["metrics"]
    lazy = workload == "mclr-deadline-1m"
    assert ("gather_synth_ms" in got) == lazy
    want = [k for k in NEW if lazy or k != "gather_synth_ms"]
    assert all(got[k]["value"] > 0 for k in want), got
    # three history series read one value at a time, at every eval point
    rounds, every = traffic["rounds"], traffic["eval_every"]
    points = len(range(0, rounds, every)) + ((rounds - 1) % every > 0)
    assert got["eval_fetches_per_round"]["value"] == pytest.approx(
        3 * points / rounds)
    # and that is what the program read, counted apart from its counter
    assert got["eval_fetches_per_round"]["value"] == pytest.approx(
        reads[0] / (calls * rounds))
    # the plan-building and phase metrics still come from the harness
    assert {"plan_build_ms", "eval_ms"} <= set(got)
