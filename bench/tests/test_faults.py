"""A whole benchmark run at a test size on the CPU (the look for a chip
skipped), once sound and once for each fault the cells can have, planted
in the timed path underneath: the aggregation returns the state unchanged,
aggregates half of the batch (the mean over the rest), or moves the
params double.  The sound run must read ``correct`` true and every faulty
run false, by a compared number over its limit.  (The cells run on one
chip, so there is no exchange between chips to leave out.)

  python -m pytest bench/tests
"""
from __future__ import annotations

import json

import pytest

from bench.tests import small

CELLS = ["mclr-deadline-1m", "mclr-sync"]


def _half(kw, K):
    import jax.numpy as jnp
    keep = (jnp.arange(K) < max(K // 2, 1)).astype(jnp.float32)
    m = kw.get("mask")
    kw["mask"] = keep if m is None else m * keep
    return kw


def fault(name, agg, stale):
    """Replacements of ``ops.folb_aggregate_buffers`` and
    ``ops.folb_staleness_buffers`` carrying the fault ``name``."""
    import jax.numpy as jnp

    def as_stale(w, d, g, psi_gamma=None, mesh=None, guard=None):
        K = g.shape[0]
        return stale(w, d, g, jnp.zeros((K,)), 0.0, psi_gamma=psi_gamma,
                     **_half({}, K))

    def wrap(f, half_fn):
        def g(w, d, gr, *a, **k):
            if name == "half_batch":
                return half_fn(w, d, gr, *a, **k)
            new, scores = f(w, d, gr, *a, **k)
            if name == "state_unchanged":
                return w, scores
            return 2.0 * new - w, scores          # moved double
        return g

    def stale_half(w, d, g, tau, alpha, *a, **k):
        return stale(w, d, g, tau, alpha, *a, **_half(k, g.shape[0]))

    return wrap(agg, as_stale), wrap(stale, stale_half)


def run_cell(workload, capsys, seed=424242424242):
    import jax
    from bench import run
    jax.clear_caches()
    config, traffic = small.cell(workload)
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "0.3", "--trace", "0"],
                  overrides={"config": config, "traffic": traffic},
                  require_tpu=False)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload, capsys):
    res = run_cell(workload, capsys)
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("name", ["state_unchanged", "half_batch",
                                  "answer_altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, name, capsys, monkeypatch):
    from repro.kernels import ops
    agg, stale = fault(name, ops.folb_aggregate_buffers,
                       ops.folb_staleness_buffers)
    monkeypatch.setattr(ops, "folb_aggregate_buffers", agg)
    monkeypatch.setattr(ops, "folb_staleness_buffers", stale)
    res = run_cell(workload, capsys)
    assert res["correct"] is False
    over = [k for k, c in res["checks"].items() if c["value"] > c["limit"]]
    assert over, res["checks"]
