"""Telemetry subsystem tests.

The acceptance bar, in order of importance:

1. telemetry OFF is bit-for-bit invisible — params, history, ids-free
   results identical to a run of the same config without the knob, for
   every engine (loop, sync scan, deadline, fedbuff, sweeps) and both
   aggregation dtypes (the flag must not perturb the traced program);
2. telemetry ON agrees exactly across engines (loop == scan == sweep
   member) and matches an independent numpy recomputation of the
   aggregation-score math;
3. trace export schema-validates (required keys, per-track monotonic
   timestamps) and rejects tampered events;
4. host-phase profiles cover >= 90% of the run wall time;
5. modeled network byte series are consistent with the event plans.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import fed
from repro.configs.paper_models import MCLR
from repro.data.federated import LazyFederatedData, stack_devices
from repro.data.synthetic import synthetic_alpha_beta
from repro.fed.async_engine import (AsyncFLConfig, build_deadline_plan,
                                    build_fedbuff_plan,
                                    deadline_selection_probs, run_async)
from repro.fed.scan_engine import run_async_compiled, run_federated_compiled
from repro.fed.simulator import FLConfig, run_federated
from repro.fed.sweep_engine import (SweepSpec, run_async_sweep_compiled,
                                    run_sweep_compiled)
from repro.models import small
from repro.sysmodel import (PopulationSpec, expected_latencies,
                            heterogeneous_fleet, round_cost_for)
from repro.telemetry import (METRIC_KEYS, STALE_BINS, NULL_PROFILER,
                             PhaseProfiler, profiler_for, round_metrics,
                             selection_entropy, validate_trace, write_trace)
from repro.telemetry import metrics as tmetrics
from repro.telemetry import profiler as tprof
from repro.telemetry.trace import (REQUIRED_KEYS, deadline_trace_events,
                                   fedbuff_trace_events, queue_trace_events)

N_DEV = 14
ROUNDS = 4

_fed = stack_devices(
    synthetic_alpha_beta(0, n_devices=N_DEV, alpha=1.0, beta=1.0,
                         mean_size=50), seed=0)
# strong straggler tail so deadlines cut devices and the slot pool,
# staleness histogram, and late-flush paths all light up
_fleet = heterogeneous_fleet(1, N_DEV, straggler_frac=0.4,
                             straggler_slowdown=30.0)
_params = small.init_small(MCLR, jax.random.PRNGKey(0))
_cost = round_cost_for(MCLR, _params)
_sizes = np.asarray(_fed.mask.sum(axis=1))
_lat = expected_latencies(_fleet, _cost, mean_steps=10, n_examples=_sizes)
_DEADLINE = float(np.quantile(_lat, 0.5))


def _sync_cfg(telemetry, algo="folb", agg_dtype="float32"):
    return FLConfig(algo=algo, n_selected=4, max_local_steps=3, seed=3,
                    agg_dtype=agg_dtype, telemetry=telemetry)


def _async_cfg(telemetry, mode, algo="folb", agg_dtype="float32"):
    kw = (dict(deadline=_DEADLINE) if mode == "deadline"
          else dict(buffer_size=3, concurrency=6))
    return AsyncFLConfig(mode=mode, algo=algo, n_selected=5,
                         max_local_steps=3, staleness_alpha=0.5, seed=7,
                         agg_dtype=agg_dtype, telemetry=telemetry, **kw)


def _tree_eq(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


def _metrics_eq(a, b):
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(np.asarray(a[k]), np.asarray(b[k])), k


_LAZY_DATA = LazyFederatedData(n_devices=200, seed=3, eval_cohort=12)
_LAZY_FLEET = PopulationSpec(n_devices=200, seed=7, straggler_frac=0.4,
                             straggler_slowdown=20.0)


def _lazy_cfg():
    return AsyncFLConfig(mode="deadline", algo="folb", n_selected=4,
                         max_local_steps=3, staleness_alpha=0.5, seed=7,
                         deadline=0.05, sampler="indexed")


def _run(engine, cfg):
    if engine == "loop":
        return run_federated(MCLR, _fed, cfg, rounds=ROUNDS, fleet=_fleet)
    if engine == "scan":
        return run_federated_compiled(MCLR, _fed, cfg, rounds=ROUNDS,
                                      fleet=_fleet)
    if engine == "async":
        return run_async(MCLR, _fed, cfg, _fleet, rounds=ROUNDS)
    return run_async_compiled(MCLR, _fed, cfg, _fleet, rounds=ROUNDS)


# --------------------------------------------------------------------------
# 1. telemetry off is bit-for-bit invisible
# --------------------------------------------------------------------------

class TestTelemetryOffInvisible:
    @pytest.mark.parametrize("agg_dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("engine", ["loop", "scan"])
    def test_sync_engines(self, engine, agg_dtype):
        off = _run(engine, _sync_cfg(False, agg_dtype=agg_dtype))
        on = _run(engine, _sync_cfg(True, agg_dtype=agg_dtype))
        assert _tree_eq(off.params, on.params)
        assert off.history == on.history
        assert off.metrics is None and off.profile is None
        assert on.metrics is not None and on.profile is not None

    @pytest.mark.parametrize("agg_dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("mode", ["deadline", "fedbuff"])
    @pytest.mark.parametrize("engine", ["async", "async_scan"])
    def test_async_engines(self, engine, mode, agg_dtype):
        off = _run(engine, _async_cfg(False, mode, agg_dtype=agg_dtype))
        on = _run(engine, _async_cfg(True, mode, agg_dtype=agg_dtype))
        assert _tree_eq(off.params, on.params)
        assert off.history == on.history
        assert off.metrics is None and off.profile is None
        assert on.metrics is not None and on.profile is not None

    def test_sweep_engines(self):
        for off_spec, on_spec, runner, extra in (
                (SweepSpec.from_grid(_sync_cfg(False), lr=(0.05, 0.1)),
                 SweepSpec.from_grid(_sync_cfg(True), lr=(0.05, 0.1)),
                 run_sweep_compiled, dict(fleet=_fleet)),
                (SweepSpec.from_grid(_async_cfg(False, "deadline"),
                                     lr=(0.05, 0.1)),
                 SweepSpec.from_grid(_async_cfg(True, "deadline"),
                                     lr=(0.05, 0.1)),
                 lambda m, f, s, rounds, **kw: run_async_sweep_compiled(
                     m, f, s, _fleet, rounds, **kw), dict())):
            off = runner(MCLR, _fed, off_spec, rounds=ROUNDS, **extra)
            on = runner(MCLR, _fed, on_spec, rounds=ROUNDS, **extra)
            assert off.profile is None and on.profile is not None
            for ro, rn in zip(off.results, on.results):
                assert _tree_eq(ro.params, rn.params)
                assert ro.history == rn.history
                assert ro.metrics is None and rn.metrics is not None

    @pytest.mark.parametrize("engine", ["scan", "lazy_deadline"])
    def test_recording_is_invisible(self, engine):
        def go():
            if engine == "scan":
                return fed.run(MCLR, _fed, _sync_cfg(False), ROUNDS,
                               fleet=_fleet, eval_every=2)
            return fed.run(MCLR, _LAZY_DATA, _lazy_cfg(), ROUNDS,
                           fleet=_LAZY_FLEET, eval_every=2)
        off = go()
        with tprof.recording():
            on = go()
        assert tprof.snapshot()["spans"]
        tprof.reset()
        assert _tree_eq(off.params, on.params)
        assert off.history == on.history
        assert off.profile is None and on.profile is None


# --------------------------------------------------------------------------
# 2. telemetry on: engines agree, math matches a numpy recomputation
# --------------------------------------------------------------------------

class TestMetricParityAcrossEngines:
    @pytest.mark.parametrize("algo", ["folb", "fedavg", "folb2"])
    def test_sync_loop_vs_scan(self, algo):
        loop = _run("loop", _sync_cfg(True, algo=algo))
        scan = _run("scan", _sync_cfg(True, algo=algo))
        _metrics_eq(loop.metrics, scan.metrics)
        assert np.array_equal(loop.ids, scan.ids)
        assert loop.metrics["score_mean"].shape == (ROUNDS,)
        assert loop.metrics["stale_hist"].shape == (ROUNDS, STALE_BINS)

    @pytest.mark.parametrize("mode", ["deadline", "fedbuff"])
    def test_async_eager_vs_scan(self, mode):
        eager = _run("async", _async_cfg(True, mode))
        scan = _run("async_scan", _async_cfg(True, mode))
        _metrics_eq(eager.metrics, scan.metrics)
        assert np.array_equal(eager.ids, scan.ids)

    def test_sweep_member_matches_solo(self):
        spec = SweepSpec.from_grid(_sync_cfg(True), lr=(0.05, 0.1),
                                   mu=(0.0, 0.01))
        sweep = run_sweep_compiled(MCLR, _fed, spec, rounds=ROUNDS,
                                   fleet=_fleet)
        for i in (0, 3):
            solo = run_federated_compiled(MCLR, _fed, spec.member(i),
                                          rounds=ROUNDS, fleet=_fleet)
            _metrics_eq(sweep[i].metrics, solo.metrics)

    def test_async_sweep_member_matches_solo(self):
        spec = SweepSpec.from_grid(_async_cfg(True, "deadline"),
                                   lr=(0.05, 0.1))
        sweep = run_async_sweep_compiled(MCLR, _fed, spec, _fleet,
                                         rounds=ROUNDS)
        solo = run_async_compiled(MCLR, _fed, spec.member(1), _fleet,
                                  rounds=ROUNDS)
        _metrics_eq(sweep[1].metrics, solo.metrics)


class TestRoundMetricsMath:
    """`round_metrics` against a from-scratch numpy reimplementation."""

    def _numpy_reference(self, deltas, grads, psi, gammas, tau, alpha, mask):
        m = mask.astype(np.float64)
        disc = (1.0 + tau) ** (-alpha)
        n = m.sum()
        g1 = (grads * m[:, None]).sum(0) / max(n, 1.0)
        scores = (grads @ g1 - psi * gammas * (g1 @ g1)) * disc * m
        weights = scores / max(np.abs(scores).sum(), 1e-30)
        p = np.abs(weights)
        p = p[p > 0]
        mean_delta = (deltas * m[:, None]).sum(0) / max(n, 1.0)
        hist = np.zeros(STALE_BINS)
        np.add.at(hist, np.clip(tau.astype(int), 0, STALE_BINS - 1), m)
        return {
            "score_min": scores[m > 0].min() if n else 0.0,
            "score_mean": scores.sum() / max(n, 1.0),
            "score_max": scores[m > 0].max() if n else 0.0,
            "weight_entropy": float(-(p * np.log(p)).sum()),
            "grad_norm": np.linalg.norm(g1),
            "delta_norm": np.linalg.norm(mean_delta),
            "n_contrib": n, "stale_hist": hist,
        }

    def test_folb_scores_match_numpy(self):
        rng = np.random.default_rng(0)
        K, D = 6, 11
        deltas = rng.normal(size=(K, D)).astype(np.float32)
        grads = rng.normal(size=(K, D)).astype(np.float32)
        gammas = rng.uniform(0.5, 2.0, K).astype(np.float32)
        tau = rng.integers(0, 12, K).astype(np.float32)
        mask = (rng.uniform(size=K) > 0.3).astype(np.float32)
        psi, alpha = 0.7, 0.5
        got = round_metrics(
            {"w": jnp.zeros(D)}, {"w": jnp.zeros(D)}, {"w": jnp.asarray(deltas)},
            {"w": jnp.asarray(grads)}, folb=True, psi=psi,
            gammas=jnp.asarray(gammas), tau=jnp.asarray(tau), alpha=alpha,
            mask=jnp.asarray(mask))
        ref = self._numpy_reference(deltas.astype(np.float64),
                                    grads.astype(np.float64), psi,
                                    gammas.astype(np.float64),
                                    tau.astype(np.float64), alpha, mask)
        for k, v in ref.items():
            np.testing.assert_allclose(np.asarray(got[k]), v, rtol=2e-5,
                                       err_msg=k)
        assert set(got) == set(METRIC_KEYS)

    def test_mean_family_weights(self):
        """fedavg-family scores are the discounted mask itself."""
        rng = np.random.default_rng(1)
        K, D = 5, 7
        deltas = jnp.asarray(rng.normal(size=(K, D)).astype(np.float32))
        grads = jnp.asarray(rng.normal(size=(K, D)).astype(np.float32))
        tau = jnp.asarray([0.0, 1.0, 2.0, 3.0, 9.0], jnp.float32)
        got = round_metrics({"w": jnp.zeros(D)}, {"w": jnp.zeros(D)},
                            {"w": deltas}, {"w": grads}, folb=False,
                            tau=tau, alpha=1.0)
        disc = (1.0 + np.asarray(tau)) ** -1.0
        np.testing.assert_allclose(got["score_mean"], disc.mean(), rtol=1e-6)
        np.testing.assert_allclose(got["score_max"], disc.max(), rtol=1e-6)
        # τ=9 lands in the overflow bin
        assert got["stale_hist"][STALE_BINS - 1] == 1.0

    def test_all_masked_is_finite(self):
        D = 4
        z = jnp.zeros((3, D))
        got = round_metrics({"w": jnp.zeros(D)}, {"w": jnp.zeros(D)},
                            {"w": z}, {"w": z}, folb=True,
                            mask=jnp.zeros(3))
        for k in METRIC_KEYS:
            assert np.isfinite(np.asarray(got[k])).all(), k

    def test_update_norm_tracks_param_motion(self):
        D = 4
        z = jnp.zeros((2, D))
        got = round_metrics({"w": jnp.zeros(D)}, {"w": jnp.full(D, 2.0)},
                            {"w": z}, {"w": z})
        np.testing.assert_allclose(got["update_norm"], 2.0 * np.sqrt(D),
                                   rtol=1e-6)


# --------------------------------------------------------------------------
# 3. trace export
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def deadline_plan():
    afl = _async_cfg(True, "deadline")
    sp = deadline_selection_probs(afl, _fleet, _cost, _sizes)
    return build_deadline_plan(afl, _fleet, _cost, _sizes, ROUNDS,
                               jax.random.PRNGKey(7), sp)


@pytest.fixture(scope="module")
def fedbuff_plan():
    afl = _async_cfg(True, "fedbuff")
    return build_fedbuff_plan(afl, _fleet, _cost, _sizes, ROUNDS,
                              jax.random.PRNGKey(7))


class TestTraceExport:
    def test_deadline_trace_valid(self, deadline_plan):
        ev = deadline_trace_events(deadline_plan, fleet=_fleet, cost=_cost,
                                   sizes=_sizes)
        counts = validate_trace(ev)
        # R server spans + 3 phase spans per dispatch (± wait spans)
        assert counts["X"] >= ROUNDS + 3 * deadline_plan.ids.size
        assert counts["M"] >= 2
        for e in ev:
            for k in REQUIRED_KEYS:
                assert k in e

    def test_deadline_trace_without_latency_model(self, deadline_plan):
        ev = deadline_trace_events(deadline_plan)
        counts = validate_trace(ev)
        # one round-trip span per dispatch instead of phase spans
        assert counts["X"] == ROUNDS + deadline_plan.ids.size

    def test_fedbuff_trace_valid(self, fedbuff_plan):
        ev = fedbuff_trace_events(fedbuff_plan, fleet=_fleet, cost=_cost,
                                  sizes=_sizes)
        counts = validate_trace(ev)
        assert counts["i"] == ROUNDS          # one flush instant per round
        n_disp = len(fedbuff_plan.all_ids)
        assert counts["X"] >= ROUNDS + 3 * n_disp

    def test_fedbuff_trace_needs_clocks(self, fedbuff_plan):
        import dataclasses
        old = dataclasses.replace(fedbuff_plan, dispatch_clock=None)
        with pytest.raises(ValueError, match="clocks"):
            fedbuff_trace_events(old)

    def test_monotonic_per_track(self, deadline_plan):
        ev = deadline_trace_events(deadline_plan, fleet=_fleet, cost=_cost,
                                   sizes=_sizes)
        last = {}
        for e in ev:
            if e["ph"] == "M":
                continue
            track = (e["pid"], e["tid"])
            assert e["ts"] >= last.get(track, 0.0)
            last[track] = e["ts"]

    def test_validate_rejects_tampering(self, deadline_plan):
        ev = deadline_trace_events(deadline_plan)
        bad = [dict(e) for e in ev]
        del bad[0]["ts"]
        with pytest.raises(ValueError, match="missing required key"):
            validate_trace(bad)
        bad = [dict(e) for e in ev]
        bad[-1]["ts"] = -5.0
        with pytest.raises(ValueError, match="negative ts"):
            validate_trace(bad)
        # swap two spans on one track to break monotonicity
        bad = [dict(e) for e in ev]
        spans = [i for i, e in enumerate(bad)
                 if e["ph"] == "X" and e["pid"] == 0]
        bad[spans[0]]["ts"], bad[spans[-1]]["ts"] = \
            bad[spans[-1]]["ts"], bad[spans[0]]["ts"]
        with pytest.raises(ValueError, match="monotonic"):
            validate_trace(bad)
        with pytest.raises(ValueError, match="non-empty"):
            validate_trace([])

    def test_write_trace_roundtrip(self, deadline_plan, tmp_path):
        ev = deadline_trace_events(deadline_plan, fleet=_fleet, cost=_cost,
                                   sizes=_sizes)
        path = write_trace(str(tmp_path / "sub" / "trace.json"), ev)
        with open(path) as f:
            doc = json.load(f)
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        validate_trace(doc["traceEvents"])
        assert len(doc["traceEvents"]) == len(ev)

    def test_queue_trace(self):
        from repro.sysmodel import EventQueue
        q = EventQueue()
        q.push(0.5, "dispatch", device=3)
        q.push(0.1, "flush", n=2)
        drained = []
        while len(q):
            drained.append(q.pop())
        ev = queue_trace_events(drained)
        counts = validate_trace(ev)
        assert counts["i"] == 2


# --------------------------------------------------------------------------
# 4. host-phase profiling
# --------------------------------------------------------------------------

class TestProfiler:
    def test_phases_cover_run(self):
        res = _run("async_scan", _async_cfg(True, "deadline"))
        prof = res.profile
        assert prof["total_s"] > 0
        assert set(prof["phases"]) >= {"setup", "plan_build", "scan",
                                       "eval", "collect"}
        attributed = sum(prof["phases"].values())
        # acceptance: phase sum within 10% of the run total
        assert prof["coverage"] >= 0.9
        assert attributed <= prof["total_s"] * 1.01 + 1e-6

    def test_loop_engine_phases(self):
        res = _run("loop", _sync_cfg(True))
        assert set(res.profile["phases"]) >= {"setup", "rounds", "eval",
                                              "collect"}
        assert res.profile["coverage"] >= 0.9

    def test_null_profiler_is_free(self):
        assert profiler_for(False) is NULL_PROFILER
        with NULL_PROFILER.phase("anything"):
            pass
        assert NULL_PROFILER.finish() is None

    def test_explicit_profiler_wins(self):
        p = PhaseProfiler()
        assert profiler_for(False, p) is p
        with p.phase("a"):
            pass
        s = p.finish()
        assert "a" in s["phases"]


def _count_reads(monkeypatch, prefix):
    """Device arrays read to the host while the innermost recorded span's
    name starts with ``prefix``, counted where jax hands the data over
    (``np.asarray`` takes the buffer protocol, ``float()`` ``_value``),
    so independently of the recorder's own counter."""
    from jax._src.array import ArrayImpl
    reads = [0]

    def inside():
        stack = tprof._THREAD.stack
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


class TestRecorder:
    def test_off_builds_no_annotation(self, monkeypatch):
        def refuse(*a, **k):
            raise AssertionError("TraceAnnotation built while off")
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
        tprof.reset()
        assert tprof.span("eval/fetch") is NULL_PROFILER.phase("eval")
        tprof.count("d2h_fetches")
        tprof.to_device(np.zeros(3))
        tprof.fetch(jnp.zeros(3))
        fed.run(MCLR, _fed, _sync_cfg(False), ROUNDS)
        fed.run(MCLR, _LAZY_DATA, _lazy_cfg(), ROUNDS, fleet=_LAZY_FLEET)
        assert tprof.snapshot() == {"spans": {}, "counters": {},
                                    "calls": 0}

    def test_nesting_self_time_and_counters(self):
        tprof.reset()
        with tprof.recording():
            with tprof.span("outer"):
                tprof.count("n", 2)
                with tprof.span("outer/inner") as inner:
                    tprof.count("n")
                    tprof.to_device(np.zeros(4, np.float32))
                    tprof.fetch(jnp.zeros(2))
                    tprof.fetch(np.zeros(2))      # on the host: no read
                    sum(range(10000))
            tprof.count("n", 5)
        assert not tprof.is_recording()
        outer = tprof.RECORDER.spans[-1]
        assert inner.parent is outer and outer.parent is None
        snap = tprof.snapshot()
        o, i = snap["spans"]["outer"], snap["spans"]["outer/inner"]
        assert o["count"] == i["count"] == 1
        assert o["self_seconds"] == pytest.approx(
            o["seconds"] - i["seconds"])
        assert i["self_seconds"] == i["seconds"] > 0
        assert snap["counters"] == {
            "outer": {"n": 2},
            "outer/inner": {"n": 1, "h2d_transfers": 1, "h2d_bytes": 16,
                            "d2h_fetches": 1},
            "": {"n": 5}}
        tprof.reset()
        assert tprof.snapshot()["spans"] == {}
        assert not tprof.RECORDER.spans

    def test_to_device_counts_host_arrays_only(self):
        tprof.reset()
        on_device = jnp.zeros(3)
        with tprof.recording():
            with tprof.span("a"):
                tprof.to_device(np.zeros(4, np.float32))
                tprof.to_device(np.arange(6), jnp.int32)
                tprof.to_device(on_device)
                tprof.to_device(on_device, jnp.int32)
        snap = tprof.snapshot()
        tprof.reset()
        assert snap["counters"] == {"a": {"h2d_transfers": 2,
                                          "h2d_bytes": 40}}

    def test_step_draws_are_one_transfer_per_scan_call(self):
        """The resident scan engine's step budgets reach the device as
        one table: one transfer, no read, whatever the round count."""
        tprof.reset()
        with tprof.recording():
            fed.run(MCLR, _fed, _sync_cfg(False), ROUNDS)
            fed.run(MCLR, _fed, _sync_cfg(False), 2 * ROUNDS)
        snap = tprof.snapshot()
        tprof.reset()
        assert snap["spans"]["plan_build/step_draws"]["count"] == 2
        assert snap["counters"]["plan_build/step_draws"] == {
            "h2d_transfers": 2,
            "h2d_bytes": 4 * 3 * ROUNDS * _sync_cfg(False).n_selected}

    def test_deadline_step_draws_stay_on_the_host(self):
        afl = _async_cfg(False, "deadline")
        sp = deadline_selection_probs(afl, _fleet, _cost, _sizes)
        tprof.reset()
        with tprof.recording():
            plan = build_deadline_plan(afl, _fleet, _cost, _sizes, ROUNDS,
                                       jax.random.PRNGKey(7), sp)
        snap = tprof.snapshot()
        tprof.reset()
        assert snap["spans"]["plan_build/step_draws"]["count"] == 1
        assert "plan_build/step_draws" not in snap["counters"]
        assert plan.n_steps.dtype == np.int32

    def test_one_call_id_per_run(self):
        tprof.reset()
        with tprof.recording():
            fed.run(MCLR, _fed, _sync_cfg(False), ROUNDS)
            fed.run(MCLR, _fed, _sync_cfg(False), ROUNDS)
        spans = list(tprof.RECORDER.spans)
        assert tprof.snapshot()["calls"] == 2
        tprof.reset()
        calls = [s.call for s in spans]
        assert set(calls) == {1, 2}
        # each call's spans are contiguous, and none of them outlives it
        assert calls == sorted(calls)
        assert tprof._THREAD.call is None

    @pytest.mark.parametrize("engine,eval_every", [
        ("sync", 1), ("sync", 3), ("lazy_deadline", 1)])
    def test_eval_history_is_one_fetch_per_call(self, monkeypatch, engine,
                                                eval_every):
        """The eval history of a call is read to the host in one fetch,
        however many eval points the call has."""
        reads = _count_reads(monkeypatch, "eval/")
        tprof.reset()
        if engine == "sync":
            res = fed.run(MCLR, _fed, _sync_cfg(False), ROUNDS,
                          eval_every=eval_every, profiler=PhaseProfiler())
        else:
            res = fed.run(MCLR, _LAZY_DATA, _lazy_cfg(), ROUNDS,
                          fleet=_LAZY_FLEET, eval_every=eval_every,
                          profiler=PhaseProfiler())
        points = len(range(0, ROUNDS, eval_every)) \
            + ((ROUNDS - 1) % eval_every > 0)
        assert len(res["train_loss"]) == points
        prof = res.profile
        assert prof["counters"]["eval/fetch"]["d2h_fetches"] == 1
        assert reads[0] == 1
        assert set(prof["spans"]) >= {"setup", "plan_build", "eval",
                                      "eval/device", "eval/fetch"}
        # a call given a profiler records for its length only
        assert not tprof.is_recording()
        # the phase is the parent of its steps
        spans = {s.name: s for s in tprof.RECORDER.spans}
        assert spans["eval/fetch"].parent is spans["eval"]
        tprof.reset()

    def test_every_read_is_counted(self, monkeypatch):
        """Every device read inside a recorded span goes through the
        counted ``fetch``: the counter equals the reads jax saw."""
        reads = _count_reads(monkeypatch, "")
        tprof.reset()
        with tprof.recording():
            fed.run(MCLR, _fed, _sync_cfg(False), ROUNDS, fleet=_fleet,
                    profiler=PhaseProfiler())
            fed.run(MCLR, _LAZY_DATA, _lazy_cfg(), ROUNDS,
                    fleet=_LAZY_FLEET, profiler=PhaseProfiler())
        snap = tprof.snapshot()
        tprof.reset()
        fetches = sum(c.get("d2h_fetches", 0)
                      for c in snap["counters"].values())
        assert fetches == reads[0] > 0

    def test_profiler_keeps_its_own_spans(self):
        tprof.reset()
        prof = PhaseProfiler()
        fed.run(MCLR, _fed, _sync_cfg(False), ROUNDS, profiler=prof)
        before = prof.summary()
        tprof.reset()
        fed.run(MCLR, _fed, _sync_cfg(False), ROUNDS)
        assert prof.summary()["spans"] == before["spans"]
        assert before["spans"]["eval/fetch"]["count"] == 1
        assert tprof.snapshot()["spans"] == {}

    def test_span_log_is_bounded(self, monkeypatch):
        monkeypatch.setattr(tprof, "SPAN_LOG", 3)
        tprof.reset()
        with tprof.recording():
            for _ in range(5):
                with tprof.span("a"):
                    pass
        assert len(tprof.RECORDER.spans) == 3
        assert tprof.snapshot()["spans"]["a"]["count"] == 5
        monkeypatch.undo()
        tprof.reset()

    def test_threads_keep_their_own_parents(self):
        import threading
        tprof.reset()
        inner = {}
        started = threading.Barrier(2)

        def work(name):
            with tprof.span(name):
                started.wait()
                with tprof.span(name + "/step") as s:
                    inner[name] = s
                started.wait()

        with tprof.recording():
            threads = [threading.Thread(target=work, args=(n,))
                       for n in ("a", "b")]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        for name, s in inner.items():
            assert s.parent.name == name and s.parent.parent is None
        tprof.reset()

    def test_h2d_bytes_of_gathered_cohorts(self):
        tprof.reset()
        with tprof.recording():
            res = fed.run(MCLR, _LAZY_DATA, _lazy_cfg(), ROUNDS,
                          fleet=_LAZY_FLEET)
        snap = tprof.snapshot()
        tprof.reset()
        d = _LAZY_DATA.gather(res.ids)
        want = sum(d[k].nbytes for k in ("x", "y", "mask"))
        assert snap["counters"]["gather/to_device"]["h2d_bytes"] == want
        assert {"plan_build/key_chain", "plan_build/step_draws",
                "plan_build/timeline", "plan_build/pool",
                "gather/synthesize", "gather/to_device", "gather/pool_init",
                "eval/cohort", "eval/device", "eval/fetch"} == set(
                    snap["spans"])

    def test_synth_devices_counts_unique_cohort_ids(self):
        """``gather/synthesize`` counts the unique devices it synthesized;
        the eval cohort, gathered under ``eval/cohort``, is not counted."""
        tprof.reset()
        with tprof.recording():
            res = fed.run(MCLR, _LAZY_DATA, _lazy_cfg(), ROUNDS,
                          fleet=_LAZY_FLEET)
        snap = tprof.snapshot()
        tprof.reset()
        assert snap["spans"]["gather/synthesize"]["count"] == 1
        assert snap["counters"]["gather/synthesize"]["synth_devices"] == len(
            np.unique(res.ids))
        assert "synth_devices" not in snap["counters"].get("eval/cohort", {})


# --------------------------------------------------------------------------
# 5. network byte series consistent with the event plans
# --------------------------------------------------------------------------

class TestNetworkSeries:
    def test_deadline_bytes_match_plan(self, deadline_plan):
        afl = _async_cfg(True, "deadline")
        D = int(sum(x.size for x in jax.tree.leaves(_params)))
        net = tmetrics.deadline_network_series(D, afl, deadline_plan)
        pay = tmetrics.payload_bytes(D, afl.agg_dtype, uploads_gradient=True)
        np.testing.assert_allclose(
            net["bytes_up"],
            np.asarray(deadline_plan.n_arrived, float) * pay["up"])
        assert (net["bytes_down"]
                == deadline_plan.ids.shape[1] * pay["down"]).all()

    def test_pool_series_conserves_stragglers(self, deadline_plan):
        pool = tmetrics.deadline_pool_series(deadline_plan)
        assert (pool["pool_live"] >= 0).all()
        assert (pool["pool_live"] <= deadline_plan.n_slots).all()
        # every aggregated update is an on-time arrival or a late flush
        K = deadline_plan.ids.shape[1]
        np.testing.assert_allclose(
            pool["n_arrived"], (K - pool["n_cut"]) + pool["n_late"])

    def test_bf16_halves_uplink(self):
        afl32 = _async_cfg(True, "fedbuff")
        afl16 = _async_cfg(True, "fedbuff", agg_dtype="bfloat16")
        plan = build_fedbuff_plan(afl32, _fleet, _cost, _sizes, ROUNDS,
                                  jax.random.PRNGKey(7))
        n32 = tmetrics.fedbuff_network_series(100, afl32, plan)
        n16 = tmetrics.fedbuff_network_series(100, afl16, plan)
        np.testing.assert_allclose(n16["bytes_up"] * 2, n32["bytes_up"])
        np.testing.assert_allclose(n16["bytes_down"], n32["bytes_down"])

    def test_engine_attaches_series(self):
        res = _run("async_scan", _async_cfg(True, "deadline"))
        for k in ("bytes_up", "bytes_down", "n_cut", "n_late", "pool_live",
                  "pool_frac"):
            assert k in res.metrics, k
            assert np.asarray(res.metrics[k]).shape == (ROUNDS,)
        assert res.metrics["selection_entropy"] >= 0.0
        # stale histograms account for exactly the contributing updates
        np.testing.assert_allclose(res.metrics["stale_hist"].sum(axis=1),
                                   res.metrics["n_contrib"])

    def test_selection_entropy_bounds(self):
        assert selection_entropy(np.zeros(10, int), 8) == 0.0
        uniform = selection_entropy(np.arange(8), 8)
        np.testing.assert_allclose(uniform, np.log(8), rtol=1e-12)
