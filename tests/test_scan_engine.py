"""Scan-compiled engine: the whole-run lax.scan execution path must
reproduce the python-loop engine bit-for-bit on a fixed seed — history,
wall-clock, and final parameters — and reject configs it cannot compile."""
import re

import jax
import numpy as np
import pytest

from repro import fed as fed_api
from repro.configs.paper_models import MCLR
from repro.data.federated import stack_devices
from repro.data.synthetic import synthetic_alpha_beta
from repro.fed import scan_engine
from repro.fed.async_engine import AsyncFLConfig
from repro.fed.scan_engine import draw_round_inputs, run_federated_compiled
from repro.fed.simulator import FLConfig, run_federated
from repro.sysmodel import heterogeneous_fleet, uniform_fleet

N_DEV = 20
ROUNDS = 5


@pytest.fixture(scope="module")
def fed_data():
    devs = synthetic_alpha_beta(0, n_devices=N_DEV, alpha=1.0, beta=1.0,
                                mean_size=60)
    return stack_devices(devs, seed=0)


def _assert_bit_for_bit(h_loop, h_scan, check_clock=False):
    assert h_loop["round"] == h_scan["round"]
    assert h_loop["train_loss"] == h_scan["train_loss"]
    assert h_loop["train_acc"] == h_scan["train_acc"]
    assert h_loop["test_acc"] == h_scan["test_acc"]
    if check_clock:
        assert h_loop["wall_clock"] == h_scan["wall_clock"]
    for a, b in zip(jax.tree.leaves(h_loop.params),
                    jax.tree.leaves(h_scan.params)):
        assert (np.asarray(a) == np.asarray(b)).all()


class TestParity:
    def test_folb_bit_for_bit(self, fed_data):
        """Acceptance criterion: the compiled engine reproduces the
        python-loop FOLB trajectory bit-for-bit on a fixed seed."""
        fl = FLConfig(algo="folb", n_selected=5, seed=3)
        h_loop = run_federated(MCLR, fed_data, fl, rounds=ROUNDS)
        h_scan = run_federated_compiled(MCLR, fed_data, fl, rounds=ROUNDS)
        _assert_bit_for_bit(h_loop, h_scan)

    @pytest.mark.parametrize("algo,psi", [("fedavg", 0.0),
                                          ("fedprox", 0.0),
                                          ("folb_het", 0.1),
                                          ("folb2", 0.0),
                                          ("fednu_norm", 0.0),
                                          ("fednu_signed", 0.0),
                                          ("fednu_direct", 0.0)])
    def test_other_algos_bit_for_bit(self, fed_data, algo, psi):
        fl = FLConfig(algo=algo, n_selected=4, psi=psi, seed=1,
                      mu=0.0 if algo == "fedavg" else 1.0)
        h_loop = run_federated(MCLR, fed_data, fl, rounds=3)
        h_scan = run_federated_compiled(MCLR, fed_data, fl, rounds=3)
        _assert_bit_for_bit(h_loop, h_scan)

    @pytest.mark.parametrize("algo", ["folb", "fednu_norm"])
    def test_fleet_wall_clock_parity(self, fed_data, algo):
        """Identical simulated wall-clock: both engines replay the same
        fleet cost model over the same sampled device ids (fednu also
        exercises the all-device probe phase of the clock replay)."""
        fleet = heterogeneous_fleet(1, N_DEV, straggler_frac=0.3,
                                    straggler_slowdown=10.0)
        fl = FLConfig(algo=algo, n_selected=5, seed=0)
        h_loop = run_federated(MCLR, fed_data, fl, rounds=ROUNDS,
                               fleet=fleet)
        h_scan = run_federated_compiled(MCLR, fed_data, fl, rounds=ROUNDS,
                                        fleet=fleet)
        _assert_bit_for_bit(h_loop, h_scan, check_clock=True)

    def test_pytree_backend_parity_too(self, fed_data):
        """Parity is a property of the engine, not the flat kernel: the
        legacy pytree aggregation scans identically."""
        fl = FLConfig(algo="folb", n_selected=4, seed=5,
                      agg_backend="pytree")
        h_loop = run_federated(MCLR, fed_data, fl, rounds=3)
        h_scan = run_federated_compiled(MCLR, fed_data, fl, rounds=3)
        _assert_bit_for_bit(h_loop, h_scan)

    def test_eval_every(self, fed_data):
        fl = FLConfig(algo="folb", n_selected=4, seed=0)
        h = run_federated_compiled(MCLR, fed_data, fl, rounds=6,
                                   eval_every=3)
        assert h["round"] == [0, 3, 5]

    def test_uniform_fleet_matches_async_fast_path_seed(self, fed_data):
        """Triangle check: scan == loop == async(D=∞) on one seed — ties
        the new engine into the existing cross-engine parity guarantee."""
        from repro.fed.async_engine import AsyncFLConfig, run_async
        fleet = uniform_fleet(N_DEV)
        fl = FLConfig(algo="folb", n_selected=5, seed=3)
        afl = AsyncFLConfig(mode="deadline", algo="folb", n_selected=5,
                            seed=3)
        h_scan = run_federated_compiled(MCLR, fed_data, fl, rounds=4,
                                        fleet=fleet)
        h_async = run_async(MCLR, fed_data, afl, fleet, rounds=4)
        assert h_scan["train_loss"] == h_async["train_loss"]
        assert h_scan["wall_clock"] == h_async["wall_clock"]


class TestDeadlineSelection:
    """Deadline-aware scan selection: the async deadline engine's
    latency-aware sampling distribution is static per fleet, so the
    pre-computed vector lets the compiled (and python-loop) sync engines
    run the deadline-FOLB sweep's selection policy."""

    def test_loop_scan_parity_with_sel_probs(self, fed_data):
        """Custom selection probabilities preserve engine parity."""
        import jax.numpy as jnp
        probs = jnp.linspace(1.0, 3.0, N_DEV)
        probs = probs / probs.sum()
        fl = FLConfig(algo="folb", n_selected=4, seed=1)
        h_loop = run_federated(MCLR, fed_data, fl, rounds=3,
                               sel_probs=probs)
        h_scan = run_federated_compiled(MCLR, fed_data, fl, rounds=3,
                                        sel_probs=probs)
        _assert_bit_for_bit(h_loop, h_scan)

    def test_scan_runs_deadline_folb_sweep_config(self, fed_data):
        """With every device inside a generous-but-finite deadline, the
        async latency-aware deadline run IS a sequence of synchronous
        rounds under the static latency-aware distribution — the scan
        engine fed the pre-computed probs reproduces it bit-for-bit,
        simulated wall-clock included."""
        import jax.numpy as jnp
        import numpy as np
        from repro.fed import simulator
        from repro.fed.async_engine import AsyncFLConfig, run_async
        from repro.fed.scan_engine import latency_selection_probs
        from repro.models import small
        from repro.sysmodel import expected_latencies, round_cost_for
        fleet = heterogeneous_fleet(2, N_DEV, straggler_frac=0.3,
                                    straggler_slowdown=4.0)
        fl = FLConfig(algo="folb", n_selected=5, seed=3)
        params = small.init_small(MCLR, jax.random.PRNGKey(fl.seed))
        cost = round_cost_for(MCLR, params, uploads_gradient=True)
        sizes = np.asarray(fed_data.mask.sum(axis=1))
        lat = expected_latencies(fleet, cost,
                                 mean_steps=simulator.mean_local_steps(fl),
                                 n_examples=sizes)
        deadline = float(np.max(lat)) * 3.0   # everyone makes it

        probs = latency_selection_probs(MCLR, fed_data, fl, fleet, deadline)
        assert probs.shape == (N_DEV,)
        assert float(jnp.std(probs)) > 0.0          # genuinely non-uniform
        assert abs(float(jnp.sum(probs)) - 1.0) < 1e-6

        afl = AsyncFLConfig(mode="deadline", algo="folb", n_selected=5,
                            latency_aware=True, deadline=deadline,
                            staleness_alpha=0.5, seed=3)
        h_async = run_async(MCLR, fed_data, afl, fleet, rounds=4)
        assert all(n == 5 for n in h_async["n_arrived"])   # no stragglers
        h_scan = run_federated_compiled(MCLR, fed_data, fl, rounds=4,
                                        fleet=fleet, sel_probs=probs)
        assert h_scan["train_loss"] == h_async["train_loss"]
        assert h_scan["test_acc"] == h_async["test_acc"]
        assert h_scan["wall_clock"] == h_async["wall_clock"]


class TestServerOpt:
    """FedOpt-style server optimizers ride the scan carry: the compiled
    engine applies the same jitted ``server_round_update`` (delta fp32
    cast sequence + optimizer arithmetic) the python loop does, so the
    two stay bit-for-bit even though XLA fuses e.g. the momentum FMA."""

    @pytest.mark.parametrize("server_opt,server_lr",
                             [("momentum", 1.0),
                              ("adam", 0.3),
                              ("sgd", 0.5)])
    def test_server_opt_bit_for_bit(self, fed_data, server_opt, server_lr):
        fl = FLConfig(algo="folb", n_selected=4, seed=2,
                      server_opt=server_opt, server_lr=server_lr)
        h_loop = run_federated(MCLR, fed_data, fl, rounds=4)
        h_scan = run_federated_compiled(MCLR, fed_data, fl, rounds=4)
        _assert_bit_for_bit(h_loop, h_scan)

    def test_server_opt_changes_trajectory(self, fed_data):
        """The carried optimizer state must actually do something."""
        base = FLConfig(algo="folb", n_selected=4, seed=2)
        mom = FLConfig(algo="folb", n_selected=4, seed=2,
                       server_opt="momentum")
        h_base = run_federated_compiled(MCLR, fed_data, base, rounds=4)
        h_mom = run_federated_compiled(MCLR, fed_data, mom, rounds=4)
        assert h_base["train_loss"] != h_mom["train_loss"]

    def test_plain_sgd_path_unchanged(self, fed_data):
        """server_opt='sgd', lr=1.0 must stay on the original (no-carry)
        scan program — guarded by parity with the python loop."""
        fl = FLConfig(algo="folb", n_selected=4, seed=6)
        h_loop = run_federated(MCLR, fed_data, fl, rounds=3)
        h_scan = run_federated_compiled(MCLR, fed_data, fl, rounds=3)
        _assert_bit_for_bit(h_loop, h_scan)


class TestInputs:
    def test_round_inputs_match_loop_sequence(self):
        """Pre-drawn keys/steps replicate the loop's host-side sequence."""
        fl = FLConfig(algo="folb", n_selected=6, seed=9)
        key = jax.random.PRNGKey(fl.seed)
        keys, steps = draw_round_inputs(fl, 4, key)
        k = key
        from repro.fed.simulator import local_step_draws
        for t in range(4):
            k, sub = jax.random.split(k)
            assert (np.asarray(keys[t]) == np.asarray(sub)).all()
            assert (np.asarray(steps[t])
                    == np.asarray(local_step_draws(t, 6, fl))).all()

    @pytest.mark.parametrize("het", [True, False])
    def test_step_table_is_int32_rounds_by_k(self, het):
        from repro.fed.simulator import local_step_table
        fl = FLConfig(n_selected=6, max_local_steps=7, het_steps=het)
        table = local_step_table(5, 6, fl)
        assert isinstance(table, np.ndarray)
        assert table.dtype == np.int32 and table.shape == (5, 6)
        assert ((1 <= table) & (table <= 7)).all()
        assert local_step_table(0, 6, fl).shape == (0, 6)

    @pytest.mark.parametrize("het", [True, False])
    def test_step_table_rows_are_the_loop_draws(self, het):
        """The python loop's per-round draw is the table's row, and the
        row is the paper protocol's round-indexed seed."""
        from repro.fed.simulator import local_step_draws, local_step_table
        fl = FLConfig(n_selected=6, max_local_steps=7, het_steps=het)
        table = local_step_table(8, 6, fl)
        for t in range(8):
            row = local_step_draws(t, 6, fl)
            assert row.dtype == np.int32
            assert (np.asarray(row) == table[t]).all()
            want = (np.random.default_rng(10_000 + t).integers(1, 8, 6)
                    if het else np.full(6, 7))
            assert (table[t] == want).all()

    @pytest.mark.parametrize("het,grid,want", [
        (True, False, [[1, 2, 3, 3], [3, 4, 1, 4], [4, 3, 1, 2]]),
        (False, False, [[4, 3, 5, 5], [5, 5, 3, 5], [4, 4, 5, 4]]),
        (True, True, [[[1, 2, 3, 3], [3, 4, 1, 4], [4, 3, 1, 2]],
                      [[1, 2, 3, 3], [3, 2, 1, 4], [2, 2, 1, 2]]]),
    ])
    def test_scenario_steps_unchanged(self, het, grid, want):
        """A fixed scenario's step budgets: the integers the engines
        replayed when each round's draw went through the device."""
        from repro.fed.simulator import (scenario_grid_round_inputs,
                                         scenario_round_inputs)
        from repro.sysmodel.scenario import ScenarioConfig, ScenarioGrid
        fl = FLConfig(n_selected=4, max_local_steps=5, het_steps=het)
        sc = ScenarioConfig(partial_prob=0.5, drop_prob=0.2, seed=3)
        if grid:
            steps = scenario_grid_round_inputs(fl, 3, ScenarioGrid((
                sc, ScenarioConfig(partial_prob=0.9, completeness_min=0.2,
                                   seed=5))))[0]
        else:
            steps = scenario_round_inputs(fl, 3, sc)[0]
        assert steps.dtype == np.int32
        assert steps.tolist() == want

    def test_deterministic_across_calls(self, fed_data):
        fl = FLConfig(algo="folb", n_selected=4, seed=7)
        h1 = run_federated_compiled(MCLR, fed_data, fl, rounds=3)
        h2 = run_federated_compiled(MCLR, fed_data, fl, rounds=3)
        assert h1["train_loss"] == h2["train_loss"]

    @pytest.mark.parametrize("rounds,eval_every", [
        (1, 1), (5, 1), (5, 2), (7, 3), (6, 3), (4, 10)])
    @pytest.mark.parametrize("member_axis", [False, True])
    def test_eval_rows_are_the_eval_points(self, rounds, eval_every,
                                           member_axis):
        """The replay's sliced row selection takes exactly the
        ``_eval_points`` rows, for solo (R, D) and sweep (R, S, D)
        trajectories."""
        from repro.fed.scan_engine import _eval_points, _eval_rows
        shape = (rounds, 3, 5) if member_axis else (rounds, 5)
        traj = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
        got = np.asarray(_eval_rows(traj, rounds, eval_every))
        want = traj[np.asarray(_eval_points(rounds, eval_every))]
        assert got.shape == want.shape and (got == want).all()


def _replay_inputs(fed_data, shape):
    """Device eval arrays, the MCLR flat spec and a seeded trajectory of
    flat parameter vectors of leading ``shape``."""
    from repro.core import flat as flat_lib
    from repro.fed import simulator
    from repro.models import small
    spec = flat_lib.spec_of(small.init_small(MCLR, jax.random.PRNGKey(0)))
    traj = np.random.default_rng(11).normal(
        0.0, 0.3, shape + (spec.D_pad,)).astype(np.float32)
    return simulator.device_arrays(fed_data), spec, jax.numpy.asarray(traj)


class TestHistoryReplay:
    @pytest.mark.parametrize("rounds,eval_every,timeline", [
        (6, 2, False), (7, 3, False), (7, 3, True)],
        ids=["multiple", "extra_last_row", "timeline"])
    def test_lists_are_the_per_scalar_reads(self, fed_data, rounds,
                                            eval_every, timeline):
        """The history read in one fetch holds exactly the floats that
        reading each device scalar with ``float()`` gives, in the same
        order, with the host timeline series at the eval points."""
        from repro.fed.scan_engine import (_eval_points, _eval_rows,
                                           eval_history_replay, eval_traj)
        (train, test, p), spec, traj = _replay_inputs(fed_data, (rounds,))
        rng = np.random.default_rng(rounds)
        series = {"wall_clock": np.cumsum(rng.random(rounds)),
                  "n_arrived": rng.integers(0, 5, rounds),
                  "stale_mean": rng.random(rounds)} if timeline else {}
        hist = eval_history_replay(
            MCLR, spec, train, test, p, traj, rounds, eval_every,
            clocks=series.get("wall_clock"),
            n_arrived=series.get("n_arrived"),
            stale_mean=series.get("stale_mean"))
        rows = _eval_rows(traj, rounds, eval_every)
        tr_loss, tr_acc = eval_traj(MCLR, spec, rows, train, p)
        _, te_acc = eval_traj(MCLR, spec, rows, test, p)
        ts = _eval_points(rounds, eval_every)
        want = {"round": ts,
                "train_loss": [float(v) for v in tr_loss],
                "test_acc": [float(v) for v in te_acc],
                "train_acc": [float(v) for v in tr_acc]}
        want.update({k: [float(v[t]) for t in ts]
                     for k, v in series.items()})
        assert hist == want
        assert all(type(v) is float for k, vs in hist.items()
                   if k != "round" for v in vs)

    def test_sweep_members_are_solo_replays_in_one_fetch(self, fed_data):
        """Each member of the sweep replay equals the solo replay of its
        trajectory, with shared (R,) and per-member (S, R) series, and
        the whole sweep is read in one fetch.  Nine eval points fill the
        eval chunk of 8 rows in both, so both run the same row program."""
        from repro.fed.scan_engine import (eval_history_replay,
                                           eval_history_replay_sweep)
        from repro.telemetry import profiler as tprof
        rounds, eval_every, S = 17, 2, 3
        (train, test, p), spec, traj = _replay_inputs(fed_data, (rounds, S))
        rng = np.random.default_rng(5)
        clocks = np.cumsum(rng.random((S, rounds)), axis=1)
        stale = rng.random(rounds)
        tprof.reset()
        with tprof.recording():
            hists = eval_history_replay_sweep(
                MCLR, spec, train, test, p, traj, rounds, eval_every,
                clocks=clocks, stale_mean=stale)
        snap = tprof.snapshot()
        tprof.reset()
        assert snap["counters"]["eval/fetch"]["d2h_fetches"] == 1
        assert len(hists) == S
        for i, hist in enumerate(hists):
            assert hist == eval_history_replay(
                MCLR, spec, train, test, p, traj[:, i], rounds, eval_every,
                clocks=clocks[i], stale_mean=stale)


# the smallest fed.run that reaches each round-program entry: resident
# sync, lazy deadline, lazy fedbuff
def _lazy_run(afl):
    from repro.data.federated import LazyFederatedData
    from repro.sysmodel import PopulationSpec
    return lambda _: fed_api.run(
        MCLR, LazyFederatedData(n_devices=24, seed=3), afl, 2,
        fleet=PopulationSpec(n_devices=24, seed=7, straggler_frac=0.4,
                             straggler_slowdown=20.0))


_ENTRY_RUNS = {
    "scan_rounds": lambda fed_data: fed_api.run(
        MCLR, fed_data, FLConfig(algo="folb", n_selected=3, seed=0), 2),
    "scan_deadline_cohort": _lazy_run(AsyncFLConfig(
        mode="deadline", algo="folb", n_selected=4, deadline=40.0,
        sampler="indexed")),
    "scan_fedbuff_cohort": _lazy_run(AsyncFLConfig(
        mode="fedbuff", algo="folb", buffer_size=3, concurrency=5,
        sampler="indexed")),
}


class TestRoundProgramNames:
    @pytest.mark.parametrize("entry", sorted(_ENTRY_RUNS))
    def test_entry_lowers_to_its_trace_name(self, fed_data, monkeypatch,
                                            entry):
        """A device trace names each round program ``jit_<entry>``, the
        names the chip benchmark reads its round-program and aggregation-
        kernel times by; a run of each kind lowers exactly one."""
        real = getattr(scan_engine, entry)
        lowered = []

        def spy(*args, **kwargs):
            lowered.append(real.lower(*args, **kwargs))
            return real(*args, **kwargs)

        monkeypatch.setattr(scan_engine, entry, spy)
        _ENTRY_RUNS[entry](fed_data)
        assert len(lowered) == 1
        name = re.search(r"module @(\w+)", lowered[0].as_text()).group(1)
        assert name == f"jit_{entry}"
