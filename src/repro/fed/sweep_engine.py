"""Plan-reuse sweep engine: batched compiled runs over ONE fleet timeline.

FOLB's tuning knobs — lr, μ (prox weight), ψ (heterogeneity temperature),
the staleness discount α, the server-optimizer step size — are pure
learning-math scalars: they never touch device selection, the local-step
draws, or the simulated fleet timeline.  A hyper-parameter sweep therefore
shares everything that is expensive to build or compile:

  * the event plan (``async_engine.build_deadline_plan`` /
    ``build_fedbuff_plan``) and the pre-drawn key chain are built ONCE and
    replayed by every sweep member;
  * the learning math for all S configs runs in a SINGLE XLA program:
    the one scan body of ``scan_engine`` with ``member="sweep"``, which
    vmaps the very per-round steps a solo run calls (the shared jitted
    ``fl_round``, ``deadline_slow_step``, ``fedbuff_round_step`` and
    ``server_round_update``) over a stacked (S, D) flat-param carry —
    plus the (S,)-stacked hypers and, for the async modes, the
    (S, P, ...) pending pools — inside one ``lax.scan`` over rounds.

Per-config host cost drops to ~zero (no per-member plan building, input
drawing, or dispatch) and the compile cost is amortized S-fold.  Because
the vmapped program applies the identical op sequence per member — the
sweepable scalars are traced *operands* everywhere (see
``simulator.SWEEPABLE_FIELDS``), never trace constants — sweep member i
is **bit-for-bit identical** to a solo ``run_federated_compiled`` /
``run_async_compiled`` run of config i: params, history, wall clock,
arrival counts, staleness means (property-tested across engines, grids
and agg dtypes in tests/test_sweep_engine.py).

The sweepable/timeline split is *enforced*: ``SweepSpec`` rejects any
override of a field that could alter the shared timeline or the traced
program structure (deadline, fleet seed, concurrency, K, algo, ...), so
future config fields cannot silently corrupt plan reuse.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flat as flat_lib
from repro.core import tuning
from repro.data.federated import FederatedData
from repro.fed import async_engine as async_lib
from repro.fed import scan_engine
from repro.fed import simulator
from repro.fed import server_opt as sopt
from repro.models import small
from repro.sysmodel import round_cost_for
from repro.telemetry import profiler as tprof

AnyConfig = Union[simulator.FLConfig, async_lib.AsyncFLConfig]

# selection of the fednu baselines depends on the current parameters, so
# sweep members would sample different devices — no shared timeline exists
_UNSWEEPABLE_ALGOS = ("fednu_direct", "fednu_signed", "fednu_norm")


def sweepable_fields(cfg: AnyConfig) -> Tuple[str, ...]:
    """The sweepable field set for a config instance (engine-dependent)."""
    if isinstance(cfg, async_lib.AsyncFLConfig):
        return async_lib.SWEEPABLE_FIELDS
    return simulator.SWEEPABLE_FIELDS


def _uses_server_opt(cfg: simulator.FLConfig) -> bool:
    return cfg.server_opt != "sgd" or cfg.server_lr != 1.0


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """S config variations of one base config, sharing one timeline.

    ``overrides`` holds one mapping per sweep member; keys must come from
    the engine's sweepable field set (``simulator.SWEEPABLE_FIELDS`` /
    ``async_engine.SWEEPABLE_FIELDS``).  Overriding any other field —
    deadline, seed, n_selected, concurrency, algo, agg dtype, ... —
    raises: those fields change the fleet timeline or the traced program
    structure, so they cannot vary inside one batched program.

    Build grids with ``SweepSpec.from_grid(base, lr=(...), mu=(...))``
    (cross product via ``core.tuning.sweep_grid``) or pass explicit
    member dicts.
    """
    base: AnyConfig
    overrides: Tuple[Mapping[str, float], ...]

    def __post_init__(self):
        if not self.overrides:
            raise ValueError("SweepSpec needs at least one member")
        object.__setattr__(self, "overrides",
                           tuple(dict(o) for o in self.overrides))
        allowed = set(sweepable_fields(self.base))
        for i, o in enumerate(self.overrides):
            bad = set(o) - allowed
            if bad:
                raise ValueError(
                    f"member {i} sweeps non-sweepable field(s) "
                    f"{sorted(bad)}: these are timeline-affecting or "
                    f"program-static — only {sorted(allowed)} may vary "
                    f"within one sweep")
        if self.base.algo in _UNSWEEPABLE_ALGOS:
            raise ValueError(
                f"algo {self.base.algo!r} derives its selection "
                f"distribution from the current parameters — sweep "
                f"members would sample different devices and share no "
                f"timeline")
        if isinstance(self.base, simulator.FLConfig):
            # server_opt='sgd' with server_lr == 1.0 runs a structurally
            # different program (no optimizer state in the carry); a sweep
            # is one program, so the predicate must agree across members
            flags = {_uses_server_opt(m) for m in self.members()}
            if len(flags) > 1:
                raise ValueError(
                    "server_lr sweep mixes the plain path (sgd @ lr=1.0) "
                    "with the server-optimizer path — use a non-sgd "
                    "server_opt or keep every member's server_lr != 1.0")

    @classmethod
    def from_grid(cls, base: AnyConfig, **axes: Sequence[float]
                  ) -> "SweepSpec":
        """Cross-product grid over named sweepable axes."""
        return cls(base=base, overrides=tuning.sweep_grid(**axes))

    @property
    def n_configs(self) -> int:
        return len(self.overrides)

    def member(self, i: int) -> AnyConfig:
        """The full config of sweep member i (for solo parity runs)."""
        return dataclasses.replace(self.base, **self.overrides[i])

    def members(self) -> Tuple[AnyConfig, ...]:
        return tuple(self.member(i) for i in range(self.n_configs))

    def stacked_hypers(self) -> dict:
        """The (S,)-stacked traced-operand view of every sweepable field
        (base value where a member doesn't override) — the axis the sweep
        programs vmap over."""
        return {
            name: tprof.to_device(
                [float(o.get(name, getattr(self.base, name)))
                 for o in self.overrides], jnp.float32)
            for name in sweepable_fields(self.base)}


@dataclasses.dataclass
class SweepResult:
    """One ``FedRunResult`` per sweep member, plus the spec that made
    them.  Timeline quantities (wall clock, n_arrived, stale_mean, ids)
    are identical across members by construction.  With the base config's
    ``telemetry`` on, each member result carries its own (R, ·) metrics
    slice of the (R, S, ·) stacked scan outputs, and `profile` holds the
    run-level host-phase timer summary (one compiled run serves all S)."""
    spec: SweepSpec
    results: Tuple[simulator.FedRunResult, ...]
    profile: Optional[dict] = None

    def __len__(self) -> int:
        return len(self.results)

    def __getitem__(self, i: int) -> simulator.FedRunResult:
        return self.results[i]

    def __iter__(self):
        return iter(self.results)


# ----------------------------------------------------------- sync sweeps

def run_sweep_compiled(model_cfg, fed: FederatedData, spec: SweepSpec,
                       rounds: int,
                       init_key: Optional[jax.Array] = None,
                       eval_every: int = 1, fleet=None, sel_probs=None,
                       mesh=None, profiler=None,
                       scenario=None) -> SweepResult:
    """All S sync configs of ``spec`` in one compiled run.

    Every member's result is bit-for-bit what a solo
    ``run_federated_compiled(model_cfg, fed, spec.member(i), ...)`` (and
    hence the python loop) produces — params, history, and the fleet
    wall-clock, which is computed once and shared since all members
    sample identical devices.

    ``scenario`` is a RUN-level knob (never sweepable): one realization
    of the failure channels is folded into the shared timeline and
    replayed identically by every member.
    """
    from repro.telemetry import metrics as tmetrics
    from repro.telemetry import profiler_for
    base = spec.base
    assert isinstance(base, simulator.FLConfig), \
        "run_sweep_compiled takes an FLConfig sweep; use " \
        "run_async_sweep_compiled for AsyncFLConfig"
    prof = profiler_for(base.telemetry, profiler)
    from repro.sysmodel import scenario as scenario_mod
    sc = scenario_mod.as_active(scenario)
    if sc is not None:
        scenario_mod.check_sync(sc)
    with prof.phase("setup"):
        S = spec.n_configs
        key = init_key if init_key is not None \
            else jax.random.PRNGKey(base.seed)
        params = small.init_small(model_cfg, key)
        train, test, p = simulator.device_arrays(fed)
        fspec = flat_lib.spec_of(params)
        w0 = flat_lib.ravel(fspec, params)
        w0_S = jnp.broadcast_to(w0, (S,) + w0.shape)
    with prof.phase("plan_build"):
        xs, sc_lat = scan_engine.sync_round_xs(base, rounds, key, sc)
        # uniform across members (SweepSpec validates), so member 0
        # decides — the same predicate each member's solo run applies
        use_so = _uses_server_opt(spec.member(0))
        so_state0_S = None
        if use_so:
            so_cfg = sopt.ServerOptConfig(kind=base.server_opt, lr=1.0)
            so0 = sopt.init_server_state(so_cfg, params)
            so_state0_S = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape), so0)
    with prof.phase("scan"):
        w_final_S, ys = scan_engine.scan_rounds(
            model_cfg, base.timeline_config(), fspec, (w0_S, so_state0_S),
            xs, spec.stacked_hypers(), train, p, sel_probs, mesh=mesh,
            member="sweep")
        if base.telemetry or profiler is not None:
            # an explicit profiler wants honest phase attribution: block
            # here so the async scan's compute doesn't land in `eval`
            jax.block_until_ready(ys)

    with prof.phase("eval"):
        clocks = None
        if fleet is not None:
            assert fleet.n_devices == fed.n_devices, \
                (fleet.n_devices, fed.n_devices)
            clocks = scan_engine.sync_clock_replay(
                model_cfg, params, fed, base.algo, fleet,
                tprof.fetch(ys["ids"]),
                tprof.fetch(ys["ids2"]) if "ids2" in ys else None,
                tprof.fetch(xs["steps"]), rounds, lat_scale=sc_lat)
        hists = scan_engine.eval_history_replay_sweep(
            model_cfg, fspec, train, test, p, ys["params"], rounds,
            eval_every, clocks)
    with prof.phase("collect"):
        ids_np = tprof.fetch(ys["ids"])
        shared = None
        if base.telemetry:
            # the network series and selection entropy are timeline-only —
            # one copy serves every member
            D = int(sum(x.size for x in jax.tree.leaves(params)))
            shared = tmetrics.sync_network_series(D, base, rounds,
                                                  fed.n_devices)
            shared["selection_entropy"] = tmetrics.selection_entropy(
                ids_np, fed.n_devices)
        results = []
        for i in range(S):
            metrics = None
            if base.telemetry:
                metrics = {k: tprof.fetch(v[:, i])
                           for k, v in ys["metrics"].items()}
                metrics.update(shared)
            results.append(simulator.FedRunResult(
                history=hists[i],
                params=flat_lib.unravel(fspec, w_final_S[i]),
                ids=ids_np, metrics=metrics))
    return SweepResult(spec=spec, results=tuple(results),
                       profile=prof.finish())


# ---------------------------------------------------------- async sweeps

def run_async_sweep_compiled(model_cfg, fed: FederatedData,
                             spec: SweepSpec, fleet, rounds: int,
                             init_key: Optional[jax.Array] = None,
                             eval_every: int = 1, mesh=None,
                             plan=None, profiler=None,
                             scenario=None) -> SweepResult:
    """All S async configs of ``spec`` against ONE event plan.

    The plan (and the pre-drawn key chain inside it) is built once from
    the base config — sweepable fields provably cannot move it — and
    replayed for every member inside a single compiled scan.  Member i is
    bit-for-bit identical to a solo ``run_async_compiled`` (and hence
    ``run_async``) with config i: params, wall clock, n_arrived,
    stale_mean.  ``plan`` accepts a pre-built ``async_engine.build_plan``
    value for reuse across calls.  ``scenario`` (RUN-level, never
    sweepable) folds one failure-channel realization into the freshly
    built plan, shared by every member; it is ignored when ``plan=`` is
    supplied.
    """
    from repro.telemetry import metrics as tmetrics
    from repro.telemetry import profiler_for
    base = spec.base
    assert isinstance(base, async_lib.AsyncFLConfig), \
        "run_async_sweep_compiled takes an AsyncFLConfig sweep; use " \
        "run_sweep_compiled for FLConfig"
    assert fleet.n_devices == fed.n_devices, (fleet.n_devices, fed.n_devices)
    prof = profiler_for(base.telemetry, profiler)
    with prof.phase("setup"):
        S = spec.n_configs
        key = init_key if init_key is not None \
            else jax.random.PRNGKey(base.seed)
        params = small.init_small(model_cfg, key)
        train, test, p = simulator.device_arrays(fed)
        sizes = np.asarray(fed.mask.sum(axis=1))
        cost = round_cost_for(model_cfg, params,
                              uploads_gradient="folb" in base.algo)
        afl_t = base.timeline_config()
        sync_fl = afl_t.sync_config()
        hypers_S = spec.stacked_hypers()
        fspec = flat_lib.spec_of(params)
        w0 = flat_lib.ravel(fspec, params)
        w0_S = jnp.broadcast_to(w0, (S,) + w0.shape)
    bcast = lambda tree_: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (S,) + x.shape), tree_)

    if base.mode == "deadline":
        with prof.phase("plan_build"):
            sel_probs = async_lib.deadline_selection_probs(base, fleet,
                                                           cost, sizes)
            if plan is None:
                plan = async_lib.build_deadline_plan(base, fleet, cost,
                                                     sizes, rounds, key,
                                                     sel_probs,
                                                     scenario=scenario)
            pend0_S = bcast(async_lib.pool_init(model_cfg, sync_fl, params,
                                                train, plan.n_slots + 1))
        with prof.phase("scan"):
            w_final_S, ys = scan_engine.scan_deadline_cohort(
                model_cfg, afl_t, fspec, (w0_S, pend0_S),
                scan_engine.deadline_xs(plan), hypers_S, train, p, sel_probs,
                mesh=mesh, member="sweep",
                always_slow=not bool(np.asarray(plan.fast).any()))
            if base.telemetry or profiler is not None:
                jax.block_until_ready(ys)
        clocks, n_arr = plan.round_end, plan.n_arrived
    else:
        with prof.phase("plan_build"):
            if plan is None:
                plan = async_lib.build_fedbuff_plan(base, fleet, cost,
                                                    sizes, rounds, key,
                                                    scenario=scenario)
            pend0 = async_lib.pool_init(model_cfg, sync_fl, params, train,
                                        plan.n_slots)
            # the seed dispatches all start from the SAME initial params
            # but member-specific lr/mu: vmap the shared jitted seeding step
            seed_corr = (None if plan.seed_corrupt is None
                         else tprof.to_device(plan.seed_corrupt))
            pend0_S = jax.vmap(
                lambda pend, h: async_lib.fedbuff_seed_pool(
                    model_cfg, afl_t, params, pend,
                    simulator._client_batch(
                        train, tprof.to_device(plan.seed_ids)),
                    tprof.to_device(plan.seed_steps),
                    tprof.to_device(plan.seed_slots), h,
                    seed_corr))(bcast(pend0), hypers_S)
        with prof.phase("scan"):
            w_final_S, ys = scan_engine.scan_fedbuff_cohort(
                model_cfg, afl_t, fspec, (w0_S, pend0_S),
                scan_engine.fedbuff_xs(plan), hypers_S, train, mesh=mesh,
                member="sweep")
            if base.telemetry or profiler is not None:
                jax.block_until_ready(ys)
        clocks = plan.flush_clock
        n_arr = (np.full(rounds, base.buffer_size)
                 if plan.flush_mask is None
                 else plan.flush_mask.sum(axis=1).astype(np.int64))

    with prof.phase("eval"):
        hists = scan_engine.eval_history_replay_sweep(
            model_cfg, fspec, train, test, p, ys["params"], rounds,
            eval_every, clocks=clocks, n_arrived=n_arr,
            stale_mean=plan.stale_mean)
    with prof.phase("collect"):
        shared = None
        if base.telemetry:
            # network traffic and pool occupancy are plan-derived — the
            # whole point of the sweep is that the plan is shared
            D = int(sum(x.size for x in jax.tree.leaves(params)))
            if base.mode == "deadline":
                shared = tmetrics.deadline_network_series(D, base, plan)
                shared.update(tmetrics.deadline_pool_series(plan))
            else:
                shared = tmetrics.fedbuff_network_series(D, base, plan)
            shared["selection_entropy"] = tmetrics.selection_entropy(
                np.asarray(plan.ids).reshape(-1), fed.n_devices)
        results = []
        for i in range(S):
            metrics = None
            if base.telemetry:
                metrics = {k: tprof.fetch(v[:, i])
                           for k, v in ys["metrics"].items()}
                metrics.update(shared)
            results.append(simulator.FedRunResult(
                history=hists[i],
                params=flat_lib.unravel(fspec, w_final_S[i]),
                ids=np.asarray(plan.ids), metrics=metrics))
    return SweepResult(spec=spec, results=tuple(results),
                       profile=prof.finish())


# ------------------------------------------------------- scenario grids
#
# The dual of the hyper sweep: a hyper sweep varies the learning math
# over ONE shared timeline, a scenario grid varies the TIMELINE (failure
# realizations, hence masks/arrivals/pools) under one learning config.
# The same scan body runs with member="grid": the round steps are vmapped
# over per-cell xs rows and per-cell pending pools, with the round keys
# and hypers shared, so grid cell i stays bit-for-bit identical to a solo
# run under scenario i (tests/test_scenario_grid.py).

@dataclasses.dataclass
class ScenarioGridResult:
    """One ``FedRunResult`` per grid cell, plus the grid that made them.
    ``plan_digests`` (async modes) are each cell's solo plan digest —
    identical to an independent solo build's, since the grid builders
    construct the per-cell plans with the solo builders."""
    grid: "object"
    results: Tuple[simulator.FedRunResult, ...]
    plan_digests: Optional[Tuple[str, ...]] = None
    profile: Optional[dict] = None

    def __len__(self) -> int:
        return len(self.results)

    def __getitem__(self, i: int) -> simulator.FedRunResult:
        return self.results[i]

    def __iter__(self):
        return iter(self.results)


def _stack_to_rows(a, dtype=None):
    """(S, R, ...) plan array -> (R, S, ...) scan xs."""
    return tprof.to_device(np.moveaxis(np.asarray(a), 0, 1), dtype)


def run_scenario_grid_compiled(model_cfg, fed: FederatedData,
                               fl: simulator.FLConfig, grid, rounds: int,
                               init_key: Optional[jax.Array] = None,
                               eval_every: int = 1, fleet=None,
                               sel_probs=None, mesh=None,
                               profiler=None) -> ScenarioGridResult:
    """All S sync scenarios of ``grid`` in one compiled run.

    Cell i's result is bit-for-bit what a solo
    ``run_federated_compiled(..., scenario=grid[i])`` produces: params,
    history including the per-cell wall-clock replay (each cell's jitter
    realization times its own clock), and byte accounting (sync network
    series are timeline-length-only, hence cell-independent)."""
    from repro.sysmodel import scenario as scenario_mod
    from repro.telemetry import metrics as tmetrics
    from repro.telemetry import profiler_for
    if fl.algo in _UNSWEEPABLE_ALGOS:
        raise ValueError(
            f"algo {fl.algo!r} derives its selection distribution from "
            f"the current parameters — grid cells diverge after round 1, "
            f"so no shared selection stream exists; run the cells solo")
    for c in grid.cells:
        scenario_mod.check_sync(c)
    prof = profiler_for(fl.telemetry, profiler)
    with prof.phase("setup"):
        S = len(grid)
        key = init_key if init_key is not None \
            else jax.random.PRNGKey(fl.seed)
        params = small.init_small(model_cfg, key)
        train, test, p = simulator.device_arrays(fed)
        fspec = flat_lib.spec_of(params)
        w0 = flat_lib.ravel(fspec, params)
        w0_S = jnp.broadcast_to(w0, (S,) + w0.shape)
    with prof.phase("plan_build"):
        sc_steps, sc_mask, sc_lat, sc_corr = \
            simulator.scenario_grid_round_inputs(fl, rounds, grid)
        xs = {"key": scan_engine._split_chain(key, rounds),
              "steps": _stack_to_rows(sc_steps),
              "up_mask": _stack_to_rows(sc_mask)}
        if sc_corr is not None:
            xs["corrupt"] = _stack_to_rows(sc_corr)
        use_so = _uses_server_opt(fl)
        so_state0_S = None
        if use_so:
            so_cfg = sopt.ServerOptConfig(kind=fl.server_opt, lr=1.0)
            so0 = sopt.init_server_state(so_cfg, params)
            so_state0_S = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape), so0)
    with prof.phase("scan"):
        w_final_S, ys = scan_engine.scan_rounds(
            model_cfg, fl.timeline_config(), fspec, (w0_S, so_state0_S), xs,
            simulator.hypers_of(fl), train, p, sel_probs, mesh=mesh,
            member="grid")
        if fl.telemetry or profiler is not None:
            jax.block_until_ready(ys)
    with prof.phase("eval"):
        clocks_S = None
        if fleet is not None:
            assert fleet.n_devices == fed.n_devices, \
                (fleet.n_devices, fed.n_devices)
            ids_all = tprof.fetch(ys["ids"])
            ids2_all = tprof.fetch(ys["ids2"]) if "ids2" in ys else None
            # per-cell clock replay: each cell's completeness-scaled steps
            # and jitter realization time its own wall clock (jitter-free
            # cells take the exact lat_scale=None host path a solo run
            # takes)
            clocks_S = np.stack([
                scan_engine.sync_clock_replay(
                    model_cfg, params, fed, fl.algo, fleet, ids_all,
                    ids2_all, np.asarray(sc_steps[i]), rounds,
                    lat_scale=None if grid[i].jitter_sigma == 0.0
                    else sc_lat[i])
                for i in range(S)])
        hists = scan_engine.eval_history_replay_sweep(
            model_cfg, fspec, train, test, p, ys["params"], rounds,
            eval_every, clocks_S)
    with prof.phase("collect"):
        ids_np = tprof.fetch(ys["ids"])
        shared = None
        if fl.telemetry:
            # bytes are spent whether or not an upload decodes, so the
            # sync network series depend only on the timeline length —
            # one copy is exactly each cell's solo series
            D = int(sum(x.size for x in jax.tree.leaves(params)))
            shared = tmetrics.sync_network_series(D, fl, rounds,
                                                  fed.n_devices)
            shared["selection_entropy"] = tmetrics.selection_entropy(
                ids_np, fed.n_devices)
        results = []
        for i in range(S):
            metrics = None
            if fl.telemetry:
                metrics = {k: tprof.fetch(v[:, i])
                           for k, v in ys["metrics"].items()}
                metrics.update(shared)
            results.append(simulator.FedRunResult(
                history=hists[i],
                params=flat_lib.unravel(fspec, w_final_S[i]),
                ids=ids_np, metrics=metrics))
    return ScenarioGridResult(grid=grid, results=tuple(results),
                              profile=prof.finish())


def run_async_scenario_grid_compiled(model_cfg, fed: FederatedData, afl,
                                     grid, fleet, rounds: int,
                                     init_key: Optional[jax.Array] = None,
                                     eval_every: int = 1, mesh=None,
                                     profiler=None) -> ScenarioGridResult:
    """All S async scenarios of ``grid`` against stacked per-cell plans.

    The grid plan builders construct each cell's plan with the solo
    builders (``plan_digests[i]`` IS the solo digest), pad the
    data-dependent widths to the grid max with bit-inert rows, and stack;
    one compiled scan then replays every cell.  Cell i is bit-for-bit a
    solo ``run_async_compiled(..., scenario=grid[i])``: params, wall
    clock, arrival counts, staleness means, and the per-cell plan-derived
    byte accounting."""
    from repro.telemetry import metrics as tmetrics
    from repro.telemetry import profiler_for
    assert isinstance(afl, async_lib.AsyncFLConfig), \
        "run_async_scenario_grid_compiled takes an AsyncFLConfig; use " \
        "run_scenario_grid_compiled for FLConfig"
    assert fleet.n_devices == fed.n_devices, (fleet.n_devices, fed.n_devices)
    prof = profiler_for(afl.telemetry, profiler)
    with prof.phase("setup"):
        S = len(grid)
        key = init_key if init_key is not None \
            else jax.random.PRNGKey(afl.seed)
        params = small.init_small(model_cfg, key)
        train, test, p = simulator.device_arrays(fed)
        sizes = np.asarray(fed.mask.sum(axis=1))
        cost = round_cost_for(model_cfg, params,
                              uploads_gradient="folb" in afl.algo)
        afl_t = afl.timeline_config()
        sync_fl = afl_t.sync_config()
        hypers = async_lib.hypers_of(afl)
        fspec = flat_lib.spec_of(params)
        w0 = flat_lib.ravel(fspec, params)
        w0_S = jnp.broadcast_to(w0, (S,) + w0.shape)
    bcast = lambda tree_: jax.tree.map(
        lambda x: jnp.broadcast_to(x, (S,) + x.shape), tree_)

    if afl.mode == "deadline":
        with prof.phase("plan_build"):
            sel_probs = async_lib.deadline_selection_probs(afl, fleet,
                                                           cost, sizes)
            gplan = async_lib.build_deadline_plan_grid(
                afl, fleet, cost, sizes, rounds, key, grid, sel_probs)
            pend0_S = bcast(async_lib.pool_init(
                model_cfg, sync_fl, params, train, gplan.n_slots + 1))
        with prof.phase("scan"):
            w_final_S, ys = scan_engine.scan_deadline_cohort(
                model_cfg, afl_t, fspec, (w0_S, pend0_S),
                scan_engine.deadline_xs(gplan, put=_stack_to_rows), hypers,
                train, p, sel_probs, mesh=mesh, member="grid",
                always_slow=not bool(np.asarray(gplan.fast).any()))
            if afl.telemetry or profiler is not None:
                jax.block_until_ready(ys)
        clocks_S, n_arr_S = gplan.round_end, gplan.n_arrived
    else:
        with prof.phase("plan_build"):
            gplan = async_lib.build_fedbuff_plan_grid(
                afl, fleet, cost, sizes, rounds, key, grid)
            pend0 = async_lib.pool_init(model_cfg, sync_fl, params, train,
                                        gplan.n_slots)
            seed_corr = (None if gplan.seed_corrupt is None
                         else tprof.to_device(gplan.seed_corrupt))
            # every cell seeds from the same initial params but its own
            # dispatch stream: vmap the shared jitted seeding step over
            # the per-cell seed rows
            pend0_S = jax.vmap(
                lambda pend, sids, ssteps, sslots, scorr:
                async_lib.fedbuff_seed_pool(
                    model_cfg, afl_t, params, pend,
                    simulator._client_batch(train, sids), ssteps, sslots,
                    hypers, scorr),
                in_axes=(0, 0, 0, 0,
                         0 if seed_corr is not None else None))(
                bcast(pend0), tprof.to_device(gplan.seed_ids),
                tprof.to_device(gplan.seed_steps),
                tprof.to_device(gplan.seed_slots), seed_corr)
        with prof.phase("scan"):
            w_final_S, ys = scan_engine.scan_fedbuff_cohort(
                model_cfg, afl_t, fspec, (w0_S, pend0_S),
                scan_engine.fedbuff_xs(gplan, put=_stack_to_rows), hypers,
                train, mesh=mesh, member="grid")
            if afl.telemetry or profiler is not None:
                jax.block_until_ready(ys)
        clocks_S = gplan.flush_clock
        n_arr_S = gplan.flush_mask.sum(axis=2).astype(np.int64)

    with prof.phase("eval"):
        hists = scan_engine.eval_history_replay_sweep(
            model_cfg, fspec, train, test, p, ys["params"], rounds,
            eval_every, clocks=clocks_S, n_arrived=n_arr_S,
            stale_mean=gplan.stale_mean)
    with prof.phase("collect"):
        D = int(sum(x.size for x in jax.tree.leaves(params)))
        results = []
        for i in range(S):
            plan_i = gplan.plans[i]
            metrics = None
            if afl.telemetry:
                # network/pool series are plan-derived and per-cell: each
                # cell's solo plan yields exactly its solo series
                metrics = {k: tprof.fetch(v[:, i])
                           for k, v in ys["metrics"].items()}
                if afl.mode == "deadline":
                    metrics.update(tmetrics.deadline_network_series(
                        D, afl, plan_i))
                    metrics.update(tmetrics.deadline_pool_series(plan_i))
                else:
                    metrics.update(tmetrics.fedbuff_network_series(
                        D, afl, plan_i))
                metrics["selection_entropy"] = tmetrics.selection_entropy(
                    plan_i.ids, fed.n_devices)
            results.append(simulator.FedRunResult(
                history=hists[i],
                params=flat_lib.unravel(fspec, w_final_S[i]),
                ids=np.asarray(plan_i.ids), metrics=metrics))
    return ScenarioGridResult(
        grid=grid, results=tuple(results),
        plan_digests=tuple(async_lib.plan_digest(p) for p in gplan.plans),
        profile=prof.finish())
