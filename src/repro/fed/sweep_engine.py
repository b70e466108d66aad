"""Plan-reuse sweep engine: batched compiled runs over ONE fleet timeline.

FOLB's tuning knobs — lr, μ (prox weight), ψ (heterogeneity temperature),
the staleness discount α, the server-optimizer step size — are pure
learning-math scalars: they never touch device selection, the local-step
draws, or the simulated fleet timeline.  A hyper-parameter sweep therefore
shares everything that is expensive to build or compile:

  * the event plan (``async_engine.build_deadline_plan`` /
    ``build_fedbuff_plan``) and the pre-drawn key chain are built ONCE and
    replayed by every sweep member;
  * the learning math for all S configs runs in a SINGLE XLA program: the
    same per-round step functions the solo engines scan
    (``scan_engine.make_sync_round_step`` / ``make_deadline_step`` /
    ``make_fedbuff_step``, which call the shared jitted ``fl_round``,
    ``deadline_slow_step``, ``fedbuff_round_step`` and
    ``server_round_update``) are vmapped over a stacked (S, D) flat-param
    carry — plus the (S,)-stacked hypers and, for the async modes, the
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
import functools
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

@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh",))
def sweep_scan_rounds(model_cfg, fl, spec: flat_lib.FlatSpec, w0_S, data,
                      p_weights, keys, steps, hypers_S, sel_probs=None,
                      so_state0_S=None, up_mask=None, corrupt=None,
                      *, mesh=None):
    """The whole-sweep XLA program: one ``lax.scan`` over rounds whose
    body vmaps the SAME per-round step the solo scan uses
    (``scan_engine.make_sync_round_step``) over the stacked (S, D) carry
    and (S,) hypers.  Selection stays unbatched inside the vmap (keys and
    probs are shared), so every member samples the same devices — the
    shared-timeline property, asserted by ``out_axes=None`` on the ids.
    """
    use_so = so_state0_S is not None
    step = scan_engine.make_sync_round_step(
        model_cfg, fl, spec, use_so, data, p_weights, sel_probs, mesh)

    # ids stay unbatched (out_axes None asserts the shared timeline);
    # per-round metrics DO vary per member (deltas depend on lr/mu), so
    # with telemetry they come back stacked along the sweep axis
    extras_axes = {"ids": None}
    if fl.algo == "folb2":
        extras_axes["ids2"] = None
    if fl.telemetry:
        extras_axes["metrics"] = 0

    def body(carry, xs):
        w_S, so_S = carry if use_so else (carry, None)
        # the scenario mask/corruption rows are timeline-shared: one row
        # per round, closed over unbatched so every member drops (and
        # corrupts) the same uploads
        parts = list(xs)
        corr = parts.pop() if corrupt is not None else None
        um = parts.pop() if up_mask is not None else None
        sub, n_steps = parts
        vstep = jax.vmap(
            lambda w, so, h: step(w, so, sub, n_steps, h, um, corr),
            in_axes=(0, 0 if use_so else None, 0),
            out_axes=(0, 0 if use_so else None, extras_axes))
        w_new, so_S, extras = vstep(w_S, so_S, hypers_S)
        ys = {"params": w_new, **extras}
        return ((w_new, so_S) if use_so else w_new), ys

    carry0 = (w0_S, so_state0_S) if use_so else w0_S
    xs = (keys, steps)
    if up_mask is not None:
        xs = xs + (up_mask,)
    if corrupt is not None:
        xs = xs + (corrupt,)
    carry, ys = jax.lax.scan(body, carry0, xs)
    return (carry[0] if use_so else carry), ys


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
        if sc is None:
            keys, steps = scan_engine.draw_round_inputs(base, rounds, key)
            up_mask = sc_lat = corrupt = None
        else:
            sc_steps, sc_mask, sc_lat, sc_corr = \
                simulator.scenario_round_inputs(base, rounds, sc)
            keys = scan_engine._split_chain(key, rounds)
            steps = tprof.to_device(sc_steps)
            up_mask = tprof.to_device(sc_mask)
            corrupt = None if sc_corr is None \
                else tprof.to_device(sc_corr)
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
        w_final_S, ys = sweep_scan_rounds(
            model_cfg, base.timeline_config(), fspec, w0_S, train, p, keys,
            steps, spec.stacked_hypers(), sel_probs, so_state0_S, up_mask,
            corrupt, mesh=mesh)
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
                tprof.fetch(steps), rounds, lat_scale=sc_lat)
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

@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh", "always_slow"))
def sweep_scan_deadline(model_cfg, afl, spec: flat_lib.FlatSpec, w0_S,
                        pend0_S, data, p_weights, keys, ids, steps, arrived,
                        store_slot, due_slot, due_mask, due_tau, fast,
                        hypers_S, sel_probs=None, corrupt=None,
                        *, mesh=None, always_slow=False):
    """Whole-sweep deadline program: scan over the ONE shared event plan,
    vmapping ``scan_engine.make_deadline_step`` over the stacked carries
    (flat params + per-member straggler pools) and hypers.  ``corrupt``
    ((R, K) f32 payload factors) is timeline-shared: the per-round row is
    closed over unbatched so every member corrupts the same uploads.
    ``always_slow`` skips the step's cond (bit-identical when the plan
    has no fast rounds — see ``grid_scan_deadline``)."""
    step = scan_engine.make_deadline_step(model_cfg, afl, spec, data,
                                          p_weights, sel_probs, mesh,
                                          always_slow=always_slow)

    def body(carry, xs):
        w_S, pend_S = carry
        if corrupt is None:
            corr = None
        else:
            *xs, corr = xs
            xs = tuple(xs)
        if afl.telemetry:
            w_new, pend_S, m = jax.vmap(
                lambda w, pend, h: step(w, pend, xs, h, corr))(w_S, pend_S,
                                                               hypers_S)
            return (w_new, pend_S), {"params": w_new, "metrics": m}
        w_new, pend_S = jax.vmap(
            lambda w, pend, h: step(w, pend, xs, h, corr))(w_S, pend_S,
                                                           hypers_S)
        return (w_new, pend_S), w_new

    xs = (keys, ids, steps, arrived, store_slot, due_slot, due_mask,
          due_tau, fast)
    if corrupt is not None:
        xs = xs + (corrupt,)
    (w_final, _), ws = jax.lax.scan(body, (w0_S, pend0_S), xs)
    return w_final, ws


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh",))
def sweep_scan_fedbuff(model_cfg, afl, spec: flat_lib.FlatSpec, w0_S,
                       pend0_S, data, ids, steps, store_slot, flush_slot,
                       tau, hypers_S, flush_mask=None, corrupt=None,
                       *, mesh=None):
    """Whole-sweep fedbuff program: scan the shared flush schedule,
    vmapping ``scan_engine.make_fedbuff_step`` over the stacked carries
    (flat params + per-member in-flight pools) and hypers.
    ``flush_mask`` ((R, M) f32, the scenario drop channel) and ``corrupt``
    ((R, W) f32 payload factors) are timeline-shared: the per-round rows
    are closed over unbatched so every member drops/corrupts the same
    uploads."""
    step = scan_engine.make_fedbuff_step(model_cfg, afl, spec, data, mesh)

    def body(carry, xs):
        w_S, pend_S = carry
        parts = list(xs)
        corr = parts.pop() if corrupt is not None else None
        fm = parts.pop() if flush_mask is not None else None
        xs = tuple(parts)
        if afl.telemetry:
            w_new, pend_S, m = jax.vmap(
                lambda w, pend, h: step(w, pend, xs, h, fm, corr))(
                    w_S, pend_S, hypers_S)
            return (w_new, pend_S), {"params": w_new, "metrics": m}
        w_new, pend_S = jax.vmap(
            lambda w, pend, h: step(w, pend, xs, h, fm, corr))(w_S, pend_S,
                                                               hypers_S)
        return (w_new, pend_S), w_new

    xs = (ids, steps, store_slot, flush_slot, tau)
    if flush_mask is not None:
        xs = xs + (flush_mask,)
    if corrupt is not None:
        xs = xs + (corrupt,)
    (w_final, _), ws = jax.lax.scan(body, (w0_S, pend0_S), xs)
    return w_final, ws


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
            w_final_S, ws = sweep_scan_deadline(
                model_cfg, afl_t, fspec, w0_S, pend0_S, train, p,
                tprof.to_device(plan.keys), tprof.to_device(plan.ids),
                tprof.to_device(plan.n_steps),
                tprof.to_device(plan.arrived, jnp.float32),
                tprof.to_device(plan.store_slot),
                tprof.to_device(plan.due_slot),
                tprof.to_device(plan.due_mask), tprof.to_device(plan.due_tau),
                tprof.to_device(plan.fast), hypers_S, sel_probs,
                None if plan.corrupt is None
                else tprof.to_device(plan.corrupt), mesh=mesh,
                always_slow=not bool(np.asarray(plan.fast).any()))
            if base.telemetry or profiler is not None:
                jax.block_until_ready(ws)
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
                    model_cfg, afl_t, params, pend, train,
                    tprof.to_device(plan.seed_ids),
                    tprof.to_device(plan.seed_steps),
                    tprof.to_device(plan.seed_slots), h,
                    seed_corr))(bcast(pend0), hypers_S)
        with prof.phase("scan"):
            w_final_S, ws = sweep_scan_fedbuff(
                model_cfg, afl_t, fspec, w0_S, pend0_S, train,
                tprof.to_device(plan.ids), tprof.to_device(plan.n_steps),
                tprof.to_device(plan.store_slot),
                tprof.to_device(plan.flush_slot),
                tprof.to_device(plan.tau), hypers_S,
                None if plan.flush_mask is None
                else tprof.to_device(plan.flush_mask),
                None if plan.corrupt is None
                else tprof.to_device(plan.corrupt), mesh=mesh)
            if base.telemetry or profiler is not None:
                jax.block_until_ready(ws)
        clocks = plan.flush_clock
        n_arr = (np.full(rounds, base.buffer_size)
                 if plan.flush_mask is None
                 else plan.flush_mask.sum(axis=1).astype(np.int64))

    params_traj = ws["params"] if base.telemetry else ws
    with prof.phase("eval"):
        hists = scan_engine.eval_history_replay_sweep(
            model_cfg, fspec, train, test, p, params_traj, rounds,
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
                           for k, v in ws["metrics"].items()}
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
# The same shared round steps are vmapped — here over per-cell xs rows
# and per-cell pending pools, with the hypers closed over unbatched —
# so grid cell i stays bit-for-bit identical to a solo run under
# scenario i (tests/test_scenario_grid.py).

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


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh",))
def grid_scan_rounds(model_cfg, fl, spec: flat_lib.FlatSpec, w0_S, data,
                     p_weights, keys, steps_S, hypers, up_mask_S,
                     corrupt_S=None, sel_probs=None, so_state0_S=None,
                     *, mesh=None):
    """Whole-grid sync program: one ``lax.scan`` over rounds whose body
    vmaps ``scan_engine.make_sync_round_step`` over the S_scenario axis
    of the carry and the per-cell step/mask/corrupt rows.  Selection is
    scenario-independent (ids are drawn before the failure channels
    apply), so keys and hypers stay unbatched and ``out_axes=None`` on
    the ids structurally asserts the shared selection stream."""
    use_so = so_state0_S is not None
    step = scan_engine.make_sync_round_step(
        model_cfg, fl, spec, use_so, data, p_weights, sel_probs, mesh)

    extras_axes = {"ids": None}
    if fl.algo == "folb2":
        extras_axes["ids2"] = None
    if fl.telemetry:
        extras_axes["metrics"] = 0

    def body(carry, xs):
        w_S, so_S = carry if use_so else (carry, None)
        parts = list(xs)
        corr_S = parts.pop() if corrupt_S is not None else None
        sub, steps_t, um_t = parts
        vstep = jax.vmap(
            lambda w, so, ns, um, corr: step(w, so, sub, ns, hypers, um,
                                             corr),
            in_axes=(0, 0 if use_so else None, 0, 0,
                     0 if corrupt_S is not None else None),
            out_axes=(0, 0 if use_so else None, extras_axes))
        w_new, so_S, extras = vstep(w_S, so_S, steps_t, um_t, corr_S)
        ys = {"params": w_new, **extras}
        return ((w_new, so_S) if use_so else w_new), ys

    carry0 = (w0_S, so_state0_S) if use_so else w0_S
    xs = (keys, steps_S, up_mask_S)
    if corrupt_S is not None:
        xs = xs + (corrupt_S,)
    carry, ys = jax.lax.scan(body, carry0, xs)
    return (carry[0] if use_so else carry), ys


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh", "always_slow"))
def grid_scan_deadline(model_cfg, afl, spec: flat_lib.FlatSpec, w0_S,
                       pend0_S, data, p_weights, keys, ids_S, steps_S,
                       arrived_S, store_slot_S, due_slot_S, due_mask_S,
                       due_tau_S, fast_S, hypers, sel_probs=None,
                       corrupt_S=None, *, mesh=None, always_slow=False):
    """Whole-grid deadline program: scan the stacked plan, vmapping
    ``scan_engine.make_deadline_step`` over each cell's plan rows and
    straggler pool.  The round subkeys stay unbatched (one timeline
    config, one key chain); the per-cell ``fast`` flags lower the step's
    ``lax.cond`` to a select under vmap, which keeps the taken branch's
    values bit-identical to the solo scan's — but a select executes BOTH
    branches for every cell, so the driver passes ``always_slow=True``
    (skip the cond, bit-identical) whenever no cell has a fast round,
    which is the norm for active drop scenarios."""
    step = scan_engine.make_deadline_step(model_cfg, afl, spec, data,
                                          p_weights, sel_probs, mesh,
                                          always_slow=always_slow)

    def body(carry, xs):
        w_S, pend_S = carry
        sub = xs[0]
        rest = xs[1:]
        if corrupt_S is not None:
            *rest, corr = rest
            rest = tuple(rest)
        else:
            corr = None
        in_ax = (0, 0, 0, 0 if corrupt_S is not None else None)

        def one(w, pend, row, corr_c):
            return step(w, pend, (sub,) + row, hypers, corr_c)

        if afl.telemetry:
            w_new, pend_S, m = jax.vmap(one, in_axes=in_ax)(
                w_S, pend_S, rest, corr)
            return (w_new, pend_S), {"params": w_new, "metrics": m}
        w_new, pend_S = jax.vmap(one, in_axes=in_ax)(w_S, pend_S, rest, corr)
        return (w_new, pend_S), w_new

    xs = (keys, ids_S, steps_S, arrived_S, store_slot_S, due_slot_S,
          due_mask_S, due_tau_S, fast_S)
    if corrupt_S is not None:
        xs = xs + (corrupt_S,)
    (w_final, _), ws = jax.lax.scan(body, (w0_S, pend0_S), xs)
    return w_final, ws


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh",))
def grid_scan_fedbuff(model_cfg, afl, spec: flat_lib.FlatSpec, w0_S,
                      pend0_S, data, ids_S, steps_S, store_slot_S,
                      flush_slot_S, tau_S, hypers, flush_mask_S,
                      corrupt_S=None, *, mesh=None):
    """Whole-grid fedbuff program: scan the stacked flush schedule,
    vmapping ``scan_engine.make_fedbuff_step`` over each cell's dispatch
    rows and in-flight pool.  Active cells always carry a flush mask
    (the drop channel's per-flush validity), so it is a required
    per-cell operand here."""
    step = scan_engine.make_fedbuff_step(model_cfg, afl, spec, data, mesh)

    def body(carry, xs):
        w_S, pend_S = carry
        parts = list(xs)
        corr = parts.pop() if corrupt_S is not None else None
        fm = parts.pop()
        rest = tuple(parts)
        in_ax = (0, 0, 0, 0, 0 if corrupt_S is not None else None)

        def one(w, pend, row, fm_c, corr_c):
            return step(w, pend, row, hypers, fm_c, corr_c)

        if afl.telemetry:
            w_new, pend_S, m = jax.vmap(one, in_axes=in_ax)(
                w_S, pend_S, rest, fm, corr)
            return (w_new, pend_S), {"params": w_new, "metrics": m}
        w_new, pend_S = jax.vmap(one, in_axes=in_ax)(w_S, pend_S, rest, fm,
                                                     corr)
        return (w_new, pend_S), w_new

    xs = (ids_S, steps_S, store_slot_S, flush_slot_S, tau_S, flush_mask_S)
    if corrupt_S is not None:
        xs = xs + (corrupt_S,)
    (w_final, _), ws = jax.lax.scan(body, (w0_S, pend0_S), xs)
    return w_final, ws


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
        keys = scan_engine._split_chain(key, rounds)
        steps_S = _stack_to_rows(sc_steps)
        up_mask_S = _stack_to_rows(sc_mask)
        corrupt_S = None if sc_corr is None else _stack_to_rows(sc_corr)
        use_so = _uses_server_opt(fl)
        so_state0_S = None
        if use_so:
            so_cfg = sopt.ServerOptConfig(kind=fl.server_opt, lr=1.0)
            so0 = sopt.init_server_state(so_cfg, params)
            so_state0_S = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (S,) + x.shape), so0)
    with prof.phase("scan"):
        w_final_S, ys = grid_scan_rounds(
            model_cfg, fl.timeline_config(), fspec, w0_S, train, p, keys,
            steps_S, simulator.hypers_of(fl), up_mask_S, corrupt_S,
            sel_probs, so_state0_S, mesh=mesh)
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
            w_final_S, ws = grid_scan_deadline(
                model_cfg, afl_t, fspec, w0_S, pend0_S, train, p,
                tprof.to_device(gplan.keys), _stack_to_rows(gplan.ids),
                _stack_to_rows(gplan.n_steps),
                _stack_to_rows(gplan.arrived, jnp.float32),
                _stack_to_rows(gplan.store_slot),
                _stack_to_rows(gplan.due_slot),
                _stack_to_rows(gplan.due_mask),
                _stack_to_rows(gplan.due_tau),
                _stack_to_rows(gplan.fast), hypers, sel_probs,
                None if gplan.corrupt is None
                else _stack_to_rows(gplan.corrupt), mesh=mesh,
                always_slow=not bool(np.asarray(gplan.fast).any()))
            if afl.telemetry or profiler is not None:
                jax.block_until_ready(ws)
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
                    model_cfg, afl_t, params, pend, train, sids, ssteps,
                    sslots, hypers, scorr),
                in_axes=(0, 0, 0, 0,
                         0 if seed_corr is not None else None))(
                bcast(pend0), tprof.to_device(gplan.seed_ids),
                tprof.to_device(gplan.seed_steps),
                tprof.to_device(gplan.seed_slots), seed_corr)
        with prof.phase("scan"):
            w_final_S, ws = grid_scan_fedbuff(
                model_cfg, afl_t, fspec, w0_S, pend0_S, train,
                _stack_to_rows(gplan.ids), _stack_to_rows(gplan.n_steps),
                _stack_to_rows(gplan.store_slot),
                _stack_to_rows(gplan.flush_slot), _stack_to_rows(gplan.tau),
                hypers, _stack_to_rows(gplan.flush_mask),
                None if gplan.corrupt is None
                else _stack_to_rows(gplan.corrupt), mesh=mesh)
            if afl.telemetry or profiler is not None:
                jax.block_until_ready(ws)
        clocks_S = gplan.flush_clock
        n_arr_S = gplan.flush_mask.sum(axis=2).astype(np.int64)

    params_traj = ws["params"] if afl.telemetry else ws
    with prof.phase("eval"):
        hists = scan_engine.eval_history_replay_sweep(
            model_cfg, fspec, train, test, p, params_traj, rounds,
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
                           for k, v in ws["metrics"].items()}
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
