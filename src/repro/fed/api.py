"""One front door for every federated run: ``repro.fed.run``.

The repo grew six entry points — ``run_federated`` /
``run_federated_compiled`` (sync loop / scan), ``run_async`` /
``run_async_compiled`` (async loop / scan), and the two sweep drivers —
whose call sites had to know which engine matched which config and which
knobs each engine accepts.  ``run(...)`` dispatches on the *config type*
(``FLConfig`` vs ``AsyncFLConfig`` vs ``SweepSpec``) plus an ``engine``
selector, validates knob combinations up front with actionable errors,
and returns the same ``FedRunResult`` / ``SweepResult`` the underlying
engines produce — bit-for-bit, because it only forwards.

    from repro import fed
    res  = fed.run(MCLR, data, FLConfig(algo="folb"), rounds=100)
    res  = fed.run(MCLR, data, afl, rounds=50, fleet=fleet)   # async
    grid = fed.run(MCLR, data, SweepSpec.from_grid(fl, lr=(...)),
                   rounds=100, fleet=fleet)                    # sweep

Engine selection:

  * ``"auto"`` (default) — the compiled ``lax.scan`` engine, the fast
    path for every config type.
  * ``"scan"`` — explicitly the compiled engine.
  * ``"loop"`` — the python-loop reference engine (sync and async solo
    runs only; sweeps are scan-only by construction).

The six historical entry points remain importable from their home
modules and from here, but the ones re-exported by this module warn
``DeprecationWarning`` and forward unchanged — new code should call
``fed.run``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Mapping, Optional, Union

from repro.data.federated import LazyFederatedData as _LazyData
from repro.fed import async_engine as _async
from repro.fed import scan_engine as _scan
from repro.fed import simulator as _sim
from repro.fed import sweep_engine as _sweep
from repro.telemetry import profiler as _profiler

_ENGINES = ("auto", "loop", "scan")

RunConfig = Union[_sim.FLConfig, _async.AsyncFLConfig, _sweep.SweepSpec]


def _with_telemetry(cfg, telemetry: Optional[bool]):
    """telemetry=None respects the config; a bool overrides it."""
    if telemetry is None or cfg.telemetry == bool(telemetry):
        return cfg
    return dataclasses.replace(cfg, telemetry=bool(telemetry))


def _as_sweep_spec(cfg, sweep) -> _sweep.SweepSpec:
    """Normalize the (cfg, sweep=) combination to one SweepSpec."""
    if isinstance(cfg, _sweep.SweepSpec):
        if sweep is not None:
            raise ValueError(
                "pass the sweep either as cfg (a SweepSpec) or via "
                "sweep=, not both")
        return cfg
    if isinstance(sweep, _sweep.SweepSpec):
        if sweep.base != cfg:
            raise ValueError(
                "sweep= is a SweepSpec whose base config differs from "
                "cfg — pass the SweepSpec as cfg, or build it from this "
                "base with SweepSpec.from_grid(cfg, ...)")
        return sweep
    if isinstance(sweep, Mapping):
        # axes mapping: {"lr": (0.01, 0.1), "mu": (0.0, 1.0)}
        return _sweep.SweepSpec.from_grid(cfg, **sweep)
    raise ValueError(
        f"sweep= must be a SweepSpec or a mapping of sweepable axes "
        f"(e.g. {{'lr': (0.01, 0.1)}}), got {type(sweep).__name__}")


def run(model_cfg, fed, cfg: RunConfig, rounds: int, *,
        engine: str = "auto",
        sweep=None,
        fleet=None,
        plan=None,
        mesh=None,
        eval_every: int = 1,
        telemetry: Optional[bool] = None,
        scenario=None,
        key=None,
        sel_probs=None,
        profiler=None):
    """Run any federated configuration through the matching engine.

    Parameters
    ----------
    model_cfg, fed : the model config and ``FederatedData`` every engine
        takes as its first two arguments.  A ``LazyFederatedData``
        routes to the population-scale cohort engines (O(K) per-round
        cost at any fleet size; requires ``sampler="indexed"`` configs,
        and ``fleet`` may be a ``PopulationSpec``).
    cfg : ``FLConfig`` (sync), ``AsyncFLConfig`` (async), or
        ``SweepSpec`` (batched hyper-parameter sweep; its base config
        picks sync vs async).
    rounds : number of communication rounds (async: aggregations).
    engine : ``"auto"`` | ``"loop"`` | ``"scan"``.  ``auto`` resolves to
        the compiled scan engine.  ``loop`` is the python-loop reference
        engine — unavailable for sweeps.
    sweep : alternative way to request a sweep — a mapping of sweepable
        axes (``{"lr": (0.01, 0.1)}``, cross product via
        ``SweepSpec.from_grid``) or a pre-built ``SweepSpec`` whose base
        must equal ``cfg``.
    fleet : ``DeviceFleet``; required for async configs, optional for
        sync (enables the simulated wall clock).
    plan : pre-built async event plan (``async_engine.build_plan``) to
        replay; async scan/sweep engines only.
    mesh / eval_every / key / sel_probs / profiler : forwarded to the
        engine (``key`` is the ``init_key``).
    telemetry : None respects ``cfg.telemetry``; a bool overrides it
        (via ``dataclasses.replace``).
    scenario : ``repro.sysmodel.ScenarioConfig`` failure channels —
        including the payload-corruption channels (``nan_prob`` /
        ``scale_prob`` / ``flip_prob``); a RUN-level knob, applied
        identically by loop and scan engines.  A
        ``repro.sysmodel.ScenarioGrid`` batches S scenarios into ONE
        compiled program (scan engine, resident data only), each cell
        bit-for-bit its solo run.  The defense side is the config's
        ``guard`` field (``repro.kernels.GuardConfig``), which is
        static — jit-cache-keyed, never sweepable — and validated by
        the config itself (FOLB algos on the flat backend only).

    Returns ``FedRunResult`` for solo configs, ``SweepResult`` for
    sweeps, ``ScenarioGridResult`` for scenario grids.

    A call given a ``profiler`` records spans and counters
    (``repro.telemetry.profiler``) for its length, as does every call
    while recording is switched on; the spans of one call share a call
    id.
    """
    with _profiler.call(profiler):
        return _run(model_cfg, fed, cfg, rounds, engine=engine, sweep=sweep,
                    fleet=fleet, plan=plan, mesh=mesh,
                    eval_every=eval_every, telemetry=telemetry,
                    scenario=scenario, key=key, sel_probs=sel_probs,
                    profiler=profiler)


def _run(model_cfg, fed, cfg, rounds, *, engine, sweep, fleet, plan, mesh,
         eval_every, telemetry, scenario, key, sel_probs, profiler):
    if engine not in _ENGINES:
        raise ValueError(
            f"engine must be one of {_ENGINES}, got {engine!r}")
    scenario_grid = None
    if scenario is not None:
        from repro.sysmodel import scenario as _scenario_mod
        if isinstance(scenario, _scenario_mod.ScenarioGrid):
            scenario_grid, scenario = scenario, None
        elif not isinstance(scenario, _scenario_mod.ScenarioConfig):
            raise TypeError(
                f"scenario= must be a repro.sysmodel.ScenarioConfig "
                f"(failure-injection channels) or a ScenarioGrid "
                f"(batched cells), got "
                f"{type(scenario).__name__}; the defense knob is the "
                f"config's guard field (repro.kernels.GuardConfig)")

    if isinstance(fed, _LazyData):
        # population-scale path: O(K) per-round cost, shapes never in N
        from repro.fed import lazy_engine as _lazy
        if isinstance(cfg, _sweep.SweepSpec) or sweep is not None:
            raise ValueError(
                "lazy populations cannot run sweeps yet: the sweep "
                "engines vmap over resident (N, M, ...) stacks — "
                "materialize() the data, or run solo lazy runs per "
                "member")
        if scenario_grid is not None:
            raise ValueError(
                "lazy populations do not support scenario grids: the "
                "grid engine stacks resident per-cell event plans — "
                "materialize() the data, or run the cells solo on a "
                "resident dataset")
        if scenario is not None:
            # a null scenario is bit-invisible everywhere, including
            # here: only an ACTIVE scenario needs the resident plans
            from repro.sysmodel import scenario as _scenario_mod
            scenario = _scenario_mod.as_active(scenario)
        if scenario is not None:
            raise ValueError(
                "lazy populations do not support failure scenarios: "
                "the scenario channels are realized over resident "
                "plans — materialize() and use the resident engines")
        if sel_probs is not None:
            raise ValueError(
                "sel_probs= is an (N,)-vector knob, exactly the O(N) "
                "state lazy populations avoid — lazy runs use "
                "sampler='indexed' uniform selection")
        if engine == "loop":
            raise ValueError(
                "lazy populations run on the compiled cohort engines "
                "only (engine='scan'/'auto'): the python-loop "
                "reference engines gather from resident stacks — "
                "materialize() to compare against them")
        cfg = _with_telemetry(cfg, telemetry)
        if isinstance(cfg, _async.AsyncFLConfig):
            if fleet is None:
                raise ValueError(
                    "async configs need fleet=: pass the "
                    "PopulationSpec (or a DeviceFleet) the event "
                    "timeline is built from")
            return _lazy.run_async_lazy(
                model_cfg, fed, cfg, fleet, rounds, init_key=key,
                eval_every=eval_every, mesh=mesh, plan=plan,
                profiler=profiler)
        if not isinstance(cfg, _sim.FLConfig):
            raise TypeError(
                f"cfg must be FLConfig or AsyncFLConfig for lazy "
                f"populations, got {type(cfg).__name__}")
        if plan is not None:
            raise ValueError(
                "plan= is an async-engine knob (a pre-built event "
                "plan); sync runs have no event plan")
        return _lazy.run_federated_lazy(
            model_cfg, fed, cfg, rounds, init_key=key,
            eval_every=eval_every, fleet=fleet, mesh=mesh,
            profiler=profiler)

    if isinstance(cfg, _sweep.SweepSpec) or sweep is not None:
        if scenario_grid is not None:
            raise ValueError(
                "scenario grids cannot combine with hyper sweeps yet "
                "(the S_scenario x S_hyper cross product is a planned "
                "follow-on): run the grid once per sweep member, or the "
                "sweep once per scenario")
        spec = _as_sweep_spec(cfg, sweep)
        if engine == "loop":
            raise ValueError(
                "engine='loop' cannot run sweeps: the sweep engines are "
                "single compiled programs (that is the point) — use "
                "engine='scan'/'auto', or loop over spec.members() with "
                "solo run() calls")
        if telemetry is not None and spec.base.telemetry != bool(telemetry):
            spec = dataclasses.replace(
                spec, base=_with_telemetry(spec.base, telemetry))
        if isinstance(spec.base, _async.AsyncFLConfig):
            if fleet is None:
                raise ValueError(
                    "async sweeps need fleet=: the event timeline is "
                    "built from the device fleet "
                    "(repro.sysmodel.heterogeneous_fleet / uniform_fleet)")
            if sel_probs is not None:
                raise ValueError(
                    "sel_probs= is a sync-engine knob; the async "
                    "deadline engine derives its selection distribution "
                    "from the fleet (latency_aware) or uses uniform "
                    "sampling")
            return _sweep.run_async_sweep_compiled(
                model_cfg, fed, spec, fleet, rounds, init_key=key,
                eval_every=eval_every, mesh=mesh, plan=plan,
                profiler=profiler, scenario=scenario)
        if plan is not None:
            raise ValueError(
                "plan= is an async-engine knob (a pre-built event plan); "
                "sync sweeps draw their inputs from the config seed")
        return _sweep.run_sweep_compiled(
            model_cfg, fed, spec, rounds, init_key=key,
            eval_every=eval_every, fleet=fleet, sel_probs=sel_probs,
            mesh=mesh, profiler=profiler, scenario=scenario)

    if scenario_grid is not None:
        if engine == "loop":
            raise ValueError(
                "engine='loop' cannot run scenario grids: the grid "
                "engine is one compiled program (that is the point) — "
                "use engine='scan'/'auto', or loop over grid.cells with "
                "solo run() calls")
        if plan is not None:
            raise ValueError(
                "plan= cannot combine with a scenario grid: the grid "
                "builds one stacked plan per cell from its own scenario "
                "realizations")
        cfg = _with_telemetry(cfg, telemetry)
        if isinstance(cfg, _async.AsyncFLConfig):
            if fleet is None:
                raise ValueError(
                    "async configs need fleet=: the event timeline is "
                    "built from the device fleet "
                    "(repro.sysmodel.heterogeneous_fleet / uniform_fleet)")
            if sel_probs is not None:
                raise ValueError(
                    "sel_probs= is a sync-engine knob; the async "
                    "deadline engine derives its selection distribution "
                    "from the fleet (latency_aware) or uses uniform "
                    "sampling")
            return _sweep.run_async_scenario_grid_compiled(
                model_cfg, fed, cfg, scenario_grid, fleet, rounds,
                init_key=key, eval_every=eval_every, mesh=mesh,
                profiler=profiler)
        if not isinstance(cfg, _sim.FLConfig):
            raise TypeError(
                f"cfg must be FLConfig or AsyncFLConfig for a scenario "
                f"grid, got {type(cfg).__name__}")
        return _sweep.run_scenario_grid_compiled(
            model_cfg, fed, cfg, scenario_grid, rounds, init_key=key,
            eval_every=eval_every, fleet=fleet, sel_probs=sel_probs,
            mesh=mesh, profiler=profiler)

    if isinstance(cfg, _async.AsyncFLConfig):
        cfg = _with_telemetry(cfg, telemetry)
        if fleet is None:
            raise ValueError(
                "async configs need fleet=: the event timeline is built "
                "from the device fleet "
                "(repro.sysmodel.heterogeneous_fleet / uniform_fleet)")
        if sel_probs is not None:
            raise ValueError(
                "sel_probs= is a sync-engine knob; the async deadline "
                "engine derives its selection distribution from the "
                "fleet (latency_aware) or uses uniform sampling")
        if engine == "loop":
            return _async.run_async(
                model_cfg, fed, cfg, fleet, rounds, init_key=key,
                eval_every=eval_every, mesh=mesh, plan=plan,
                profiler=profiler, scenario=scenario)
        return _scan.run_async_compiled(
            model_cfg, fed, cfg, fleet, rounds, init_key=key,
            eval_every=eval_every, mesh=mesh, plan=plan,
            profiler=profiler, scenario=scenario)

    if isinstance(cfg, _sim.FLConfig):
        cfg = _with_telemetry(cfg, telemetry)
        if plan is not None:
            raise ValueError(
                "plan= is an async-engine knob (a pre-built event plan); "
                "sync runs have no event plan — drop it, or pass an "
                "AsyncFLConfig")
        if engine == "loop":
            return _sim.run_federated(
                model_cfg, fed, cfg, rounds, init_key=key,
                eval_every=eval_every, fleet=fleet, sel_probs=sel_probs,
                mesh=mesh, profiler=profiler, scenario=scenario)
        return _scan.run_federated_compiled(
            model_cfg, fed, cfg, rounds, init_key=key,
            eval_every=eval_every, fleet=fleet, sel_probs=sel_probs,
            mesh=mesh, profiler=profiler, scenario=scenario)

    raise TypeError(
        f"cfg must be FLConfig, AsyncFLConfig or SweepSpec, got "
        f"{type(cfg).__name__}")


# ------------------------------------------------- deprecated old names
#
# The historical per-engine entry points, re-exported with a
# DeprecationWarning.  They forward verbatim (same results bit-for-bit);
# the canonical implementations stay in their home modules.

def _deprecated(target, replacement: str):
    @functools.wraps(target)
    def wrapper(*args, **kwargs):
        warnings.warn(
            f"repro.fed.{target.__name__} is deprecated; use "
            f"repro.fed.run({replacement})", DeprecationWarning,
            stacklevel=2)
        return target(*args, **kwargs)
    return wrapper


run_federated = _deprecated(_sim.run_federated, "..., engine='loop'")
run_federated_compiled = _deprecated(_scan.run_federated_compiled, "...")
run_async = _deprecated(_async.run_async,
                        "..., fleet=fleet, engine='loop'")
run_async_compiled = _deprecated(_scan.run_async_compiled,
                                 "..., fleet=fleet")
run_sweep_compiled = _deprecated(_sweep.run_sweep_compiled,
                                 "..., sweep spec as cfg")
run_async_sweep_compiled = _deprecated(_sweep.run_async_sweep_compiled,
                                       "..., sweep spec as cfg")
