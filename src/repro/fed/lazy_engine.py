"""Population-scale federated engines: O(K) per-round cost at any N.

The resident engines hold every device's data as an (N, M, ...) stack
and (for selection) an (N,) probability vector, so host plan-build cost,
device memory, and compiled-program shapes all grow with the fleet.  At
production scale (K ≈ 10–100 sampled from N ≈ 10⁶) almost all of that is
wasted: a run only ever touches the ~R·K dispatched devices.

These engines take the lazy descriptions instead — a
``repro.sysmodel.PopulationSpec`` (generative fleet) and a
``repro.data.LazyFederatedData`` (generative per-device datasets) — and
restructure the run so nothing scales with N:

  * selection uses ``sampler="indexed"`` (O(K) uniform id draws, no (N,)
    vector) — the plan's pre-drawn ``(R, K)`` id grid is the only record
    of who participates;
  * the host gathers the ``(R, K, M, ...)`` cohort batches once, up
    front, and the scan consumes them as its ``"batch"`` rows: the one
    scan body of ``scan_engine`` runs the same mode steps as a resident
    run, which read the batch from the row in place of gathering from
    resident stacks, so the traced programs (``scan_engine.scan_rounds``
    / ``scan_deadline_cohort`` / ``scan_fedbuff_cohort`` over
    ``simulator.fl_round_cohort``, ``async_engine.deadline_slow_step``,
    ``fedbuff_round_step``) have shapes in K, R and the pool width only;
  * plan builders run on the lazy gather protocol
    (``PopulationSpec.gather_caps`` / ``gather_avail`` /
    ``LazyFederatedData.sizes``), so event-plan construction is O(R·K);
  * global evaluation runs over ``data.eval_ids()`` — everyone at small
    N, a bounded stride cohort (``eval_cohort``) at population scale.

Equivalence contract (tests/test_population.py): on the SAME config with
``sampler="indexed"``, a lazy run and a resident run over
``spec.materialize()`` / ``data.materialize()`` produce bit-for-bit
identical params, history, wall clocks, and plan digests — the lazy
gathers are literally rows of the materialized arrays, and the round
math runs the same shared units (``_local_updates_batch``,
``_sync_aggregate``) and the very step functions of the resident runs.

Scope: cohort-shaped algorithms only (``simulator.COHORT_ALGOS`` — the
all-N-scoring fednu baselines and folb2's second draw are inherently
O(N)), no telemetry, no failure scenarios; the validations raise with
the resident-engine alternative spelled out.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.core import flat as flat_lib
from repro.data.federated import LazyFederatedData
from repro.fed import async_engine as async_lib
from repro.fed import scan_engine
from repro.fed import server_opt as sopt
from repro.fed import simulator
from repro.models import small
from repro.sysmodel import round_cost_for
from repro.telemetry import profiler as tprof


def _check_lazy_config(cfg, kind: str) -> None:
    """The lazy engines' envelope, with actionable errors."""
    if cfg.sampler != "indexed":
        raise ValueError(
            f"lazy {kind} runs need sampler='indexed': the categorical "
            f"sampler draws from an (N,) probability vector, which is "
            f"exactly the O(N) state lazy populations exist to avoid — "
            f"set sampler='indexed' on the config (a different, "
            f"self-consistent id timeline), or materialize() the "
            f"population and use the resident engines")
    if cfg.algo not in simulator.COHORT_ALGOS:
        raise ValueError(
            f"lazy runs support the cohort-shaped algorithms "
            f"{simulator.COHORT_ALGOS}, not {cfg.algo!r}: fednu* probes "
            f"every device's gradient and folb2 draws a second scored "
            f"cohort — both inherently O(N); materialize() for those")
    if cfg.telemetry:
        raise ValueError(
            "lazy runs do not support telemetry=True yet (the network/"
            "pool series assume a resident plan over a materialized "
            "fleet); run with telemetry=False, or materialize()")


def _eval_arrays(data: LazyFederatedData):
    """Gather the evaluation cohort once: train/test batches plus the
    size weights, computed from the gathered mask exactly as
    ``materialize()`` computes ``fed.p`` — so at ``eval_cohort=None``
    and small N the arrays (and every eval result) are bit-for-bit the
    resident engines' inputs."""
    with tprof.span("eval/cohort"):
        d = data.gather(data.eval_ids())
        sizes = d["mask"].sum(axis=1)
        p = (sizes / sizes.sum()).astype(np.float32)
        return _to_device(d), _to_device(d, "test_"), tprof.to_device(p)


def _to_device(d, prefix: str = ""):
    """The gathered ``<prefix>x`` / ``y`` / ``mask`` arrays of ``d`` as
    ``{"x", "y", "mask"}`` device arrays."""
    return {k: tprof.to_device(d[prefix + k]) for k in ("x", "y", "mask")}


def _round_batches(data: LazyFederatedData, ids: np.ndarray):
    """The scan's per-round cohort inputs: train arrays only, stacked
    (R, K, M, ...) jnp arrays."""
    with tprof.span("gather/synthesize"):
        d = data.gather(ids)
        if tprof.is_recording():
            tprof.count("synth_devices", len(np.unique(ids)))
    with tprof.span("gather/to_device"):
        return _to_device(d)


# ------------------------------------------------------------- sync engine

def run_federated_lazy(model_cfg, data: LazyFederatedData,
                       fl: simulator.FLConfig, rounds: int,
                       init_key: Optional[jax.Array] = None,
                       eval_every: int = 1, fleet=None, mesh=None,
                       profiler=None) -> simulator.FedRunResult:
    """Synchronous federated run over a lazy population.

    The id timeline is ``sampler="indexed"``'s: the same key chain and
    O(K) uniform draws ``simulator.fl_round`` makes in-program, pre-drawn
    on the host so the cohort batches can be gathered up front.  History,
    params, ids, and (with ``fleet``, a ``PopulationSpec`` or
    ``DeviceFleet``) wall clocks are bit-for-bit the resident engines'
    on the materialized data.
    """
    from repro.telemetry import profiler_for
    _check_lazy_config(fl, "sync")
    prof = profiler_for(False, profiler)
    with prof.phase("setup"):
        key = init_key if init_key is not None \
            else jax.random.PRNGKey(fl.seed)
        params = small.init_small(model_cfg, key)
        spec = flat_lib.spec_of(params)
        w0 = flat_lib.ravel(spec, params)
    with prof.phase("plan_build"):
        subs, steps = scan_engine.draw_round_inputs(fl, rounds, key)
        with tprof.span("plan_build/key_chain"):
            ids = tprof.fetch(async_lib._draw_ids_chain_indexed(
                subs, data.n_devices, fl.n_selected))
        use_so = fl.server_opt != "sgd" or fl.server_lr != 1.0
        so_state0 = sopt.init_server_state(
            sopt.ServerOptConfig(kind=fl.server_opt, lr=1.0), params) \
            if use_so else None
    with prof.phase("gather"):
        batches = _round_batches(data, ids)
    with prof.phase("scan"):
        w_final, ys = scan_engine.scan_rounds(
            model_cfg, fl.timeline_config(), spec, (w0, so_state0),
            {"batch": batches, "steps": steps}, simulator.hypers_of(fl),
            mesh=mesh)
    with prof.phase("eval"):
        train, test, p = _eval_arrays(data)
        clocks = None
        if fleet is not None:
            assert fleet.n_devices == data.n_devices, \
                (fleet.n_devices, data.n_devices)
            clocks = scan_engine.sync_clock_replay(
                model_cfg, params, data, fl.algo, fleet, ids, None,
                tprof.fetch(steps), rounds)
        hist = scan_engine.eval_history_replay(
            model_cfg, spec, train, test, p, ys["params"], rounds,
            eval_every, clocks)
    return simulator.FedRunResult(
        history=hist, params=flat_lib.unravel(spec, w_final), ids=ids,
        metrics=None, profile=prof.finish())


# ------------------------------------------------------------ async engine

def run_async_lazy(model_cfg, data: LazyFederatedData, afl, fleet,
                   rounds: int, init_key: Optional[jax.Array] = None,
                   eval_every: int = 1, mesh=None, plan=None,
                   profiler=None) -> simulator.FedRunResult:
    """Async (deadline / fedbuff) federated run over a lazy population.

    ``fleet`` is a ``PopulationSpec`` (or any fleet implementing the
    gather protocol — a materialized ``DeviceFleet`` produces the
    bit-identical plan and run).  The event plan is built through the
    O(R·K) lazy gathers, the R cohort batches are gathered once on the
    host, and one ``lax.scan`` replays the plan through the mode's step
    functions on those cohorts.  ``plan`` replays a pre-built event plan instead (it must
    come from this (afl, fleet, rounds, key) timeline).
    """
    from repro.telemetry import profiler_for
    _check_lazy_config(afl, "async")
    if plan is not None and any(
            getattr(plan, f, None) is not None
            for f in ("corrupt", "drop_mask", "lost_mask", "flush_mask",
                      "seed_corrupt")):
        raise ValueError(
            "lazy runs do not support failure scenarios: the supplied "
            "plan embeds scenario channels — rebuild it without a "
            "scenario, or materialize() and use the resident engines")
    prof = profiler_for(False, profiler)
    with prof.phase("setup"):
        assert fleet.n_devices == data.n_devices, \
            (fleet.n_devices, data.n_devices)
        key = init_key if init_key is not None \
            else jax.random.PRNGKey(afl.seed)
        params = small.init_small(model_cfg, key)
        cost = round_cost_for(model_cfg, params,
                              uploads_gradient="folb" in afl.algo)
        afl_t = afl.timeline_config()
        sync_fl = afl_t.sync_config()
        hypers = async_lib.hypers_of(afl)
        spec = flat_lib.spec_of(params)
        w0 = flat_lib.ravel(spec, params)

    if afl.mode == "deadline":
        with prof.phase("plan_build"):
            if plan is None:
                plan = async_lib.build_deadline_plan(
                    afl, fleet, cost, data.sizes, rounds, key)
        with prof.phase("gather"):
            batches = _round_batches(data, plan.ids)
            with tprof.span("gather/pool_init"):
                pend0 = async_lib.pool_init(
                    model_cfg, sync_fl, params,
                    {k: v[0] for k, v in batches.items()}, plan.n_slots + 1)
        with prof.phase("scan"):
            w_final, ys = scan_engine.scan_deadline_cohort(
                model_cfg, afl_t, spec, (w0, pend0),
                scan_engine.deadline_xs(plan, batches), hypers, mesh=mesh)
        clocks, n_arr = plan.round_end, plan.n_arrived
    else:
        with prof.phase("plan_build"):
            if plan is None:
                plan = async_lib.build_fedbuff_plan(
                    afl, fleet, cost, data.sizes, rounds, key)
        with prof.phase("gather"):
            seed_batch = _round_batches(data, plan.seed_ids)
            batches = _round_batches(data, plan.ids)
            with tprof.span("gather/pool_init"):
                pend0 = async_lib.pool_init(
                    model_cfg, sync_fl, params, seed_batch, plan.n_slots)
                pend0 = async_lib.fedbuff_seed_pool(
                    model_cfg, afl_t, params, pend0, seed_batch,
                    tprof.to_device(plan.seed_steps),
                    tprof.to_device(plan.seed_slots), hypers)
        with prof.phase("scan"):
            w_final, ys = scan_engine.scan_fedbuff_cohort(
                model_cfg, afl_t, spec, (w0, pend0),
                scan_engine.fedbuff_xs(plan, batches), hypers, mesh=mesh)
        clocks = plan.flush_clock
        n_arr = np.full(rounds, afl.buffer_size)

    with prof.phase("eval"):
        train, test, p = _eval_arrays(data)
        hist = scan_engine.eval_history_replay(
            model_cfg, spec, train, test, p, ys["params"], rounds,
            eval_every, clocks=clocks, n_arrived=n_arr,
            stale_mean=plan.stale_mean)
    return simulator.FedRunResult(
        history=hist, params=flat_lib.unravel(spec, w_final),
        ids=np.asarray(plan.ids), metrics=None, profile=prof.finish())
