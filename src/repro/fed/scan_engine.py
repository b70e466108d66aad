"""Whole-run compiled federated execution: ``lax.scan`` over rounds.

The python-loop engines pay per-round (or, for fedbuff, per-flush) host
overhead: jit dispatches, key splits, numpy step draws, and host
round-trips for every communication round.  For the paper-scale models a
round's actual math is microseconds of work, so dispatch dominates — and
sweeping schedules/hyper-parameters at scale means thousands of runs.

One scan body, ``_scan``, runs every compiled mode, data source and batch
axis.  A mode is a per-round step ``step(carry, x_t, hypers) -> (carry,
ys_t)`` over the carry ``(w_flat, state)`` (``make_sync_round_step``,
``make_deadline_step``, ``make_fedbuff_step``); ``x_t`` is one row of a
dict of per-round inputs, in which an optional operand (a scenario's
``up_mask``, ``flush_mask`` or ``corrupt``) is a key that is present or
absent.  The step reads its data source from that row: a ``"batch"`` is a
cohort the host gathered (lazy populations, ``lazy_engine``); otherwise
the round's cohort comes from the resident stacks inside the program.
``member`` adds a leading member axis: ``"sweep"`` batches the carry and
the hypers over one shared timeline (``sweep_engine`` hyper sweeps),
``"grid"`` the carry and the per-cell plan rows under one learning config
(scenario grids).  A solo run calls the step directly.

Three jitted entries wrap the body, one per mode: ``scan_rounds``,
``scan_deadline_cohort`` and ``scan_fedbuff_cohort``.  Their programs are
named ``jit_<entry>`` in a device trace, which is how the chip benchmark
finds the round programs; every compiled run of a mode, resident or lazy,
solo, sweep or grid, goes through its entry.  The front doors here are:

  * ``run_federated_compiled`` — the synchronous engine: ``fl_round`` over
    pre-drawn (key, step) inputs, optionally carrying FedOpt-style
    server-optimizer state (momentum / adam) via the same jitted
    ``server_opt.server_round_update`` the python loop applies.
  * ``run_async_compiled`` — the async engine: fleet latencies are a
    deterministic function of the seeded fleet and the pre-drawn key
    chain, so the whole event timeline (dispatch/arrival times, per-round
    due/straggler/missed partitions, fedbuff flush boundaries and τ
    counters) is pre-computed on the host into fixed-width stacked arrays
    (``async_engine.build_deadline_plan`` / ``build_fedbuff_plan``) and
    replayed through the *same* jitted step functions the python event
    loop uses (``fl_round`` on sync-parity fast rounds,
    ``deadline_slow_step`` / ``fedbuff_round_step`` otherwise).

Shared parity discipline: parameters ride the scan carry as a flat fp32
buffer (``repro.core.flat``; exact ravel/unravel round-trip), pre-drawn
host inputs replicate the python loops' exact ``jax.random.split`` chains
and round-indexed numpy draws, and evaluation + wall-clock timestamping
happen OUTSIDE the scan on the emitted per-round outputs through the very
same jitted ``simulator.eval_global`` / ``sync_round_clock`` (sync) or
the host event plan (async) — which is what makes loop and scan agree
bit-for-bit on a fixed seed (``tests/test_scan_engine.py``,
``tests/test_async_scan.py``).

Memory note: the scans emit the (rounds, D_pad) fp32 parameter trajectory
so history evaluation can happen post-hoc; at paper scale (D ~ 1e3-1e5)
this is negligible.  For 100M+ parameter models use
``repro.fed.distributed`` instead.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flat as flat_lib
from repro.data.federated import FederatedData
from repro.fed import async_engine as async_lib
from repro.fed import simulator
from repro.fed import server_opt as sopt
from repro.models import small
from repro.sysmodel import round_cost_for
from repro.sysmodel import scenario as scenario_mod
from repro.telemetry import profiler as tprof


@functools.partial(jax.jit, static_argnums=(1,))
def _split_chain(key, rounds: int):
    """The python loop's ``key, sub = jax.random.split(key)`` chain as one
    compiled scan (identical key values — threefry is deterministic —
    without `rounds` host dispatches)."""
    def body(k, _):
        ks = jax.random.split(k)
        return ks[0], ks[1]

    _, subs = jax.lax.scan(body, key, None, length=rounds)
    return subs


def draw_round_inputs(fl: simulator.FLConfig, rounds: int, init_key):
    """Pre-draw the per-round (selection key, local-step budgets) sequence.

    Replicates the python-loop engine's host side exactly: the
    ``key, sub = jax.random.split(key)`` chain and the round-indexed numpy
    step draws of ``simulator.local_step_table``, moved to the device in
    one transfer — so a scan over these inputs sees the same randomness
    as ``run_federated``.
    """
    with tprof.span("plan_build/key_chain"):
        subs = _split_chain(init_key, rounds)
    with tprof.span("plan_build/step_draws"):
        steps = tprof.to_device(
            simulator.local_step_table(rounds, fl.n_selected, fl))
    return subs, steps


def sync_round_xs(fl: simulator.FLConfig, rounds: int, init_key, sc=None):
    """The sync scan's per-round rows, and the scenario's latency scales
    for the clock replay (None without jitter).  With an active scenario
    ``sc`` the key chain is the unmodified program's, the steps and the
    upload mask carry the realized completeness and drop channels, and
    ``corrupt`` the payload-corruption factors (absent when off)."""
    if sc is None:
        keys, steps = draw_round_inputs(fl, rounds, init_key)
        return {"key": keys, "steps": steps}, None
    sc_steps, sc_mask, sc_lat, sc_corr = \
        simulator.scenario_round_inputs(fl, rounds, sc)
    xs = {"key": _split_chain(init_key, rounds),
          "steps": tprof.to_device(sc_steps),
          "up_mask": tprof.to_device(sc_mask)}
    if sc_corr is not None:
        xs["corrupt"] = tprof.to_device(sc_corr)
    return xs, sc_lat


# per-round outputs every member of a sweep or grid shares: selection is
# drawn before any member's own math, and out_axes None asserts it
_SHARED_YS = ("ids", "ids2")


def _scan(step, carry0, xs, hypers, member=None):
    """The scan body of every compiled run: ``lax.scan`` of ``step`` over
    the per-round rows of the dict ``xs``, from ``carry0 = (w_flat,
    state)``.  Returns (final carry, ys), ys holding the post-round
    ``"params"`` trajectory and whatever of ``ids``/``ids2``/``metrics``
    the step emits (a step emits None for an output it does not have).

    ``member`` (static) adds a leading member axis: None calls ``step``
    directly (a solo run, never through a size-1 vmap, so the solo
    program is the step's own); ``"sweep"`` vmaps it over the carry and
    ``hypers``, every xs row shared; ``"grid"`` over the carry and every
    xs row but the round ``"key"`` (one timeline config, one key chain),
    ``hypers`` shared."""
    def emit(carry, x_t, h):
        carry, extras = step(carry, x_t, h)
        return carry, {k: v for k, v in extras.items() if v is not None}

    if member is None:
        round_fn = emit
    else:
        def one(carry, x_t, h):
            carry, ys = emit(carry, x_t, h)
            shared = {k: ys.pop(k) for k in _SHARED_YS if k in ys}
            return carry, ys, shared

        if member == "sweep":
            in_axes = (0, None, 0)
        else:
            in_axes = (0, {k: None if k == "key" else 0 for k in xs}, None)
        vone = jax.vmap(one, in_axes=in_axes, out_axes=(0, 0, None))

        def round_fn(carry, x_t, h):
            carry, ys, shared = vone(carry, x_t, h)
            return carry, {**ys, **shared}

    def body(carry, x_t):
        carry, ys = round_fn(carry, x_t, hypers)
        return carry, {"params": carry[0], **ys}

    return jax.lax.scan(body, carry0, xs)


def _sync_round(model_cfg, fl, params, x, hypers, data, p_weights,
                sel_probs, mesh):
    """One synchronous round on row ``x``: ``fl_round_cohort`` on a cohort
    the host gathered (``x["batch"]``), else ``fl_round`` drawing the
    selection in the program from ``x["key"]`` over the resident
    stacks."""
    if "batch" in x:
        return simulator.fl_round_cohort(
            model_cfg, fl, params, x["batch"], x["steps"], hypers,
            x.get("up_mask"), x.get("corrupt"), mesh=mesh)
    return simulator.fl_round(
        model_cfg, fl, params, data, p_weights, x["key"], x["steps"],
        sel_probs, hypers, x.get("up_mask"), x.get("corrupt"), mesh=mesh)


def _cohort(x, data):
    """Row ``x``'s dispatched cohort: ``x["batch"]`` when the host gathered
    it, else the rows of ``x["ids"]`` gathered from the resident stacks
    in the program, under the local solve's trace scope as
    ``simulator._local_updates`` gathers."""
    if "batch" in x:
        return x["batch"]
    with jax.named_scope("local_solve"):
        return simulator._client_batch(data, x["ids"])


def make_sync_round_step(model_cfg, fl: simulator.FLConfig,
                         spec: flat_lib.FlatSpec, data, p_weights, sel_probs,
                         mesh):
    """One synchronous round as a ``_scan`` step over ``(w_flat,
    so_state)``: unravel → ``fl_round`` / ``fl_round_cohort`` → the
    server-optimizer update when ``so_state`` is not None → ravel.
    ``fl`` must be the canonical ``timeline_config()``; every sweepable
    scalar arrives via ``hypers``."""
    so_cfg = sopt.ServerOptConfig(kind=fl.server_opt, lr=1.0)

    def step(carry, x, hypers):
        w_flat, so_state = carry
        params = flat_lib.unravel(spec, w_flat)
        new_params, diag = _sync_round(model_cfg, fl, params, x, hypers,
                                       data, p_weights, sel_probs, mesh)
        if so_state is not None:
            new_params, so_state = sopt.server_round_update(
                so_cfg, params, so_state, new_params, hypers["server_lr"])
        return ((flat_lib.ravel(spec, new_params), so_state),
                {k: diag.get(k) for k in ("ids", "ids2", "metrics")})

    return step


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh", "member"))
def scan_rounds(model_cfg, fl: simulator.FLConfig, spec: flat_lib.FlatSpec,
                carry0, xs, hypers, data=None, p_weights=None,
                sel_probs=None, *, mesh=None, member=None):
    """The whole-run sync program: ``_scan`` of ``make_sync_round_step``.

    ``carry0`` is ``(w0_flat, so_state0)``, where ``so_state0`` (None for
    plain SGD at server lr 1) seeds the server-optimizer state.  ``xs``
    rows: ``"steps"`` and either ``"key"`` (resident ``data``;
    ``sel_probs`` is the static selection distribution) or ``"batch"``
    (host-gathered cohorts), plus the optional scenario rows
    ``"up_mask"`` (the arrived-upload mask) and ``"corrupt"`` (payload
    factors), each (rounds, K) f32.  ``fl`` is the canonical timeline
    config, ``hypers`` the traced sweepable scalars
    (``simulator.hypers_of``), ``mesh`` shards the flat aggregation's D
    axis.  Returns (final flat params, ys)."""
    step = make_sync_round_step(model_cfg, fl, spec, data, p_weights,
                                sel_probs, mesh)
    (w_final, _), ys = _scan(step, carry0, xs, hypers, member)
    return w_final, ys


def latency_selection_probs(model_cfg, fed: FederatedData, fl, fleet,
                            deadline: float) -> jax.Array:
    """Pre-compute the static latency-aware selection distribution.

    The async deadline engine's ``latency_aware`` sampling distribution
    P ∝ σ((D − ℓ_k)/s) depends only on the fleet's expected per-device
    latencies — it is round-invariant.  Computing it once on the host lets
    the compiled scan engine (and ``run_federated``) run the
    deadline-FOLB sweep's selection policy; the chain below mirrors
    ``async_engine.deadline_selection_probs`` exactly so the
    distributions agree bit-for-bit.
    """
    import numpy as np
    from repro.core import selection
    from repro.sysmodel import expected_latencies
    params = small.init_small(model_cfg, jax.random.PRNGKey(
        getattr(fl, "seed", 0)))
    cost = round_cost_for(model_cfg, params,
                          uploads_gradient="folb" in fl.algo)
    sizes = np.asarray(fed.mask.sum(axis=1))
    exp_lat = tprof.to_device(expected_latencies(
        fleet, cost, mean_steps=simulator.mean_local_steps(fl),
        n_examples=sizes))
    return selection.latency_aware_probs(
        jnp.ones((fleet.n_devices,)), exp_lat, deadline)


def sync_clock_replay(model_cfg, params, fed: FederatedData, algo: str,
                      fleet, ids_all, ids2_all, steps_np,
                      rounds: int, lat_scale=None) -> np.ndarray:
    """Replay the fleet wall-clock over a whole run's sampled ids via the
    same ``sync_round_clock`` the python loop advances round by round.
    The clock depends only on the timeline (ids/steps/fleet/cost), never
    on sweepable hyper-parameters — one replay serves every member of a
    sweep.  ``lat_scale`` (optional, (rounds, K)) is the scenario jitter
    channel, forwarded per round."""
    cost, probe_cost, sizes = simulator.fleet_cost_setup(
        model_cfg, params, fed, algo)
    clocks = np.empty(rounds, np.float64)
    clock_now = 0.0
    for t in range(rounds):
        clock_now = simulator.sync_round_clock(
            fleet, cost, probe_cost, sizes, algo, ids_all[t],
            None if ids2_all is None else ids2_all[t],
            steps_np[t], clock_now,
            lat_scale=None if lat_scale is None else lat_scale[t])
        clocks[t] = clock_now
    return clocks


# rows vmapped together inside one dispatch.  A full vmap over E·S rows
# materializes an (E·S, N, M, C) logits tensor and goes memory-bound on
# wide sweeps; chunking keeps the working set ~CHUNK× one eval while the
# whole trajectory stays a single dispatch (lax.map over row chunks).
_EVAL_CHUNK = 8


@functools.partial(jax.jit, static_argnums=(0, 1))
def _eval_traj_chunks(model_cfg, spec: flat_lib.FlatSpec, traj_chunks,
                      data, p_weights):
    def one(w_flat):
        return simulator.eval_global(
            model_cfg, flat_lib.unravel(spec, w_flat), data, p_weights)
    return jax.lax.map(lambda rows: jax.vmap(one)(rows), traj_chunks)


def eval_traj(model_cfg, spec: flat_lib.FlatSpec, traj, data, p_weights):
    """``eval_global`` over a stack of flat parameter vectors ->
    ((E,) losses, (E,) accs) in ONE dispatch instead of one per
    (round, member).  Bit-identical per row to the unbatched call (the
    loop-vs-scan and sweep-vs-solo parity suites pin this; vmap batch
    size does not change a row's result, so neither does the chunking)."""
    E = traj.shape[0]
    chunk = min(_EVAL_CHUNK, E)
    pad = (-E) % chunk
    if pad:
        tail = jnp.broadcast_to(traj[-1:], (pad,) + traj.shape[1:])
        traj = jnp.concatenate([jnp.asarray(traj), tail])
    chunks = jnp.asarray(traj).reshape((-1, chunk) + traj.shape[1:])
    out = _eval_traj_chunks(model_cfg, spec, chunks, data, p_weights)
    return jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:])[:E], out)


def _eval_points(rounds: int, eval_every: int):
    return [t for t in range(rounds)
            if t % eval_every == 0 or t == rounds - 1]


def _eval_rows(params_traj, rounds: int, eval_every: int):
    """The ``_eval_points`` rows of a per-round trajectory, taken with
    static slices: XLA:TPU compiles a row gather of a 1e8-wide trajectory
    in minutes (146 s for a described v5e at D = 1.0e8), a slice in under
    a second."""
    traj = jnp.asarray(params_traj)
    rows = jax.lax.slice_in_dim(traj, 0, rounds, stride=eval_every)
    if (rounds - 1) % eval_every:
        rows = jnp.concatenate(
            [rows, jax.lax.slice_in_dim(traj, rounds - 1, rounds)])
    return rows


# one dispatch, where an eager ``jnp.stack`` makes one per operand and one
# to join them
@jax.jit
def _stack_curves(*curves):
    return jnp.stack(curves)


def _history_curves(model_cfg, spec: flat_lib.FlatSpec, train, test, p,
                    params_traj, rounds: int, eval_every: int) -> np.ndarray:
    """Train loss, test accuracy and train accuracy at every eval point of
    a (rounds, *batch, D_pad) trajectory, as one (3, E, *batch) host
    array: two ``eval_traj`` dispatches, stacked on the device and read
    in ONE fetch (a copy, so each value is the float32 ``eval_global``
    gives)."""
    with tprof.span("eval/device"):
        traj = _eval_rows(params_traj, rounds, eval_every)
        rows = traj.reshape((-1, traj.shape[-1]))
        tr_loss, tr_acc = eval_traj(model_cfg, spec, rows, train, p)
        _, te_acc = eval_traj(model_cfg, spec, rows, test, p)
        curves = _stack_curves(tr_loss, te_acc, tr_acc)
    with tprof.span("eval/fetch"):
        return tprof.fetch(curves).reshape((3,) + traj.shape[:-1])


def _history(ts, curves: np.ndarray, series: dict) -> dict:
    """One history dict from a (3, E) host block of ``_history_curves``
    and the host timeline ``series`` (per-round, indexed at ``ts``)."""
    tr_loss, te_acc, tr_acc = curves.tolist()
    hist = {"round": list(ts), "train_loss": tr_loss, "test_acc": te_acc,
            "train_acc": tr_acc}
    for k, row in series.items():
        if row is not None:
            hist[k] = [float(row[t]) for t in ts]
    return hist


def eval_history_replay(model_cfg, spec: flat_lib.FlatSpec, train, test, p,
                        params_traj, rounds: int, eval_every: int,
                        clocks=None, n_arrived=None, stale_mean=None):
    """Post-hoc history evaluation on an emitted (rounds, D_pad) parameter
    trajectory through the same jitted eval math every engine uses —
    shared by the solo compiled runs (sync and async); the sweep engine
    batches further via ``eval_history_replay_sweep``.  The eval-point
    rows are evaluated in one vmapped dispatch (``eval_traj``), row-wise
    bit-identical to the python loops' per-round ``eval_global`` calls,
    and the whole history is read to the host in one fetch.
    ``clocks``/``n_arrived``/``stale_mean`` are optional per-round host
    timeline series to record alongside (the async engines pass all three
    from their plan)."""
    curves = _history_curves(model_cfg, spec, train, test, p, params_traj,
                             rounds, eval_every)
    return _history(_eval_points(rounds, eval_every), curves,
                    {"wall_clock": clocks, "n_arrived": n_arrived,
                     "stale_mean": stale_mean})


def eval_history_replay_sweep(model_cfg, spec: flat_lib.FlatSpec, train,
                              test, p, params_traj_RS, rounds: int,
                              eval_every: int, clocks=None, n_arrived=None,
                              stale_mean=None):
    """Sweep-native history evaluation: ONE batched dispatch over every
    (eval round, member) pair of an (R, S, D_pad) trajectory instead of
    R·S separate ``eval_global`` dispatches, read in one fetch.  Returns S
    history dicts, member i equal to
    ``eval_history_replay(..., params_traj_RS[:, i], ...)``.

    The timeline series (clocks / n_arrived / stale_mean) accept either a
    shared (R,) vector — hyper sweeps, one plan for all members — or a
    per-member (S, R) stack (scenario grids, one timeline per cell)."""
    curves = _history_curves(model_cfg, spec, train, test, p,
                             params_traj_RS, rounds, eval_every)
    ts = _eval_points(rounds, eval_every)
    series = {"wall_clock": clocks, "n_arrived": n_arrived,
              "stale_mean": stale_mean}
    return [_history(ts, curves[:, :, i],
                     {k: row[i] if np.ndim(row) == 2 else row
                      for k, row in series.items()})
            for i in range(curves.shape[2])]


def run_federated_compiled(model_cfg, fed: FederatedData,
                           fl: simulator.FLConfig, rounds: int,
                           init_key: Optional[jax.Array] = None,
                           eval_every: int = 1,
                           fleet=None, sel_probs=None,
                           mesh=None, profiler=None, scenario=None
                           ) -> simulator.FedRunResult:
    """Drop-in replacement for ``run_federated`` on fixed schedules.

    Bit-for-bit identical history on the same seed (shared round math,
    shared jitted eval, shared fleet cost replay, shared jitted server
    optimizer), one XLA dispatch for the whole run instead of one per
    round.  ``sel_probs`` (e.g. from ``latency_selection_probs``) replaces
    uniform sampling; ``mesh`` shards the flat aggregation's D axis (the
    params and the local solves stay whole on every device).

    With ``fl.telemetry`` the scan additionally emits the per-round
    metrics pytree (extra scan outputs — same program otherwise) and the
    result carries them as (rounds, ·) arrays plus the host-phase profile
    (setup / plan_build / scan / eval phases; the first call's jit
    compilation lands inside ``scan``).

    ``scenario`` (``repro.sysmodel.ScenarioConfig``) realizes the seeded
    failure channels at plan-build time — the same draws the python loop
    replays — and folds them into the scanned step/mask inputs; None (or
    an all-off config) is bit-for-bit the unmodified program.
    """
    from repro.telemetry import metrics as tmetrics
    from repro.telemetry import profiler_for
    prof = profiler_for(fl.telemetry, profiler)
    sc = scenario_mod.as_active(scenario)
    if sc is not None:
        scenario_mod.check_sync(sc)
    with prof.phase("setup"):
        key = init_key if init_key is not None \
            else jax.random.PRNGKey(fl.seed)
        params = small.init_small(model_cfg, key)
        with tprof.span("setup/to_device"):
            train, test, p = simulator.device_arrays(fed)
        spec = flat_lib.spec_of(params)
        w0 = flat_lib.ravel(spec, params)
    with prof.phase("plan_build"):
        xs, sc_lat = sync_round_xs(fl, rounds, key, sc)
        so_cfg = sopt.ServerOptConfig(kind=fl.server_opt, lr=1.0)
        use_so = fl.server_opt != "sgd" or fl.server_lr != 1.0
        so_state0 = sopt.init_server_state(so_cfg, params) if use_so \
            else None
    with prof.phase("scan"):
        w_final, ys = scan_rounds(
            model_cfg, fl.timeline_config(), spec, (w0, so_state0), xs,
            simulator.hypers_of(fl), train, p, sel_probs, mesh=mesh)
        if fl.telemetry:
            # attribute device time honestly when profiling (jax dispatch
            # is async); the telemetry-off path never adds a barrier
            jax.block_until_ready(ys)

    with prof.phase("eval"):
        clocks = None
        if fleet is not None:
            assert fleet.n_devices == fed.n_devices, \
                (fleet.n_devices, fed.n_devices)
            clocks = sync_clock_replay(
                model_cfg, params, fed, fl.algo, fleet,
                tprof.fetch(ys["ids"]),
                tprof.fetch(ys["ids2"]) if "ids2" in ys else None,
                tprof.fetch(xs["steps"]), rounds, lat_scale=sc_lat)
        hist = eval_history_replay(model_cfg, spec, train, test, p,
                                   ys["params"], rounds, eval_every, clocks)
    with prof.phase("collect"):
        ids_np = tprof.fetch(ys["ids"])
        metrics = None
        if fl.telemetry:
            metrics = {k: tprof.fetch(v) for k, v in ys["metrics"].items()}
            D = int(sum(x.size for x in jax.tree.leaves(params)))
            metrics.update(tmetrics.sync_network_series(
                D, fl, rounds, fed.n_devices))
            metrics["selection_entropy"] = tmetrics.selection_entropy(
                ids_np, fed.n_devices)
    return simulator.FedRunResult(
        history=hist, params=flat_lib.unravel(spec, w_final), ids=ids_np,
        metrics=metrics, profile=prof.finish())


# --------------------------------------------------- compiled async engines

def make_deadline_step(model_cfg, afl, spec: flat_lib.FlatSpec, data,
                       p_weights, sel_probs, mesh, always_slow=False):
    """One planned deadline round as a ``_scan`` step over ``(w_flat,
    pool)``: sync-parity fast rounds (``x["fast"]``) run the same jitted
    sync round the python loop calls (under ``lax.cond``), every other
    round the shared ``async_engine.deadline_slow_step`` against the
    pending-straggler slot pool.  ``afl`` must be the canonical
    ``timeline_config()``.

    ``always_slow`` (static): skip the cond and run the slow branch
    unconditionally.  Bit-identical whenever the caller's entire fast
    array is False (cond on a False predicate IS the slow branch) — the
    vmapped grid/sweep runs use it because their batched cond lowers to a
    select that executes BOTH branches for every member, and any active
    drop scenario leaves essentially no fast rounds to select."""
    fl = afl.sync_config()

    def step(carry, x, hypers):
        w_flat, pend = carry
        params = flat_lib.unravel(spec, w_flat)

        # both branches return a metrics pytree (None without telemetry);
        # the schemas are structurally identical by construction (the sync
        # round is the τ = 0 full-mask case), which lax.cond requires
        def fast_fn(params, pend):
            new, diag = _sync_round(model_cfg, fl, params, x, hypers, data,
                                    p_weights, sel_probs, mesh)
            return flat_lib.ravel(spec, new), pend, diag.get("metrics")

        def slow_fn(params, pend):
            new, pend2, m = async_lib.deadline_slow_step(
                model_cfg, afl, params, pend, _cohort(x, data), x["steps"],
                x["arrived"], x["store_slot"], x["due_slot"], x["due_mask"],
                x["due_tau"], hypers, x.get("corrupt"), mesh=mesh)
            return flat_lib.ravel(spec, new), pend2, m

        if always_slow:
            w_new, pend, m = slow_fn(params, pend)
        else:
            w_new, pend, m = jax.lax.cond(x["fast"], fast_fn, slow_fn,
                                          params, pend)
        return (w_new, pend), {"metrics": m}

    return step


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh", "member", "always_slow"))
def scan_deadline_cohort(model_cfg, afl, spec: flat_lib.FlatSpec, carry0,
                         xs, hypers, data=None, p_weights=None,
                         sel_probs=None, *, mesh=None, member=None,
                         always_slow=False):
    """The whole-run deadline program: ``_scan`` of ``make_deadline_step``
    over the planned timeline (``deadline_xs``), carrying ``(w0_flat,
    pool0)``.  Lazy cohort runs pass their gathered batches; resident,
    sweep and grid runs the round keys and ids over ``data``."""
    step = make_deadline_step(model_cfg, afl, spec, data, p_weights,
                              sel_probs, mesh, always_slow=always_slow)
    (w_final, _), ys = _scan(step, carry0, xs, hypers, member)
    return w_final, ys


def deadline_xs(plan, batch=None, put=tprof.to_device):
    """A deadline plan's per-round scan rows, each moved to the device by
    ``put`` (a grid stacks (S, R) cell plans into (R, S) rows).  The
    cohort is ``batch`` (host-gathered cohorts) or, without one, the
    plan's round keys (shared by every cell) and dispatched ids; the
    optional ``corrupt`` row is the scenario's payload factors."""
    xs = {"steps": put(plan.n_steps),
          "arrived": put(plan.arrived, jnp.float32),
          "store_slot": put(plan.store_slot),
          "due_slot": put(plan.due_slot), "due_mask": put(plan.due_mask),
          "due_tau": put(plan.due_tau), "fast": put(plan.fast)}
    if batch is not None:
        xs["batch"] = batch
    else:
        xs["key"] = tprof.to_device(plan.keys)
        xs["ids"] = put(plan.ids)
    if plan.corrupt is not None:
        xs["corrupt"] = put(plan.corrupt)
    return xs


def make_fedbuff_step(model_cfg, afl, spec: flat_lib.FlatSpec, data, mesh):
    """One planned fedbuff flush as a ``_scan`` step over ``(w_flat,
    pool)``: the shared ``async_engine.fedbuff_round_step`` on the round's
    dispatch cohort.  ``afl`` must be the canonical
    ``timeline_config()``."""
    def step(carry, x, hypers):
        w_flat, pend = carry
        new, pend, m = async_lib.fedbuff_round_step(
            model_cfg, afl, flat_lib.unravel(spec, w_flat), pend,
            _cohort(x, data), x["steps"], x["store_slot"], x["flush_slot"],
            x["tau"], hypers, x.get("flush_mask"), x.get("corrupt"),
            mesh=mesh)
        return (flat_lib.ravel(spec, new), pend), {"metrics": m}

    return step


@functools.partial(jax.jit, static_argnums=(0, 1, 2),
                   static_argnames=("mesh", "member"))
def scan_fedbuff_cohort(model_cfg, afl, spec: flat_lib.FlatSpec, carry0, xs,
                        hypers, data=None, *, mesh=None, member=None):
    """The whole-run fedbuff program: ``_scan`` of ``make_fedbuff_step``
    over the planned flush schedule (``fedbuff_xs``), carrying the
    in-flight update pool.  Lazy cohort runs pass their gathered batches;
    resident, sweep and grid runs the dispatched ids over ``data``."""
    step = make_fedbuff_step(model_cfg, afl, spec, data, mesh)
    (w_final, _), ys = _scan(step, carry0, xs, hypers, member)
    return w_final, ys


def fedbuff_xs(plan, batch=None, put=tprof.to_device):
    """A fedbuff plan's per-round scan rows, each moved to the device by
    ``put``.  The cohort is ``batch`` or the plan's dispatched ids; the
    optional scenario rows are ``flush_mask`` ((R, M) f32, the failed
    uploads a flush excludes) and ``corrupt`` ((R, W) f32 payload factors
    of each planned dispatch)."""
    xs = {"steps": put(plan.n_steps), "store_slot": put(plan.store_slot),
          "flush_slot": put(plan.flush_slot), "tau": put(plan.tau)}
    if batch is not None:
        xs["batch"] = batch
    else:
        xs["ids"] = put(plan.ids)
    for k in ("flush_mask", "corrupt"):
        if getattr(plan, k) is not None:
            xs[k] = put(getattr(plan, k))
    return xs


def run_async_compiled(model_cfg, fed: FederatedData, afl,
                       fleet, rounds: int,
                       init_key: Optional[jax.Array] = None,
                       eval_every: int = 1,
                       mesh=None, plan=None,
                       profiler=None,
                       scenario=None) -> simulator.FedRunResult:
    """Drop-in replacement for ``async_engine.run_async``: the virtual-
    event scan.

    The host pre-computes the entire event timeline (the plan), one
    ``lax.scan`` replays the learning math through the same jitted step
    functions the python event loop uses, and history evaluation replays
    outside the scan on the emitted parameter trajectory — bit-for-bit
    identical history (params, ids, staleness means, wall clock) for both
    deadline and fedbuff modes (tests/test_async_scan.py).  ``plan``
    replays a pre-built event plan (``async_engine.build_plan``) instead
    of rebuilding it — plans depend only on timeline fields, so one plan
    serves any sweepable-hyper variation of ``afl``.  ``scenario``
    (``repro.sysmodel.ScenarioConfig``) folds the seeded failure channels
    into the freshly built plan; it is ignored when ``plan=`` is supplied
    (the plan already embeds its own scenario realization).

    With ``afl.telemetry`` the scan additionally emits the per-round
    metrics pytree and the result carries them (plus the plan-derived
    network/pool series) and the host-phase profile.
    """
    from repro.telemetry import metrics as tmetrics
    from repro.telemetry import profiler_for
    prof = profiler_for(afl.telemetry, profiler)
    with prof.phase("setup"):
        assert fleet.n_devices == fed.n_devices, \
            (fleet.n_devices, fed.n_devices)
        key = init_key if init_key is not None \
            else jax.random.PRNGKey(afl.seed)
        params = small.init_small(model_cfg, key)
        with tprof.span("setup/to_device"):
            train, test, p = simulator.device_arrays(fed)
        sizes = np.asarray(fed.mask.sum(axis=1))
        cost = round_cost_for(model_cfg, params,
                              uploads_gradient="folb" in afl.algo)
        afl_t = afl.timeline_config()
        sync_fl = afl_t.sync_config()
        hypers = async_lib.hypers_of(afl)
        spec = flat_lib.spec_of(params)
        w0 = flat_lib.ravel(spec, params)

    if afl.mode == "deadline":
        with prof.phase("plan_build"):
            sel_probs = async_lib.deadline_selection_probs(afl, fleet, cost,
                                                           sizes)
            if plan is None:
                plan = async_lib.build_deadline_plan(afl, fleet, cost,
                                                     sizes, rounds, key,
                                                     sel_probs,
                                                     scenario=scenario)
            pend0 = async_lib.pool_init(model_cfg, sync_fl, params, train,
                                        plan.n_slots + 1)
        with prof.phase("scan"):
            w_final, ys = scan_deadline_cohort(
                model_cfg, afl_t, spec, (w0, pend0), deadline_xs(plan),
                hypers, train, p, sel_probs, mesh=mesh)
            if afl.telemetry:
                jax.block_until_ready(ys)
        clocks, n_arr = plan.round_end, plan.n_arrived
    else:
        with prof.phase("plan_build"):
            if plan is None:
                plan = async_lib.build_fedbuff_plan(afl, fleet, cost, sizes,
                                                    rounds, key,
                                                    scenario=scenario)
            pend0 = async_lib.pool_init(model_cfg, sync_fl, params, train,
                                        plan.n_slots)
            pend0 = async_lib.fedbuff_seed_pool(
                model_cfg, afl_t, params, pend0,
                simulator._client_batch(train,
                                        tprof.to_device(plan.seed_ids)),
                tprof.to_device(plan.seed_steps),
                tprof.to_device(plan.seed_slots), hypers,
                None if plan.seed_corrupt is None
                else tprof.to_device(plan.seed_corrupt))
        with prof.phase("scan"):
            w_final, ys = scan_fedbuff_cohort(
                model_cfg, afl_t, spec, (w0, pend0), fedbuff_xs(plan),
                hypers, train, mesh=mesh)
            if afl.telemetry:
                jax.block_until_ready(ys)
        clocks = plan.flush_clock
        n_arr = (np.full(rounds, afl.buffer_size)
                 if plan.flush_mask is None
                 else plan.flush_mask.sum(axis=1).astype(np.int64))

    with prof.phase("eval"):
        hist = eval_history_replay(model_cfg, spec, train, test, p,
                                   ys["params"], rounds, eval_every,
                                   clocks=clocks, n_arrived=n_arr,
                                   stale_mean=plan.stale_mean)
    with prof.phase("collect"):
        metrics = None
        if afl.telemetry:
            metrics = {k: tprof.fetch(v) for k, v in ys["metrics"].items()}
            D = int(sum(x.size for x in jax.tree.leaves(params)))
            if afl.mode == "deadline":
                metrics.update(tmetrics.deadline_network_series(D, afl,
                                                                plan))
                metrics.update(tmetrics.deadline_pool_series(plan))
            else:
                metrics.update(tmetrics.fedbuff_network_series(D, afl,
                                                               plan))
            metrics["selection_entropy"] = tmetrics.selection_entropy(
                plan.ids, fed.n_devices)
    return simulator.FedRunResult(
        history=hist, params=flat_lib.unravel(spec, w_final),
        ids=np.asarray(plan.ids), metrics=metrics, profile=prof.finish())
