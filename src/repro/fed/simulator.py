"""Small-scale federated simulator (vmap-over-clients strategy).

Implements the paper's full algorithm suite on the paper's own model scale
(MCLR / MLP / LSTM, hundreds-to-thousands of devices):

  fedavg        — uniform sampling, mean aggregation, μ = 0          [20]
  fedprox       — uniform sampling, mean aggregation, prox μ         [21]
  fednu_direct  — Sec. III-D1: exact LB-near-optimal sampling (needs all
                  N gradients; communication-expensive upper baseline)
  fednu_signed  — fednu_direct + Eq. 5 signed aggregation (Prop. 1)
  fednu_norm    — Sec. III-D2: P ∝ ||∇F_k|| Cauchy-Schwarz estimate
  folb          — Alg. 2 with S1 = S2 (Eq. IV-C), the paper's main method
  folb2         — Alg. 2 two-set variant (Eq. IV-A), 2K devices
  folb_het      — Sec. V heterogeneity-aware aggregation (Eq. V-B)

Device computational heterogeneity follows the paper's protocol: each
selected device draws a uniform number of local steps in [1, max_local]
from a round-indexed seed shared across algorithms.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, selection, tree, tuning
from repro.data.federated import FederatedData
from repro.kernels import ops
from repro.models import small
from repro.optim import solvers
from repro.telemetry import profiler as tprof

ALGOS = ("fedavg", "fedprox", "fednu_direct", "fednu_signed", "fednu_norm",
         "folb", "folb2", "folb_het")
AGG_BACKENDS = ("flat", "pytree")
AGG_DTYPES = ("bfloat16", "float32")

# The sweepable / timeline split (enforced at trace time): these FLConfig
# fields are pure *learning-math* scalars — they never touch device
# selection, local-step draws, the fleet timeline, or the traced program
# STRUCTURE — so the jitted round steps take them as traced operands (a
# `hypers` dict) instead of baking them into the static config.  Two
# configs differing only in sweepable fields therefore share one compiled
# program (`timeline_config()` canonicalizes them for the jit cache), and
# the sweep engine (`repro.fed.sweep_engine`) can vmap the same steps over
# a stacked hypers axis.  Every OTHER field is timeline-affecting or
# program-static and must stay constant across a sweep.
SWEEPABLE_FIELDS = ("lr", "mu", "psi", "server_lr")


def mean_local_steps(cfg) -> float:
    """Expected local-step budget under the paper's capability protocol
    (shared by the async engine and the static latency-aware selection
    precompute, so both derive identical expected latencies)."""
    return ((1 + cfg.max_local_steps) / 2.0 if cfg.het_steps
            else float(cfg.max_local_steps))


@dataclasses.dataclass(frozen=True)
class FLConfig:
    algo: str = "folb"
    n_selected: int = 10        # K
    mu: float = 1.0             # prox weight (0 for fedavg)
    lr: float = 0.05
    max_local_steps: int = 20
    het_steps: bool = True      # random 1..max per device (paper protocol)
    psi: float = 0.0            # heterogeneity penalty weight (folb_het)
    # aggregation backend for the folb/folb_het hot path: "flat" streams
    # stacked (K, D) buffers through the fused Pallas kernel (interpret
    # mode on CPU); "pytree" keeps the reference leafwise rules.
    agg_backend: str = "flat"
    # storage dtype of the flat (K, D) grad/delta buffers: bf16 halves the
    # HBM streaming traffic (fp32 accumulation stays inside the kernels);
    # "float32" restores exact-to-pytree buffers.
    agg_dtype: str = "bfloat16"
    # beyond-paper: server optimizer over the round aggregate (FedOpt-style)
    server_opt: str = "sgd"     # sgd | momentum | adam
    server_lr: float = 1.0      # 1.0 + sgd == the paper's plain application
    # observability: emit structured per-round metrics (repro.telemetry)
    # as extra outputs of the jitted round steps and attach a host-phase
    # profile to the run result.  A STATIC program-structure flag — it
    # changes the traced program (part of the jit cache key, preserved by
    # timeline_config, never sweepable); off is bit-for-bit the pre-
    # telemetry program.
    telemetry: bool = False
    # robust aggregation (repro.kernels.guard.GuardConfig): non-finite
    # rejection / norm clipping / score gating inside the fused flat
    # aggregation kernel.  STATIC like `telemetry` (jit-cache-keyed,
    # preserved by timeline_config, never sweepable); None is bit-for-bit
    # the unguarded program.
    guard: Optional[Any] = None
    # uniform-selection sampler: "categorical" draws K ids from an (N,)
    # probability vector (needed whenever sel_probs overrides uniform);
    # "indexed" draws K uniform ids directly — O(K) work, no (N,) vector,
    # REQUIRED for lazy populations where N may be 10⁶.  Timeline-
    # affecting and program-static: the two samplers are separate,
    # self-consistent id timelines (never sweepable).
    sampler: str = "categorical"
    seed: int = 0

    def __post_init__(self):
        assert self.algo in ALGOS, self.algo
        assert self.agg_backend in AGG_BACKENDS, self.agg_backend
        assert self.agg_dtype in AGG_DTYPES, self.agg_dtype
        if self.sampler not in ("categorical", "indexed"):
            raise ValueError(f"unknown sampler {self.sampler!r}")
        if self.sampler == "indexed" and self.algo.startswith("fednu"):
            raise ValueError(
                "sampler='indexed' is uniform-only; the fednu baselines "
                "derive their own selection distribution from all N "
                "gradients (inherently O(N)) — use sampler='categorical'")
        if self.guard is not None:
            from repro.kernels.guard import as_guard
            as_guard(self.guard)
            if self.algo not in ("folb", "folb_het"):
                raise ValueError(
                    f"guard requires algo 'folb' or 'folb_het' (the guard "
                    f"runs inside the fused FOLB kernel), got {self.algo!r}")
            if self.agg_backend != "flat":
                raise ValueError(
                    "guard requires agg_backend='flat' — the defenses are "
                    "streaming passes over the flat (K, D) buffers")

    def timeline_config(self) -> "FLConfig":
        """The jit-cache key: this config with every SWEEPABLE field
        canonicalized.  The jitted round steps read sweepable values only
        from their traced ``hypers`` operand, so two configs that differ
        in sweepables map to the same static argument — one compiled
        program serves the whole sweep."""
        return dataclasses.replace(self, lr=0.0, mu=0.0, psi=0.0,
                                   server_lr=1.0)


def hypers_of(cfg: "FLConfig") -> Dict[str, jnp.ndarray]:
    """The traced-operand view of a config's sweepable fields (f32
    scalars, explicitly typed so the x64 CI leg doesn't promote them)."""
    return tuning.hypers_of(cfg, SWEEPABLE_FIELDS)


def _step_row(t: int, k: int, cfg) -> np.ndarray:
    """Round ``t``'s ``k`` local-step budgets, int32, on the host."""
    if not cfg.het_steps:
        return np.full((k,), cfg.max_local_steps, np.int32)
    return np.random.default_rng(10_000 + t).integers(
        1, cfg.max_local_steps + 1, k).astype(np.int32)


def local_step_table(rounds: int, k: int, cfg) -> np.ndarray:
    """Device-capability protocol (paper Sec. VI-A): the per-round
    local-step budgets of ``rounds`` rounds as one host ``(rounds, k)``
    int32 table.  Row ``t`` is drawn from the round-indexed numpy seed
    ``10_000 + t`` (``max_local_steps`` everywhere without
    ``het_steps``), so every compared algorithm — and every engine, sync
    and async — sees identical device capabilities.  `cfg` is any config
    with het_steps/max_local_steps (FLConfig or AsyncFLConfig)."""
    table = np.empty((rounds, k), np.int32)
    for t in range(rounds):
        table[t] = _step_row(t, k, cfg)
    return table


def local_step_draws(t: int, k: int, cfg) -> jnp.ndarray:
    """Row ``t`` of ``local_step_table`` moved to the device: the python
    loop's per-round budgets, the same integers as the compiled engines'
    table by construction."""
    return tprof.to_device(_step_row(t, k, cfg))


def scenario_round_inputs(fl, rounds: int, scenario):
    """Realize an ACTIVE scenario over a sync schedule: the per-round
    step draws with the completeness channel applied, the f32 upload
    mask (0.0 = transmission failed), the per-dispatch latency
    multiplier (None when jitter is off), and the per-dispatch payload
    corruption factor (None when every payload channel is off).  Shared
    by the python loop and the scan engine so both replay the identical
    realization.  Returns (steps (R, K) int32, up_mask (R, K) f32,
    lat_scale or None, corrupt (R, K) f32 or None).
    """
    from repro.sysmodel import scenario as scenario_mod
    base = local_step_table(rounds, fl.n_selected, fl)
    g = scenario_mod.realize(scenario, (rounds, fl.n_selected))
    steps = scenario_mod.scale_steps(base, g.comp)
    up_mask = (~g.drop).astype(np.float32)
    return steps, up_mask, g.lat_scale, g.corrupt


def scenario_grid_round_inputs(fl, rounds: int, grid):
    """Stacked ``scenario_round_inputs`` over a ``ScenarioGrid``: every
    array gains a leading S_scenario axis, and slice ``[i]`` is
    byte-identical to ``scenario_round_inputs(fl, rounds, grid[i])``
    (same base step draws, independently seeded cell realizations).
    ``lat_scale`` slices for jitter-free cells are exact ones.  Returns
    (steps (S, R, K) int32, up_mask (S, R, K) f32, lat_scale (S, R, K)
    or None, corrupt (S, R, K) f32 or None)."""
    from repro.sysmodel import scenario as scenario_mod
    base = local_step_table(rounds, fl.n_selected, fl)
    g = scenario_mod.realize_grid(grid, (rounds, fl.n_selected))
    steps = scenario_mod.scale_steps(np.broadcast_to(
        base, g.comp.shape), g.comp)
    up_mask = (~g.drop).astype(np.float32)
    return steps, up_mask, g.lat_scale, g.corrupt


def device_arrays(fed: FederatedData):
    """(train, test, p): the train and test stacks of ``fed`` as
    ``{"x", "y", "mask"}`` device arrays, and its size weights."""
    put = tprof.to_device
    train = {"x": put(fed.x), "y": put(fed.y), "mask": put(fed.mask)}
    test = {"x": put(fed.test_x), "y": put(fed.test_y),
            "mask": put(fed.test_mask)}
    return train, test, put(fed.p)


def _client_batch(data, ids):
    return {"x": data["x"][ids], "y": data["y"][ids], "mask": data["mask"][ids]}


def _all_grads(model_cfg, params, data):
    """∇F_k(w) for every device k -> stacked pytree (N, ...)."""
    def one(x, y, m):
        return jax.grad(lambda p: small.small_loss(
            model_cfg, p, {"x": x, "y": y, "mask": m}))(params)
    return jax.vmap(one)(data["x"], data["y"], data["mask"])


def _global_grad(grads_all, p_weights):
    """∇f(w) = Σ_k p_k ∇F_k(w)."""
    return jax.tree.map(
        lambda g: jnp.tensordot(p_weights, g.astype(jnp.float32), axes=1),
        grads_all)


def _local_updates_batch(model_cfg, params, batch, n_steps, fl: FLConfig,
                         hypers=None):
    """vmapped device updates over a pre-gathered (K, M, ...) cohort
    batch -> stacked (deltas, grads, gammas).  The shared local-solve
    unit of `fl_round` (through `_local_updates`, which gathers from the
    (N, M, ...) stack first), `fl_round_cohort` and the async steps
    (which take a gathered batch) — one function, so every path runs
    the identical math."""
    lr = fl.lr if hypers is None else hypers["lr"]
    mu = fl.mu if hypers is None else hypers["mu"]

    def one(x, y, m, steps):
        return solvers.local_update(
            lambda p, b: small.small_loss(model_cfg, p, b),
            params, {"x": x, "y": y, "mask": m},
            lr=lr, mu=mu, n_steps=steps, max_steps=fl.max_local_steps)

    # the device trace names the client solves by this scope
    with jax.named_scope("local_solve"):
        return jax.vmap(one)(batch["x"], batch["y"], batch["mask"],
                             n_steps)


def _local_updates(model_cfg, params, data, ids, n_steps, fl: FLConfig,
                   hypers=None):
    """vmapped device updates for the sampled multiset -> stacked
    (deltas, grads, gammas).  ``hypers`` carries the traced lr/mu (the
    engines always pass it; ``None`` falls back to the config's floats for
    direct callers and shape-only ``eval_shape`` probes)."""
    with jax.named_scope("local_solve"):
        batch = _client_batch(data, ids)
    return _local_updates_batch(model_cfg, params, batch, n_steps, fl,
                                hypers)


def apply_corruption(deltas, grads, corrupt):
    """Scenario payload corruption: multiply every leaf of device k's
    delta AND gradient by the per-dispatch factor ``corrupt[k]`` (NaN,
    ±scale_mag, −1, or exactly 1.0 for benign payloads — a float multiply
    by 1.0 is bit-exact, so benign rows are unchanged).  ``corrupt=None``
    keeps the traced program identical to the pre-corruption one.  Shared
    by every engine so loop and scan corrupt identically."""
    if corrupt is None:
        return deltas, grads

    def mul(x):
        c = corrupt.reshape((-1,) + (1,) * (x.ndim - 1))
        return x * c.astype(x.dtype)

    return jax.tree.map(mul, deltas), jax.tree.map(mul, grads)


def _mask_guard(new, params, up_mask):
    """All-uploads-failed guard for the masked pytree rules: keep the old
    parameters bit-for-bit when every selected upload dropped (mirrors
    the async engine's `_apply_aggregation`; `w + 0·x` alone would flip
    the sign of negative zeros)."""
    alive = jnp.sum(up_mask) > 0.0
    return jax.tree.map(lambda n, w: jnp.where(alive, n, w), new, params)


def _sync_aggregate(fl: FLConfig, params, deltas, grads, gammas, h,
                    up_mask, tau0, mesh, diag):
    """Shared sync-round aggregation for the cohort-shaped algorithms
    (fedavg / fedprox / folb / folb_het): everything after the local
    updates, factored out of `fl_round` so the lazy-population cohort
    step (`fl_round_cohort`) runs the identical traced ops.  Writes the
    guard info dict into ``diag`` when the robust kernel is active."""
    if fl.algo in ("fedavg", "fedprox"):
        if up_mask is None:
            new = aggregation.fedavg_aggregate(params, deltas)
        else:
            new = _mask_guard(aggregation.mean_staleness(
                params, deltas, tau0, alpha=0.0, mask=up_mask),
                params, up_mask)
    elif fl.algo in ("folb", "folb_het") and fl.agg_backend == "flat":
        # default hot path: stack everything into flat (K, D) buffers
        # (bf16 grads/deltas unless agg_dtype says otherwise) and run the
        # fused Pallas aggregation (2 streaming passes instead of ~2K
        # leafwise reductions), D-sharded when a mesh is given
        pg = h["psi"] * gammas if fl.algo == "folb_het" else None
        if fl.guard is not None:
            if up_mask is None:
                new, _, ginfo = ops.folb_aggregate_tree(
                    params, deltas, grads, psi_gammas=pg,
                    buf_dtype=jnp.dtype(fl.agg_dtype), mesh=mesh,
                    guard=fl.guard)
            else:
                new, _, ginfo = ops.folb_staleness_slots_tree(
                    params, deltas, grads, up_mask, tau0, alpha=0.0,
                    psi_gammas=pg, buf_dtype=jnp.dtype(fl.agg_dtype),
                    mesh=mesh, guard=fl.guard)
            diag["guard"] = ginfo
        elif up_mask is None:
            new, _ = ops.folb_aggregate_tree(
                params, deltas, grads, psi_gammas=pg,
                buf_dtype=jnp.dtype(fl.agg_dtype), mesh=mesh)
        else:
            # the masked-slot staleness kernel at τ = 0 IS masked folb
            # (disc == 1 exactly); it self-guards the all-masked case
            new, _ = ops.folb_staleness_slots_tree(
                params, deltas, grads, up_mask, tau0, alpha=0.0,
                psi_gammas=pg, buf_dtype=jnp.dtype(fl.agg_dtype),
                mesh=mesh)
    elif fl.algo == "folb":
        if up_mask is None:
            new = aggregation.folb_single_set(params, deltas, grads)
        else:
            new = _mask_guard(aggregation.folb_staleness(
                params, deltas, grads, tau0, alpha=0.0, mask=up_mask),
                params, up_mask)
    elif fl.algo == "folb_het":
        if up_mask is None:
            new = aggregation.folb_het(params, deltas, grads, gammas,
                                       h["psi"])
        else:
            new = _mask_guard(aggregation.folb_staleness(
                params, deltas, grads, tau0, alpha=0.0, gammas=gammas,
                psi=h["psi"], mask=up_mask), params, up_mask)
    else:
        raise ValueError(fl.algo)
    return new


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("mesh",))
def fl_round(model_cfg, fl: FLConfig, params, data, p_weights, key, n_steps,
             sel_probs=None, hypers=None, up_mask=None, corrupt=None, *,
             mesh=None):
    """One communication round.  Returns (new_params, diagnostics).

    ``sel_probs`` overrides the uniform selection distribution (e.g. the
    pre-computed static latency-aware probabilities of a deadline fleet);
    the fednu baselines ignore it (they derive their own).  ``hypers`` is
    the traced-operand view of the sweepable fields (see ``hypers_of``);
    the engines always pass it so sweepable values never enter the trace
    as constants, and any dict containing lr/mu/psi works (extra keys
    ride along unused).  ``mesh`` (static) shards the flat aggregation's
    D axis over a device mesh.

    ``up_mask`` is the scenario drop channel: a traced (K,) f32 mask with
    0.0 on uploads that failed in transit.  Masked devices still ran (and
    were waited for — the wall-clock is plan-side) but are excluded from
    aggregation via each rule's staleness-mask form at τ = 0, α = 0, so
    ``up_mask=None`` leaves the traced program exactly as before.

    ``corrupt`` is the scenario payload-corruption channel: a traced (K,)
    f32 factor (NaN / ±scale_mag / −1, exactly 1.0 when benign) applied
    multiplicatively to each device's uploaded delta and gradient.  With
    ``fl.guard`` set (static GuardConfig; folb/folb_het + flat backend
    only) the fused aggregation kernel rejects non-finite rows, clips
    inflated norms, and gates outlier scores; the diagnostics then carry
    the guard's post-rejection info dict under ``diag["guard"]``.
    """
    h = hypers if hypers is not None else hypers_of(fl)
    k_sel, k_sel2 = jax.random.split(key)
    N = data["x"].shape[0]
    K = fl.n_selected
    diag: Dict[str, Any] = {}
    tau0 = None if up_mask is None else jnp.zeros((K,), jnp.float32)

    if fl.algo in ("fednu_direct", "fednu_signed", "fednu_norm"):
        # naive baselines: probe all N devices first (expensive comms)
        grads_all = _all_grads(model_cfg, params, data)
        gg = _global_grad(grads_all, p_weights)
        if fl.algo == "fednu_norm":
            norms = jax.vmap(tree.tree_norm)(grads_all)
            probs = selection.norm_estimate_probs(norms)
        else:
            inner = jax.vmap(lambda g: tree.tree_dot(g, gg))(grads_all)
            probs = selection.lb_near_optimal_probs(inner)
        ids = selection.sample_multiset(k_sel, probs, K)
        deltas, grads, gammas = _local_updates(
            model_cfg, params, data, ids, n_steps, fl, h)
        deltas, grads = apply_corruption(deltas, grads, corrupt)
        if fl.algo == "fednu_signed":
            new = aggregation.signed_aggregate(params, deltas, grads, gg,
                                               mask=up_mask)
        elif up_mask is None:
            new = aggregation.fedavg_aggregate(params, deltas)
        else:
            new = aggregation.mean_staleness(params, deltas, tau0,
                                             alpha=0.0, mask=up_mask)
        if up_mask is not None:
            new = _mask_guard(new, params, up_mask)
        diag["probs_entropy"] = -jnp.sum(probs * jnp.log(probs + 1e-12))
        diag["ids"] = ids
        if fl.telemetry:
            from repro.telemetry import metrics as tmetrics
            diag["metrics"] = tmetrics.metrics_for_algo(
                fl.algo, params, new, deltas, grads, psi=h["psi"],
                gammas=gammas, mask=up_mask)
        return new, diag

    if sel_probs is None and fl.sampler == "indexed":
        # O(K) uniform draw, no (N,) probability vector; sel_probs
        # overrides (latency-aware selection is inherently O(N) and
        # validated against the indexed sampler upstream)
        ids = selection.sample_uniform_ids(k_sel, N, K)
        probs = None
    else:
        probs = selection.uniform_probs(N) if sel_probs is None else sel_probs
        ids = selection.sample_multiset(k_sel, probs, K)
    deltas, grads, gammas = _local_updates(
        model_cfg, params, data, ids, n_steps, fl, h)
    deltas, grads = apply_corruption(deltas, grads, corrupt)

    if fl.algo == "folb2":
        ids2 = selection.sample_uniform_ids(k_sel2, N, K) if probs is None \
            else selection.sample_multiset(k_sel2, probs, K)
        batch2 = _client_batch(data, ids2)
        grads_s2 = jax.vmap(
            lambda x, y, m: jax.grad(lambda p: small.small_loss(
                model_cfg, p, {"x": x, "y": y, "mask": m}))(params)
        )(batch2["x"], batch2["y"], batch2["mask"])
        new = aggregation.folb_two_set(params, deltas, grads, grads_s2,
                                       mask=up_mask)
        if up_mask is not None:
            new = _mask_guard(new, params, up_mask)
        diag["ids2"] = ids2
    else:
        new = _sync_aggregate(fl, params, deltas, grads, gammas, h,
                              up_mask, tau0, mesh, diag)
    diag["gamma_mean"] = jnp.mean(gammas)
    diag["ids"] = ids
    if fl.telemetry:
        # a sync round is the τ = 0, full-mask case of the async metrics
        # schema, so every engine's metric pytrees are structurally
        # identical (required by the deadline scan's lax.cond)
        from repro.telemetry import metrics as tmetrics
        diag["metrics"] = tmetrics.metrics_for_algo(
            fl.algo, params, new, deltas, grads, psi=h["psi"],
            gammas=gammas, mask=up_mask, guard=diag.get("guard"))
    return new, diag


# algorithms whose round math touches only the selected cohort — the ones
# the lazy-population engines support (fednu probes all N gradients and
# folb2 contacts a second in-jit-sampled set; both need resident data)
COHORT_ALGOS = ("fedavg", "fedprox", "folb", "folb_het")


@functools.partial(jax.jit, static_argnums=(0, 1),
                   static_argnames=("mesh",))
def fl_round_cohort(model_cfg, fl: FLConfig, params, batch, n_steps,
                    hypers=None, up_mask=None, corrupt=None, *, mesh=None):
    """Cohort form of `fl_round` for lazy populations: selection already
    happened on the host (the plan's pre-drawn ids) and ``batch`` is the
    pre-gathered (K, M, ...) cohort, so the traced program's shapes
    depend on K — never on N — and device memory is O(K·M·D).  Runs the
    same `_local_updates_batch` + `_sync_aggregate` units as `fl_round`,
    which is what makes a lazy run bit-for-bit a materialized run.
    ``COHORT_ALGOS`` only (validated by the lazy engine front door)."""
    h = hypers if hypers is not None else hypers_of(fl)
    K = batch["x"].shape[0]
    diag: Dict[str, Any] = {}
    tau0 = None if up_mask is None else jnp.zeros((K,), jnp.float32)
    deltas, grads, gammas = _local_updates_batch(
        model_cfg, params, batch, n_steps, fl, h)
    deltas, grads = apply_corruption(deltas, grads, corrupt)
    new = _sync_aggregate(fl, params, deltas, grads, gammas, h,
                          up_mask, tau0, mesh, diag)
    diag["gamma_mean"] = jnp.mean(gammas)
    if fl.telemetry:
        from repro.telemetry import metrics as tmetrics
        diag["metrics"] = tmetrics.metrics_for_algo(
            fl.algo, params, new, deltas, grads, psi=h["psi"],
            gammas=gammas, mask=up_mask, guard=diag.get("guard"))
    return new, diag


@functools.partial(jax.jit, static_argnums=(0,))
def eval_global(model_cfg, params, data, p_weights):
    """Device-weighted global loss f(w) = Σ p_k F_k(w) and accuracy."""
    losses = jax.vmap(
        lambda x, y, m: small.small_loss(model_cfg, params,
                                         {"x": x, "y": y, "mask": m})
    )(data["x"], data["y"], data["mask"])
    accs = jax.vmap(
        lambda x, y, m: small.small_accuracy(model_cfg, params,
                                             {"x": x, "y": y, "mask": m})
    )(data["x"], data["y"], data["mask"])
    return jnp.sum(losses * p_weights), jnp.sum(accs * p_weights)


@dataclasses.dataclass
class FedRunResult:
    """Round history + final parameters.

    The scalar time-series live in `history` (Dict[str, List[float]]); the
    final parameter pytree is a separate field instead of being smuggled
    into the history dict.  Mapping-style reads (`result["test_acc"]`)
    delegate to `history` so plotting/benchmark code treats it like the
    plain dict it used to receive.

    `ids` records the actual per-round selected/dispatched device ids as a
    (rounds, K) int array — every engine fills it (the async engines read
    it straight off their event plan).  With `telemetry` on, `metrics`
    carries the structured per-round arrays (repro.telemetry.metrics;
    in-scan stats plus host-derived network/pool series) and `profile` the
    host-phase timer summary (repro.telemetry.profiler).
    """
    history: Dict[str, List[float]]
    params: Any
    ids: Optional[np.ndarray] = None
    metrics: Optional[Dict[str, Any]] = None
    profile: Optional[Dict[str, Any]] = None

    def __getitem__(self, key: str) -> List[float]:
        return self.history[key]

    def __contains__(self, key: str) -> bool:
        return key in self.history

    def get(self, key: str, default=None):
        return self.history.get(key, default)

    def keys(self):
        return self.history.keys()


def fleet_cost_setup(model_cfg, params, fed, algo: str):
    """Cost model pieces for fleet-timestamped runs: (round cost, gradient
    probe cost, per-device dataset sizes).  Shared by the python-loop and
    scan-compiled engines so both replay identical wall-clocks.  For a
    lazy ``LazyFederatedData`` the sizes come back as its O(K)-indexable
    view instead of an (N,) reduction over the resident mask."""
    from repro.sysmodel import RoundCost, round_cost_for
    cost = round_cost_for(model_cfg, params,
                          uploads_gradient="folb" in algo or "fednu" in algo)
    # a gradient probe (fednu baselines, folb2's S2 set): one fwd+bwd
    # pass over the local data, then upload the gradient (1x params)
    probe_cost = RoundCost(
        flops_per_step_example=cost.flops_per_step_example,
        down_bytes=cost.down_bytes, up_bytes=cost.down_bytes)
    sizes = fed.sizes if hasattr(fed, "gather_sizes") \
        else np.asarray(fed.mask.sum(axis=1))
    return cost, probe_cost, sizes


def sync_round_clock(fleet, cost, probe_cost, sizes, algo: str,
                     ids: np.ndarray, ids2: Optional[np.ndarray],
                     n_steps, clock_now: float,
                     lat_scale: Optional[np.ndarray] = None) -> float:
    """Advance the simulated wall-clock by one synchronous round (full
    barrier: the round costs as much as its slowest selected device).

    ``lat_scale`` (scenario jitter, (K,)) applies to the K update
    dispatches only — the fednu/folb2 gradient probes are separate
    transmissions outside the scenario's per-dispatch draw grid."""
    from repro.sysmodel import RoundCost, plan_sync_round
    start = clock_now
    phase_cost = cost
    if algo.startswith("fednu"):
        # the naive baselines first probe ALL N devices for their
        # gradients — the defining communication cost the paper's
        # FOLB avoids; the server can only sample after the slowest
        # probe lands.  Selected devices already hold w^t and have
        # uploaded ∇F_k, so the update phase costs only local
        # compute + the delta upload.
        all_ids = np.arange(fleet.n_devices)
        probe = plan_sync_round(fleet, all_ids, np.ones(len(all_ids)),
                                probe_cost, start=start, n_examples=sizes)
        start = probe.round_end
        phase_cost = RoundCost(
            flops_per_step_example=cost.flops_per_step_example,
            down_bytes=0.0, up_bytes=probe_cost.down_bytes)
    plan = plan_sync_round(fleet, ids, tprof.fetch(n_steps), phase_cost,
                           start=start, n_examples=sizes[ids],
                           lat_scale=lat_scale)
    clock_now = plan.round_end
    if ids2 is not None:   # folb2 contacts a second K-device set
        plan2 = plan_sync_round(fleet, ids2, np.ones(len(ids2)), probe_cost,
                                start=start, n_examples=sizes[ids2])
        clock_now = max(clock_now, plan2.round_end)
    return clock_now


def run_federated(model_cfg, fed: FederatedData, fl: FLConfig, rounds: int,
                  init_key: Optional[jax.Array] = None,
                  eval_every: int = 1, fleet=None, sel_probs=None,
                  mesh=None, profiler=None, scenario=None) -> FedRunResult:
    """Python-loop driver.  Heterogeneous local-step draws are generated from
    a round-indexed numpy seed so all compared algorithms see identical
    device capabilities (paper Sec. VI-A).

    With a `repro.sysmodel.DeviceFleet`, each synchronous round is also
    timestamped on the simulated wall-clock: the round costs as much time
    as its slowest selected device (full barrier, no deadline), and the
    cumulative clock is recorded in history["wall_clock"] at eval points —
    making sync runs comparable to the async engine on one time axis.

    With ``fl.telemetry`` the result additionally carries per-round
    metrics (in-scan stats from `fl_round` plus the modeled network
    series) and a host-phase profile; ``profiler`` overrides the
    auto-created `repro.telemetry.PhaseProfiler`.

    ``scenario`` (`repro.sysmodel.ScenarioConfig`) activates the seeded
    failure channels: drop masks uploads out of aggregation (the fleet
    clock still waits — and charges bytes — for them), completeness
    rescales the local-step draws, jitter multiplies latencies, and the
    payload channels (nan/scale/flip) corrupt arrived updates before
    aggregation (pair with ``fl.guard`` for the robust kernel).  Dropout
    is rejected (the sync barrier would wait forever).  A null/None
    scenario is bit-for-bit the scenario-free program.
    """
    from repro.telemetry import metrics as tmetrics
    from repro.telemetry import profiler_for
    prof = profiler_for(fl.telemetry, profiler)
    with prof.phase("setup"):
        from repro.sysmodel import scenario as scenario_mod
        sc = scenario_mod.as_active(scenario)
        sc_steps = sc_mask = sc_lat = sc_corr = None
        if sc is not None:
            scenario_mod.check_sync(sc)
            sc_steps, sc_mask, sc_lat, sc_corr = scenario_round_inputs(
                fl, rounds, sc)
        key = init_key if init_key is not None \
            else jax.random.PRNGKey(fl.seed)
        params = small.init_small(model_cfg, key)
        train, test, p = device_arrays(fed)

        hist: Dict[str, List[float]] = {"round": [], "train_loss": [],
                                        "test_acc": [], "train_acc": []}
        cost = probe_cost = sizes = None
        if fleet is not None:
            assert fleet.n_devices == fed.n_devices, \
                (fleet.n_devices, fed.n_devices)
            cost, probe_cost, sizes = fleet_cost_setup(model_cfg, params,
                                                       fed, fl.algo)
            hist["wall_clock"] = []
        clock_now = 0.0
        from repro.fed import server_opt as sopt
        # sweepable scalars ride as traced operands against the canonical
        # static config: configs differing only in lr/mu/psi/server_lr
        # share one compiled round program (and the sweep engine vmaps the
        # same one)
        fl_t = fl.timeline_config()
        hypers = hypers_of(fl)
        so_cfg = sopt.ServerOptConfig(kind=fl.server_opt, lr=1.0)
        so_state = sopt.init_server_state(so_cfg, params)
        use_server_opt = fl.server_opt != "sgd" or fl.server_lr != 1.0
    ids_all: List[Any] = []
    mlist: List[Any] = []
    for t in range(rounds):
        with prof.phase("rounds"):
            if sc is None:
                n_steps = local_step_draws(t, fl.n_selected, fl)
                up_mask = corrupt = None
            else:
                n_steps = tprof.to_device(sc_steps[t])
                up_mask = tprof.to_device(sc_mask[t])
                corrupt = None if sc_corr is None \
                    else tprof.to_device(sc_corr[t])
            key, sub = jax.random.split(key)
            new_params, diag = fl_round(model_cfg, fl_t, params, train, p,
                                        sub, n_steps, sel_probs, hypers,
                                        up_mask, corrupt, mesh=mesh)
            ids_all.append(diag["ids"])
            if fl.telemetry:
                mlist.append(diag["metrics"])
            if fleet is not None:
                clock_now = sync_round_clock(
                    fleet, cost, probe_cost, sizes, fl.algo,
                    tprof.fetch(diag["ids"]),
                    tprof.fetch(diag["ids2"]) if "ids2" in diag else None,
                    n_steps, clock_now,
                    lat_scale=None if sc_lat is None else sc_lat[t])
            if use_server_opt:
                # one shared jitted unit (delta cast sequence + optimizer)
                # so the scan engine can replay it bit-for-bit
                params, so_state = sopt.server_round_update(
                    so_cfg, params, so_state, new_params,
                    hypers["server_lr"])
            else:
                params = new_params
        if t % eval_every == 0 or t == rounds - 1:
            with prof.phase("eval"):
                tr_loss, tr_acc = eval_global(model_cfg, params, train, p)
                _, te_acc = eval_global(model_cfg, params, test, p)
                hist["round"].append(t)
                hist["train_loss"].append(tprof.fetch_float(tr_loss))
                hist["train_acc"].append(tprof.fetch_float(tr_acc))
                hist["test_acc"].append(tprof.fetch_float(te_acc))
                if fleet is not None:
                    hist["wall_clock"].append(clock_now)
    with prof.phase("collect"):
        ids_np = np.stack([tprof.fetch(i) for i in ids_all]) \
            if ids_all else None
        metrics = None
        if fl.telemetry:
            metrics = tmetrics.stack_metrics(mlist)
            D = int(sum(x.size for x in jax.tree.leaves(params)))
            metrics.update(tmetrics.sync_network_series(
                D, fl, rounds, fed.n_devices))
            metrics["selection_entropy"] = tmetrics.selection_entropy(
                ids_np, fed.n_devices)
    return FedRunResult(history=hist, params=params, ids=ids_np,
                        metrics=metrics, profile=prof.finish())


def rounds_to_accuracy(hist, target: float) -> int:
    """Table-I metric: first round whose test accuracy reaches `target`
    (-1 if never).  Accepts a history mapping or a FedRunResult."""
    for r, acc in zip(hist["round"], hist["test_acc"]):
        if acc >= target:
            return r
    return -1


def seconds_to_accuracy(hist, target: float) -> float:
    """Time-to-accuracy: simulated wall-clock seconds until test accuracy
    first reaches `target` (-1.0 if never).  Requires a run that recorded
    history["wall_clock"] (fleet-timestamped sync run or the async engine).
    """
    for s, acc in zip(hist["wall_clock"], hist["test_acc"]):
        if acc >= target:
            return float(s)
    return -1.0
