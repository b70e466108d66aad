"""Asynchronous federated execution engine over the system model.

The third execution engine (alongside the sync vmap simulator and the
O(1)-memory distributed round engine): FOLB driven by simulated wall-clock
time instead of a round counter.  Two modes:

  deadline — FedCS-style barriered rounds with a per-round deadline D.
             The server dispatches K devices, aggregates whatever arrives
             by D, and closes the round.  Stragglers are NOT discarded:
             their uploads land in a later round and join that round's
             aggregation with staleness τ = rounds elapsed, discounted by
             (1 + τ)^{-α} inside the FOLB score (Eq. V-B extended) — the
             ψγ heterogeneity penalty becomes an actual scheduling signal.
             With D = ∞ every device arrives, τ ≡ 0, and the round math
             dispatches to the *same* fused sync round as the vmap
             simulator, so the two engines agree bit-for-bit.

  fedbuff  — buffered fully-async (Nguyen et al., FedBuff): `concurrency`
             devices run at all times; the server aggregates every
             `buffer_size` arrivals; each update is discounted by its
             version staleness.  No global barrier exists — progress is
             measured purely on the virtual clock.

Execution is split into a host-side **event plan** and a device-side
replay.  Fleet latencies are a deterministic function of the seeded fleet
and the pre-drawn key chain, so `build_deadline_plan` / `build_fedbuff_plan`
pre-compute the whole event timeline — dispatch/arrival times, per-round
due/straggler/missed partitions, fedbuff flush boundaries and staleness
counters τ — into fixed-width stacked arrays (a static straggler budget
with masked slots; pending updates live in a fixed **slot pool** addressed
by plan-assigned indices).  The python loop (`run_async`) replays the plan
one jitted step per round; the compiled engine
(`repro.fed.scan_engine.run_async_compiled`) replays the *same* jitted
step functions inside one `lax.scan` — which is what makes the two
bit-for-bit identical (params, ids, staleness, wall clock).

Device latency, bandwidth, and availability come from a
``repro.sysmodel.DeviceFleet``; selection can be latency-aware
(P ∝ |I_k|·σ((D − ℓ_k)/s), `repro.core.selection.latency_aware_probs`).
"""
from __future__ import annotations

import dataclasses
import functools
import heapq
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, selection, tuning
from repro.data.federated import FederatedData
from repro.fed import simulator
from repro.kernels import ops
from repro.models import small
from repro.sysmodel import (DeviceFleet, EventQueue, device_latencies,
                            expected_latencies, plan_deadline_run,
                            round_cost_for)
from repro.sysmodel import scenario as scenario_mod
from repro.telemetry import profiler as tprof

ASYNC_MODES = ("deadline", "fedbuff")
# aggregation bases the async engine can run (the sync-parity fast path
# additionally requires the algo to exist in the sync simulator)
ASYNC_ALGOS = ("fedavg", "fedprox", "folb", "folb_het")

# AsyncFLConfig's sweepable / timeline split (see
# ``simulator.SWEEPABLE_FIELDS``): pure learning-math scalars that never
# touch the event timeline — the plans built by ``build_deadline_plan`` /
# ``build_fedbuff_plan`` are byte-identical across any values of these
# fields (guarded by tests/test_sweep_engine.py), which is what makes one
# plan reusable by a whole hyper-parameter sweep.
SWEEPABLE_FIELDS = ("lr", "mu", "psi", "staleness_alpha")


@dataclasses.dataclass(frozen=True)
class AsyncFLConfig:
    mode: str = "deadline"        # deadline | fedbuff
    algo: str = "folb"            # fedavg | fedprox | folb | folb_het
    n_selected: int = 10          # K dispatched per round (deadline mode)
    mu: float = 1.0
    lr: float = 0.05
    max_local_steps: int = 20
    het_steps: bool = True
    deadline: float = math.inf    # seconds per round (deadline mode)
    buffer_size: int = 10         # M: aggregate every M arrivals (fedbuff)
    concurrency: int = 20         # in-flight devices (fedbuff)
    staleness_alpha: float = 0.0  # (1+τ)^{-α} score discount; 0 = off
    psi: float = 0.0              # Sec. V heterogeneity penalty weight
    latency_aware: bool = False   # deadline-aware selection probabilities
    agg_backend: str = "flat"     # flat (fused Pallas kernel) | pytree
    agg_dtype: str = "bfloat16"   # (K, D) buffer storage dtype (flat only)
    # observability: per-round metrics from the jitted steps + host-phase
    # profile (see FLConfig.telemetry — same static, never-sweepable flag)
    telemetry: bool = False
    # robust aggregation (repro.kernels.guard.GuardConfig) inside the
    # fused flat kernel — static, jit-cache-keyed, never sweepable; None
    # is bit-for-bit the unguarded program (see FLConfig.guard)
    guard: Optional[object] = None
    # uniform-selection sampler (see FLConfig.sampler): "indexed" draws
    # O(K) ids with no (N,) probability vector — required for lazy
    # populations; incompatible with latency_aware (expected latencies
    # over all N are inherently O(N)).  Timeline-affecting, never
    # sweepable.
    sampler: str = "categorical"
    seed: int = 0

    def __post_init__(self):
        assert self.mode in ASYNC_MODES, self.mode
        assert self.algo in ASYNC_ALGOS, self.algo
        assert self.agg_backend in simulator.AGG_BACKENDS, self.agg_backend
        assert self.agg_dtype in simulator.AGG_DTYPES, self.agg_dtype
        if self.sampler not in ("categorical", "indexed"):
            raise ValueError(f"unknown sampler {self.sampler!r}")
        if self.sampler == "indexed" and self.latency_aware:
            raise ValueError(
                "sampler='indexed' is uniform-only: latency-aware "
                "selection needs expected latencies for every device "
                "(O(N)) — use sampler='categorical' or drop latency_aware")
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

    def sync_config(self) -> simulator.FLConfig:
        """The synchronous FLConfig whose round math this config reduces to
        when every device arrives on time with zero staleness."""
        return simulator.FLConfig(
            algo=self.algo, n_selected=self.n_selected, mu=self.mu,
            lr=self.lr, max_local_steps=self.max_local_steps,
            het_steps=self.het_steps, psi=self.psi,
            agg_backend=self.agg_backend, agg_dtype=self.agg_dtype,
            telemetry=self.telemetry, guard=self.guard,
            sampler=self.sampler, seed=self.seed)

    def timeline_config(self) -> "AsyncFLConfig":
        """The jit-cache key: this config with every SWEEPABLE field
        canonicalized (the jitted steps read those only from their traced
        ``hypers`` operand)."""
        return dataclasses.replace(self, lr=0.0, mu=0.0, psi=0.0,
                                   staleness_alpha=0.0)


def hypers_of(afl: AsyncFLConfig) -> Dict[str, jnp.ndarray]:
    """Traced-operand view of an async config's sweepable fields.  A
    superset of what ``simulator.fl_round`` needs (lr/mu/psi), so the same
    dict serves the sync-parity fast path and the staleness slow steps."""
    return tuning.hypers_of(afl, SWEEPABLE_FIELDS)


def _concat0(a, b):
    """Concatenate two stacked pytrees along the client axis."""
    return jax.tree.map(lambda x, y: jnp.concatenate([x, y], axis=0), a, b)


def _apply_aggregation(afl: AsyncFLConfig, params, deltas, grads, gammas,
                       tau: jnp.ndarray, mask=None, mesh=None, hypers=None):
    """Staleness-discounted aggregation over the arrived set.

    With `mask` the slot arrays have a static width and invalid slots are
    excluded by the mask (fixed-budget contract of the event plans); an
    all-masked budget returns `params` unchanged, bit-exact.  ``hypers``
    carries the traced staleness_alpha / psi (``None`` falls back to the
    config's floats for direct callers).

    Returns ``(new_params, ginfo)``: ``ginfo`` is the guarded kernel's
    info dict (post-guard mask + rejection counters) when ``afl.guard``
    is set, else None — ``guard=None`` keeps every traced program exactly
    as before.
    """
    h = hypers if hypers is not None else hypers_of(afl)
    alpha = h["staleness_alpha"]
    if afl.algo in ("fedavg", "fedprox"):
        new = aggregation.mean_staleness(params, deltas, tau, alpha=alpha,
                                         mask=mask)
    elif afl.agg_backend == "flat":
        # default hot path: flat (K, D) buffers (bf16 storage unless
        # agg_dtype overrides) through the fused Pallas staleness kernel
        # (interpret mode on CPU), D-sharded when a mesh is given.  psi
        # may be traced, so the branch is on the (static) algo only; the
        # kernel treats psi_gammas=None as exact zeros, so psi == 0 is
        # bit-identical either way.
        pg = h["psi"] * gammas if afl.algo == "folb_het" else None
        if afl.guard is not None:
            if mask is not None:
                new, _, ginfo = ops.folb_staleness_slots_tree(
                    params, deltas, grads, mask, tau,
                    alpha=alpha, psi_gammas=pg,
                    buf_dtype=jnp.dtype(afl.agg_dtype), mesh=mesh,
                    guard=afl.guard)
            else:
                new, _, ginfo = ops.folb_staleness_tree(
                    params, deltas, grads, tau, alpha=alpha, psi_gammas=pg,
                    buf_dtype=jnp.dtype(afl.agg_dtype), mesh=mesh,
                    guard=afl.guard)
            return new, ginfo
        if mask is not None:
            new, _ = ops.folb_staleness_slots_tree(
                params, deltas, grads, mask, tau,
                alpha=alpha, psi_gammas=pg,
                buf_dtype=jnp.dtype(afl.agg_dtype), mesh=mesh)
            return new, None
        new, _ = ops.folb_staleness_tree(params, deltas, grads, tau,
                                         alpha=alpha, psi_gammas=pg,
                                         buf_dtype=jnp.dtype(afl.agg_dtype),
                                         mesh=mesh)
        return new, None
    else:
        new = aggregation.folb_staleness(
            params, deltas, grads, tau, alpha=alpha,
            gammas=gammas if afl.algo == "folb_het" else None,
            psi=h["psi"], mask=mask)
    if mask is not None:  # empty budget: params unchanged, bit-exact
        alive = jnp.sum(mask) > 0.0
        new = jax.tree.map(lambda n, w: jnp.where(alive, n, w), new, params)
    return new, None


# ------------------------------------------------------------- event plans

@dataclasses.dataclass(frozen=True)
class DeadlinePlan:
    """Host-precomputed timeline of a deadline run (R rounds, K dispatched).

    Pending straggler updates live in a slot pool of `n_slots` rows (+1
    dump row at index `n_slots` for arrived devices' writes); `store_slot`
    says where each round stashes its stragglers, `due_slot`/`due_mask`/
    `due_tau` which (masked, fixed budget `n_due`) pool rows each round
    aggregates as late arrivals.
    """
    keys: np.ndarray        # (R, 2) uint32 round subkeys (the loop's `sub`)
    ids: np.ndarray         # (R, K) int32 sampled device ids
    n_steps: np.ndarray     # (R, K) int32 local-step draws
    arrival: np.ndarray     # (R, K) float64 upload-completion times
    arrived: np.ndarray     # (R, K) bool made-the-deadline
    round_end: np.ndarray   # (R,)  float64 server round close
    fast: np.ndarray        # (R,) bool: all arrived, nothing due -> fl_round
    store_slot: np.ndarray  # (R, K) int32 pool slot per straggler (dump else)
    due_slot: np.ndarray    # (R, S) int32 pool slots due this round
    due_mask: np.ndarray    # (R, S) float32 valid-slot mask
    due_tau: np.ndarray     # (R, S) float32 staleness in rounds
    n_arrived: np.ndarray   # (R,) int64 arrived + due count
    stale_mean: np.ndarray  # (R,) float64 mean τ over the aggregated set
    n_slots: int            # pool rows (dump row index == n_slots)
    n_due: int              # S: static late-arrival budget per round
    # scenario channels (None on scenario-free plans — the pre-scenario
    # layout; `plan_digest` iterates dataclass fields, so these hash too):
    # `arrived` above already excludes dropped/lost dispatches, these
    # record WHY so telemetry/tests can account uploads vs silence
    drop_mask: Optional[np.ndarray] = None    # (R, K) bool upload failed
    lost_mask: Optional[np.ndarray] = None    # (R, K) bool device offline
    n_failed_up: Optional[np.ndarray] = None  # (R,) int64 failed uploads
    #   landing (paying their bytes) inside each round's window
    corrupt: Optional[np.ndarray] = None      # (R, K) f32 payload factor


@dataclasses.dataclass(frozen=True)
class FedBuffPlan:
    """Host-precomputed timeline of a fedbuff run (R flushes of M).

    `seed_*` are the initial `concurrency` dispatches (computed on the
    initial params, before the first flush); each round then dispatches
    exactly M devices (one per arrival pop) and flushes M pool rows.
    """
    seed_ids: np.ndarray     # (C,) int32
    seed_steps: np.ndarray   # (C,) int32
    seed_slots: np.ndarray   # (C,) int32
    ids: np.ndarray          # (R, M) int32 devices dispatched during round
    n_steps: np.ndarray      # (R, M) int32
    store_slot: np.ndarray   # (R, M) int32 pool slot per dispatch
    flush_slot: np.ndarray   # (R, M) int32 pool rows aggregated this round
    tau: np.ndarray          # (R, M) float32 version staleness at flush
    flush_clock: np.ndarray  # (R,) float64 wall clock of the M-th arrival
    stale_mean: np.ndarray   # (R,) float64
    n_slots: int             # pool rows (max concurrently live updates)
    # per-dispatch clocks over ALL C + R*M dispatches (seeds first) — the
    # telemetry trace export's raw material; None on externally-built
    # plans that predate the fields
    dispatch_clock: Optional[np.ndarray] = None  # (C + R*M,) float64
    arrival_clock: Optional[np.ndarray] = None   # (C + R*M,) float64
    all_ids: Optional[np.ndarray] = None         # (C + R*M,) int32
    all_steps: Optional[np.ndarray] = None       # (C + R*M,) int32
    # scenario channels (None on scenario-free plans): flushes count real
    # arrivals only, and a dropped upload occupies its flush position but
    # is masked out of the aggregation by `flush_mask`.  A *lost* (dropout)
    # dispatch frees its slot at the loss event and fires a replacement
    # dispatch, so rounds can dispatch MORE than M devices: the dispatch
    # arrays above pad to the widest round (pad rows: id 0, 1 step, the
    # dump slot at index n_slots−1, corruption 1.0) and `n_disp` records
    # each round's real dispatch count.  The per-dispatch arrays are
    # sliced to the dispatches actually made.
    flush_mask: Optional[np.ndarray] = None      # (R, M) float32
    drop_mask: Optional[np.ndarray] = None       # (n_dispatched,) bool
    lost_mask: Optional[np.ndarray] = None       # (n_dispatched,) bool
    n_disp: Optional[np.ndarray] = None          # (R,) int64 real dispatches
    seed_corrupt: Optional[np.ndarray] = None    # (C,) f32 payload factor
    corrupt: Optional[np.ndarray] = None         # (R, W) f32 payload factor


@functools.partial(jax.jit, static_argnums=(2,))
def _draw_ids_chain(subs, probs, k: int):
    """The deadline loop's per-round id sampling, batched: for each round
    subkey, split off the selection key and draw the K-multiset — the same
    values the eager `sample_multiset(split(sub)[0], probs, K)` sequence
    produces, in one compiled call."""
    def one(sub):
        k_sel, _ = jax.random.split(sub)
        return selection.sample_multiset(k_sel, probs, k)
    return jax.vmap(one)(subs)


@jax.jit
def _draw_cids_chain(subs, probs):
    """The fedbuff loop's per-dispatch device draw, batched."""
    return jax.vmap(lambda s: selection.sample_multiset(s, probs, 1)[0])(subs)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw_ids_chain_indexed(subs, n: int, k: int):
    """`_draw_ids_chain` for ``sampler="indexed"``: the same
    split-then-draw key discipline, but an O(K) uniform id draw with no
    (N,) probability vector — host selection cost per round is
    independent of fleet size."""
    def one(sub):
        k_sel, _ = jax.random.split(sub)
        return selection.sample_uniform_ids(k_sel, n, k)
    return jax.vmap(one)(subs)


@functools.partial(jax.jit, static_argnums=(1,))
def _draw_cids_chain_indexed(subs, n: int):
    """`_draw_cids_chain` for ``sampler="indexed"`` (O(1) per dispatch)."""
    return jax.vmap(
        lambda s: selection.sample_uniform_ids(s, n, 1)[0])(subs)


def deadline_selection_probs(afl: AsyncFLConfig, fleet: DeviceFleet, cost,
                             sizes: np.ndarray):
    """The static latency-aware selection distribution (or None for
    uniform).  Expected latencies don't change round to round, so the
    vector is computed once — the same vector
    ``scan_engine.latency_selection_probs`` hands the compiled sync
    engine, which is what lets the scan run this sweep's selection
    policy."""
    if not afl.latency_aware:
        return None
    exp_lat = tprof.to_device(expected_latencies(
        fleet, cost, mean_steps=simulator.mean_local_steps(afl),
        n_examples=sizes))
    return selection.latency_aware_probs(
        jnp.ones((fleet.n_devices,)), exp_lat, afl.deadline)


def build_deadline_plan(afl: AsyncFLConfig, fleet: DeviceFleet, cost,
                        sizes: np.ndarray, rounds: int, init_key,
                        sel_probs=None, scenario=None) -> DeadlinePlan:
    """Pre-compute the whole deadline-mode event timeline on the host.

    Replicates the per-round host sequence exactly — the
    ``key, sub = jax.random.split(key)`` chain, the round-indexed numpy
    step draws, and `plan_sync_round`'s float arithmetic (via the
    vectorized `plan_deadline_run`) — then simulates the pending-straggler
    set to assign pool slots and fixed-width masked due budgets.

    An active ``scenario`` folds the failure channels into the plan
    arrays: completeness rescales the step draws, jitter multiplies the
    latencies, lost (dropout) dispatches never arrive (forcing the round
    to its cutoff — dropout requires a finite deadline), and dropped
    uploads arrive on schedule but are excluded from aggregation and the
    straggler pool (they are charged as failed-upload bytes in the round
    their arrival lands in).  ``plan.arrived`` remains the aggregation
    mask; `drop_mask`/`lost_mask`/`n_failed_up` record the failures, and
    `corrupt` carries the payload channels' per-dispatch factors.
    """
    from repro.fed.scan_engine import _split_chain
    K = afl.n_selected
    with tprof.span("plan_build/key_chain"):
        subs = _split_chain(init_key, rounds)
        if sel_probs is None and afl.sampler == "indexed":
            # O(K) per round: never build the (N,) uniform vector
            ids = tprof.fetch(
                _draw_ids_chain_indexed(subs, fleet.n_devices, K), np.int32)
        else:
            probs = sel_probs if sel_probs is not None \
                else selection.uniform_probs(fleet.n_devices)
            ids = tprof.fetch(_draw_ids_chain(subs, probs, K), np.int32)
        keys = tprof.fetch(subs)
    with tprof.span("plan_build/step_draws"):
        n_steps = simulator.local_step_table(rounds, K, afl)
    sc = scenario_mod.as_active(scenario)
    with tprof.span("plan_build/timeline"):
        if sc is None:
            arrival, arrived, round_end = plan_deadline_run(
                fleet, ids, n_steps, cost, deadline=afl.deadline,
                n_examples=sizes)
            drop = lost = None
        else:
            scenario_mod.check_deadline(sc, afl.deadline)
            g = scenario_mod.realize(sc, (rounds, K))
            n_steps = scenario_mod.scale_steps(n_steps, g.comp)
            drop, lost = g.drop, g.lost
            arrival, arrived, round_end = plan_deadline_run(
                fleet, ids, n_steps, cost, deadline=afl.deadline,
                n_examples=sizes, lat_scale=g.lat_scale, lost=lost)
            # `arrived` excludes lost dispatches already
            # (plan_deadline_run); exclude failed uploads from aggregation
            # too — they land on time but carry nothing
            arrived = arrived & ~drop
    with tprof.span("plan_build/pool"):
        # {"arrival", "t0", "slot"} in insertion order
        pending: List[Dict] = []
        failed_pending: List[float] = []   # arrival clocks of dropped uploads
        free: List[int] = []
        pool = 0
        store_slot = np.full((rounds, K), -1, np.int64)
        due_lists: List[List] = []
        fast = np.zeros(rounds, bool)
        n_arrived = np.zeros(rounds, np.int64)
        n_failed = np.zeros(rounds, np.int64)
        stale_sum = np.zeros(rounds)
        for t in range(rounds):
            if sc is not None:
                # failed-upload byte accounting: a dropped dispatch's upload
                # still lands on the network at its arrival time (possibly in
                # a LATER round for dropped stragglers) — drain before the
                # fast-round shortcut so fast rounds are charged too
                failed_pending.extend(arrival[t, i]
                                      for i in np.flatnonzero(drop[t]))
                n_failed[t] = sum(1 for a in failed_pending
                                  if a <= round_end[t])
                failed_pending = [a for a in failed_pending
                                  if a > round_end[t]]
            due = [pu for pu in pending if pu["arrival"] <= round_end[t]]
            if arrived[t].all() and not due:
                fast[t] = True
                due_lists.append([])
                n_arrived[t] = K
                continue
            pending = [pu for pu in pending if pu["arrival"] > round_end[t]]
            # free due slots BEFORE allocating this round's stragglers: the
            # step function gathers due rows before storing, so same-round
            # slot reuse is safe
            for pu in due:
                heapq.heappush(free, pu["slot"])
            if sc is None:
                stragglers = np.flatnonzero(~arrived[t])
            else:
                # dropped/lost dispatches are DISCARDED, never parked: their
                # updates go to the dump row like an on-time device's write
                stragglers = np.flatnonzero(~arrived[t] & ~drop[t] & ~lost[t])
            for i in stragglers:
                if free:
                    slot = heapq.heappop(free)
                else:
                    slot = pool
                    pool += 1
                store_slot[t, i] = slot
                pending.append({"arrival": arrival[t, i], "t0": t,
                                "slot": slot})
            due_lists.append([(pu["slot"], t - pu["t0"]) for pu in due])
            n_arrived[t] = int(arrived[t].sum()) + len(due)
            stale_sum[t] = float(sum(tau for _, tau in due_lists[-1]))
        S = max((len(d) for d in due_lists), default=0)
        due_slot = np.full((rounds, S), pool, np.int64)
        due_mask = np.zeros((rounds, S), np.float32)
        due_tau = np.zeros((rounds, S), np.float32)
        for t, d in enumerate(due_lists):
            for j, (slot, tau) in enumerate(d):
                due_slot[t, j] = slot
                due_mask[t, j] = 1.0
                due_tau[t, j] = tau
        store_slot = np.where(store_slot < 0, pool, store_slot)
        stale_mean = np.where(n_arrived > 0,
                              stale_sum / np.maximum(n_arrived, 1), 0.0)
        return DeadlinePlan(
            keys=keys, ids=ids, n_steps=n_steps, arrival=arrival,
            arrived=arrived, round_end=round_end, fast=fast,
            store_slot=store_slot.astype(np.int32),
            due_slot=due_slot.astype(np.int32), due_mask=due_mask,
            due_tau=due_tau, n_arrived=n_arrived, stale_mean=stale_mean,
            n_slots=pool, n_due=S,
            drop_mask=drop, lost_mask=lost,
            n_failed_up=None if sc is None else n_failed,
            corrupt=None if sc is None else g.corrupt)


class _FedBuffCapacity(Exception):
    """Internal: a fedbuff plan-build attempt ran out of pre-drawn
    dispatches (lost-dispatch replacements outgrew the draw grid)."""


def build_fedbuff_plan(afl: AsyncFLConfig, fleet: DeviceFleet, cost,
                       sizes: np.ndarray, rounds: int,
                       init_key, scenario=None) -> FedBuffPlan:
    """Pre-compute the whole fedbuff event timeline on the host.

    Device latencies don't depend on parameter values, so the entire
    dispatch/arrival/flush interleaving — including which pool slot every
    in-flight update occupies and its staleness at flush — is known before
    any model math runs.  The key chain, per-dispatch numpy step draws,
    and (time, seq) event ordering replicate the original event loop
    exactly.

    An active ``scenario`` draws one failure realization over the whole
    dispatch stream: completeness rescales per-dispatch steps, jitter
    multiplies latencies, the payload channels stamp per-dispatch
    corruption factors, and a *dropped* dispatch still arrives (it counts
    toward the M-arrival flush trigger and spends its upload bytes) but
    is masked out of the aggregation via ``flush_mask``.  A *lost*
    (dropout) dispatch never arrives: the server notices at the would-be
    arrival time, reclaims the in-flight slot, and fires a replacement
    dispatch — the in-flight fleet stays at ``concurrency`` forever
    instead of leaking slots until the queue runs dry.

    Replacements consume dispatch draws beyond the loss-free
    ``C + R·M``, and the per-channel streams are drawn over the whole
    dispatch grid at once (a longer grid is a different realization, not
    an extension), so the builder rebuilds from scratch with doubled
    draw capacity until the timeline fits; pathological loss rates that
    outrun every doubling raise an actionable error.
    """
    M, C = afl.buffer_size, afl.concurrency
    total = C + rounds * M
    for _ in range(5):
        try:
            return _build_fedbuff_attempt(afl, fleet, cost, sizes, rounds,
                                          init_key, scenario, total)
        except _FedBuffCapacity:
            total *= 2
    raise ValueError(
        f"fedbuff scenario: dropout losses depleted the dispatch budget — "
        f"even {total // 2} pre-drawn dispatches (16x the loss-free "
        f"{C + rounds * M}) were consumed by lost-dispatch replacements "
        f"for {rounds} flushes of {M} at concurrency {C}; lower "
        f"dropout_prob or raise concurrency")


def _build_fedbuff_attempt(afl: AsyncFLConfig, fleet: DeviceFleet, cost,
                           sizes: np.ndarray, rounds: int, init_key,
                           scenario, total: int) -> FedBuffPlan:
    from repro.fed.scan_engine import _split_chain
    M, C = afl.buffer_size, afl.concurrency
    subs = _split_chain(init_key, total)
    sc = scenario_mod.as_active(scenario)
    g = scenario_mod.realize(sc, (total,)) if sc is not None else None
    if afl.latency_aware and math.isfinite(afl.deadline):
        exp_lat = tprof.to_device(expected_latencies(
            fleet, cost, mean_steps=simulator.mean_local_steps(afl),
            n_examples=sizes))
        probs = selection.latency_aware_probs(
            jnp.ones((fleet.n_devices,)), exp_lat, afl.deadline)
        cids = tprof.fetch(_draw_cids_chain(subs, probs), np.int64)
    elif afl.sampler == "indexed":
        cids = tprof.fetch(
            _draw_cids_chain_indexed(subs, fleet.n_devices), np.int64)
    else:
        probs = selection.uniform_probs(fleet.n_devices)
        cids = tprof.fetch(_draw_cids_chain(subs, probs), np.int64)
    steps = np.empty(total, np.int64)
    for d in range(total):
        step_rng = np.random.default_rng(20_000 + d)
        steps[d] = (int(step_rng.integers(1, afl.max_local_steps + 1))
                    if afl.het_steps else afl.max_local_steps)
    if g is not None:
        # completeness rescales the step budget BEFORE the latency model
        # runs: partial work comes back earlier AND trains less
        steps = scenario_mod.scale_steps(steps, g.comp)
    # one vectorized latency call for every dispatch of the run
    lats = device_latencies(fleet, cids, steps, cost, n_examples=sizes[cids])
    if g is not None and g.lat_scale is not None:
        lats = lats * g.lat_scale
    always_on = fleet.always_on

    events = EventQueue()
    free: List[int] = []
    slot_of = np.empty(total, np.int64)
    version_of = np.empty(total, np.int64)

    # the C seed dispatches all start at t=0 / version 0: vectorized
    # emission — one next_online call for the whole batch, slots 0..C-1,
    # one batch push (seq order == per-dispatch push order)
    begin0 = np.zeros(C) if always_on else fleet.next_online(cids[:C], 0.0)
    slot_of[:C] = np.arange(C)
    version_of[:C] = 0
    if g is None:
        events.push_batch(begin0 + lats[:C], "arrival", "d", range(C))
    else:
        # a lost seed dispatch occupies its slot until the server notices
        # at the would-be arrival — the loss event that reclaims it
        arr0 = begin0 + lats[:C]
        live0 = np.flatnonzero(~g.lost[:C])
        events.push_batch(arr0[live0], "arrival", "d", live0)
        lost0 = np.flatnonzero(g.lost[:C])
        if len(lost0):
            events.push_batch(arr0[lost0], "lost", "d", lost0)
    pool = C
    n_dispatched = C
    # per-dispatch clocks, recorded for the telemetry trace export
    disp_clock = np.zeros(total, np.float64)
    arr_clock = np.empty(total, np.float64)
    arr_clock[:C] = begin0 + lats[:C]

    def do_dispatch(at: float, version: int) -> int:
        nonlocal n_dispatched, pool
        if n_dispatched >= total:
            raise _FedBuffCapacity
        d = n_dispatched
        n_dispatched += 1
        begin = at if always_on \
            else float(fleet.next_online(cids[d:d + 1], at)[0])
        if free:
            slot = heapq.heappop(free)
        else:
            slot = pool
            pool += 1
        slot_of[d], version_of[d] = slot, version
        disp_clock[d], arr_clock[d] = at, begin + lats[d]
        if g is None or not g.lost[d]:
            events.push(begin + lats[d], "arrival", d=d)
        else:
            # a lost dispatch never uploads: the server times it out at
            # the would-be arrival, reclaiming the slot and replacing it
            events.push(begin + lats[d], "lost", d=d)
        return d
    flush_slot = np.empty((rounds, M), np.int64)
    tau = np.empty((rounds, M), np.float32)
    flush_clock = np.empty(rounds, np.float64)
    flush_mask = None if g is None else np.ones((rounds, M), np.float32)
    disp_rounds: List[List[int]] = []
    for t in range(rounds):
        flush_d: List[int] = []
        disp_d: List[int] = []
        quarantine: List[int] = []
        clock = 0.0
        while len(flush_d) < M:
            if len(events) == 0:
                raise ValueError(
                    f"fedbuff scenario: dropout depleted the in-flight "
                    f"fleet at flush {t} — every pending dispatch was "
                    f"lost; lower dropout_prob or raise concurrency")
            ev = events.pop()
            clock = ev.time
            if ev.kind == "lost":
                # reclaim the leaked slot — quarantined until the round
                # closes so a same-round replacement can never land in a
                # slot another of this round's dispatches already stored
                # to (duplicate .at[].set indices have unspecified order)
                quarantine.append(int(slot_of[ev.payload["d"]]))
                disp_d.append(do_dispatch(clock, t))  # keep C in flight
                continue
            flush_d.append(ev.payload["d"])
            disp_d.append(do_dispatch(clock, t))  # keep C in flight
        flush_slot[t] = slot_of[flush_d]
        tau[t] = t - version_of[flush_d]
        flush_clock[t] = clock
        if g is not None:
            # a dropped arrival triggered its flush position (and its
            # replacement dispatch) but carries no usable update
            flush_mask[t] = (~g.drop[flush_d]).astype(np.float32)
        disp_rounds.append(disp_d)
        # slots free only AFTER the flush: a dispatch made during this
        # round can never steal a slot the flush still needs
        for d in flush_d:
            heapq.heappush(free, slot_of[d])
        for s in quarantine:
            heapq.heappush(free, s)
    # rounds dispatch M + (losses noticed that round) devices: pad the
    # dispatch arrays to the widest round.  Pad rows are inert — device 0
    # at 1 step, stored to the dump row (index n_slots − 1, never
    # flushed), corruption factor exactly 1.0
    n_disp = np.array([len(d) for d in disp_rounds], np.int64)
    W = int(n_disp.max()) if sc is not None else M
    ids = np.zeros((rounds, W), np.int64)
    n_steps = np.ones((rounds, W), np.int64)
    store_slot = np.full((rounds, W), pool, np.int64)
    corrupt = None if g is None or g.corrupt is None \
        else np.ones((rounds, W), np.float32)
    for t, dd in enumerate(disp_rounds):
        n = len(dd)
        ids[t, :n] = cids[dd]
        n_steps[t, :n] = steps[dd]
        store_slot[t, :n] = slot_of[dd]
        if corrupt is not None:
            corrupt[t, :n] = g.corrupt[dd]
    used = n_dispatched    # replacements may leave draw capacity unused
    return FedBuffPlan(
        seed_ids=cids[:C].astype(np.int32),
        seed_steps=steps[:C].astype(np.int32),
        seed_slots=slot_of[:C].astype(np.int32),
        ids=ids.astype(np.int32), n_steps=n_steps.astype(np.int32),
        store_slot=store_slot.astype(np.int32),
        flush_slot=flush_slot.astype(np.int32), tau=tau,
        flush_clock=flush_clock, stale_mean=tau.mean(axis=1).astype(float),
        n_slots=pool + 1 if sc is not None else pool,
        dispatch_clock=disp_clock[:used], arrival_clock=arr_clock[:used],
        all_ids=cids[:used].astype(np.int32),
        all_steps=steps[:used].astype(np.int32),
        flush_mask=flush_mask,
        drop_mask=None if g is None else g.drop[:used],
        lost_mask=None if g is None else g.lost[:used],
        n_disp=None if sc is None else n_disp,
        seed_corrupt=None if g is None or g.corrupt is None
        else g.corrupt[:C],
        corrupt=corrupt)


def build_plan(afl: AsyncFLConfig, fleet: DeviceFleet, cost,
               sizes: np.ndarray, rounds: int, init_key, sel_probs=None,
               scenario=None):
    """Mode dispatcher for the event-plan builders.

    Plans are *engine-agnostic reusable values*: a ``DeadlinePlan`` /
    ``FedBuffPlan`` depends only on the timeline fields of ``afl`` (never
    on ``SWEEPABLE_FIELDS`` — guarded by tests/test_sweep_engine.py), so
    one plan built here can be replayed by the python event loop
    (``run_async(plan=...)``), the compiled scan
    (``scan_engine.run_async_compiled(plan=...)``), and every member of a
    hyper-parameter sweep (``sweep_engine.run_async_sweep_compiled``).
    """
    if afl.mode == "deadline":
        return build_deadline_plan(afl, fleet, cost, sizes, rounds,
                                   init_key, sel_probs, scenario=scenario)
    return build_fedbuff_plan(afl, fleet, cost, sizes, rounds, init_key,
                              scenario=scenario)


def plan_digest(plan) -> str:
    """Content hash of a plan (every array field's bytes + the static
    ints, field-name tagged).  Two configs produce interchangeable plans
    iff their digests match — the sweepable/timeline split's guard."""
    import hashlib
    h = hashlib.sha256()
    for f in dataclasses.fields(plan):
        v = getattr(plan, f.name)
        h.update(f.name.encode())
        if isinstance(v, np.ndarray):
            h.update(str(v.dtype).encode() + str(v.shape).encode())
            h.update(np.ascontiguousarray(v).tobytes())
        else:
            h.update(repr(v).encode())
    return h.hexdigest()


# ------------------------------------------------------- scenario-grid plans
#
# A ScenarioGrid's cells share one timeline config (and hence one key
# chain / selection stream) but realize different failure channels, so
# their solo plans differ only in the realized arrays AND in their
# data-dependent static widths (straggler pool, due budget, fedbuff
# dispatch width).  The grid builders below construct each cell's plan
# with the EXISTING solo builders — cell digests are the solo digests by
# construction — then pad every width up to the grid max using the same
# inert-row conventions the solo builders already rely on (masked due
# rows aimed at the cell's own dump row, fedbuff pad dispatches of
# device 0 / 1 step / dump slot / corruption 1.0) and stack along a
# leading S_scenario axis.  Padding is bit-invisible: masked rows enter
# the fixed-budget aggregation as exact 0·x terms (the masked-slot
# contract of tests/test_event_plan.py), and appending them does not
# perturb the reduction (checked empirically for every aggregation
# backend × dtype × guard on this XLA build).

@dataclasses.dataclass(frozen=True)
class DeadlinePlanGrid:
    """Stacked deadline plans: every realized array of `DeadlinePlan`
    with a leading S_scenario axis, widths padded to the grid max.
    ``plans[i]`` keeps cell *i*'s untouched solo plan (same digest as an
    independent solo build) for byte accounting and telemetry."""
    plans: Tuple[DeadlinePlan, ...]
    keys: np.ndarray        # (R, 2) uint32 — shared round subkeys
    ids: np.ndarray         # (S, R, K) int32
    n_steps: np.ndarray     # (S, R, K) int32
    arrived: np.ndarray     # (S, R, K) bool
    store_slot: np.ndarray  # (S, R, K) int32
    due_slot: np.ndarray    # (S, R, n_due) int32
    due_mask: np.ndarray    # (S, R, n_due) float32
    due_tau: np.ndarray     # (S, R, n_due) float32
    fast: np.ndarray        # (S, R) bool
    round_end: np.ndarray   # (S, R) float64
    n_arrived: np.ndarray   # (S, R) int64
    stale_mean: np.ndarray  # (S, R) float64
    n_slots: int            # padded pool rows (max over cells)
    n_due: int              # padded due budget (max over cells)
    corrupt: Optional[np.ndarray] = None  # (S, R, K) f32, uniform presence

    @property
    def n_cells(self) -> int:
        return len(self.plans)


@dataclasses.dataclass(frozen=True)
class FedBuffPlanGrid:
    """Stacked fedbuff plans (see `DeadlinePlanGrid`): dispatch width W
    and the slot pool pad to the grid max with the solo builder's own
    inert pad rows; the flush geometry (R, M) is width-stable."""
    plans: Tuple[FedBuffPlan, ...]
    seed_ids: np.ndarray     # (S, C) int32
    seed_steps: np.ndarray   # (S, C) int32
    seed_slots: np.ndarray   # (S, C) int32
    ids: np.ndarray          # (S, R, W) int32
    n_steps: np.ndarray      # (S, R, W) int32
    store_slot: np.ndarray   # (S, R, W) int32
    flush_slot: np.ndarray   # (S, R, M) int32
    tau: np.ndarray          # (S, R, M) float32
    flush_mask: np.ndarray   # (S, R, M) float32 — cells are active
    flush_clock: np.ndarray  # (S, R) float64
    stale_mean: np.ndarray   # (S, R) float64
    n_slots: int             # padded pool rows incl. dump (max over cells)
    seed_corrupt: Optional[np.ndarray] = None  # (S, C) f32
    corrupt: Optional[np.ndarray] = None       # (S, R, W) f32

    @property
    def n_cells(self) -> int:
        return len(self.plans)


def build_deadline_plan_grid(afl: AsyncFLConfig, fleet: DeviceFleet, cost,
                             sizes: np.ndarray, rounds: int, init_key, grid,
                             sel_probs=None) -> DeadlinePlanGrid:
    """Per-cell solo deadline plans, padded and stacked over S_scenario.

    Masked due padding aims at each cell's own dump row (`p.n_slots`,
    mask 0, τ 0) — exactly the solo builder's masked-slot default — so a
    padded row gathers real zeros and contributes an exact 0·x term."""
    plans = tuple(build_deadline_plan(afl, fleet, cost, sizes, rounds,
                                      init_key, sel_probs, scenario=c)
                  for c in grid.cells)
    keys = plans[0].keys
    for p in plans[1:]:
        # one timeline config => one key chain; the fast-round path
        # resamples ids from these subkeys, so sharing them is what lets
        # the grid keep selection identical to every solo run
        assert np.array_equal(p.keys, keys)
    n_due = max(p.n_due for p in plans)
    n_slots = max(p.n_slots for p in plans)
    due_slot = np.stack([
        np.concatenate([p.due_slot, np.full(
            (rounds, n_due - p.n_due), p.n_slots, np.int32)], axis=1)
        for p in plans])
    due_mask = np.stack([
        np.concatenate([p.due_mask, np.zeros(
            (rounds, n_due - p.n_due), np.float32)], axis=1)
        for p in plans])
    due_tau = np.stack([
        np.concatenate([p.due_tau, np.zeros(
            (rounds, n_due - p.n_due), np.float32)], axis=1)
        for p in plans])
    corrupt = None if not grid.corrupting \
        else np.stack([p.corrupt for p in plans])
    return DeadlinePlanGrid(
        plans=plans, keys=keys,
        ids=np.stack([p.ids for p in plans]),
        n_steps=np.stack([p.n_steps for p in plans]),
        arrived=np.stack([p.arrived for p in plans]),
        store_slot=np.stack([p.store_slot for p in plans]),
        due_slot=due_slot, due_mask=due_mask, due_tau=due_tau,
        fast=np.stack([p.fast for p in plans]),
        round_end=np.stack([p.round_end for p in plans]),
        n_arrived=np.stack([p.n_arrived for p in plans]),
        stale_mean=np.stack([p.stale_mean for p in plans]),
        n_slots=n_slots, n_due=n_due, corrupt=corrupt)


def build_fedbuff_plan_grid(afl: AsyncFLConfig, fleet: DeviceFleet, cost,
                            sizes: np.ndarray, rounds: int, init_key,
                            grid) -> FedBuffPlanGrid:
    """Per-cell solo fedbuff plans, padded and stacked over S_scenario.

    Dispatch-width padding reuses the solo builder's inert-row recipe
    (device 0, 1 step, the cell's dump slot `p.n_slots − 1`, corruption
    1.0): pad dispatches store to a row no flush ever gathers."""
    plans = tuple(build_fedbuff_plan(afl, fleet, cost, sizes, rounds,
                                     init_key, scenario=c)
                  for c in grid.cells)
    W = max(p.ids.shape[1] for p in plans)

    def pad_disp(p, arr, fill):
        out = np.full((rounds, W), fill, arr.dtype)
        out[:, :arr.shape[1]] = arr
        return out

    corrupt = None
    seed_corrupt = None
    if grid.corrupting:
        corrupt = np.stack([pad_disp(p, p.corrupt, 1.0) for p in plans])
        seed_corrupt = np.stack([p.seed_corrupt for p in plans])
    return FedBuffPlanGrid(
        plans=plans,
        seed_ids=np.stack([p.seed_ids for p in plans]),
        seed_steps=np.stack([p.seed_steps for p in plans]),
        seed_slots=np.stack([p.seed_slots for p in plans]),
        ids=np.stack([pad_disp(p, p.ids, 0) for p in plans]),
        n_steps=np.stack([pad_disp(p, p.n_steps, 1) for p in plans]),
        store_slot=np.stack([pad_disp(p, p.store_slot, p.n_slots - 1)
                             for p in plans]),
        flush_slot=np.stack([p.flush_slot for p in plans]),
        tau=np.stack([p.tau for p in plans]),
        flush_mask=np.stack([p.flush_mask for p in plans]),
        flush_clock=np.stack([p.flush_clock for p in plans]),
        stale_mean=np.stack([p.stale_mean for p in plans]),
        n_slots=max(p.n_slots for p in plans),
        seed_corrupt=seed_corrupt, corrupt=corrupt)


def build_plan_grid(afl: AsyncFLConfig, fleet: DeviceFleet, cost,
                    sizes: np.ndarray, rounds: int, init_key, grid,
                    sel_probs=None):
    """Mode dispatcher for the grid plan builders."""
    if afl.mode == "deadline":
        return build_deadline_plan_grid(afl, fleet, cost, sizes, rounds,
                                        init_key, grid, sel_probs)
    return build_fedbuff_plan_grid(afl, fleet, cost, sizes, rounds,
                                   init_key, grid)


# ------------------------------------------------- shared jitted round steps

def pool_init(model_cfg, fl: simulator.FLConfig, params, batch, n_rows: int):
    """Zero pending-update pool with the exact per-row leaf shapes/dtypes
    of one local update (deltas tree, grads tree, gammas) on ``batch``'s
    devices: any (B, M, ...) stack, a gathered cohort or the resident
    data itself.  Probed abstractly on one row, so nothing runs and no
    (N, M, ...) stack is needed."""
    steps = jnp.ones((1,), jnp.int32)
    d_s, g_s, gam_s = jax.eval_shape(
        lambda p, b: simulator._local_updates_batch(
            model_cfg, p, {k: b[k][:1] for k in ("x", "y", "mask")}, steps,
            fl), params, batch)
    row = lambda s: jnp.zeros((n_rows,) + s.shape[1:], s.dtype)
    return (jax.tree.map(row, d_s), jax.tree.map(row, g_s),
            jnp.zeros((n_rows,), gam_s.dtype))


@functools.partial(jax.jit, static_argnums=(0, 1), static_argnames=("mesh",))
def deadline_slow_step(model_cfg, afl: AsyncFLConfig, params, pend, batch,
                       n_steps, arrived_mask, store_slot, due_slot,
                       due_mask, due_tau, hypers=None, corrupt=None, *,
                       mesh=None):
    """One non-fast deadline round: compute the K dispatched updates on
    their gathered ``batch``, gather this round's due stragglers from the
    pool, stash this round's misses, and run the fixed-budget masked
    staleness aggregation.  Returns (new params, pool, metrics), the
    metrics None without telemetry.

    Shared verbatim by the python event loop, the compiled scan, and the
    vmapped sweep engine — the bit-for-bit parity between `run_async` and
    `run_async_compiled` rests on both replaying this exact program
    (separate jit graphs of the "same" math are not guaranteed
    bit-identical).  Callers holding resident data gather the cohort
    first (`simulator._client_batch`); lazy runs pass the host-gathered
    cohort, so the shapes depend on K and the pool width, never on N.
    ``hypers`` carries the traced sweepable scalars.

    ``corrupt`` (scenario payload channels, (K,) f32) multiplies the K
    dispatched payloads before they are stored or aggregated — a
    corrupted straggler parks its corrupted payload and poisons the
    round it lands in, not the round that computed it.  ``None`` keeps
    the pre-corruption trace exactly.
    """
    h = hypers if hypers is not None else hypers_of(afl)
    deltas, grads, gammas = simulator._local_updates_batch(
        model_cfg, params, batch, n_steps, afl.sync_config(), h)
    deltas, grads = simulator.apply_corruption(deltas, grads, corrupt)
    pend_d, pend_g, pend_gam = pend
    # gather due rows BEFORE storing: a slot aggregated this round may be
    # reallocated to one of this round's stragglers
    due_d = jax.tree.map(lambda x: x[due_slot], pend_d)
    due_g = jax.tree.map(lambda x: x[due_slot], pend_g)
    due_gam = pend_gam[due_slot]
    # stash this round's stragglers (arrived rows land in the dump slot,
    # whose contents are only ever read through a masked-out due slot)
    pend = _pool_store(pend, store_slot, deltas, grads, gammas)
    K = gammas.shape[0]
    tau = jnp.concatenate([jnp.zeros((K,), jnp.float32), due_tau])
    mask = jnp.concatenate([arrived_mask.astype(jnp.float32), due_mask])
    deltas_all = _concat0(deltas, due_d)
    grads_all = _concat0(grads, due_g)
    gammas_all = jnp.concatenate([gammas, due_gam])
    if corrupt is not None:
        # corruption breaks the masked-row contract the aggregation rules
        # rely on (a NaN row enters the reductions as 0·NaN = NaN): a
        # corrupted straggler still in flight — and the dump row read
        # through masked due slots — must contribute true zeros, arriving
        # only in the round its due slot unmasks
        def _mrow(x):
            m = mask.reshape((-1,) + (1,) * (x.ndim - 1))
            return jnp.where(m > 0.0, x, jnp.zeros((), x.dtype))
        deltas_all = jax.tree.map(_mrow, deltas_all)
        grads_all = jax.tree.map(_mrow, grads_all)
    new_params, ginfo = _apply_aggregation(
        afl, params, deltas_all, grads_all, gammas_all, tau, mask=mask,
        mesh=mesh, hypers=h)
    m = None
    if afl.telemetry:
        from repro.telemetry import metrics as tmetrics
        m = tmetrics.metrics_for_algo(
            afl.algo, params, new_params, deltas_all, grads_all,
            psi=h["psi"], gammas=gammas_all, tau=tau,
            alpha=h["staleness_alpha"], mask=mask, guard=ginfo)
    return new_params, pend, m


@functools.partial(jax.jit, static_argnums=(0, 1))
def fedbuff_seed_pool(model_cfg, afl: AsyncFLConfig, params, pend, batch,
                      n_steps, store_slot, hypers=None, corrupt=None):
    """Compute the initial `concurrency` dispatches on the initial params
    from their gathered ``batch`` and stash them in their pool slots (one
    batched update call).  ``corrupt`` stamps the scenario payload
    factors on the seed uploads."""
    h = hypers if hypers is not None else hypers_of(afl)
    deltas, grads, gammas = simulator._local_updates_batch(
        model_cfg, params, batch, n_steps, afl.sync_config(), h)
    deltas, grads = simulator.apply_corruption(deltas, grads, corrupt)
    return _pool_store(pend, store_slot, deltas, grads, gammas)


def _pool_store(pend, store_slot, deltas, grads, gammas):
    """Stash a batch of updates into their plan-assigned pool slots."""
    pend_d, pend_g, pend_gam = pend
    pend_d = jax.tree.map(lambda b, x: b.at[store_slot].set(x),
                          pend_d, deltas)
    pend_g = jax.tree.map(lambda b, x: b.at[store_slot].set(x),
                          pend_g, grads)
    pend_gam = pend_gam.at[store_slot].set(gammas)
    return (pend_d, pend_g, pend_gam)


@functools.partial(jax.jit, static_argnums=(0, 1), static_argnames=("mesh",))
def fedbuff_round_step(model_cfg, afl: AsyncFLConfig, params, pend, batch,
                       n_steps, store_slot, flush_slot, tau,
                       hypers=None, flush_mask=None, corrupt=None, *,
                       mesh=None):
    """One fedbuff flush round: batch-compute the dispatches made during
    this round (all reference the current params — the server version only
    bumps at the flush) on their gathered ``batch``, store them, then
    aggregate the M flushed rows.  Returns (new params, pool, metrics),
    the metrics None without telemetry.

    Storing happens BEFORE the flush gather: a device dispatched this
    round can arrive fast enough to be part of this very flush.  Shared
    verbatim by the python event loop, the compiled scan, and the vmapped
    sweep engine; resident callers gather the cohort first, lazy runs
    pass the host-gathered one (shapes in the plan's dispatch width W and
    the pool size, never N).

    ``flush_mask`` (scenario drop channel, (M,) f32) excludes flushed
    rows whose upload failed in transit; ``None`` keeps the pre-scenario
    trace exactly.  ``corrupt`` ((W,) f32, the plan's padded dispatch
    width) stamps the payload-corruption factors on this round's
    dispatches before they are stored; pad rows carry exactly 1.0.
    """
    h = hypers if hypers is not None else hypers_of(afl)
    deltas, grads, gammas = simulator._local_updates_batch(
        model_cfg, params, batch, n_steps, afl.sync_config(), h)
    deltas, grads = simulator.apply_corruption(deltas, grads, corrupt)
    pend = _pool_store(pend, store_slot, deltas, grads, gammas)
    pend_d, pend_g, pend_gam = pend
    flush_d = jax.tree.map(lambda x: x[flush_slot], pend_d)
    flush_g = jax.tree.map(lambda x: x[flush_slot], pend_g)
    flush_gam = pend_gam[flush_slot]
    new_params, ginfo = _apply_aggregation(afl, params, flush_d, flush_g,
                                           flush_gam, tau, mask=flush_mask,
                                           mesh=mesh, hypers=h)
    m = None
    if afl.telemetry:
        from repro.telemetry import metrics as tmetrics
        m = tmetrics.metrics_for_algo(
            afl.algo, params, new_params, flush_d, flush_g, psi=h["psi"],
            gammas=flush_gam, tau=tau, alpha=h["staleness_alpha"],
            mask=flush_mask, guard=ginfo)
    return new_params, pend, m


# ----------------------------------------------------------- python driver

def run_async(model_cfg, fed: FederatedData, afl: AsyncFLConfig,
              fleet: DeviceFleet, rounds: int,
              init_key: Optional[jax.Array] = None,
              eval_every: int = 1, mesh=None,
              plan=None, profiler=None,
              scenario=None) -> simulator.FedRunResult:
    """Run `rounds` server aggregations of async FOLB on the system model.

    In deadline mode a "round" is one deadline-barriered aggregation; in
    fedbuff mode it is one buffer flush (M arrivals).  History carries the
    simulated wall-clock at every eval point, so time-to-accuracy is
    directly comparable with fleet-timestamped synchronous runs.
    ``plan`` replays a pre-built event plan (see ``build_plan``) instead
    of rebuilding it — it must come from this (afl, fleet, rounds, key)
    timeline.

    The result's ``ids`` are the plan's dispatched device ids.  With
    ``afl.telemetry`` the result additionally carries per-round metrics
    (in-scan stats plus the plan-derived network/pool series) and a
    host-phase profile; ``profiler`` overrides the auto-created one.

    ``scenario`` (`repro.sysmodel.ScenarioConfig`) folds the seeded
    failure channels into the plan at build time; it is ignored when a
    pre-built ``plan`` is supplied (the plan already embeds whatever
    scenario it was built with).
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
        train, test, p = simulator.device_arrays(fed)
        sizes = np.asarray(fed.mask.sum(axis=1))
        cost = round_cost_for(model_cfg, params,
                              uploads_gradient="folb" in afl.algo)

    hist: Dict[str, List[float]] = {
        "round": [], "wall_clock": [], "train_loss": [], "train_acc": [],
        "test_acc": [], "n_arrived": [], "stale_mean": []}

    def record(t: int, clock_now: float, n_arrived: int, stale_mean: float,
               cur_params):
        with prof.phase("eval"):
            tr_loss, tr_acc = simulator.eval_global(model_cfg, cur_params,
                                                    train, p)
            _, te_acc = simulator.eval_global(model_cfg, cur_params, test, p)
            hist["round"].append(t)
            hist["wall_clock"].append(float(clock_now))
            hist["train_loss"].append(tprof.fetch_float(tr_loss))
            hist["train_acc"].append(tprof.fetch_float(tr_acc))
            hist["test_acc"].append(tprof.fetch_float(te_acc))
            hist["n_arrived"].append(float(n_arrived))
            hist["stale_mean"].append(float(stale_mean))

    if afl.mode == "deadline":
        params, plan, mlist = _run_deadline(
            model_cfg, afl, fleet, cost, sizes, train, p, key, params,
            rounds, eval_every, record, mesh=mesh, plan=plan, prof=prof,
            scenario=scenario)
    else:
        params, plan, mlist = _run_fedbuff(
            model_cfg, afl, fleet, cost, sizes, train, key, params, rounds,
            eval_every, record, mesh=mesh, plan=plan, prof=prof,
            scenario=scenario)
    with prof.phase("collect"):
        metrics = None
        if afl.telemetry:
            metrics = tmetrics.stack_metrics(mlist)
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
    return simulator.FedRunResult(history=hist, params=params,
                                  ids=np.asarray(plan.ids),
                                  metrics=metrics, profile=prof.finish())


# ------------------------------------------------------------- deadline mode

def _run_deadline(model_cfg, afl, fleet, cost, sizes, train, p, key, params,
                  rounds, eval_every, record, mesh=None, plan=None,
                  prof=None, scenario=None):
    from repro.telemetry import NULL_PROFILER
    prof = prof if prof is not None else NULL_PROFILER
    mlist: List = []
    # canonical static configs + traced hypers: every sweepable value
    # reaches the shared jitted steps as an operand (one trace per
    # timeline, shared across hyper-parameter values)
    afl_t = afl.timeline_config()
    sync_fl = afl_t.sync_config()
    hypers = hypers_of(afl)
    with prof.phase("plan_build"):
        sel_probs = deadline_selection_probs(afl, fleet, cost, sizes)
        if plan is None:
            plan = build_deadline_plan(afl, fleet, cost, sizes, rounds, key,
                                       sel_probs, scenario=scenario)
        pend = pool_init(model_cfg, sync_fl, params, train,
                         plan.n_slots + 1)
    for t in range(rounds):
        with prof.phase("rounds"):
            params, pend = _deadline_round(
                model_cfg, afl_t, sync_fl, params, pend, train, p, plan, t,
                sel_probs, hypers, mlist, mesh)
        if t % eval_every == 0 or t == rounds - 1:
            record(t, plan.round_end[t], int(plan.n_arrived[t]),
                   float(plan.stale_mean[t]), params)
    return params, plan, mlist


def _deadline_round(model_cfg, afl_t, sync_fl, params, pend, train, p, plan,
                    t, sel_probs, hypers, mlist, mesh):
    n_steps = tprof.to_device(plan.n_steps[t])
    corrupt = None if plan.corrupt is None \
        else tprof.to_device(plan.corrupt[t])
    if plan.fast[t]:
        # sync-parity fast path: every dispatched device made the
        # deadline and no stale upload joins, so every τ is 0 and the
        # (1+τ)^{-α} discount is the constant 1.0 for ANY α — the round
        # is EXACTLY one synchronous round; reuse the simulator's fused
        # round (same jitted computation => bit-for-bit agreement in
        # the D = ∞ limit, and ~3x less host time per round).  With
        # latency-aware selection the pre-computed sel_probs make
        # fl_round resample the very same ids as the plan from the
        # same key.
        params, diag = simulator.fl_round(
            model_cfg, sync_fl, params, train, p,
            tprof.to_device(plan.keys[t]), n_steps, sel_probs, hypers,
            None, corrupt, mesh=mesh)
        if sync_fl.telemetry:
            mlist.append(diag["metrics"])
        return params, pend
    params, pend, m = deadline_slow_step(
        model_cfg, afl_t, params, pend,
        simulator._client_batch(train, tprof.to_device(plan.ids[t])),
        n_steps, tprof.to_device(plan.arrived[t], jnp.float32),
        tprof.to_device(plan.store_slot[t]),
        tprof.to_device(plan.due_slot[t]),
        tprof.to_device(plan.due_mask[t]),
        tprof.to_device(plan.due_tau[t]), hypers, corrupt, mesh=mesh)
    if afl_t.telemetry:
        mlist.append(m)
    return params, pend


# -------------------------------------------------------------- fedbuff mode

def _run_fedbuff(model_cfg, afl, fleet, cost, sizes, train, key, params,
                 rounds, eval_every, record, mesh=None, plan=None,
                 prof=None, scenario=None):
    from repro.telemetry import NULL_PROFILER
    prof = prof if prof is not None else NULL_PROFILER
    mlist: List = []
    afl_t = afl.timeline_config()
    hypers = hypers_of(afl)
    with prof.phase("plan_build"):
        if plan is None:
            plan = build_fedbuff_plan(afl, fleet, cost, sizes, rounds, key,
                                      scenario=scenario)
        pend = pool_init(model_cfg, afl_t.sync_config(), params, train,
                         plan.n_slots)
        pend = fedbuff_seed_pool(model_cfg, afl_t, params, pend,
                                 simulator._client_batch(
                                     train, tprof.to_device(plan.seed_ids)),
                                 tprof.to_device(plan.seed_steps),
                                 tprof.to_device(plan.seed_slots), hypers,
                                 corrupt=None if plan.seed_corrupt is None
                                 else tprof.to_device(plan.seed_corrupt))
    for t in range(rounds):
        with prof.phase("rounds"):
            params, pend, m = fedbuff_round_step(
                model_cfg, afl_t, params, pend,
                simulator._client_batch(train, tprof.to_device(plan.ids[t])),
                tprof.to_device(plan.n_steps[t]),
                tprof.to_device(plan.store_slot[t]),
                tprof.to_device(plan.flush_slot[t]),
                tprof.to_device(plan.tau[t]), hypers,
                flush_mask=None if plan.flush_mask is None
                else tprof.to_device(plan.flush_mask[t]),
                corrupt=None if plan.corrupt is None
                else tprof.to_device(plan.corrupt[t]), mesh=mesh)
            if afl_t.telemetry:
                mlist.append(m)
        if t % eval_every == 0 or t == rounds - 1:
            n_arrived = (afl.buffer_size if plan.flush_mask is None
                         else int(plan.flush_mask[t].sum()))
            record(t, plan.flush_clock[t], n_arrived,
                   float(plan.stale_mean[t]), params)
    return params, plan, mlist
