"""Production-scale federated round engine (scan-over-clients strategy).

One ``train_step`` = one FOLB communication round on a framework-scale
model: the K sampled clients of the round are simulated datacenter-side
(standard federated-simulation-at-scale).  Client batches carry a leading
K axis; clients are iterated with ``lax.scan`` so gradient/delta memory is
O(1) in K regardless of model size.

Two-pass structure (the key to O(1) memory *and* exact FOLB weights):

  pass 1:  g1 = (1/K) Σ_k ∇F_k(w^t)           (one grad eval per client)
  pass 2:  per client — reuse ∇F_k(w^t) as the first prox-step gradient,
           run E prox-SGD steps, compute γ_k and
           I_k = ⟨∇F_k, g1⟩ − ψ γ_k ‖g1‖², and accumulate the
           *unnormalized* Σ_k I_k·Δ_k plus the scalar Σ_k |I_k|.
  final:   w^{t+1} = w^t + (Σ I_k Δ_k) / (Σ |I_k|)
           — valid because Eq. IV-C / V-B normalization is a scalar.

With ψ = 0 this is exactly the paper's single-set FOLB (Eq. IV-C); with
ψ > 0 it is the heterogeneity-aware rule (Eq. V-B); algo='fedavg'/'fedprox'
degrade to mean aggregation (Eq. 2).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import tree
from repro.models import model as model_lib

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class RoundConfig:
    algo: str = "folb"          # fedavg | fedprox | folb | folb_het
    n_clients: int = 8          # K (leading axis of the client batch)
    local_steps: int = 2        # E prox-SGD steps per client
    lr: float = 1e-2
    mu: float = 0.01            # prox weight (fedavg forces 0)
    psi: float = 0.0            # heterogeneity penalty (folb_het)
    remat: bool = True
    remat_group: int = 1        # checkpoint every N layers (memory knob)
    fsdp_params: bool = False   # shard params over data too (memory vs
                                # per-layer weight-gather tradeoff; §Perf B)
    # aggregation route for the FOLB algos:
    #   "scan" — the original O(1)-in-K two-pass tree accumulation (only
    #            choice when a (K, D) buffer cannot exist: 10B+ models);
    #   "flat" — one client sweep emitting bf16 flat deltas/grads, then
    #            the SAME fused (optionally D-sharded) Pallas aggregation
    #            every other engine uses (kernels.ops).  O(K·D/2) bytes —
    #            the right trade at fed100m scale, and it removes this
    #            engine's duplicated score/weight algebra.
    agg_backend: str = "scan"   # scan | flat
    agg_dtype: str = "bfloat16"  # flat-buffer storage dtype (flat only)

    def __post_init__(self):
        assert self.agg_backend in ("scan", "flat"), self.agg_backend
        assert self.agg_dtype in ("bfloat16", "float32"), self.agg_dtype

    @property
    def effective_mu(self) -> float:
        return 0.0 if self.algo == "fedavg" else self.mu


def _f32(t):
    return tree.tree_cast(t, jnp.float32)


def make_loss_fn(cfg, remat: bool, remat_group: int = 1) -> Callable:
    def loss(p, b):
        return model_lib.loss_fn(cfg, p, b, remat=remat,
                                 remat_group=remat_group)
    return loss


def _client_slice(batch, k):
    return jax.tree.map(lambda x: x[k], batch)


def _gamma(loss_fn, w_new, w_ref, cb, g_ref, mu):
    """γ_k = ||∇h(w_new)|| / ||∇F_k(w^t)|| (Assumption 4 inexactness)."""
    gh = jax.tree.map(
        lambda gl, wl, rl: gl.astype(jnp.float32)
        + mu * (wl.astype(jnp.float32) - rl.astype(jnp.float32)),
        jax.grad(loss_fn)(w_new, cb), w_new, w_ref)
    return jnp.clip(
        tree.tree_norm(gh)
        / jnp.maximum(tree.tree_norm(g_ref), 1e-12), 0.0, 1.0)


def folb_round(cfg, rc: RoundConfig, params: Params, batch: Dict,
               param_shardings=None, acc_shardings=None, mesh=None
               ) -> Tuple[Params, Dict[str, jnp.ndarray]]:
    """One federated round.  batch leaves: (K, per_client_batch, ...).

    param_shardings: optional NamedSharding pytree matching params — applied
    as sharding constraints on the fp32 accumulators and local-solve
    iterates.  Scan carries block GSPMD propagation, so without these the
    round's gradient accumulators get replicated (measured: 10 GiB/device
    for a 7B model on a 256-chip mesh).

    mesh: optional flat-buffer mesh (``sharding.specs.folb_mesh``) — only
    meaningful with ``rc.agg_backend == "flat"``, where it D-shards the
    shared fused aggregation.
    """
    loss_fn = make_loss_fn(cfg, rc.remat, rc.remat_group)
    vg = jax.value_and_grad(loss_fn)
    mu = rc.effective_mu
    K = rc.n_clients

    def constrain(t):
        if param_shardings is None:
            return t
        return jax.lax.with_sharding_constraint(t, param_shardings)

    def constrain_acc(t):
        # fp32 accumulators: FSDP-style (data+model) sharding — they are
        # elementwise-only, so the tighter layout costs one resharding
        # all-to-all per client and saves GiBs of HBM (see
        # sharding.specs.accumulator_specs).
        if acc_shardings is None:
            return constrain(t)
        return jax.lax.with_sharding_constraint(t, acc_shardings)

    compute = lambda w: tree.tree_cast(w, jnp.dtype(cfg.param_dtype))
    vgs = jax.value_and_grad(
        lambda p, b: model_lib.loss_and_stats(cfg, p, b, rc.remat,
                                              rc.remat_group), has_aux=True)

    def local_solve(g0, cb):
        """E prox-SGD steps on h_k(w, w^t), entirely in the parameter
        layout and dtype.  Updates in the device dtype (bf16 at scale) are
        the γ-inexact local solver of Assumption 4 — and the delta
        w_new − w^t is then EXACT in that dtype (Sterbenz: the operands
        differ by far less than 2×), so no fp32 parameter-layout state is
        ever needed (§Perf B1/B2: fp32 temporaries and in-loop
        fsdp↔param resharding previously cost 10.6–17.7 TB/chip/round of
        all-gathers on mixtral train_4k).  Params held in a wider dtype
        than ``cfg.param_dtype`` (float32 master weights) are the iterate's
        dtype instead: the model and its gradients run in
        ``cfg.param_dtype``, the steps in the master dtype.  g0 =
        ∇F_k(w^t) is reused as the first step's gradient (the prox term
        vanishes at w = w^t).  Returns (w, the held-expert stats of the
        E − 1 further gradient evaluations, summed)."""
        sgd = lambda w, g: constrain(jax.tree.map(
            lambda wl, gl: wl - jnp.asarray(rc.lr, wl.dtype)
            * gl.astype(wl.dtype), w, g))
        w = sgd(params, g0)
        if rc.local_steps == 1:
            return w, None

        def body(w, _):
            (_, st), g = vgs(compute(w), cb)
            g = jax.tree.map(
                lambda gl, wl, rl: gl.astype(wl.dtype)
                + jnp.asarray(mu, wl.dtype) * (wl - rl), g, w, params)
            return sgd(w, g), st

        w, sts = jax.lax.scan(body, w, None, length=rc.local_steps - 1)
        return w, jax.tree.map(lambda a: jnp.sum(a, axis=0), sts)

    if rc.agg_backend == "flat" and rc.algo in ("folb", "folb_het"):
        # shared-path reroute: ONE client sweep emits flat bf16 deltas and
        # grads; g1, the K scores, and the weighted apply all run inside
        # the same fused (optionally D-sharded) Pallas aggregation every
        # other engine uses (kernels.ops) — this engine keeps only the
        # local solves.  The two-pass structure below becomes unnecessary
        # because the kernel's score phase owns the <∇F_k, g1> reduction.
        from repro.core import flat as flat_lib
        from repro.kernels import folb_aggregate as _folb
        from repro.kernels import ops as kernel_ops
        D = sum(x.size for x in jax.tree.leaves(params))
        pad_to = (_folb.shard_alignment(mesh) if mesh is not None
                  else _folb.pad_unit(D))
        spec = flat_lib.spec_of(params, pad_to=pad_to)
        bspec = flat_lib.with_buf_dtype(spec, rc.agg_dtype)

        def client(lsum, cb):
            (l, st), g_k = vgs(compute(params), cb)
            g_k = constrain(g_k)
            w_new, st_more = local_solve(g_k, cb)
            if st_more is not None:
                st = jax.tree.map(jnp.add, st, st_more)
            delta = jax.tree.map(jnp.subtract, w_new, params)
            gamma = (_gamma(loss_fn, w_new, params, cb, g_k, mu)
                     if rc.algo == "folb_het"
                     else jnp.zeros((), jnp.float32))
            return lsum + l, (flat_lib.ravel(bspec, delta),
                              flat_lib.ravel(bspec, g_k), gamma, l, st)

        loss_sum, (deltas, grads, gammas, losses, stats) = jax.lax.scan(
            client, jnp.zeros((), jnp.float32), batch)
        w_flat = flat_lib.ravel(spec, params)
        pg = rc.psi * gammas if rc.algo == "folb_het" else None
        new_flat, scores = kernel_ops.folb_aggregate_buffers(
            w_flat, deltas, grads, psi_gamma=pg, mesh=mesh)
        # diagnostics-only extra sweep (the kernel keeps its g1 internal)
        g1_sq = jnp.sum(jnp.mean(grads.astype(jnp.float32), axis=0) ** 2)
        metrics = {
            "client_loss": loss_sum / K,
            "client_losses": losses,
            "g1_norm": jnp.sqrt(g1_sq),
            "weight_denom": jnp.sum(jnp.abs(scores)),
            "scores": scores,
            # held-expert layers: summed over the round's K x E gradient
            # evaluations, per layer
            **jax.tree.map(lambda a: jnp.sum(a, axis=0), stats),
        }
        return flat_lib.unravel(spec, new_flat), metrics

    # ---- pass 1: global-gradient estimate g1 = mean_k grad F_k(w^t)
    # NOTE ordering: reshard the bf16 gradient into the FSDP accumulator
    # layout FIRST, then upcast — converting in the parameter layout first
    # materializes full-size f32 temporaries (3.75 GiB/leaf on mixtral).
    def p1(carry, cb):
        gsum, lsum = carry
        l, g = vg(params, cb)
        # pin the cotangent in the PARAM layout first: without this the
        # fsdp constraint propagates backward into the per-layer weight-
        # cotangent accumulation loop, whose dynamic-update-slice on an
        # L-sharded stack degenerates to gather-whole-stack-per-layer
        # (measured 12 TiB/chip/round of all-gathers on mixtral).
        g = constrain(g)
        g = _f32(constrain_acc(g))
        return (constrain_acc(tree.tree_add(gsum, g)), lsum + l), None

    (gsum, loss_sum), _ = jax.lax.scan(
        p1, (constrain_acc(tree.tree_zeros_like(params, jnp.float32)),
             jnp.zeros((), jnp.float32)), batch)
    g1 = constrain_acc(tree.tree_scale(gsum, 1.0 / K))
    g1_sq = tree.tree_sqnorm(g1)

    # ---- pass 2: local solves + unnormalized FOLB accumulation
    def p2(carry, cb):
        acc, denom = carry
        g_k = constrain(jax.grad(loss_fn)(params, cb))  # see p1 note
        w_new, _ = local_solve(g_k, cb)
        # delta: exact bf16 subtract in the param layout, reshard to the
        # accumulator layout (param->fsdp is a free local slice), THEN
        # upcast — the only fp32 copy lives in the small fsdp layout.
        delta = _f32(constrain_acc(constrain(
            jax.tree.map(jnp.subtract, w_new, params))))
        if rc.algo in ("fedavg", "fedprox"):
            i_k = jnp.ones((), jnp.float32)
            score = i_k
        else:
            i_k = tree.tree_dot(constrain_acc(g_k), g1)
            score = i_k
            if rc.algo == "folb_het":
                gamma = _gamma(loss_fn, w_new, params, cb, g_k, mu)
                score = i_k - rc.psi * gamma * g1_sq
        acc = constrain_acc(jax.tree.map(
            lambda a, d: a + score * d, acc, delta))
        return (acc, denom + jnp.abs(score)), score

    (acc, denom), scores = jax.lax.scan(
        p2, (constrain_acc(tree.tree_zeros_like(params, jnp.float32)),
             jnp.zeros((), jnp.float32)), batch)

    new_params = jax.tree.map(
        lambda w, a: (w.astype(jnp.float32)
                      + a / jnp.maximum(denom, 1e-30)).astype(w.dtype),
        params, acc)
    metrics = {
        "client_loss": loss_sum / K,
        "g1_norm": jnp.sqrt(g1_sq),
        "weight_denom": denom,
        "scores": scores,
    }
    return new_params, metrics


def fedavg_round(cfg, rc: RoundConfig, params: Params, batch: Dict):
    """Baseline round (mean aggregation) via the same engine."""
    return folb_round(cfg, dataclasses.replace(rc, algo="fedavg"),
                      params, batch)


def sgd_step(cfg, params: Params, batch: Dict, lr: float, remat: bool = True
             ) -> Tuple[Params, Dict[str, jnp.ndarray]]:
    """Centralized SGD step (the 'why not just do gradient descent at the
    server' baseline of Sec. III-D) — batch has no client axis."""
    loss, g = jax.value_and_grad(make_loss_fn(cfg, remat))(params, batch)
    new = jax.tree.map(
        lambda w, gl: (w.astype(jnp.float32)
                       - lr * gl.astype(jnp.float32)).astype(w.dtype),
        params, g)
    return new, {"loss": loss}
