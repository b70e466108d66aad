"""Pallas TPU kernel: fused FOLB aggregation (the paper's hot spot).

The FOLB single-set rule (Eq. IV-C / V-B) over a parameter vector of size D
with K clients requires, implemented naively:
    K passes over HBM for the inner products <∇F_k, g1>,
    1 pass for Σ|I_k| normalization (scalar),
    K+1 passes for the weighted delta sum.
This kernel fuses everything into TWO streaming passes (one for the dots,
one for the weighted sum — the normalizer is a sequential dependency), with
the (K, TILE) working set resident in VMEM and fp32 accumulation.

Phase 1 (``folb_scores``):  grid over D tiles, accumulating the K inner
products into a VMEM (K,) accumulator (+ the ψγ correction applied by the
wrapper).
Phase 2 (``folb_apply``):   grid over D tiles, computing
w + Σ_k (I_k/Σ|I|)·Δ_k tile-by-tile.

Dtype contract: the ``(K, D)`` grad/delta buffers may be bf16 (the
bandwidth-optimal storage — see ``core.flat.FlatSpec.buf_dtype``); every
tile is upcast on load and the VMEM accumulators / the parameter stream
stay fp32, so halving the HBM traffic costs one bf16 rounding per input
element and nothing in the reduction.

Sharding: ``folb_aggregate_sharded`` / ``folb_aggregate_stale_sharded``
run the same two phases under ``shard_map`` with the D axis split over a
mesh axis — each shard does purely local streaming sweeps and the only
collective is one (K+1,)-sized ``psum`` (the inner products and ‖g1‖²)
between the phases; the score/normalize algebra is replicated K-sized
scalar work.  On a 1-shard mesh the psum is the identity and the local
shapes equal the global ones, so the sharded path is bit-identical to the
single-device kernel (tests/test_sharded_agg.py).

Adaptation note (DESIGN.md §4): the paper's TF implementation evaluates
these as K separate reductions on GPU; on TPU the fusion converts ~2K HBM
sweeps of the full parameter vector into 2.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

TILE_D = 1024        # D padding unit: 8 lane-widths, the usual tile
_MAX_TILE_D = 1 << 15   # longest tile; longer ones buy no bandwidth
_MIN_TILE_D = 128       # one lane-width: the floor when K is very large
# VMEM for the double-buffered (K, tile) input blocks of one kernel: half
# of v5e's 16 MiB default scoped VMEM, leaving the rest for the (1, tile)
# parameter/output blocks and the compiler's scratch.  The described-v5e
# compiles in tests/test_tpu_compile.py pin that this fits.
_VMEM_BLOCK_BUDGET = 8 << 20
_INTERPRET_MAX_GRID = 512   # interpret mode unrolls the grid at trace time


def pad_unit(D: int) -> int:
    """Padding unit of a D-parameter flat buffer: the longest tile (a
    power-of-two multiple of TILE_D, at most _MAX_TILE_D) whose padding
    costs at most 1/1024 of D, so ``_pick_tile`` can reach long tiles at
    large D; TILE_D for D up to 2**20."""
    t = TILE_D
    while t < _MAX_TILE_D and 2 * t * 1024 <= D:
        t *= 2
    return t


def _pick_tile(D: int, K: int, itemsize: int, n_streams: int = 1) -> int:
    """Tile length along D for a kernel streaming ``n_streams`` ``(K, D)``
    inputs of ``itemsize`` bytes per element.

    The largest power-of-two multiple of TILE_D that divides D, keeps the
    grid longer than 256 steps, and keeps the double-buffered input
    blocks — K padded to the dtype's sublane tile (8 rows of 4 bytes) —
    within ``_VMEM_BLOCK_BUDGET``.  At K ≤ 16 every dtype and stream
    count gets the 32768-lane cap; larger K shrinks the tile, down to one
    lane-width below TILE_D when K is in the hundreds.
    """
    rows_per_tile = 32 // itemsize
    rows = -(-K // rows_per_tile) * rows_per_tile

    def fits(t):
        return n_streams * 2 * rows * t * itemsize <= _VMEM_BLOCK_BUDGET

    t = TILE_D
    while (t < _MAX_TILE_D and D % (2 * t) == 0 and D // t > 256
           and fits(2 * t)):
        t *= 2
    while t > _MIN_TILE_D and not fits(t):
        t //= 2
    return t


def _scores_kernel(grads_ref, g1_ref, acc_ref):
    """One D-tile: acc[k] += grads[k, tile] . g1[tile]."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    g = grads_ref[...].astype(jnp.float32)        # (K, TILE)
    v = g1_ref[...].astype(jnp.float32)           # (1, TILE)
    acc_ref[...] += jnp.sum(g * v, axis=1, keepdims=True)  # (K, 1)


def _apply_kernel(w_ref, deltas_ref, weights_ref, out_ref):
    """One D-tile: out = w + Σ_k weights[k]·Δ[k, tile]."""
    d = deltas_ref[...].astype(jnp.float32)       # (K, TILE)
    wgt = weights_ref[...].astype(jnp.float32)    # (K, 1)
    upd = jnp.sum(d * wgt, axis=0)                # (TILE,)
    out_ref[...] = (w_ref[...].astype(jnp.float32)
                    + upd[None, :]).astype(out_ref.dtype)


def _guard_stats_kernel(deltas_ref, grads_ref, norm_ref, fin_ref):
    """One D-tile of the guard's streaming stats pass: per-row delta
    sqnorm accumulation plus a per-row finite flag (min-accumulated, so
    one bad tile poisons the row's flag but never the accumulators —
    non-finite lanes are zeroed before the square)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        norm_ref[...] = jnp.zeros_like(norm_ref)
        fin_ref[...] = jnp.ones_like(fin_ref)

    d = deltas_ref[...].astype(jnp.float32)       # (K, TILE)
    g = grads_ref[...].astype(jnp.float32)        # (K, TILE)
    fin_t = (jnp.all(jnp.isfinite(d), axis=1, keepdims=True)
             & jnp.all(jnp.isfinite(g), axis=1, keepdims=True))
    fin_ref[...] = jnp.minimum(fin_ref[...], fin_t.astype(jnp.float32))
    d2 = jnp.where(jnp.isfinite(d), d, 0.0)
    norm_ref[...] += jnp.sum(d2 * d2, axis=1, keepdims=True)


def folb_scores(grads: jnp.ndarray, g1: jnp.ndarray,
                interpret: bool = False) -> jnp.ndarray:
    """(K, D), (D,) -> (K,) inner products, single HBM pass.

    Accepts fp32 or bf16 ``grads``/``g1``; accumulation is fp32 either way.
    In interpret mode (CPU) the grid is unrolled at trace time, so very
    long sweeps fall back to an einsum with identical fp32-accumulation
    semantics (different reduction order only).
    """
    K, D = grads.shape
    tile = _pick_tile(D, K, grads.dtype.itemsize)
    assert D % tile == 0, (D, tile)
    if interpret and D // tile > _INTERPRET_MAX_GRID:
        return jnp.einsum("kd,d->k", grads.astype(jnp.float32),
                          g1.astype(jnp.float32))
    out = pl.pallas_call(
        _scores_kernel,
        grid=(D // tile,),
        in_specs=[
            pl.BlockSpec((K, tile), lambda i: (0, i)),
            pl.BlockSpec((1, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((K, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((K, 1), jnp.float32),
        interpret=interpret,
    )(grads, g1[None, :])
    return out[:, 0]


def folb_apply(w: jnp.ndarray, deltas: jnp.ndarray, weights: jnp.ndarray,
               interpret: bool = False) -> jnp.ndarray:
    """(D,), (K, D), (K,) -> (D,) updated parameters, single HBM pass.

    ``deltas`` may be bf16 (upcast per tile); ``w`` and the output keep
    ``w.dtype`` with the add performed in fp32.
    """
    K, D = deltas.shape
    tile = _pick_tile(D, K, deltas.dtype.itemsize)
    assert D % tile == 0, (D, tile)
    if interpret and D // tile > _INTERPRET_MAX_GRID:
        upd = jnp.tensordot(weights.astype(jnp.float32),
                            deltas.astype(jnp.float32), axes=1)
        return (w.astype(jnp.float32) + upd).astype(w.dtype)
    out = pl.pallas_call(
        _apply_kernel,
        grid=(D // tile,),
        in_specs=[
            pl.BlockSpec((1, tile), lambda i: (0, i)),
            pl.BlockSpec((K, tile), lambda i: (0, i)),
            pl.BlockSpec((K, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, D), w.dtype),
        interpret=interpret,
    )(w[None, :], deltas, weights[:, None])
    return out[0]


def guard_stats(deltas: jnp.ndarray, grads: jnp.ndarray,
                interpret: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(K, D), (K, D) -> ((K,) delta sqnorms, (K,) finite flags), one
    fused HBM pass.  Non-finite lanes are zeroed before squaring so the
    norm accumulator stays finite even on corrupted rows; the finite
    flag is 1.0 iff every delta AND grad lane of the row is finite.
    """
    K, D = deltas.shape
    tile = _pick_tile(D, K, max(deltas.dtype.itemsize, grads.dtype.itemsize),
                      n_streams=2)
    assert D % tile == 0, (D, tile)
    if interpret and D // tile > _INTERPRET_MAX_GRID:
        d = deltas.astype(jnp.float32)
        g = grads.astype(jnp.float32)
        fin = (jnp.all(jnp.isfinite(d), axis=1)
               & jnp.all(jnp.isfinite(g), axis=1)).astype(jnp.float32)
        d2 = jnp.where(jnp.isfinite(d), d, 0.0)
        return jnp.sum(d2 * d2, axis=1), fin
    norms, fin = pl.pallas_call(
        _guard_stats_kernel,
        grid=(D // tile,),
        in_specs=[
            pl.BlockSpec((K, tile), lambda i: (0, i)),
            pl.BlockSpec((K, tile), lambda i: (0, i)),
        ],
        out_specs=[pl.BlockSpec((K, 1), lambda i: (0, 0)),
                   pl.BlockSpec((K, 1), lambda i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((K, 1), jnp.float32),
                   jax.ShapeDtypeStruct((K, 1), jnp.float32)],
        interpret=interpret,
    )(deltas, grads)
    return norms[:, 0], fin[:, 0]


def masked_median(x: jnp.ndarray, m: jnp.ndarray) -> jnp.ndarray:
    """Median of ``x`` over entries with ``m > 0`` (midpoint of the two
    central order statistics); 0.0 on an empty set.  ``x`` must be
    finite and non-negative where masked-in (|scores|, norms)."""
    K = x.shape[0]
    s = jnp.sort(jnp.where(m > 0.0, x, jnp.inf))
    n = jnp.sum((m > 0.0).astype(jnp.int32))
    lo = jnp.clip((n - 1) // 2, 0, K - 1)
    hi = jnp.clip(n // 2, 0, K - 1)
    med = 0.5 * (s[lo] + s[hi])
    return jnp.where(n > 0, med, 0.0)


def folb_aggregate(w: jnp.ndarray, deltas: jnp.ndarray, grads: jnp.ndarray,
                   g1: jnp.ndarray, psi_gamma: jnp.ndarray,
                   g1_sq: jnp.ndarray, interpret: bool = False
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused FOLB aggregation; matches kernels.ref.folb_aggregate_ref."""
    inner = folb_scores(grads, g1, interpret=interpret)
    scores = inner - psi_gamma.astype(jnp.float32) * g1_sq.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(jnp.abs(scores)), 1e-30)
    new_w = folb_apply(w, deltas, scores / denom, interpret=interpret)
    return new_w, scores


def folb_aggregate_stale(w: jnp.ndarray, deltas: jnp.ndarray,
                         grads: jnp.ndarray, tau: jnp.ndarray,
                         alpha: jnp.ndarray, psi_gamma: jnp.ndarray,
                         mask: jnp.ndarray, interpret: bool = False
                         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Flat-buffer staleness-discounted FOLB (async engines' hot rule).

    Matches ``core.aggregation.folb_staleness`` on the flattened problem:
        I_k = (<g_k, g1> − ψγ_k ||g1||²) · (1 + τ_k)^{−α} · m_k
    with g1 the masked mean of the arrived gradients, reusing the same two
    streaming Pallas phases as ``folb_aggregate`` (the score/normalize
    algebra between them is K-sized scalar work).
    """
    m = mask.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(m), 1.0)
    g1 = jnp.tensordot(m, grads.astype(jnp.float32), axes=1) / n
    g1_sq = jnp.sum(g1 * g1)
    inner = folb_scores(grads, g1, interpret=interpret)
    scores = inner - psi_gamma.astype(jnp.float32) * g1_sq
    scores = scores * jnp.power(1.0 + tau.astype(jnp.float32), -alpha) * m
    denom = jnp.maximum(jnp.sum(jnp.abs(scores)), 1e-30)
    new_w = folb_apply(w, deltas, scores / denom, interpret=interpret)
    return new_w, scores


# ------------------------------------------------------------ guarded path

def _guard_algebra(inner, g1_sq, norms_sq, finite, m_in, tau, alpha,
                   psi_gamma, guard):
    """Shared post-stats guard algebra (K-sized scalar work, replicated
    under sharding): scores from the globally reduced inner products,
    score gating and norm clipping against masked medians, rejection
    counters.  Returns (weights, scores, m0, n_nonfinite, n_clipped,
    n_gated); ``guard`` is static so the disabled defenses trace away.
    """
    fin = finite if guard.nonfinite else jnp.ones_like(finite)
    m0 = m_in * fin
    scores = inner - psi_gamma.astype(jnp.float32) * g1_sq
    scores = scores * jnp.power(1.0 + tau.astype(jnp.float32), -alpha) * m0
    n_nonfinite = jnp.sum(m_in * (1.0 - finite))
    n_gated = jnp.zeros((), jnp.float32)
    if guard.gate_mult > 0.0:
        med = masked_median(jnp.abs(scores), m0)
        keep = (jnp.abs(scores) <= guard.gate_mult * med).astype(jnp.float32)
        # a zero median means no meaningful score spread to trim against
        keep = jnp.where(med > 0.0, keep, jnp.ones_like(keep))
        n_gated = jnp.sum(m0 * (1.0 - keep))
        m0 = m0 * keep
        scores = scores * keep
    clipf = jnp.ones_like(m0)
    n_clipped = jnp.zeros((), jnp.float32)
    if guard.clip_mult > 0.0:
        norms = jnp.sqrt(norms_sq)
        thresh = guard.clip_mult * masked_median(norms, m0)
        do_clip = (norms > thresh) & (thresh > 0.0)
        clipf = jnp.where(do_clip, thresh / jnp.maximum(norms, 1e-30), 1.0)
        n_clipped = jnp.sum(m0 * do_clip.astype(jnp.float32))
    denom = jnp.maximum(jnp.sum(jnp.abs(scores)), 1e-30)
    weights = scores / denom * clipf
    return weights, scores, m0, n_nonfinite, n_clipped, n_gated


def _scrub(x: jnp.ndarray) -> jnp.ndarray:
    """Zero non-finite lanes so no downstream reduction ever sees them
    (0·NaN would otherwise break the masked-row exact-cancellation
    contract).  Elementwise — whole-row rejection is the mask's job."""
    return jnp.where(jnp.isfinite(x), x, jnp.zeros((), x.dtype))


def folb_aggregate_stale_guarded(w: jnp.ndarray, deltas: jnp.ndarray,
                                 grads: jnp.ndarray, tau: jnp.ndarray,
                                 alpha: jnp.ndarray, psi_gamma: jnp.ndarray,
                                 mask: jnp.ndarray, guard,
                                 interpret: bool = False):
    """Guarded staleness FOLB: ``folb_aggregate_stale`` plus the update-
    validation defenses of ``kernels.guard.GuardConfig`` (static).

    Adds one streaming stats pass (per-row delta sqnorms + finite flags)
    ahead of the two aggregation phases; rejected rows leave the masked
    set exactly like deadline-cut ones, and an all-rejected aggregation
    returns ``w`` bit-exact.  Returns ``(new_w, scores, ginfo)`` with
    ginfo = {mask, n_nonfinite, n_clipped, n_gated} (post-guard mask).
    Matches ``kernels.guard.reference_guard`` on the weight algebra.
    """
    m_in = mask.astype(jnp.float32)
    norms_sq, finite = guard_stats(deltas, grads, interpret=interpret)
    fin = finite if guard.nonfinite else jnp.ones_like(finite)
    m0 = m_in * fin
    g_clean = _scrub(grads)
    d_clean = _scrub(deltas)
    n = jnp.maximum(jnp.sum(m0), 1.0)
    g1 = jnp.tensordot(m0, g_clean.astype(jnp.float32), axes=1) / n
    g1_sq = jnp.sum(g1 * g1)
    inner = folb_scores(g_clean, g1, interpret=interpret)
    weights, scores, m0, nf, nc, ng = _guard_algebra(
        inner, g1_sq, norms_sq, finite, m_in, tau, alpha, psi_gamma, guard)
    new_w = folb_apply(w, d_clean, weights, interpret=interpret)
    new_w = jnp.where(jnp.sum(m0) > 0.0, new_w, w)
    ginfo = {"mask": m0, "n_nonfinite": nf, "n_clipped": nc, "n_gated": ng}
    return new_w, scores, ginfo


# ------------------------------------------------------------ D-sharded path

def shard_alignment(mesh, axis: str = "d") -> int:
    """Flat buffers consumed by the sharded kernels must pad D to a
    multiple of (shards × TILE_D) so every shard's local sweep is
    tile-aligned — pass this as ``pad_to`` to ``core.flat.spec_of``."""
    return TILE_D * mesh.shape[axis]


def folb_aggregate_sharded(w: jnp.ndarray, deltas: jnp.ndarray,
                           grads: jnp.ndarray, psi_gamma: jnp.ndarray,
                           mesh, axis: str = "d", interpret: bool = False
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """FOLB aggregation with the D axis sharded over ``mesh.shape[axis]``.

    Per shard: a local mean for the g1 slice, the two local Pallas sweeps,
    and one (K+1,)-sized psum carrying the inner products and ‖g1‖².
    Computes g1 internally (unlike ``folb_aggregate``) because g1 lives
    sharded; matches ``ops.folb_aggregate_buffers(mesh=None)`` exactly on a
    1-shard mesh and to fp32-reduction-order tolerance otherwise.
    """
    K, D = grads.shape
    assert D % shard_alignment(mesh, axis) == 0, (D, dict(mesh.shape))

    def body(w_l, d_l, g_l, pg):
        g1_l = jnp.mean(g_l.astype(jnp.float32), axis=0)
        part = jnp.concatenate(
            [folb_scores(g_l, g1_l, interpret=interpret),
             jnp.sum(g1_l * g1_l)[None]])
        tot = jax.lax.psum(part, axis)
        inner, g1_sq = tot[:-1], tot[-1]
        scores = inner - pg.astype(jnp.float32) * g1_sq
        denom = jnp.maximum(jnp.sum(jnp.abs(scores)), 1e-30)
        new_w_l = folb_apply(w_l, d_l, scores / denom, interpret=interpret)
        return new_w_l, scores

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(None, axis), P(None, axis),
                                 P(None)),
                       out_specs=(P(axis), P(None)),
                       check_vma=False)
    return fn(w, deltas, grads, psi_gamma)


def folb_aggregate_stale_sharded(w: jnp.ndarray, deltas: jnp.ndarray,
                                 grads: jnp.ndarray, tau: jnp.ndarray,
                                 alpha: jnp.ndarray, psi_gamma: jnp.ndarray,
                                 mask: jnp.ndarray, mesh, axis: str = "d",
                                 interpret: bool = False
                                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """D-sharded ``folb_aggregate_stale``: masked-mean g1 slice per shard,
    local sweeps, one (K+1,)-sized psum — same structure as
    ``folb_aggregate_sharded`` with the staleness/mask score algebra."""
    K, D = grads.shape
    assert D % shard_alignment(mesh, axis) == 0, (D, dict(mesh.shape))

    def body(w_l, d_l, g_l, tau_, alpha_, pg, mask_):
        m = mask_.astype(jnp.float32)
        n = jnp.maximum(jnp.sum(m), 1.0)
        g1_l = jnp.tensordot(m, g_l.astype(jnp.float32), axes=1) / n
        part = jnp.concatenate(
            [folb_scores(g_l, g1_l, interpret=interpret),
             jnp.sum(g1_l * g1_l)[None]])
        tot = jax.lax.psum(part, axis)
        inner, g1_sq = tot[:-1], tot[-1]
        scores = inner - pg.astype(jnp.float32) * g1_sq
        scores = scores * jnp.power(1.0 + tau_.astype(jnp.float32),
                                    -alpha_) * m
        denom = jnp.maximum(jnp.sum(jnp.abs(scores)), 1e-30)
        new_w_l = folb_apply(w_l, d_l, scores / denom, interpret=interpret)
        return new_w_l, scores

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(None, axis), P(None, axis),
                                 P(None), P(), P(None), P(None)),
                       out_specs=(P(axis), P(None)),
                       check_vma=False)
    return fn(w, deltas, grads, tau, alpha, psi_gamma, mask)


def folb_aggregate_stale_guarded_sharded(w: jnp.ndarray, deltas: jnp.ndarray,
                                         grads: jnp.ndarray,
                                         tau: jnp.ndarray, alpha: jnp.ndarray,
                                         psi_gamma: jnp.ndarray,
                                         mask: jnp.ndarray, guard, mesh,
                                         axis: str = "d",
                                         interpret: bool = False):
    """D-sharded ``folb_aggregate_stale_guarded``.

    The guard needs one extra collective: a row that is non-finite in
    ANY shard must be scrubbed from EVERY shard's g1 slice, so the
    finite flags (as per-shard non-finite counts) and the per-shard
    partial delta sqnorms ride a (2K,)-sized psum BEFORE g1, then the
    inner products take the existing (K+1,)-sized psum.  The guard
    algebra between psum B and the apply sweep is replicated K-sized
    scalar work, identical to the single-device path — bit-identical on
    a 1-shard mesh.
    """
    K, D = grads.shape
    assert D % shard_alignment(mesh, axis) == 0, (D, dict(mesh.shape))

    def body(w_l, d_l, g_l, tau_, alpha_, pg, mask_):
        m_in = mask_.astype(jnp.float32)
        norms_l, fin_l = guard_stats(d_l, g_l, interpret=interpret)
        partA = jnp.concatenate([1.0 - fin_l, norms_l])
        totA = jax.lax.psum(partA, axis)
        finite = (totA[:K] == 0.0).astype(jnp.float32)
        norms_sq = totA[K:]
        fin = finite if guard.nonfinite else jnp.ones_like(finite)
        m0 = m_in * fin
        g_clean = _scrub(g_l)
        d_clean = _scrub(d_l)
        n = jnp.maximum(jnp.sum(m0), 1.0)
        g1_l = jnp.tensordot(m0, g_clean.astype(jnp.float32), axes=1) / n
        partB = jnp.concatenate(
            [folb_scores(g_clean, g1_l, interpret=interpret),
             jnp.sum(g1_l * g1_l)[None]])
        totB = jax.lax.psum(partB, axis)
        inner, g1_sq = totB[:-1], totB[-1]
        weights, scores, m0, nf, nc, ng = _guard_algebra(
            inner, g1_sq, norms_sq, finite, m_in, tau_, alpha_, pg, guard)
        new_w_l = folb_apply(w_l, d_clean, weights, interpret=interpret)
        new_w_l = jnp.where(jnp.sum(m0) > 0.0, new_w_l, w_l)
        return new_w_l, scores, m0, jnp.stack([nf, nc, ng])

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(None, axis), P(None, axis),
                                 P(None), P(), P(None), P(None)),
                       out_specs=(P(axis), P(None), P(None), P(None)),
                       check_vma=False)
    new_w, scores, m0, counters = fn(w, deltas, grads, tau, alpha,
                                     psi_gamma, mask)
    ginfo = {"mask": m0, "n_nonfinite": counters[0],
             "n_clipped": counters[1], "n_gated": counters[2]}
    return new_w, scores, ginfo
