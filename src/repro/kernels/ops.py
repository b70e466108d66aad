"""jit'd public wrappers for the Pallas kernels.

Every wrapper picks the kernel's mode from the platform the program is
lowered for (``_by_platform``): on CPU the Pallas kernels run in interpret
mode (the kernel body executed as XLA ops, for correctness); on TPU the
same ``pallas_call`` compiles to a Mosaic ``tpu_custom_call``.  The choice
is made by ``lax.platform_dependent`` at lowering time, so a program
compiled for a described TPU from a CPU host gets the Mosaic kernel, and
importing this module initialises no backend.

The FOLB entry points come in two layers:

  * buffer level (``folb_aggregate_buffers`` / ``folb_staleness_buffers``):
    operate on pre-raveled flat buffers — fp32 ``(D,)`` params, fp32-or-
    bf16 ``(K, D)`` grads/deltas — and dispatch to the single-device fused
    kernel or, given a ``mesh``, the D-sharded ``shard_map`` variant.
  * pytree level (``folb_aggregate_tree`` / ``folb_staleness_tree``):
    ravel the pytrees (bf16 grad/delta buffers by default — half the HBM
    traffic; fp32 accumulation stays inside the kernels), call the buffer
    level, unravel.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import folb_aggregate as _folb
from repro.kernels import moe_gmm as _gmm
from repro.kernels import slstm_scan as _slstm
from repro.kernels import splash_attn as _splash
from repro.kernels import ssm_scan as _ssd


def _by_platform(kernel, *args, **static):
    """``kernel(*args, interpret=..., **static)`` with ``interpret=True``
    when the enclosing program is lowered for CPU and the compiled Mosaic
    kernel on every other platform.  ``args`` are arrays; ``static`` holds
    the non-array arguments (mesh, guard, block sizes)."""
    return jax.lax.platform_dependent(
        *args,
        cpu=lambda *a: kernel(*a, interpret=True, **static),
        default=lambda *a: kernel(*a, interpret=False, **static))


# default storage dtype for the (K, D) grad/delta buffers: bf16 halves the
# streaming traffic that dominates FOLB's server cost; parameters stay fp32
DEFAULT_BUF_DTYPE = jnp.bfloat16


@functools.partial(jax.jit, static_argnames=("causal", "sliding_window",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, sliding_window: int = 0,
                    block_q: int = _fa.DEFAULT_BLOCK_Q,
                    block_k: int = _fa.DEFAULT_BLOCK_K):
    return _by_platform(_fa.flash_attention, q, k, v, causal=causal,
                        sliding_window=sliding_window,
                        block_q=block_q, block_k=block_k)


@functools.partial(jax.jit, static_argnames=("window",))
def attention_window(q, k, v, window: int):
    """Causal attention over the last ``window`` keys (splash kernel; the
    blocks outside the window are skipped).  q: (B, S, H, hd), k/v:
    (B, S, KV, hd) -> (B, S, H * hd)."""
    return _by_platform(_splash.splash_attention, q, k, v, window=window)


@jax.jit
def attention_full(q, k, v):
    """Causal attention over every earlier key (splash kernel; the blocks
    above the diagonal are skipped).  Shapes as ``attention_window``."""
    return _by_platform(_splash.splash_attention, q, k, v, window=0)


@jax.jit
def moe_grouped_ffn(xs, w_gate, w_up, w_down, sizes):
    """SiLU-gated FFN of each held expert over its rows of ``xs`` (sorted
    by expert, ``sizes`` rows each; rows past them come back 0) with the
    megablox grouped matrix products."""
    return _by_platform(_gmm.glu_ffn, xs, w_gate, w_up, w_down, sizes)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, loga, w, Bm, Cm, chunk: int = 128):
    return _by_platform(_ssd.ssd_scan, x, loga, w, Bm, Cm, chunk=chunk)


@functools.partial(jax.jit, static_argnames=("n_heads", "chunk"))
def slstm_scan(xg, r, n_heads: int, chunk: int = 256):
    return _by_platform(_slstm.slstm_scan, xg, r, n_heads=n_heads,
                        chunk=chunk)


@functools.partial(jax.jit, static_argnames=("mesh", "guard"))
def folb_aggregate_buffers(w, deltas, grads, psi_gamma=None, mesh=None,
                           guard=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-set FOLB on flat buffers; ``mesh`` (static) shards D.

    w: (D,) fp32; deltas/grads: (K, D) fp32 or bf16; psi_gamma: (K,) or
    None.  Matches ``kernels.ref.folb_aggregate_ref`` up to reduction
    order; on a 1-shard mesh the sharded path is bit-identical to
    ``mesh=None``.

    ``guard`` (static ``kernels.guard.GuardConfig`` or None) switches to
    the guarded kernel — the plain rule is its τ = 0, full-mask special
    case — and the return grows a third ``ginfo`` element (post-guard
    mask + rejection counters).  ``guard=None`` is the exact pre-guard
    program.
    """
    K = grads.shape[0]
    pg = (jnp.zeros((K,), jnp.float32) if psi_gamma is None
          else psi_gamma.astype(jnp.float32))
    if guard is not None:
        return folb_staleness_buffers(
            w, deltas, grads, jnp.zeros((K,), jnp.float32),
            jnp.zeros((), jnp.float32), psi_gamma=pg, mesh=mesh, guard=guard)
    if mesh is not None:
        return _by_platform(_folb.folb_aggregate_sharded, w, deltas, grads,
                            pg, mesh=mesh)
    g1 = jnp.mean(grads.astype(jnp.float32), axis=0)
    g1_sq = jnp.sum(g1 * g1)
    return _by_platform(_folb.folb_aggregate, w, deltas, grads, g1, pg,
                        g1_sq)


@functools.partial(jax.jit, static_argnames=("mesh", "guard"))
def folb_staleness_buffers(w, deltas, grads, tau, alpha, psi_gamma=None,
                           mask=None, mesh=None, guard=None
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Staleness-discounted flat FOLB (masked g1, (1+τ)^{−α} scores);
    matches core.aggregation.folb_staleness on the flattened problem.

    ``guard`` (static) selects the guarded kernel and adds a third
    ``ginfo`` return element; see ``folb_aggregate_buffers``.
    """
    K = grads.shape[0]
    pg = (jnp.zeros((K,), jnp.float32) if psi_gamma is None
          else psi_gamma.astype(jnp.float32))
    m = jnp.ones((K,), jnp.float32) if mask is None else mask
    tau = tau.astype(jnp.float32)
    alpha = jnp.asarray(alpha, jnp.float32)
    if guard is not None:
        if mesh is not None:
            return _by_platform(_folb.folb_aggregate_stale_guarded_sharded,
                                w, deltas, grads, tau, alpha, pg, m,
                                guard=guard, mesh=mesh)
        return _by_platform(_folb.folb_aggregate_stale_guarded, w, deltas,
                            grads, tau, alpha, pg, m, guard=guard)
    if mesh is not None:
        return _by_platform(_folb.folb_aggregate_stale_sharded, w, deltas,
                            grads, tau, alpha, pg, m, mesh=mesh)
    return _by_platform(_folb.folb_aggregate_stale, w, deltas, grads, tau,
                        alpha, pg, m)


def _whole(tree, mesh):
    """Pin ``tree`` whole on every device of ``mesh`` (identity without a
    mesh).  The pytree front-ends shard only the aggregation: the raveled
    buffers and the result are pinned whole, so the partitioner does not
    carry the D sharding on into the local solves around it, which would
    then run split over the mesh with all-to-alls (13x the compile time
    at 1e8 parameters on four v5e chips)."""
    if mesh is None:
        return tree
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.lax.with_sharding_constraint(tree, NamedSharding(mesh, P()))


def _ravel_problem(params, deltas_stacked, grads_stacked, buf_dtype, mesh):
    """Shared flattening for the pytree front-ends: (spec, flat fp32 w,
    buf_dtype (K, D) delta/grad buffers).  With a mesh, D pads to the
    shard-aligned boundary so every shard's local sweep is tile-aligned,
    and the buffers are pinned whole (``_whole``): each shard slices its
    columns locally."""
    from repro.core import flat as flat_lib
    pad_to = (_folb.shard_alignment(mesh) if mesh is not None
              else _folb.TILE_D)
    spec = flat_lib.spec_of(params, pad_to=pad_to)
    bspec = flat_lib.with_buf_dtype(spec, buf_dtype)
    w = flat_lib.ravel(spec, params)
    deltas = flat_lib.ravel_stacked(bspec, deltas_stacked)
    grads = flat_lib.ravel_stacked(bspec, grads_stacked)
    return (spec, *_whole((w, deltas, grads), mesh))


def folb_aggregate_tree(params, deltas_stacked, grads_stacked,
                        psi_gammas=None, buf_dtype=DEFAULT_BUF_DTYPE,
                        mesh=None, guard=None) -> Tuple:
    """Pytree front-end: ravel the pytrees into flat (K, D) buffers (bf16
    by default, padding D to the kernel tile / shard boundary), run the
    fused — optionally D-sharded — kernel, unravel.  Matches
    repro.core.aggregation.folb_single_set / folb_het to the buffer
    dtype's rounding.  With ``guard`` (static) the return grows a third
    ``ginfo`` element; ``guard=None`` is the exact pre-guard program."""
    from repro.core import flat as flat_lib
    spec, w, deltas, grads = _ravel_problem(
        params, deltas_stacked, grads_stacked, buf_dtype, mesh)
    if guard is not None:
        new_flat, scores, ginfo = folb_aggregate_buffers(
            w, deltas, grads, psi_gamma=psi_gammas, mesh=mesh, guard=guard)
        return flat_lib.unravel(spec, _whole(new_flat, mesh)), scores, ginfo
    new_flat, scores = folb_aggregate_buffers(w, deltas, grads,
                                              psi_gamma=psi_gammas,
                                              mesh=mesh)
    return flat_lib.unravel(spec, _whole(new_flat, mesh)), scores


def folb_staleness_tree(params, deltas_stacked, grads_stacked, tau,
                        alpha: float = 0.0, psi_gammas=None, mask=None,
                        buf_dtype=DEFAULT_BUF_DTYPE, mesh=None,
                        guard=None) -> Tuple:
    """Pytree front-end for the staleness rule (async engines): ravel, run
    the fused kernel, unravel.  Matches core.aggregation.folb_staleness.
    With ``guard`` (static) the return grows a third ``ginfo`` element."""
    from repro.core import flat as flat_lib
    spec, w, deltas, grads = _ravel_problem(
        params, deltas_stacked, grads_stacked, buf_dtype, mesh)
    if guard is not None:
        new_flat, scores, ginfo = folb_staleness_buffers(
            w, deltas, grads, tau.astype(jnp.float32),
            jnp.asarray(alpha, jnp.float32), psi_gamma=psi_gammas,
            mask=mask, mesh=mesh, guard=guard)
        return flat_lib.unravel(spec, _whole(new_flat, mesh)), scores, ginfo
    new_flat, scores = folb_staleness_buffers(
        w, deltas, grads, tau.astype(jnp.float32),
        jnp.asarray(alpha, jnp.float32), psi_gamma=psi_gammas, mask=mask,
        mesh=mesh)
    return flat_lib.unravel(spec, _whole(new_flat, mesh)), scores


def folb_staleness_slots_tree(params, deltas_slots, grads_slots, slot_mask,
                              slot_tau, alpha: float = 0.0, psi_gammas=None,
                              buf_dtype=DEFAULT_BUF_DTYPE, mesh=None,
                              guard=None) -> Tuple:
    """Fixed-budget masked-slot stale aggregation (compiled async engines).

    The stacked client axis here is a *static slot budget* (K dispatched
    + S late-arrival slots), not the realized arrival count: invalid
    slots are excluded through ``slot_mask``.  Contract (property-tested
    in tests/test_event_plan.py):

      * a masked slot never contributes — any finite garbage in a masked
        row (stale pool contents, missed stragglers, the dump row) yields
        a bit-identical aggregate, because every masked term enters the
        reductions as an exact ``0.0 * x``;
      * an all-masked budget (a deadline round where nothing arrived)
        returns ``params`` unchanged, bit-exact — not ``params + 0.0``,
        which would flip negative zeros.

    With ``guard`` (static) the guarded kernel extends the same contract
    to *rejected* slots — its all-rejected return is handled inside the
    kernel against the POST-guard mask — and the return grows a third
    ``ginfo`` element.
    """
    from repro.core import flat as flat_lib
    spec, w, deltas, grads = _ravel_problem(
        params, deltas_slots, grads_slots, buf_dtype, mesh)
    if guard is not None:
        new_flat, scores, ginfo = folb_staleness_buffers(
            w, deltas, grads, slot_tau.astype(jnp.float32),
            jnp.asarray(alpha, jnp.float32), psi_gamma=psi_gammas,
            mask=slot_mask, mesh=mesh, guard=guard)
        return flat_lib.unravel(spec, _whole(new_flat, mesh)), scores, ginfo
    new_flat, scores = folb_staleness_buffers(
        w, deltas, grads, slot_tau.astype(jnp.float32),
        jnp.asarray(alpha, jnp.float32), psi_gamma=psi_gammas,
        mask=slot_mask, mesh=mesh)
    alive = jnp.sum(slot_mask) > 0.0
    new_flat = jnp.where(alive, _whole(new_flat, mesh), w)
    return flat_lib.unravel(spec, new_flat), scores
