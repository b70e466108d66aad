"""Grouped matrix products over the experts a chip holds, through JAX's
Pallas TPU megablox ``gmm``/``tgmm`` kernels.

``lhs`` holds the rows routed to the held experts, sorted by expert, and
``sizes`` the row count of each; the kernels compute only the groups' row
tiles, so the work follows the routed load.  Rows past ``sum(sizes)`` are
not written by the kernel: this wrapper returns them as zeros and gives
them a zero gradient.  The library's jitted kernels are called through
their unjitted bodies, so that in a profiler trace the innermost
``jit(<name>)`` scope of their ops is the entry point of
``repro.kernels.ops`` that launched them.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the module, not the package's re-export of its custom-vjp ``gmm``
_mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

LANES = 128
MAX_ROW_TILE = 512
MAX_TILE = 1024


def _tile(n: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is at most 1024."""
    best = LANES
    for t in range(LANES, min(n, MAX_TILE) + 1, LANES):
        if n % t == 0:
            best = t
    return best


def row_tile(m: int) -> int:
    return MAX_ROW_TILE if m % MAX_ROW_TILE == 0 else LANES


def _tiling(m: int, k: int, n: int):
    return (row_tile(m), _tile(k), _tile(n))


def _live_rows(x, sizes):
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.where(rows < jnp.sum(sizes), x, jnp.zeros((), x.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gmm(lhs, rhs, sizes, interpret: bool = False):
    """(m, k) rows sorted by group, (G, k, n) weights, (G,) int32 sizes ->
    (m, n): row block g times ``rhs[g]``; rows past the groups are 0."""
    m, k = lhs.shape
    out = _mb.gmm.__wrapped__(lhs, rhs, sizes, lhs.dtype,
                              _tiling(m, k, rhs.shape[2]),
                              interpret=interpret)
    return _live_rows(out, sizes)


def _gmm_fwd(lhs, rhs, sizes, interpret):
    return gmm(lhs, rhs, sizes, interpret), (lhs, rhs, sizes)


def _gmm_bwd(interpret, res, g):
    lhs, rhs, sizes = res
    m, k = lhs.shape
    n = rhs.shape[2]
    g = g.astype(lhs.dtype)
    d_lhs = _mb.gmm.__wrapped__(g, rhs, sizes, lhs.dtype, _tiling(m, n, k),
                                transpose_rhs=True, interpret=interpret)
    d_rhs = _mb.tgmm.__wrapped__(lhs.swapaxes(0, 1), g, sizes, rhs.dtype,
                                 _tiling(m, k, n), None, rhs.shape[0],
                                 interpret=interpret)
    return _live_rows(d_lhs, sizes), d_rhs, None


gmm.defvjp(_gmm_fwd, _gmm_bwd)


def glu_ffn(xs, w_gate, w_up, w_down, sizes, interpret: bool = False):
    """SiLU-gated expert FFN of each held expert over its rows:
    (silu(x W_gate) * x W_up) W_down, in the rows' dtype with f32
    accumulation inside the kernels."""
    g = gmm(xs, w_gate, sizes, interpret)
    u = gmm(xs, w_up, sizes, interpret)
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)
         ).astype(xs.dtype)
    return gmm(h, w_down, sizes, interpret)
