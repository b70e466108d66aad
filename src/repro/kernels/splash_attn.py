"""Causal attention over full or windowed masks through JAX's Pallas TPU
splash-attention kernel, which skips the blocks its mask leaves empty and
has a backward pass.

Grouped-query attention runs as the kernel's multi-query form, mapped over
the batch and the key/value heads.  The sequence is padded at its end to a
whole number of blocks (the kernel's blocks are multiples of 128 lanes):
padded keys lie after every real query, so the causal mask hides them, and
the padded queries are sliced off.  Queries are scaled by head_dim^-1/2
here, since the kernel does not scale.

The kernel is called through the unjitted body of the library's
``_splash_attention``, so that in a profiler trace the innermost
``jit(<name>)`` scope of its ops is the entry point of
``repro.kernels.ops`` that launched it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as _sk, splash_attention_mask as _sm)

LANES = 128
BLOCK = 512


def padded_len(seq: int) -> int:
    return -(-seq // LANES) * LANES


def block_len(seq: int) -> int:
    """Block length along both sequences for a padded length ``seq``."""
    return BLOCK if seq % BLOCK == 0 else LANES


@functools.lru_cache(maxsize=None)
def _kernel(seq: int, window: int, group: int, interpret: bool):
    b = block_len(seq)
    if window:
        mask = _sm.LocalMask((seq, seq), (window - 1, 0), 0)
    else:
        mask = _sm.CausalMask((seq, seq))
    sizes = _sk.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                           block_q_dkv=b, block_kv_dkv=b,
                           block_kv_dkv_compute=b, block_q_dq=b,
                           block_kv_dq=b)
    # concrete mask tables even when first built inside a trace: the cached
    # kernel outlives it
    with jax.ensure_compile_time_eval():
        return _sk.make_splash_mqa_single_device(
            _sm.MultiHeadMask([mask] * group), block_sizes=sizes,
            interpret=interpret)


def splash_attention(q, k, v, window: int = 0, interpret: bool = False):
    """q: (B, S, H, hd), k/v: (B, S, KV, hd) -> (B, S, H * hd); each query
    attends to keys at or before it, the last ``window`` of them when
    ``window`` > 0."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    Sp = padded_len(S)
    kern = _kernel(Sp, window, G, interpret)
    pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
    qg = (jnp.pad(q, pad) * jnp.asarray(hd ** -0.5, q.dtype)).reshape(
        B, Sp, KV, G, hd).transpose(0, 2, 3, 1, 4)          # (B, KV, G, Sp, hd)
    kg = jnp.pad(k, pad).transpose(0, 2, 1, 3)              # (B, KV, Sp, hd)
    vg = jnp.pad(v, pad).transpose(0, 2, 1, 3)
    body = _sk._splash_attention.__wrapped__

    def one(qq, kk, vv):
        return body(kern.fwd_mask_info, kern.dq_mask_info, kern.dkv_mask_info,
                    qq, kk, vv, **kern.kwargs)

    out = jax.vmap(jax.vmap(one))(qg, kg, vg)               # (B, KV, G, Sp, hd)
    return out.transpose(0, 3, 1, 2, 4)[:, :S].reshape(B, S, H * hd)
