"""Attention blocks: GQA/MQA/MHA, causal + sliding-window, bidirectional
(encoder), KV-cache prefill/decode.  Pure-jnp einsum formulation so GSPMD
can shard heads / sequence freely; the Pallas flash kernel in
``repro.kernels`` is the TPU hot-path drop-in validated against this.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.sharding import context as shard_ctx

Params = Dict[str, Any]

NEG_INF = -1e30


def _head_sharding_plan(cfg):
    """Decide the full-sequence attention layout for the active mesh.

    Returns (repeat_kv, constrain_heads):
      * heads divisible by the model axis -> shard the head dim; kv heads are
        repeated to H first so GQA grouping never reshapes a sharded dim.
      * otherwise -> pin q/k/v replicated over 'model' (batch-only sharding)
        so GSPMD cannot shard the head_dim contraction (which would
        all-reduce full score blocks).
    Attention FLOPs are a minority term, so the replicated fallback wastes
    little; see DESIGN.md §4 and EXPERIMENTS.md §Perf.
    """
    m = shard_ctx.model_axis_size()
    if m == 1:
        return False, False
    return True, cfg.n_heads % m == 0


def init_attention(cfg, key) -> Params:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": layers.init_linear(cfg, kq, d, cfg.n_heads * hd),
        "wk": layers.init_linear(cfg, kk, d, cfg.n_kv_heads * hd),
        "wv": layers.init_linear(cfg, kv, d, cfg.n_kv_heads * hd),
        "wo": layers.init_linear(cfg, ko, cfg.n_heads * hd, d),
    }


def _qkv(cfg, p: Params, x: jnp.ndarray, positions: jnp.ndarray,
         rope=None):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = layers.apply_linear(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = layers.apply_linear(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = layers.apply_linear(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    if rope is not None or cfg.rope_theta > 0:
        q = layers.apply_rope(cfg, q, positions, rope)
        k = layers.apply_rope(cfg, k, positions, rope)
    return q, k, v


def _attend(cfg, q, k, v, mask) -> jnp.ndarray:
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd), mask: (B,Sq,Sk) or (Sq,Sk) bool."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / (hd ** 0.5)
    if mask.ndim == 2:
        mask = mask[None]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H * hd).astype(q.dtype)


def make_mask(cfg, Sq: int, Sk: int, q_offset: int = 0) -> jnp.ndarray:
    """(Sq, Sk) boolean attention mask for self-attention where query i sits
    at absolute position i + q_offset and keys at positions 0..Sk-1."""
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Sk)
    mask = jnp.ones((Sq, Sk), bool)
    if cfg.causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if cfg.sliding_window:
        mask &= kpos[None, :] > qpos[:, None] - cfg.sliding_window
    return mask


CHUNK_THRESHOLD = 2048   # use query-chunked attention above this seq len
CHUNK_BLOCK = 512


def _attend_chunked(cfg, q, k, v, q_offset: int = 0,
                    block: int = CHUNK_BLOCK) -> jnp.ndarray:
    """Query-block-chunked attention: never materializes the (S, S) score
    matrix — per block it is (block, Sk), recomputed in the backward pass
    (jax.checkpoint), the jnp analogue of flash attention.  The Pallas
    kernel in repro.kernels.flash_attention is the TPU hot-path version."""
    B, S, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    assert S % block == 0
    nb = S // block
    qb = jnp.moveaxis(q.reshape(B, nb, block, H, hd), 1, 0)
    kpos = jnp.arange(Sk)

    def body(_, inp):
        qblk, bi = inp                                  # (B, blk, H, hd)
        qpos = bi * block + jnp.arange(block) + q_offset
        mask = jnp.ones((block, Sk), bool)
        if cfg.causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if cfg.sliding_window:
            mask &= kpos[None, :] > qpos[:, None] - cfg.sliding_window
        qg = qblk.reshape(B, block, KV, G, hd)
        scores = jnp.einsum("bqkgh,bskh->bkgqs", qg.astype(jnp.float32),
                            k.astype(jnp.float32)) / (hd ** 0.5)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        ob = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
        return None, ob.reshape(B, block, H, hd).astype(q.dtype)

    _, outs = jax.lax.scan(jax.checkpoint(body), None,
                           (qb, jnp.arange(nb)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H * hd)


SEQ_SHARD_MAX = 8192   # direct seq-sharded attention up to this length
SEQ_SHARD_ENABLED = False   # §Perf C4: refuted, see _attend_auto


def _attend_auto(cfg, q, k, v, q_offset: int = 0) -> jnp.ndarray:
    """Dispatch: chunked for long sequences, direct otherwise.  Applies the
    mesh-aware head-sharding plan (see _head_sharding_plan).

    Three mesh layouts (§Perf C4):
      * heads divisible by the model axis -> shard heads (repeat kv first).
      * heads indivisible, moderate S      -> Ulysses-lite: shard q over the
        sequence dim, keep the (small, GQA) k/v replicated; scores/softmax
        stay fully local and the output reshards back to d-sharded with one
        cheap all-to-all — replaces full fp32 q/k/v all-gathers per layer
        (measured 5.7 TiB/chip/round on deepseek-coder-33b, 56 heads).
      * otherwise                          -> replicated attention.
    """
    repeat_kv, shard_heads = _head_sharding_plan(cfg)
    S = q.shape[1]
    msize = shard_ctx.model_axis_size()
    if repeat_kv and shard_heads:
        G = q.shape[2] // k.shape[2]
        if G > 1:
            k = jnp.repeat(k, G, axis=2)
            v = jnp.repeat(v, G, axis=2)
        q = shard_ctx.constrain(q, "batch", None, "model", None)
        k = shard_ctx.constrain(k, "batch", None, "model", None)
        v = shard_ctx.constrain(v, "batch", None, "model", None)
    elif repeat_kv and SEQ_SHARD_ENABLED and S <= SEQ_SHARD_MAX \
            and S % msize == 0:
        # §Perf C4 — REFUTED and disabled: sharding q over the sequence dim
        # makes GSPMD's partitioner hit "involuntary full rematerialization"
        # on the (B,KV,G,Sq,Sk) score tensor resharding (measured 686 s of
        # collectives vs 246 s for the replicated fallback on
        # deepseek-coder-33b train_4k).  Kept for reference behind the flag.
        q = shard_ctx.constrain(q, "batch", "model", None, None)
        k = shard_ctx.constrain(k, "batch", None, None, None)
        v = shard_ctx.constrain(v, "batch", None, None, None)
        mask = make_mask(cfg, S, k.shape[1], q_offset)
        out = _attend(cfg, q, k, v, mask)
        return shard_ctx.constrain(out, "batch", None, "model")
    elif repeat_kv:
        # replicated fallback for indivisible heads; the barrier keeps the
        # replication all-gather on the bf16 values (GSPMD otherwise sinks
        # the reshard past the fp32 upcast, doubling gather traffic).
        q = shard_ctx.barrier(
            shard_ctx.constrain(q, "batch", None, None, None))
        k = shard_ctx.barrier(
            shard_ctx.constrain(k, "batch", None, None, None))
        v = shard_ctx.barrier(
            shard_ctx.constrain(v, "batch", None, None, None))
    if S > CHUNK_THRESHOLD and S % CHUNK_BLOCK == 0:
        out = _attend_chunked(cfg, q, k, v, q_offset)
    else:
        mask = make_mask(cfg, S, k.shape[1], q_offset)
        out = _attend(cfg, q, k, v, mask)
    return shard_ctx.constrain(out, "batch", None, "model")


def attention_forward(cfg, p: Params, x: jnp.ndarray,
                      positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Full-sequence self attention (train / encoder / prefill compute)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _qkv(cfg, p, x, positions)
    out = _attend_auto(cfg, q, k, v)
    return layers.apply_linear(p["wo"], out)


def attention_kind_forward(cfg, kind, p: Params, x: jnp.ndarray
                           ) -> jnp.ndarray:
    """Causal self attention of one kind of a mixed stack (``kind`` an
    ``AttnKind``: its window and RoPE), training path: the splash kernel
    of ``kernels.ops.attention_window`` or ``attention_full``, which
    computes only the blocks its mask reaches.  Named scope
    ``attn_<kind.name>``."""
    from repro.kernels import ops as kernel_ops
    B, S, _ = x.shape
    with jax.named_scope(f"attn_{kind.name}"):
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        q, k, v = _qkv(cfg, p, x, positions, kind.rope)
        if kind.window:
            out = kernel_ops.attention_window(q, k, v, kind.window)
        else:
            out = kernel_ops.attention_full(q, k, v)
        return layers.apply_linear(p["wo"], out)


# ------------------------------------------------------------- KV cache

def init_kv_cache(cfg, batch: int, cache_len: int, dtype=None,
                  quantize: bool = False):
    """Decode KV cache.  quantize=True stores int8 values with a per-
    (position, head) fp16 scale — decode is memory-bound on every assigned
    arch (EXPERIMENTS.md §Roofline), so halving cache bytes halves the
    dominant roofline term (beyond-paper serving feature, §Perf D)."""
    hd = cfg.resolved_head_dim
    shape = (batch, cache_len, cfg.n_kv_heads, hd)
    if quantize:
        sshape = (batch, cache_len, cfg.n_kv_heads, 1)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float16),
                "v_scale": jnp.zeros(sshape, jnp.float16)}
    dtype = dtype or jnp.dtype(cfg.param_dtype)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_kv(x: jnp.ndarray):
    """x: (..., hd) -> (int8 values, f16 per-vector scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                    keepdims=True) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32)
                           / jnp.maximum(scale, 1e-8)), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float16)


def _dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(dtype)


def cache_len_for(cfg, seq_len: int) -> int:
    """Ring-buffer length: full seq, or the window for SWA models."""
    if cfg.sliding_window and cfg.sliding_window < seq_len:
        return cfg.sliding_window
    return seq_len


def prefill_attention(cfg, p: Params, x: jnp.ndarray, cache: Dict,
                      ) -> Tuple[jnp.ndarray, Dict]:
    """Forward over the prompt AND populate the cache (last cache_len keys)."""
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    q, k, v = _qkv(cfg, p, x, positions)
    out = _attend_auto(cfg, q, k, v)
    C = cache["k"].shape[1]
    quant = "k_scale" in cache
    if C >= S:
        place = lambda buf, val: jax.lax.dynamic_update_slice(
            buf, val.astype(buf.dtype), (0, 0, 0, 0))
    else:
        # ring buffer: keep last C positions; slot i holds position p with
        # p % C == i so that decode-time ring writes stay consistent.
        shift = S % C  # position (S - C) lands at slot (S - C) % C == shift
        place = lambda buf, val: jnp.roll(
            val[:, S - C:], shift, axis=1).astype(buf.dtype)
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache = {"k": place(cache["k"], kq),
                     "v": place(cache["v"], vq),
                     "k_scale": place(cache["k_scale"], ks),
                     "v_scale": place(cache["v_scale"], vs)}
    else:
        new_cache = {"k": place(cache["k"], k), "v": place(cache["v"], v)}
    return layers.apply_linear(p["wo"], out), new_cache


def decode_attention(cfg, p: Params, x: jnp.ndarray, cache: Dict,
                     pos: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """One-token decode. x: (B,1,d); pos: scalar absolute position of the
    new token; cache holds positions < pos (ring for SWA)."""
    B = x.shape[0]
    positions = jnp.broadcast_to(pos[None], (B, 1)) if pos.ndim == 0 else pos
    q, k, v = _qkv(cfg, p, x, positions)
    C = cache["k"].shape[1]
    slot = (pos % C).astype(jnp.int32)
    quant = "k_scale" in cache
    put = lambda buf, val: jax.lax.dynamic_update_slice(
        buf, val.astype(buf.dtype), (0, slot, 0, 0))
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache = {"k": put(cache["k"], kq), "v": put(cache["v"], vq),
                     "k_scale": put(cache["k_scale"], ks),
                     "v_scale": put(cache["v_scale"], vs)}
        new_k = _dequantize_kv(new_cache["k"], new_cache["k_scale"], q.dtype)
        new_v = _dequantize_kv(new_cache["v"], new_cache["v_scale"], q.dtype)
    else:
        new_k = put(cache["k"], k)
        new_v = put(cache["v"], v)
        new_cache = {"k": new_k, "v": new_v}
    # validity: slot j holds absolute position p_j; attend iff p_j <= pos and
    # within window.  For a full cache (C == pos ceiling) p_j = j; for ring,
    # p_j = largest value <= pos with p_j % C == j.
    j = jnp.arange(C)
    pj = pos - ((pos - j) % C)           # absolute position stored in slot j
    valid = (pj >= 0) & (pj <= pos)
    if cfg.sliding_window:
        valid &= pj > pos - cfg.sliding_window
    mask = jnp.broadcast_to(valid[None, None, :], (B, 1, C))
    out = _attend(cfg, q, new_k, new_v, mask)
    return layers.apply_linear(p["wo"], out), new_cache
