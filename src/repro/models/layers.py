"""Shared neural-net building blocks (pure jnp, functional, pytree params)."""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import context as shard_ctx

Params = Dict[str, Any]


def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------- norms

def init_norm(cfg, key, d: int) -> Params:
    p = {"scale": jnp.ones((d,), _dtype(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), _dtype(cfg))
    return p


def apply_norm(cfg, p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    # barrier before the fp32 upcast: prevents XLA from hoisting the convert
    # into the remat residual-stack write, which would store all activation
    # checkpoints in f32 instead of bf16 (2x memory; measured on
    # starcoder2-7b train_4k: 4.8 GiB vs 2.25 GiB per layer stack).
    x = shard_ctx.barrier(x)
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------- linear

def init_linear(cfg, key, d_in: int, d_out: int, scale: float = None) -> Params:
    scale = scale if scale is not None else d_in ** -0.5
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
    return {"w": w.astype(_dtype(cfg))}


def apply_linear(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return x @ p["w"]


# ---------------------------------------------------------------- MLP / GLU

def init_mlp(cfg, key, d: int, d_ff: int) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"up": init_linear(cfg, k1, d, d_ff),
         "down": init_linear(cfg, k2, d_ff, d)}
    if cfg.act in ("silu", "geglu"):
        p["gate"] = init_linear(cfg, k3, d, d_ff)
    return p


def apply_mlp(cfg, p: Params, x: jnp.ndarray) -> jnp.ndarray:
    up = apply_linear(p["up"], x)
    if cfg.act == "silu":
        h = jax.nn.silu(apply_linear(p["gate"], x)) * up
    elif cfg.act == "geglu":
        h = jax.nn.gelu(apply_linear(p["gate"], x)) * up
    else:  # gelu
        h = jax.nn.gelu(up)
    return apply_linear(p["down"], h)


# ---------------------------------------------------------------- RoPE

def rope_freqs(cfg, head_dim: int) -> jnp.ndarray:
    half = head_dim // 2
    return cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def yarn_freqs(rope, head_dim: int) -> np.ndarray:
    """Inverse frequencies (head_dim/2,) of ``rope`` (a ``RopeConfig``):
    plain RoPE, or YaRN as Hugging Face's ``_compute_yarn_parameters``
    (truncated correction range): dimensions that turn more than
    ``beta_fast`` times in ``original_max_position`` positions keep their
    frequency, those under ``beta_slow`` are divided by the factor, and a
    linear ramp blends the ones between."""
    half = head_dim // 2
    pos_freqs = rope.theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                               / head_dim)
    extra = 1.0 / pos_freqs
    if not rope.yarn_factor:
        return extra.astype(np.float32)
    inter = 1.0 / (rope.yarn_factor * pos_freqs)

    def corr(rot):
        return (head_dim * math.log(rope.original_max_position
                                    / (rot * 2 * math.pi))
                / (2 * math.log(rope.theta)))

    low = max(math.floor(corr(rope.beta_fast)), 0)
    high = min(math.ceil(corr(rope.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / (high - low),
                   0.0, 1.0)
    keep = 1.0 - ramp
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def apply_rope(cfg, x: jnp.ndarray, positions: jnp.ndarray,
               rope=None) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq).  ``rope`` (a
    ``RopeConfig``) overrides ``cfg.rope_theta``; its ``attention_factor``
    scales cos and sin."""
    hd = x.shape[-1]
    scale = 1.0
    if rope is None:
        freqs = rope_freqs(cfg, hd)                      # (hd/2,)
    else:
        freqs = jnp.asarray(yarn_freqs(rope, hd))
        scale = rope.attention_factor
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = (jnp.cos(ang) * scale)[..., :, None, :]        # (..., S, 1, hd/2)
    sin = (jnp.sin(ang) * scale)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- embeddings

def init_embed(cfg, key) -> Params:
    w = jax.random.normal(key, (cfg.vocab, cfg.d_model), jnp.float32) * 0.02
    return {"w": w.astype(_dtype(cfg))}


def embed_tokens(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["w"], tokens, axis=0)


def logits_from_hidden(cfg, params, h: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_embeddings:
        return h @ params["embed"]["w"].T
    return apply_linear(params["lm_head"], h)
