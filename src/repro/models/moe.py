"""Mixture-of-Experts FFN: grouped capacity-based scatter dispatch over
every expert (``moe_forward``), and the held-expert layer of one chip's
share of an expert-parallel layer (``moe_held_forward``).

Tokens are grouped by batch row (each sequence is a dispatch group), so the
scatter/gather stays local to the data shard that owns the sequence — no
cross-shard dispatch traffic under pjit.  Expert weights are sharded either
tensor-parallel (d_ff over the model axis; works for any expert count) or
expert-parallel (experts over the model axis; requires divisibility, e.g.
deepseek-moe's 64 experts over 16 shards).

Shared experts (DeepSeekMoE) are ordinary dense GLU FFNs applied to every
token and added to the routed output.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.sharding import context as shard_ctx

Params = Dict[str, Any]


def _glu_arity(cfg) -> int:
    return 3 if cfg.act in ("silu", "geglu") else 2


def init_moe(cfg, key) -> Params:
    """Router over all ``n_experts``; expert weights of the experts this
    chip holds (``n_held``, or all of them)."""
    m = cfg.moe
    d, ff = cfg.d_model, m.expert_d_ff
    n = m.n_held or m.n_experts
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    p: Params = {
        "router": layers.init_linear(cfg, ks[0], d, m.n_experts),
        "w_up": (jax.random.normal(ks[1], (n, d, ff), jnp.float32)
                 * d ** -0.5).astype(dt),
        "w_down": (jax.random.normal(ks[2], (n, ff, d), jnp.float32)
                   * ff ** -0.5).astype(dt),
    }
    if _glu_arity(cfg) == 3:
        p["w_gate"] = (jax.random.normal(ks[3], (n, d, ff), jnp.float32)
                       * d ** -0.5).astype(dt)
    if m.n_shared_experts:
        p["shared"] = layers.init_mlp(
            cfg, ks[4], d, m.n_shared_experts * m.shared_d_ff)
    return p


def capacity(cfg, tokens_per_group: int) -> int:
    m = cfg.moe
    c = int(tokens_per_group * m.top_k * m.capacity_factor / m.n_experts)
    return max(c, m.top_k)


def _expert_ffn(cfg, p: Params, xs: jnp.ndarray) -> jnp.ndarray:
    """xs: (..., E, C, d) expert input buffers -> same shape."""
    up = jnp.einsum("...ecd,edf->...ecf", xs, p["w_up"])
    if cfg.act == "silu":
        h = jax.nn.silu(jnp.einsum("...ecd,edf->...ecf", xs, p["w_gate"])) * up
    elif cfg.act == "geglu":
        h = jax.nn.gelu(jnp.einsum("...ecd,edf->...ecf", xs, p["w_gate"])) * up
    else:
        h = jax.nn.gelu(up)
    return jnp.einsum("...ecf,efd->...ecd", h, p["w_down"])


def moe_forward(cfg, p: Params, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d).  Groups = batch rows.  Returns (out, aux_loss)."""
    m = cfg.moe
    B, S, d = x.shape
    E, K = m.n_experts, m.top_k
    C = capacity(cfg, S)

    logits = layers.apply_linear(p["router"], x).astype(jnp.float32)  # (B,S,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)                   # (B,S,K)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # position of each (token, k) assignment within its expert's buffer
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)           # (B,S,K,E)
    flat = onehot.reshape(B, S * K, E)
    pos = jnp.cumsum(flat, axis=1) * flat - 1                          # (B,S*K,E)
    pos_in_expert = jnp.max(pos, axis=-1).reshape(B, S, K)             # (B,S,K)
    fits = pos_in_expert < C

    # scatter tokens into (B, E, C, d) buffers.  Group dim = batch row, so
    # every scatter/gather is local to the data shard that owns the
    # sequence; the explicit constraints stop GSPMD from replicating the
    # dispatch buffers (measured 43 GiB/device on mixtral prefill_32k).
    e_ax = "model" if cfg.moe.sharding == "expert" else None
    xt = x[:, :, None, :] * fits[..., None].astype(x.dtype)            # (B,S,K,d)
    clipped = jnp.clip(pos_in_expert, 0, C - 1)

    # vmap over the batch row: lowers to gather/scatter with explicit
    # operand-batching dims, which GSPMD partitions along 'batch' instead
    # of replicating (the fancy-index form replicated the (B,S,K,d)
    # cotangents in the backward pass — measured +20 GiB/device).
    def dispatch_one(xt_b, ei_b, cl_b):
        buf_b = jnp.zeros((E, C, d), x.dtype)
        return buf_b.at[ei_b, cl_b].add(xt_b, mode="drop")

    buf = jax.vmap(dispatch_one)(xt, expert_idx, clipped)              # (B,E,C,d)
    buf = shard_ctx.constrain(buf, "batch", e_ax, None, None)

    out_buf = _expert_ffn(cfg, p, buf)                                 # (B,E,C,d)
    out_buf = shard_ctx.constrain(out_buf, "batch", e_ax, None, None)

    # gather back + combine with gates
    gathered = jax.vmap(lambda ob, ei, cl: ob[ei, cl])(
        out_buf, expert_idx, clipped)                                  # (B,S,K,d)
    gathered = gathered * (gate_vals * fits.astype(jnp.float32)
                           )[..., None].astype(x.dtype)
    out = jnp.sum(gathered, axis=2)
    out = shard_ctx.constrain(out, "batch", None, None)

    if m.n_shared_experts:
        out = out + layers.apply_mlp(cfg, p["shared"], x)

    # GShard load-balance auxiliary loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(expert_idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = m.router_aux_weight * E * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def held_rows(cfg, tokens: int) -> int:
    """Rows of the held layer's sorted buffer for ``tokens`` tokens: every
    (token, expert) assignment that can land on a held expert (a token
    reaches at most min(top_k, n_held) of them), rounded up to the grouped
    kernel's row tile."""
    from repro.kernels import moe_gmm
    m = cfg.moe
    rows = tokens * min(m.top_k, m.n_held or m.n_experts)
    tile = moe_gmm.MAX_ROW_TILE if rows >= moe_gmm.MAX_ROW_TILE \
        else moe_gmm.LANES
    return -(-rows // tile) * tile


def moe_held_forward(cfg, p: Params, x: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """This chip's part of an expert-parallel MoE layer.  x: (B, S, d).

    The router scores all ``n_experts`` (softmax), each token takes its
    ``top_k`` and renormalises their probabilities; of those assignments,
    the ones to the held experts [held_offset, held_offset + n_held) are
    sorted by expert and run through the grouped FFN kernel
    (``kernels.ops.moe_grouped_ffn``), and each token gets the gated sum
    of its held experts' outputs.  The absent experts' part is left out.
    Dropless: every held assignment is computed.

    Returns (out, stats): ``moe_load`` (n_held,) int32, the rows computed
    per held expert, and ``moe_dropped`` () int32, held assignments left
    uncomputed.  Named scopes ``moe_route`` and ``moe_experts``."""
    from repro.kernels import ops as kernel_ops
    m = cfg.moe
    B, S, d = x.shape
    T, E, K = B * S, m.n_experts, m.top_k
    n = m.n_held or E
    xf = x.reshape(T, d)
    with jax.named_scope("moe_route"):
        logits = layers.apply_linear(p["router"], xf).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, K)                       # (T, K)
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        local = idx - m.held_offset
        e = jnp.where((local >= 0) & (local < n), local, n).reshape(-1)
        routed = jnp.sum(e < n)
        sizes = jnp.bincount(e, length=n + 1)[:n].astype(jnp.int32)
        rows = held_rows(cfg, T)
        order = jnp.argsort(e, stable=True)                       # held first
        if rows > T * K:       # tile padding: rows past every assignment
            order = jnp.pad(order, (0, rows - T * K),
                            constant_values=T * K)
        sel = order[:rows]
        xs = jnp.take(xf, sel // K, axis=0, mode="clip")          # (rows, d)
        # where each assignment sits in the sorted rows (past them: unheld)
        pos = jnp.zeros((T * K,), jnp.int32).at[sel].set(
            jnp.arange(rows, dtype=jnp.int32), mode="drop")
        w = jnp.where(e < n, gate.reshape(-1), 0.0).reshape(T, K)
    with jax.named_scope("moe_experts"):
        ys = kernel_ops.moe_grouped_ffn(xs, p["w_gate"], p["w_up"],
                                        p["w_down"], sizes)       # (rows, d)
        y = jnp.take(ys, pos.reshape(T, K), axis=0)               # (T, K, d)
        out = jnp.einsum("tk,tkd->td", w, y.astype(jnp.float32))
    stats = {"moe_load": sizes,
             "moe_dropped": (routed - jnp.sum(sizes)).astype(jnp.int32)}
    return out.astype(x.dtype).reshape(B, S, d), stats
