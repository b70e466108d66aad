"""Plain reference of ``mellum2-12b-a2.5b`` (``mellum2-12b-a2.5b.json``):
the forward pass and loss of one chip's share of Mellum2-12B-A2.5B, in
float32 ``jax.numpy``, written from the published config and importing
nothing of the program under test.  Run it under
``jax.default_matmul_precision("highest")``: on a TPU a float32 matrix
product is otherwise computed in bfloat16.

Per layer l, of kind ``layer_types[l]``, on one sequence x (S, d):

    a = rmsnorm(x) ;  q, k, v = a Wq, a Wk, a Wv   (32 query, 4 KV heads)
    q, k = rope(q), rope(k)          default, or YaRN on full layers
    s_h = q_h k_g^T / sqrt(128)      g = the query head's KV group
    mask: key j <= query i, and i - j < sliding_window on window layers
    x = x + softmax(s_h masked) v_g Wo
    m = rmsnorm(x) ;  p = softmax(m Wr) over all 64 experts
    top-8 of p, renormalised to sum 1 (norm_topk_prob)
    x = x + sum over held experts e of  gate_e * (silu(m Wg_e) * m Wu_e) Wd_e

then logits = rmsnorm(x) W_head over the vocabulary slice and the mean
cross-entropy of the next token.  YaRN follows Hugging Face's
``_compute_yarn_parameters`` (truncated correction range; cos and sin
times ``attention_factor``).

Departures, each one of computing order only:
- attention runs one block of queries at a time (``q_block``), each
  against every key it could reach (on a window layer, the keys of its
  own block and of the window before it) with the explicit mask, so that
  8192-token sequences fit; the loss runs one sequence at a time;
- each held expert is applied to every token and weighted by the token's
  renormalised gate for it, which is 0 for a token not routed to it:
  the same sum as over the routed tokens, with no capacity;
- ``dtype`` below float32 rounds every matrix product's operands to it
  (straight through in the backward pass): the control run.

Params come in the layout ``layers`` (a list of per-layer dicts with keys
wq, wk, wv, wo, attn_norm, moe_norm, router, w_gate, w_up, w_down),
``embed``, ``lm_head``, ``final_norm``; ``init_params`` draws them from a
seed.
"""
from __future__ import annotations

import math

EPS_KEY = "rms_norm_eps"


def _round(x, dtype):
    import jax
    import jax.numpy as jnp
    if dtype is None or jnp.dtype(dtype) == jnp.float32:
        return x
    return x + jax.lax.stop_gradient(x.astype(dtype).astype(jnp.float32) - x)


def _mm(a, b, dtype=None):
    return _round(a, dtype) @ _round(b, dtype)


def rmsnorm(x, scale, eps):
    import jax.numpy as jnp
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def inv_freq(rope: dict, head_dim: int):
    """(head_dim/2,) inverse frequencies and the cos/sin factor of one
    ``rope_parameters`` entry."""
    import numpy as np
    base = float(rope["rope_theta"])
    dims = np.arange(0, head_dim, 2, dtype=np.float64) / head_dim
    extra = 1.0 / base ** dims
    if rope.get("rope_type", "default") == "default":
        return extra, 1.0
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(rotations):   # the dimension that turns `rotations` times
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    freq = (extra / factor) * ramp + extra * (1.0 - ramp)
    return freq, float(rope["attention_factor"])


def rope(x, freq, factor):
    """x: (S, heads, hd); rotate-half RoPE at positions 0..S-1."""
    import jax.numpy as jnp
    S = x.shape[0]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(
        freq, jnp.float32)[None]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg, kind, p, a, dtype=None, q_block=512):
    import jax
    import jax.numpy as jnp
    S = a.shape[0]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    freq, factor = inv_freq(cfg["rope_parameters"][kind], hd)
    q = rope(_mm(a, p["wq"], dtype).reshape(S, H, hd), freq, factor)
    k = rope(_mm(a, p["wk"], dtype).reshape(S, KV, hd), freq, factor)
    v = _mm(a, p["wv"], dtype).reshape(S, KV, hd)
    rep = H // KV
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    window = cfg["sliding_window"] if kind == "sliding_attention" else 0
    qb = min(q_block, S)
    assert S % qb == 0
    # keys a query block can reach: all of them, or on a window layer the
    # block's own and the window before it (the mask decides within)
    span = S if not window else min(S, qb + -(-(window - 1) // qb) * qb)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)        # (qb, H, hd)
        k0 = jnp.clip(i * qb + qb - span, 0, S - span)
        ki = jax.lax.dynamic_slice_in_dim(k, k0, span)
        vi = jax.lax.dynamic_slice_in_dim(v, k0, span)
        qpos = i * qb + jnp.arange(qb)
        kpos = k0 + jnp.arange(span)
        mask = kpos[None, :] <= qpos[:, None]
        if window:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.einsum("qhd,khd->hqk", _round(qi, dtype), _round(ki, dtype)) \
            / math.sqrt(hd)
        s = jnp.where(mask[None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round(w, dtype), _round(vi, dtype))

    out = jax.lax.map(block, jnp.arange(S // qb))                # (n, qb, H, hd)
    return _mm(out.reshape(S, H * hd), p["wo"], dtype)


def experts(cfg, p, m, dtype=None):
    """This chip's held experts' part of the MoE output for m: (T, d)."""
    import jax
    import jax.numpy as jnp
    probs = jax.nn.softmax(_mm(m, p["router"], dtype), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    out = jnp.zeros_like(m)
    first = cfg["experts_held_offset"]
    for e in range(cfg["num_experts"]):
        gate = jnp.sum(jnp.where(idx == first + e, top, 0.0), axis=-1)
        h = jax.nn.silu(_mm(m, p["w_gate"][e], dtype)) \
            * _mm(m, p["w_up"][e], dtype)
        out = out + gate[:, None] * _mm(h, p["w_down"][e], dtype)
    return out


def sequence_loss(cfg, params, tokens, labels, dtype=None):
    """Summed next-token cross-entropy of one sequence (S,) over the
    vocabulary slice."""
    import jax
    import jax.numpy as jnp
    eps = cfg[EPS_KEY]
    x = params["embed"][tokens]
    for kind, p in zip(cfg["layer_types"], params["layers"]):
        def layer(x, p=p, kind=kind):
            x = x + attention(cfg, kind, p,
                              rmsnorm(x, p["attn_norm"], eps), dtype)
            return x + experts(cfg, p, rmsnorm(x, p["moe_norm"], eps), dtype)
        x = jax.checkpoint(layer)(x)
    logits = _mm(rmsnorm(x, params["final_norm"], eps), params["lm_head"],
                 dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1))


def loss(cfg, params, tokens, labels, dtype=None):
    """Mean next-token cross-entropy of a batch (b, S), one sequence at a
    time."""
    import jax
    import jax.numpy as jnp

    def one(carry, tl):
        return carry + sequence_loss(cfg, params, tl[0], tl[1], dtype), None

    total, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros((), jnp.float32),
                            (tokens, labels))
    return total / tokens.size


def init_params(cfg, seed) -> dict:
    """Seeded weights in this file's layout, float32 numpy arrays drawn
    from ``numpy.random.default_rng(seed)`` in a fixed order: each matrix
    normal over the square root of its fan-in (the embedding 0.02), the
    norms' scales 1."""
    import numpy as np
    rng = np.random.default_rng(seed)
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    n, ff = cfg["num_experts"], cfg["moe_intermediate_size"]

    def normal(shape, scale):
        return rng.standard_normal(shape, np.float32) * np.float32(scale)

    layers = []
    for _ in cfg["layer_types"]:
        layers.append({
            "wq": normal((d, H * hd), d ** -0.5),
            "wk": normal((d, KV * hd), d ** -0.5),
            "wv": normal((d, KV * hd), d ** -0.5),
            "wo": normal((H * hd, d), (H * hd) ** -0.5),
            "attn_norm": np.ones((d,), np.float32),
            "moe_norm": np.ones((d,), np.float32),
            "router": normal((d, cfg["num_experts_router"]), d ** -0.5),
            "w_gate": normal((n, d, ff), d ** -0.5),
            "w_up": normal((n, d, ff), d ** -0.5),
            "w_down": normal((n, ff, d), ff ** -0.5)})
    return {"layers": layers, "embed": normal((V, d), 0.02),
            "lm_head": normal((d, V), d ** -0.5),
            "final_norm": np.ones((d,), np.float32)}
