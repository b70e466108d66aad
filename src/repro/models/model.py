"""Model assembly: init / train-forward / prefill / decode for every
assigned architecture family.

Layer stacks are `lax.scan`-ed over stacked per-layer parameters so the
lowered HLO is O(1) in depth (critical for the 512-device dry-run).  Three
stack topologies:

  * homogeneous  — dense / moe / encoder / audio / vlm: one scan.
  * hybrid       — zamba2: outer scan over super-groups, inner scan over
                   `shared_attn_every` Mamba2 blocks, then ONE shared-
                   parameter attention block applied per super-group.
  * xlstm        — outer scan over super-groups of (slstm_every-1) mLSTM
                   blocks + 1 sLSTM block.
  * period       — moe with mixed attention (``cfg.attn_period``): outer
                   scan over super-groups, one per period, each an inner
                   scan over every run of one attention kind (e.g. three
                   window layers, then one full layer) of held-expert MoE
                   blocks.  Training path only: prefill and decode raise.

All functions are pure; `cfg` is static.  Dtype: params in
``cfg.param_dtype``, softmax/normalizers/recurrences in fp32.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, ENCODER, MAMBA2, MLSTM, MOE,
                                SHARED_ATTN, SLSTM, ArchConfig)
from repro.models import attention, layers, moe, ssm, xlstm
from repro.sharding import context as shard_ctx

Params = Dict[str, Any]


# =================================================================== init

def _init_block(cfg: ArchConfig, kind: str, key) -> Params:
    ks = jax.random.split(key, 4)
    if kind in (ATTN, ENCODER, SHARED_ATTN):
        p = {"attn_norm": layers.init_norm(cfg, ks[0], cfg.d_model),
             "attn": attention.init_attention(cfg, ks[1])}
        if cfg.d_ff:
            p["mlp_norm"] = layers.init_norm(cfg, ks[2], cfg.d_model)
            p["mlp"] = layers.init_mlp(cfg, ks[3], cfg.d_model, cfg.d_ff)
        return p
    if kind == MOE:
        return {"attn_norm": layers.init_norm(cfg, ks[0], cfg.d_model),
                "attn": attention.init_attention(cfg, ks[1]),
                "moe_norm": layers.init_norm(cfg, ks[2], cfg.d_model),
                "moe": moe.init_moe(cfg, ks[3])}
    if kind == MAMBA2:
        return {"norm": layers.init_norm(cfg, ks[0], cfg.d_model),
                "mamba": ssm.init_mamba2(cfg, ks[1])}
    if kind == MLSTM:
        return {"norm": layers.init_norm(cfg, ks[0], cfg.d_model),
                "mlstm": xlstm.init_mlstm(cfg, ks[1])}
    if kind == SLSTM:
        return {"norm": layers.init_norm(cfg, ks[0], cfg.d_model),
                "slstm": xlstm.init_slstm(cfg, ks[1])}
    raise ValueError(kind)


def _stack_init(cfg, kind, key, n: int) -> Params:
    return jax.vmap(lambda k: _init_block(cfg, kind, k))(jax.random.split(key, n))


def topology(cfg: ArchConfig) -> str:
    if cfg.attn_period:
        return "period"
    if cfg.family == "hybrid":
        return "hybrid"
    if cfg.xlstm is not None:
        return "xlstm"
    return "homo"


def homo_kind(cfg: ArchConfig) -> str:
    if cfg.family == "moe":
        return MOE
    if cfg.family in ("encoder", "audio"):
        return ENCODER
    return ATTN


def init_params(cfg: ArchConfig, key) -> Params:
    k_emb, k_body, k_fn, k_head = jax.random.split(key, 4)
    params: Params = {"final_norm": layers.init_norm(cfg, k_fn, cfg.d_model)}
    params["embed"] = layers.init_embed(cfg, k_emb)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.init_linear(cfg, k_head, cfg.d_model, cfg.vocab)
    topo = topology(cfg)
    if topo == "homo":
        params["layers"] = _stack_init(cfg, homo_kind(cfg), k_body, cfg.n_layers)
    elif topo == "period":
        # one stacked (G, run length, ...) tree per run of the period
        G = cfg.n_super_groups()
        params["period"] = tuple(
            jax.vmap(lambda k, n=n: _stack_init(cfg, MOE, k, n))(
                jax.random.split(kr, G))
            for kr, (_, n) in zip(jax.random.split(k_body, len(cfg.attn_runs())),
                                  cfg.attn_runs()))
    elif topo == "hybrid":
        G = cfg.n_super_groups()
        g = cfg.shared_attn_every
        km, ks_ = jax.random.split(k_body)
        params["mamba"] = jax.vmap(
            lambda k: _stack_init(cfg, MAMBA2, k, g))(jax.random.split(km, G))
        params["shared"] = _init_block(cfg, SHARED_ATTN, ks_)
    else:  # xlstm
        G = cfg.n_super_groups()
        m = cfg.xlstm.slstm_every - 1
        km, ks_ = jax.random.split(k_body)
        params["mlstm"] = jax.vmap(
            lambda k: _stack_init(cfg, MLSTM, k, m))(jax.random.split(km, G))
        params["slstm"] = jax.vmap(
            lambda k: _init_block(cfg, SLSTM, k))(jax.random.split(ks_, G))
    return params


# =================================================================== blocks

def _apply_block(cfg, kind: str, p: Params, x: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence block application.  Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in (ATTN, ENCODER, SHARED_ATTN):
        x = x + attention.attention_forward(
            cfg, p["attn"], layers.apply_norm(cfg, p["attn_norm"], x))
        if cfg.d_ff:
            x = x + layers.apply_mlp(
                cfg, p["mlp"], layers.apply_norm(cfg, p["mlp_norm"], x))
    elif kind == MOE:
        x = x + attention.attention_forward(
            cfg, p["attn"], layers.apply_norm(cfg, p["attn_norm"], x))
        y, aux = moe.moe_forward(
            cfg, p["moe"], layers.apply_norm(cfg, p["moe_norm"], x))
        x = x + y
    elif kind == MAMBA2:
        x = x + ssm.mamba2_forward(
            cfg, p["mamba"], layers.apply_norm(cfg, p["norm"], x))
    elif kind == MLSTM:
        x = x + xlstm.mlstm_forward(
            cfg, p["mlstm"], layers.apply_norm(cfg, p["norm"], x))
    elif kind == SLSTM:
        x = x + xlstm.slstm_forward(
            cfg, p["slstm"], layers.apply_norm(cfg, p["norm"], x))
    else:
        raise ValueError(kind)
    return x, aux


def _scan_blocks(cfg, kind: str, stacked: Params, x: jnp.ndarray,
                 remat: bool, remat_group: int = 1
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scan the homogeneous block stack.  With remat, the residual stream is
    checkpointed every `remat_group` layers (the inner scan is recomputed in
    the backward pass), dividing activation-checkpoint memory by the group
    size at the cost of one extra forward per group."""
    def body(carry, lp):
        h, aux = carry
        h, a = _apply_block(cfg, kind, lp, h)
        # the returned carry is exactly what the remat machinery saves per
        # layer: shard it over 'model' too (sequence-parallel-style) so the
        # residual-checkpoint stack costs HBM/model_parallel instead of a
        # full copy; the backward pass all-gathers one layer at a time.
        h = shard_ctx.constrain(h, "batch", None, "model")
        h = shard_ctx.barrier(h)
        return (h, aux + a), None

    L = jax.tree.leaves(stacked)[0].shape[0]
    zero = jnp.zeros((), jnp.float32)
    if remat and remat_group > 1 and L % remat_group == 0:
        grouped = jax.tree.map(
            lambda p: p.reshape((L // remat_group, remat_group) + p.shape[1:]),
            stacked)

        @jax.checkpoint
        def outer(carry, gp):
            return jax.lax.scan(body, carry, gp)

        (x, aux), _ = jax.lax.scan(outer, (x, zero), grouped)
        return x, aux
    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(body, (x, zero), stacked)
    return x, aux


def _apply_kind_block(cfg, kind, p: Params, x: jnp.ndarray):
    """One block of a mixed-attention stack: attention of ``kind`` (an
    ``AttnKind``), then the held-expert MoE.  Returns (x, MoE stats)."""
    x = x + attention.attention_kind_forward(
        cfg, kind, p["attn"], layers.apply_norm(cfg, p["attn_norm"], x))
    y, stats = moe.moe_held_forward(
        cfg, p["moe"], layers.apply_norm(cfg, p["moe_norm"], x))
    return x + y, stats


def _scan_period(cfg, runs: Tuple[Params, ...], x: jnp.ndarray,
                 remat: bool) -> Tuple[jnp.ndarray, Dict]:
    """Scan the super-groups of a mixed-attention stack; each layer is
    checkpointed when ``remat``.  Returns (x, stats stacked per layer in
    depth order: leading axis n_layers)."""
    def layer(kind):
        def body(h, lp):
            h, st = _apply_kind_block(cfg, kind, lp, h)
            h = shard_ctx.constrain(h, "batch", None, "model")
            return shard_ctx.barrier(h), st
        return jax.checkpoint(body) if remat else body

    def super_body(h, group):
        sts = []
        for (kind, _), run in zip(cfg.attn_runs(), group):
            h, st = jax.lax.scan(layer(kind), h, run)
            sts.append(st)
        return h, jax.tree.map(lambda *a: jnp.concatenate(a), *sts)

    x, stats = jax.lax.scan(super_body, x, runs)
    return x, jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), stats)


# =================================================================== forward

def backbone(cfg: ArchConfig, params: Params, h: jnp.ndarray,
             remat: bool = False, remat_group: int = 1
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply the full layer stack. h: (B, S, d) -> (B, S, d), aux loss."""
    h, aux, _ = _backbone(cfg, params, h, remat, remat_group)
    return h, aux


def _backbone(cfg: ArchConfig, params: Params, h: jnp.ndarray,
              remat: bool, remat_group: int
              ) -> Tuple[jnp.ndarray, jnp.ndarray, Dict]:
    """``backbone`` and the per-layer stats of the held-expert layers
    (``moe_held_forward``; empty for the other topologies)."""
    topo = topology(cfg)
    aux0 = jnp.zeros((), jnp.float32)
    if topo == "period":
        h, stats = _scan_period(cfg, params["period"], h, remat)
        return h, aux0, stats
    if topo == "homo":
        h, aux = _scan_blocks(cfg, homo_kind(cfg), params["layers"], h, remat,
                              remat_group)
    elif topo == "hybrid":
        shared = params["shared"]

        def super_body(carry, mamba_group):
            hh, aux = carry
            hh, a1 = _scan_blocks(cfg, MAMBA2, mamba_group, hh, remat)
            hh, a2 = _apply_block(cfg, SHARED_ATTN, shared, hh)
            hh = shard_ctx.constrain(hh, "batch", None, "model")
            return (hh, aux + a1 + a2), None

        if remat:
            super_body = jax.checkpoint(super_body)
        (h, aux), _ = jax.lax.scan(super_body, (h, aux0), params["mamba"])
    else:  # xlstm
        def super_body(carry, grp):
            hh, aux = carry
            mparams, sparams = grp
            hh, a1 = _scan_blocks(cfg, MLSTM, mparams, hh, remat)
            hh, a2 = _apply_block(cfg, SLSTM, sparams, hh)
            hh = shard_ctx.constrain(hh, "batch", None, "model")
            return (hh, aux + a1 + a2), None

        if remat:
            super_body = jax.checkpoint(super_body)
        (h, aux), _ = jax.lax.scan(
            super_body, (h, aux0), (params["mlstm"], params["slstm"]))
    return h, aux, {}


def embed_inputs(cfg: ArchConfig, params: Params, batch: Dict) -> jnp.ndarray:
    """Token + modality-stub embedding.  batch keys: tokens (B,S) int32 and
    (for audio/vlm) frontend (B,F,d) precomputed embeddings."""
    if cfg.family == "audio" or cfg.frontend_positions == -1:
        return batch["frontend"].astype(jnp.dtype(cfg.param_dtype))
    h = layers.embed_tokens(params["embed"], batch["tokens"])
    if cfg.frontend_positions > 0 and "frontend" in batch:
        fe = batch["frontend"].astype(h.dtype)
        h = jax.lax.dynamic_update_slice(h, fe, (0, 0, 0))
    if cfg.name.startswith("gemma"):
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def forward(cfg: ArchConfig, params: Params, batch: Dict,
            remat: bool = False, remat_group: int = 1
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence forward -> (logits (B,S,V), aux_loss)."""
    logits, aux, _ = _forward(cfg, params, batch, remat, remat_group)
    return logits, aux


def _forward(cfg, params, batch, remat, remat_group):
    h = embed_inputs(cfg, params, batch)
    h, aux, stats = _backbone(cfg, params, h, remat, remat_group)
    h = layers.apply_norm(cfg, params["final_norm"], h)
    return layers.logits_from_hidden(cfg, params, h), aux, stats


def loss_fn(cfg: ArchConfig, params: Params, batch: Dict,
            remat: bool = False, remat_group: int = 1) -> jnp.ndarray:
    """Mean cross-entropy (+ MoE aux).  labels: (B,S) int32, -1 = ignore."""
    return loss_and_stats(cfg, params, batch, remat, remat_group)[0]


def loss_and_stats(cfg: ArchConfig, params: Params, batch: Dict,
                   remat: bool = False, remat_group: int = 1
                   ) -> Tuple[jnp.ndarray, Dict]:
    """``loss_fn`` and the per-layer stats of the held-expert layers:
    ``moe_load`` (n_layers, n_held) rows computed per held expert and
    ``moe_dropped`` (n_layers,) held assignments left uncomputed (empty
    for a stack without them)."""
    logits, aux, stats = _forward(cfg, params, batch, remat, remat_group)
    labels = batch["labels"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                             axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    ce = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return ce + aux, stats


# =================================================================== serving

def _training_only(cfg: ArchConfig) -> None:
    if topology(cfg) == "period":
        raise NotImplementedError(
            f"{cfg.name}: a mixed-attention stack (attn_period) runs the "
            f"training path only; prefill, decode and its cache are not "
            f"implemented")


def init_cache(cfg: ArchConfig, batch: int, seq_len: int,
               quantize_kv: bool = False) -> Dict:
    """Decode cache for a maximum context of `seq_len` tokens.
    quantize_kv stores int8 values + f16 scales (halves cache HBM; decode
    is memory-bound on every assigned arch — EXPERIMENTS.md §Perf D)."""
    assert cfg.supports_decode, f"{cfg.name} is encoder-only"
    _training_only(cfg)
    dt = jnp.dtype(cfg.param_dtype)
    C = attention.cache_len_for(cfg, seq_len)
    topo = topology(cfg)
    cache: Dict[str, Any] = {"pos": jnp.zeros((), jnp.int32)}
    if topo == "homo":
        kv = jax.vmap(lambda _: attention.init_kv_cache(
            cfg, batch, C, dt, quantize=quantize_kv))(jnp.arange(cfg.n_layers))
        cache["kv"] = kv
    elif topo == "hybrid":
        G, g = cfg.n_super_groups(), cfg.shared_attn_every
        cache["ssm"] = jax.vmap(jax.vmap(
            lambda _: ssm.init_mamba_state(cfg, batch)))(
            jnp.zeros((G, g)))
        cache["kv"] = jax.vmap(
            lambda _: attention.init_kv_cache(
                cfg, batch, C, dt, quantize=quantize_kv))(jnp.arange(G))
    else:  # xlstm
        G, m = cfg.n_super_groups(), cfg.xlstm.slstm_every - 1
        cache["mlstm"] = jax.vmap(jax.vmap(
            lambda _: xlstm.init_mlstm_state(cfg, batch)))(jnp.zeros((G, m)))
        cache["slstm"] = jax.vmap(
            lambda _: xlstm.init_slstm_state(cfg, batch))(jnp.zeros(G))
    return cache


def _decode_block(cfg, kind, p, x, block_cache):
    """One-token block step -> (x, new_block_cache)."""
    if kind in (ATTN, ENCODER, SHARED_ATTN, MOE):
        xn = layers.apply_norm(cfg, p["attn_norm"], x)
        y, kv = attention.decode_attention(cfg, p["attn"], xn,
                                           block_cache["kv"], block_cache["pos"])
        x = x + y
        if kind == MOE:
            y, _ = moe.moe_forward(
                cfg, p["moe"], layers.apply_norm(cfg, p["moe_norm"], x))
            x = x + y
        elif cfg.d_ff:
            x = x + layers.apply_mlp(
                cfg, p["mlp"], layers.apply_norm(cfg, p["mlp_norm"], x))
        return x, {"kv": kv}
    if kind == MAMBA2:
        y, st = ssm.mamba2_decode(
            cfg, p["mamba"], layers.apply_norm(cfg, p["norm"], x),
            block_cache["ssm"])
        return x + y, {"ssm": st}
    if kind == MLSTM:
        y, st = xlstm.mlstm_decode(
            cfg, p["mlstm"], layers.apply_norm(cfg, p["norm"], x),
            block_cache["mlstm"])
        return x + y, {"mlstm": st}
    if kind == SLSTM:
        y, st = xlstm.slstm_decode(
            cfg, p["slstm"], layers.apply_norm(cfg, p["norm"], x),
            block_cache["slstm"])
        return x + y, {"slstm": st}
    raise ValueError(kind)


def decode_step(cfg: ArchConfig, params: Params, cache: Dict,
                tokens: jnp.ndarray) -> Tuple[jnp.ndarray, Dict]:
    """One decode step. tokens: (B, 1) int32 -> logits (B, V), new cache."""
    _training_only(cfg)
    pos = cache["pos"]
    h = layers.embed_tokens(params["embed"], tokens)
    if cfg.name.startswith("gemma"):
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    topo = topology(cfg)
    new_cache: Dict[str, Any] = {"pos": pos + 1}
    if topo == "homo":
        kind = homo_kind(cfg)

        def body(hh, inp):
            lp, kv = inp
            hh, bc = _decode_block(cfg, kind, lp, hh, {"kv": kv, "pos": pos})
            return hh, bc["kv"]

        h, kv = jax.lax.scan(body, h, (params["layers"], cache["kv"]))
        new_cache["kv"] = kv
    elif topo == "hybrid":
        shared = params["shared"]

        def super_body(hh, inp):
            mamba_group, sstates, kv = inp

            def inner(hh2, inp2):
                lp, st = inp2
                hh2, bc = _decode_block(cfg, MAMBA2, lp, hh2, {"ssm": st})
                return hh2, bc["ssm"]

            hh, new_ss = jax.lax.scan(inner, hh, (mamba_group, sstates))
            hh, bc = _decode_block(cfg, SHARED_ATTN, shared, hh,
                                   {"kv": kv, "pos": pos})
            return hh, (new_ss, bc["kv"])

        h, (ssm_st, kv) = jax.lax.scan(
            super_body, h, (params["mamba"], cache["ssm"], cache["kv"]))
        new_cache["ssm"], new_cache["kv"] = ssm_st, kv
    else:  # xlstm
        def super_body(hh, inp):
            mparams, sparams, mstates, sstate = inp

            def inner(hh2, inp2):
                lp, st = inp2
                hh2, bc = _decode_block(cfg, MLSTM, lp, hh2, {"mlstm": st})
                return hh2, bc["mlstm"]

            hh, new_m = jax.lax.scan(inner, hh, (mparams, mstates))
            hh, bc = _decode_block(cfg, SLSTM, sparams, hh, {"slstm": sstate})
            return hh, (new_m, bc["slstm"])

        h, (mst, sst) = jax.lax.scan(
            super_body, h,
            (params["mlstm"], params["slstm"], cache["mlstm"], cache["slstm"]))
        new_cache["mlstm"], new_cache["slstm"] = mst, sst
    h = layers.apply_norm(cfg, params["final_norm"], h)
    logits = layers.logits_from_hidden(cfg, params, h)[:, 0]
    return logits, new_cache


def prefill(cfg: ArchConfig, params: Params, batch: Dict,
            cache_len: int = 0, quantize_kv: bool = False
            ) -> Tuple[jnp.ndarray, Dict]:
    """Prompt processing: returns last-position logits (B, V) and a cache
    positioned at S, ready for decode_step.  cache_len (>= prompt length)
    reserves headroom for generated tokens; 0 = exactly the prompt (the
    dry-run decode shapes supply their own cache)."""
    _training_only(cfg)
    h = embed_inputs(cfg, params, batch)
    B, S, _ = h.shape
    if not cfg.supports_decode:
        h, _ = backbone(cfg, params, h)
        h = layers.apply_norm(cfg, params["final_norm"], h)
        return layers.logits_from_hidden(cfg, params, h[:, -1]), {}
    topo = topology(cfg)
    cache: Dict[str, Any] = {"pos": jnp.asarray(S, jnp.int32)}
    C = attention.cache_len_for(cfg, max(cache_len, S))

    def attn_prefill(p, hh, kv0):
        xn = layers.apply_norm(cfg, p["attn_norm"], hh)
        y, kv = attention.prefill_attention(cfg, p["attn"], xn, kv0)
        hh = hh + y
        return hh, kv

    if topo == "homo":
        kind = homo_kind(cfg)
        kv0 = attention.init_kv_cache(cfg, B, C, quantize=quantize_kv)

        def body(hh, lp):
            hh, kv = attn_prefill(lp, hh, kv0)
            if kind == MOE:
                y, _ = moe.moe_forward(
                    cfg, lp["moe"], layers.apply_norm(cfg, lp["moe_norm"], hh))
                hh = hh + y
            elif cfg.d_ff:
                hh = hh + layers.apply_mlp(
                    cfg, lp["mlp"], layers.apply_norm(cfg, lp["mlp_norm"], hh))
            return hh, kv

        h, kv = jax.lax.scan(body, h, params["layers"])
        cache["kv"] = kv
    elif topo == "hybrid":
        shared = params["shared"]
        kv0 = attention.init_kv_cache(cfg, B, C, quantize=quantize_kv)

        def super_body(hh, mamba_group):
            def inner(hh2, lp):
                xn = layers.apply_norm(cfg, lp["norm"], hh2)
                y, st = ssm.mamba2_prefill(cfg, lp["mamba"], xn)
                return hh2 + y, st

            hh, sts = jax.lax.scan(inner, hh, mamba_group)
            hh, kv = attn_prefill(shared, hh, kv0)
            return hh, (sts, kv)

        h, (ssm_st, kv) = jax.lax.scan(super_body, h, params["mamba"])
        cache["ssm"], cache["kv"] = ssm_st, kv
    else:  # xlstm
        def super_body(hh, grp):
            mparams, sparams = grp

            def inner(hh2, lp):
                xn = layers.apply_norm(cfg, lp["norm"], hh2)
                y, st = xlstm.mlstm_prefill(cfg, lp["mlstm"], xn)
                return hh2 + y, st

            hh, msts = jax.lax.scan(inner, hh, mparams)
            xn = layers.apply_norm(cfg, sparams["norm"], hh)
            y, sst = xlstm.slstm_prefill(cfg, sparams["slstm"], xn)
            return hh + y, (msts, sst)

        h, (mst, sst) = jax.lax.scan(
            super_body, h, (params["mlstm"], params["slstm"]))
        cache["mlstm"], cache["slstm"] = mst, sst
    h = layers.apply_norm(cfg, params["final_norm"], h[:, -1:])
    logits = layers.logits_from_hidden(cfg, params, h)[:, 0]
    return logits, cache
