"""Jittable step builders with mesh shardings.

  build_train_step  — one FOLB round (repro.fed.distributed.folb_round)
  build_prefill_step — prompt processing -> (next-token logits, cache)
  build_decode_step  — one-token decode against the cache
  (encoder archs use build_encoder_step for the prefill shape)

Each builder returns (jitted_fn, arg ShapeDtypeStructs) so the dry-run can
``.lower(*args).compile()`` without allocating anything.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.fed.distributed import RoundConfig, folb_round
from repro.launch import shapes as shapes_lib
from repro.models import model as model_lib
from repro.sharding import specs as specs_lib
from repro.sharding.context import use_sharding


def _named(mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def params_shape(cfg: ArchConfig):
    return jax.eval_shape(
        lambda k: model_lib.init_params(cfg, k), jax.random.PRNGKey(0))


def param_shardings(cfg: ArchConfig, mesh: Mesh):
    ps = params_shape(cfg)
    return ps, _named(mesh, specs_lib.param_specs(cfg, ps, mesh))


def build_train_step(cfg: ArchConfig, mesh: Mesh, rc: RoundConfig,
                     shape_name: str = "train_4k"):
    ps, p_shard = param_shardings(cfg, mesh)
    batch = shapes_lib.input_specs(cfg, shape_name, rc)
    b_shard = _named(mesh, specs_lib.train_batch_specs(cfg, batch, mesh))
    repl = NamedSharding(mesh, P())

    acc_shard = _named(mesh, specs_lib.accumulator_specs(cfg, ps, mesh))
    # §Perf B: fp32 round state always lives in the FSDP accumulator layout
    # (fed.distributed.local_solve).  Parameters themselves stay tensor-
    # parallel unless rc.fsdp_params or the auto-threshold says the bf16
    # shard alone is too large for HBM headroom (mixtral 5.9 GiB,
    # deepseek-33b 4.2 GiB/device) — FSDP params re-pay per-layer weight
    # all-gathers but keep the step inside 16 GiB.
    from repro.configs import n_params as _n_params
    if rc.fsdp_params or (_n_params(cfg) * 2 / mesh.shape["model"]) > 3 * 2**30:
        p_shard = _named(mesh, specs_lib.fsdp_param_specs(cfg, ps, mesh))

    def train_round(params, batch):
        with use_sharding(mesh):
            new_params, metrics = folb_round(cfg, rc, params, batch,
                                             param_shardings=p_shard,
                                             acc_shardings=acc_shard)
        return new_params, metrics

    # the params may be held in a wider dtype than cfg.param_dtype (float32
    # master weights): the shardings fit any dtype, the round keeps it
    fn = jax.jit(train_round,
                 in_shardings=(p_shard, b_shard),
                 out_shardings=(p_shard, repl),
                 donate_argnums=(0,))
    return fn, (ps, batch)


def build_encoder_step(cfg: ArchConfig, mesh: Mesh, shape_name: str):
    """Encoder-only 'prefill': full forward, mean loss (no cache)."""
    ps, p_shard = param_shardings(cfg, mesh)
    batch = shapes_lib.input_specs(cfg, shape_name)
    b_shard = _named(mesh, specs_lib.serve_batch_specs(cfg, batch, mesh))
    b_ax = specs_lib.batch_axis(mesh)

    def step(params, batch):
        with use_sharding(mesh):
            logits, _ = model_lib.forward(cfg, params, batch)
            # framewise posteriors -> return pooled predictions (B, V)
            return jnp.mean(logits.astype(jnp.float32), axis=1)

    out_sds = jax.eval_shape(step, ps, batch)
    fn = jax.jit(step, in_shardings=(p_shard, b_shard),
                 out_shardings=NamedSharding(mesh, specs_lib.enforce_divisibility(
                     P(b_ax, "model"), out_sds.shape, mesh)))
    return fn, (ps, batch)


def build_prefill_step(cfg: ArchConfig, mesh: Mesh, shape_name: str):
    if not cfg.supports_decode:
        return build_encoder_step(cfg, mesh, shape_name)
    ps, p_shard = param_shardings(cfg, mesh)
    batch = shapes_lib.input_specs(cfg, shape_name)
    b_shard = _named(mesh, specs_lib.serve_batch_specs(cfg, batch, mesh))
    b_ax = specs_lib.batch_axis(mesh)

    def step(params, batch):
        with use_sharding(mesh):
            return model_lib.prefill(cfg, params, batch)

    cache_shape = jax.eval_shape(
        lambda p, b: step(p, b)[1], ps, batch)
    cache_shard = _named(mesh, specs_lib.cache_specs(cfg, cache_shape, mesh))
    logits_sds = jax.eval_shape(lambda p, b: step(p, b)[0], ps, batch)
    logits_shard = NamedSharding(mesh, specs_lib.enforce_divisibility(
        P(b_ax, "model"), logits_sds.shape, mesh))
    fn = jax.jit(step, in_shardings=(p_shard, b_shard),
                 out_shardings=(logits_shard, cache_shard))
    return fn, (ps, batch)


def build_decode_step(cfg: ArchConfig, mesh: Mesh, shape_name: str,
                      quantize_kv: bool = False):
    ps, p_shard = param_shardings(cfg, mesh)
    inputs = shapes_lib.input_specs(cfg, shape_name, quantize_kv=quantize_kv)
    cache_shape, tokens = inputs["cache"], inputs["tokens"]
    cache_shard = _named(mesh, specs_lib.cache_specs(cfg, cache_shape, mesh))
    b_ax = specs_lib.batch_axis(mesh)
    tok_shard = NamedSharding(mesh, specs_lib.enforce_divisibility(
        P(b_ax, None), tokens.shape, mesh))

    def step(params, cache, tokens):
        with use_sharding(mesh):
            return model_lib.decode_step(cfg, params, cache, tokens)

    logits_sds = jax.eval_shape(
        lambda p, c, t: step(p, c, t)[0], ps, cache_shape, tokens)
    logits_shard = NamedSharding(mesh, specs_lib.enforce_divisibility(
        P(b_ax, "model"), logits_sds.shape, mesh))
    fn = jax.jit(step,
                 in_shardings=(p_shard, cache_shard, tok_shard),
                 out_shardings=(logits_shard, cache_shard),
                 donate_argnums=(1,))
    return fn, (ps, cache_shape, tokens)


def build_step(cfg: ArchConfig, mesh: Mesh, shape_name: str,
               rc: Optional[RoundConfig] = None,
               quantize_kv: bool = False):
    """Dispatch on the shape's kind."""
    kind = shapes_lib.SHAPES[shape_name].kind
    if kind == "train":
        return build_train_step(cfg, mesh, rc or RoundConfig(), shape_name)
    if kind == "prefill":
        return build_prefill_step(cfg, mesh, shape_name)
    return build_decode_step(cfg, mesh, shape_name, quantize_kv=quantize_kv)
