"""Assigned input shapes and ``input_specs()`` — ShapeDtypeStruct stand-ins
for every model input (weak-type-correct, shardable, no device allocation).

  train_4k     seq_len=4,096    global_batch=256   (training: one FL round)
  train_8k     seq_len=8,192    global_batch=8     (one chip's FL round:
                                                    K clients x 8/K seqs)
  prefill_32k  seq_len=32,768   global_batch=32    (inference prefill)
  decode_32k   seq_len=32,768   global_batch=128   (decode: 1 token + cache)
  long_500k    seq_len=524,288  global_batch=1     (long-context decode)

Decode shapes lower ``serve_step`` (one new token against a KV/recurrent
cache of seq_len), not ``train_step``.  Skips (encoder-only archs for decode
shapes; pure full-attention archs for long_500k) are encoded in
``combo_supported`` and documented in DESIGN.md §6.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.fed.distributed import RoundConfig
from repro.models import model as model_lib


@dataclasses.dataclass(frozen=True)
class InputShape:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # train | prefill | decode


SHAPES: Dict[str, InputShape] = {
    "train_4k": InputShape("train_4k", 4_096, 256, "train"),
    "train_8k": InputShape("train_8k", 8_192, 8, "train"),
    "prefill_32k": InputShape("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32_768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524_288, 1, "decode"),
}


def combo_supported(cfg: ArchConfig, shape: InputShape) -> Tuple[bool, str]:
    """(supported, reason-if-not)."""
    if shape.kind == "decode" and not cfg.supports_decode:
        return False, "encoder-only architecture has no decode step"
    if shape.kind != "train" and cfg.attn_period:
        return False, ("mixed-attention stack (attn_period): training "
                       "path only")
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, ("pure full-attention arch; long_500k requires "
                       "sub-quadratic attention (DESIGN.md §6)")
    return True, ""


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def train_input_specs(cfg: ArchConfig, shape: InputShape, rc: RoundConfig
                      ) -> Dict[str, Any]:
    """Client-sharded round batch: leading K client axis."""
    K = rc.n_clients
    assert shape.global_batch % K == 0
    b = shape.global_batch // K
    S = shape.seq_len
    dt = jnp.dtype(cfg.param_dtype)
    batch: Dict[str, Any] = {"labels": _sds((K, b, S), jnp.int32)}
    if cfg.family == "audio" or cfg.frontend_positions == -1:
        batch["frontend"] = _sds((K, b, S, cfg.d_model), dt)
    else:
        batch["tokens"] = _sds((K, b, S), jnp.int32)
        if cfg.frontend_positions > 0:
            batch["frontend"] = _sds(
                (K, b, cfg.frontend_positions, cfg.d_model), dt)
    return batch


def prefill_input_specs(cfg: ArchConfig, shape: InputShape) -> Dict[str, Any]:
    B, S = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.param_dtype)
    batch: Dict[str, Any] = {}
    if cfg.family == "audio" or cfg.frontend_positions == -1:
        batch["frontend"] = _sds((B, S, cfg.d_model), dt)
    else:
        batch["tokens"] = _sds((B, S), jnp.int32)
        if cfg.frontend_positions > 0:
            batch["frontend"] = _sds(
                (B, cfg.frontend_positions, cfg.d_model), dt)
    return batch


def decode_input_specs(cfg: ArchConfig, shape: InputShape,
                       quantize_kv: bool = False) -> Dict[str, Any]:
    """tokens + cache ShapeDtypeStructs (cache shaped by init_cache)."""
    B, S = shape.global_batch, shape.seq_len
    cache = jax.eval_shape(
        lambda: model_lib.init_cache(cfg, B, S, quantize_kv=quantize_kv))
    return {"tokens": _sds((B, 1), jnp.int32), "cache": cache}


def input_specs(cfg: ArchConfig, shape_name: str,
                rc: Optional[RoundConfig] = None,
                quantize_kv: bool = False) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    ok, why = combo_supported(cfg, shape)
    if not ok:
        raise ValueError(f"{cfg.name} x {shape_name}: {why}")
    if shape.kind == "train":
        return train_input_specs(cfg, shape, rc or RoundConfig())
    if shape.kind == "prefill":
        return prefill_input_specs(cfg, shape)
    return decode_input_specs(cfg, shape, quantize_kv=quantize_kv)
