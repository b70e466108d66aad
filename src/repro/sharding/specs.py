"""Parameter / activation / cache PartitionSpecs.

Rules are path-based (leaf names are stable across architectures) and apply
to the *trailing* dims of each leaf so stacked-layer leading axes (L,) or
(G, g,) are automatically replicated (they are scanned, never sharded).
A mixed-attention stack keeps one (G, run length, ...) tree per run of its
period under ``period/<run>/``, with the same leaf names; its MoE expert
leaves hold the chip's ``n_held`` experts (the router keeps all
``n_experts`` outputs), so expert mode shards the held experts.

Mesh contract (repro.launch.mesh):
  data axes  — batch / client-batch dimension ("data", plus "pod" when
               multi-pod: FL clients are embarrassingly parallel, so the
               pod axis joins the batch dimension).
  model axis — tensor parallelism: attention heads, FFN hidden, vocab,
               expert-FFN hidden (tensor mode) or the expert axis (expert
               mode), Mamba/xLSTM inner channels, decode KV heads.
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_axis(mesh: Mesh):
    ax = data_axes(mesh)
    return ax if len(ax) > 1 else ax[0]


# (path-substring, trailing spec) — first match wins.  Paths use '/' joined
# dict keys, e.g. "layers/attn/wq/w" or "mamba/mamba/in_proj/w".
_PARAM_RULES: Tuple[Tuple[str, Tuple], ...] = (
    # embeddings / head
    ("embed/w", ("model", None)),
    ("lm_head/w", (None, "model")),
    # attention: column-parallel QKV, row-parallel output
    ("attn/wq/w", (None, "model")),
    ("attn/wk/w", (None, "model")),
    ("attn/wv/w", (None, "model")),
    ("attn/wo/w", ("model", None)),
    # dense GLU MLP: column-parallel up/gate, row-parallel down
    ("mlp/up/w", (None, "model")),
    ("mlp/gate/w", (None, "model")),
    ("mlp/down/w", ("model", None)),
    # MoE experts (tensor mode; expert mode overrides below)
    ("moe/w_up", (None, None, "model")),
    ("moe/w_gate", (None, None, "model")),
    ("moe/w_down", (None, "model", None)),
    ("moe/shared/up/w", (None, "model")),
    ("moe/shared/gate/w", (None, "model")),
    ("moe/shared/down/w", ("model", None)),
    ("moe/router/w", (None, None)),
    # mamba2
    ("in_proj/w", (None, "model")),
    ("out_proj/w", ("model", None)),
    ("conv_w", (None, "model")),
    ("A_log", ("model",)),
    ("dt_bias", ("model",)),
    # ^ per-head vectors follow the inner-channel sharding
    ("mamba/mamba/D", ("model",)),
    # mLSTM
    ("mlstm/up/w", (None, "model")),
    ("mlstm/wq/w", ("model", None)),
    ("mlstm/wk/w", ("model", None)),
    ("mlstm/wv/w", ("model", None)),
    ("mlstm/w_gates/w", ("model", None)),
    ("mlstm/down/w", ("model", None)),
    # sLSTM
    ("slstm/wx/w", (None, "model")),
    ("slstm/ffn/up/w", (None, "model")),
    ("slstm/ffn/gate/w", (None, "model")),
    ("slstm/ffn/down/w", ("model", None)),
)

_EXPERT_RULES: Tuple[Tuple[str, Tuple], ...] = (
    ("moe/w_up", ("model", None, None)),
    ("moe/w_gate", ("model", None, None)),
    ("moe/w_down", ("model", None, None)),
)


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _axis_size(mesh: Mesh, ax) -> int:
    if ax is None:
        return 1
    if isinstance(ax, (tuple, list)):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def enforce_divisibility(spec: P, shape, mesh: Mesh) -> P:
    """jit argument shardings must divide evenly (GSPMD does not pad
    explicit arg shardings) — drop any axis that doesn't divide."""
    entries = tuple(spec) + (None,) * (len(shape) - len(spec))
    out = []
    for dim, ax in zip(shape, entries):
        out.append(ax if ax is not None and dim % _axis_size(mesh, ax) == 0
                   else None)
    return P(*out)


def _spec_for(path: str, ndim: int, rules) -> P:
    for frag, trailing in rules:
        if frag in path:
            pad = ndim - len(trailing)
            if pad < 0:       # leaf smaller than rule (reduced configs)
                return P(*trailing[-ndim:]) if ndim else P()
            return P(*((None,) * pad + tuple(trailing)))
    return P(*((None,) * ndim))


def param_specs(cfg, params_shape, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching `params_shape` (shapes or arrays)."""
    rules = _PARAM_RULES
    if cfg.moe is not None and cfg.moe.sharding == "expert":
        rules = _EXPERT_RULES + _PARAM_RULES
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: enforce_divisibility(
            _spec_for(_path_str(path), len(leaf.shape), rules),
            leaf.shape, mesh),
        params_shape)


def train_batch_specs(cfg, batch_shape, mesh: Mesh) -> Any:
    """Client batches (K, b, ...): K is scanned (replicated), the per-client
    batch dim b shards over the data axes."""
    b = batch_axis(mesh)

    def spec(path, leaf):
        nd = len(leaf.shape)
        return enforce_divisibility(
            P(*((None, b) + (None,) * (nd - 2))), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(spec, batch_shape)


def serve_batch_specs(cfg, batch_shape, mesh: Mesh) -> Any:
    """Serving batches (B, ...): B shards over the data axes."""
    b = batch_axis(mesh)
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: enforce_divisibility(
            P(*((b,) + (None,) * (len(leaf.shape) - 1))), leaf.shape, mesh),
        batch_shape)


def cache_specs(cfg, cache_shape, mesh: Mesh) -> Any:
    """Decode-cache sharding: leading stacked-layer dims replicated, batch
    over data axes, KV heads / inner channels over model.

    Leaf layouts (see repro.models.model.init_cache):
      kv k/v:      (L_or_G, B, C, KV, hd)  -> (None, data, None, model, None)
      ssm ssm:     (G, g, B, H, P, N)      -> (.., data, model, None, None)
      ssm conv:    (G, g, B, K-1, di)      -> (.., data, None, model)
      mlstm C:     (G, m, B, H, dh+1, dh)  -> (.., data, model, None, None)
      mlstm conv:  (G, m, B, K-1, di)      -> (.., data, None, model)
      slstm h/c/n: (G, B, d)               -> (None, data, None)
      pos:         ()                      -> ()
    """
    b = batch_axis(mesh)
    msize = mesh.shape["model"]

    def spec(path, leaf):
        pstr = _path_str(path)
        nd = len(leaf.shape)
        sh = leaf.shape
        if nd == 0:
            return P()
        if "/k" in pstr or "/v" in pstr:         # kv cache (.., B, C, KV, hd)
            B_, C_, KV_, hd_ = sh[-4], sh[-3], sh[-2], sh[-1]
            # model-axis placement preference: KV heads, else cache seq,
            # else head dim, else replicated
            if KV_ % msize == 0:
                tail = (b, None, "model", None)
            elif C_ % msize == 0:
                tail = (b, "model", None, None)
            elif hd_ % msize == 0:
                tail = (b, None, None, "model")
            else:
                tail = (b, None, None, None)
            return enforce_divisibility(
                P(*((None,) * (nd - 4) + tail)), sh, mesh)
        if "ssm/ssm" in pstr or pstr.endswith("ssm") or pstr.endswith("C"):
            return enforce_divisibility(
                P(*((None,) * (nd - 4) + (b, "model", None, None))), sh, mesh)
        if "conv" in pstr:
            return enforce_divisibility(
                P(*((None,) * (nd - 3) + (b, None, "model"))), sh, mesh)
        if nd >= 2:                               # slstm h/c/n (G,B,d)
            return enforce_divisibility(
                P(*((None,) * (nd - 2) + (b, "model"))), sh, mesh)
        return P(*((None,) * nd))

    return jax.tree_util.tree_map_with_path(spec, cache_shape)


def accumulator_specs(cfg, params_shape, mesh: Mesh) -> Any:
    """FSDP-style sharding for the FOLB round's fp32 accumulators (gsum, g1,
    acc, delta): these are elementwise-only values, so on top of the param
    sharding we shard the first additionally-divisible dim over the data
    axes.  For a 33B model this turns 8.25 GiB/device fp32 buffers into
    ~0.5 GiB/device; clients reshard their gradients into this layout once
    per round (cheap all-to-all)."""
    base = param_specs(cfg, params_shape, mesh)
    d_ax = batch_axis(mesh)
    d_size = _axis_size(mesh, d_ax)

    def add_data(leaf, spec):
        entries = list(tuple(spec) + (None,) * (len(leaf.shape) - len(spec)))
        for i, (dim, ax) in enumerate(zip(leaf.shape, entries)):
            if ax is None and dim % d_size == 0 and dim >= d_size:
                entries[i] = d_ax
                break
        return P(*entries)

    leaves, treedef = jax.tree_util.tree_flatten(params_shape)
    spec_leaves = treedef.flatten_up_to(base)
    return jax.tree_util.tree_unflatten(
        treedef, [add_data(l, s) for l, s in zip(leaves, spec_leaves)])


def fsdp_param_specs(cfg, params_shape, mesh: Mesh) -> Any:
    """FSDP sharding for the PARAMETERS (not just accumulators): like
    accumulator_specs but never shards dim 0 of layer-stacked (>=3-D)
    leaves — the layer scan dynamic-slices dim 0, and GSPMD lowers a slice
    of a dim-0-sharded stack as gather-the-whole-stack-per-layer
    ('involuntary full rematerialization', measured 17.7 TB/chip/round on
    mixtral).  Sharding d_model instead turns the per-layer cost into one
    small partial-sum all-reduce (§Perf B7)."""
    base = param_specs(cfg, params_shape, mesh)
    d_ax = batch_axis(mesh)
    d_size = _axis_size(mesh, d_ax)

    def add_data(leaf, spec):
        entries = list(tuple(spec) + (None,) * (len(leaf.shape) - len(spec)))
        start = 1 if len(leaf.shape) >= 3 else 0
        for i in range(start, len(entries)):
            dim, ax = leaf.shape[i], entries[i]
            if ax is None and dim % d_size == 0 and dim >= d_size:
                entries[i] = d_ax
                break
        return P(*entries)

    leaves, treedef = jax.tree_util.tree_flatten(params_shape)
    spec_leaves = treedef.flatten_up_to(base)
    return jax.tree_util.tree_unflatten(
        treedef, [add_data(l, s) for l, s in zip(leaves, spec_leaves)])


def to_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


# ----------------------------------------------------- flat FOLB buffer mesh

FLAT_AXIS = "d"   # the flat-buffer D axis (kernels.folb_aggregate sharded)


def folb_mesh(n_shards: int = 0) -> Mesh:
    """1-axis mesh for the D-sharded flat FOLB aggregation: the parameter
    vector splits over ``FLAT_AXIS``; the (K,) score algebra is replicated.
    ``n_shards=0`` uses every visible device.  FL clients already
    parallelize over the data axes, so the flat aggregation gets its own
    dedicated axis rather than reusing "model" (which tensor-shards 2-D
    leaves, not the raveled vector)."""
    devs = jax.devices()
    n = n_shards or len(devs)
    assert n <= len(devs), (n, len(devs))
    return jax.make_mesh((n,), (FLAT_AXIS,), devices=devs[:n],
                         axis_types=(AxisType.Auto,))
