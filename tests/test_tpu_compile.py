"""The FOLB kernels compiled for a described TPU v5e (no chip attached).

Interpret mode cannot see what the chip's compiler refuses: blocks that
overflow VMEM, tiles not aligned to the hardware layout, a kernel that
cannot be partitioned.  These tests compile each kernel with
``interpret=False`` for a ``v5e:2x2`` topology described in a fixture, at
the paper's sizes, at D ~ 1e8, at the large K of async slot budgets and
cross-device cohorts, and D-sharded over the four chips.  The topology is
described only inside the module-scoped fixture (never at import), and
every test skips when it cannot be described.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.kernels import folb_aggregate as F
from repro.kernels import ops
from repro.kernels.guard import GuardConfig

D_100M = 100_720_640      # the 1.007e8-parameter MLP, padded to 4 x TILE_D


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    return Mesh(np.array(topo.devices[:4]), ("d",),
                axis_types=(AxisType.Auto,))


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(name, K, D, dtype, s):
    """(jitted fn with interpret=False, its argument shapes)."""
    kd = _shape(s, (K, D), dtype)
    vec = _shape(s, (D,), jnp.float32)
    kvec = _shape(s, (K,), jnp.float32)
    if name == "folb_scores":
        return (lambda g, v: F.folb_scores(g, v, interpret=False)), (kd, vec)
    if name == "folb_apply":
        return ((lambda w, d, wt: F.folb_apply(w, d, wt, interpret=False)),
                (vec, kd, kvec))
    if name == "guard_stats":
        return ((lambda d, g: F.guard_stats(d, g, interpret=False)),
                (kd, kd))
    assert name == "folb_aggregate_stale", name
    return ((lambda w, d, g, tau, a, pg, m: F.folb_aggregate_stale(
        w, d, g, tau, a, pg, m, interpret=False)),
        (vec, kd, kd, kvec, _shape(s, (), jnp.float32), kvec, kvec))


def _compile_text(fn, args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


KERNELS = ("folb_scores", "folb_apply", "guard_stats", "folb_aggregate_stale")


@pytest.mark.parametrize("K,D,dtype", [
    (10, 1024, jnp.float32),        # paper-mclr, padded
    (10, 1024, jnp.bfloat16),
    (8, D_100M, jnp.bfloat16),      # large-model flat buffers
])
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles(one_chip, name, K, D, dtype):
    fn, args = _kernel_call(name, K, D, dtype, one_chip)
    assert "tpu_custom_call" in _compile_text(fn, args)


@pytest.mark.parametrize("name,K,dtype", [
    ("guard_stats", 64, jnp.bfloat16),
    ("guard_stats", 64, jnp.float32),
    ("guard_stats", 128, jnp.bfloat16),
    ("folb_scores", 128, jnp.float32),
    ("folb_apply", 128, jnp.float32),
    ("folb_scores", 256, jnp.bfloat16),
    ("folb_apply", 512, jnp.float32),
])
def test_large_k_fits_vmem(one_chip, name, K, dtype):
    """Slot budgets (K dispatched + S late) and ~100-client cohorts: the
    tile shrinks with K so the double-buffered blocks fit scoped VMEM."""
    fn, args = _kernel_call(name, K, 1 << 22, dtype, one_chip)
    assert "tpu_custom_call" in _compile_text(fn, args)


@pytest.mark.parametrize("guard", [None, GuardConfig(nonfinite=True,
                                                     clip_mult=3.0,
                                                     gate_mult=6.0)])
def test_ops_pick_mosaic_when_lowered_for_tpu(one_chip, guard):
    """The public wrappers choose interpret mode from the platform the
    program is lowered for: Mosaic for the described TPU, though this
    process's default backend is the CPU."""
    K, D = 10, 4096
    kd = _shape(one_chip, (K, D), jnp.bfloat16)
    w = _shape(one_chip, (D,), jnp.float32)
    kvec = _shape(one_chip, (K,), jnp.float32)
    low = ops.folb_staleness_buffers.lower(
        w, kd, kd, kvec, _shape(one_chip, (), jnp.float32), kvec, kvec,
        guard=guard)
    assert "tpu_custom_call" in low.as_text()
    assert "tpu_custom_call" in low.compile().as_text()
    cpu = jax.ShapeDtypeStruct
    low_cpu = ops.folb_staleness_buffers.lower(
        cpu((D,), jnp.float32), cpu((K, D), jnp.bfloat16),
        cpu((K, D), jnp.bfloat16), cpu((K,), jnp.float32),
        cpu((), jnp.float32), cpu((K,), jnp.float32), cpu((K,), jnp.float32),
        guard=guard)
    assert "tpu_custom_call" not in low_cpu.as_text()


@pytest.mark.parametrize("rounds,eval_every", [(2, 1), (5, 2)])
def test_eval_rows_take_no_gather(one_chip, rounds, eval_every):
    """The history replay picks its eval rows of a (rounds, D) trajectory
    with slices: a gather of 1e8-wide rows takes minutes to compile."""
    from repro.fed.scan_engine import _eval_rows
    traj = _shape(one_chip, (rounds, D_100M), jnp.float32)
    text = _compile_text(lambda t: _eval_rows(t, rounds, eval_every),
                         (traj,))
    assert " gather(" not in text


@pytest.mark.parametrize("K,D,dtype", [
    (10, 4096 * 4, jnp.float32),
    (8, D_100M, jnp.bfloat16),
])
def test_sharded_aggregation_compiles_on_four_chips(mesh4, K, D, dtype):
    """D-sharded over the 2x2 host: the Mosaic sweeps per shard and one
    (K+1,)-sized all-reduce between the two phases."""
    rep = NamedSharding(mesh4, P())
    w = _shape(rep, (D,), jnp.float32)
    kd = _shape(rep, (K, D), dtype)
    pg = _shape(rep, (K,), jnp.float32)
    text = jax.jit(lambda w, d, g, pg: F.folb_aggregate_sharded(
        w, d, g, pg, mesh4, interpret=False)).lower(
        w, kd, kd, pg).compile().as_text()
    assert "tpu_custom_call" in text
    n_all_reduce = (text.count(" all-reduce(")
                    + text.count(" all-reduce-start("))
    assert n_all_reduce == 1, n_all_reduce


def test_sharded_rounds_keep_solves_whole(mesh4):
    """Only the aggregation is spread over the mesh.  The pytree front-end
    pins its inputs and result whole, so the aggregated params that feed
    the next round's solves do not carry the D sharding into them: the
    compiled rounds hold the one all-reduce of the aggregation and no
    all-to-all."""
    K, F_IN, H, R = 4, 64, 512, 2
    rep = NamedSharding(mesh4, P())

    def solve(params, x):
        def loss(p):
            return jnp.sum(jnp.tanh(jnp.tanh(x @ p["w1"]) @ p["w2"]))
        g = jax.grad(loss)(params)
        return jax.tree.map(lambda a: -0.1 * a, g), g

    def rounds(params, xs):
        def body(p, x):
            deltas, grads = jax.vmap(solve, in_axes=(None, 0))(p, x)
            new, _ = ops.folb_aggregate_tree(p, deltas, grads,
                                             buf_dtype=jnp.float32,
                                             mesh=mesh4)
            return new, None
        return jax.lax.scan(body, params, xs)[0]

    params = {"w1": _shape(rep, (F_IN, H), jnp.float32),
              "w2": _shape(rep, (H, H), jnp.float32)}
    xs = _shape(rep, (R, K, 8, F_IN), jnp.float32)
    text = jax.jit(rounds).lower(params, xs).compile().as_text()
    assert "tpu_custom_call" in text
    assert " all-to-all(" not in text and " all-to-all-start(" not in text
    n_all_reduce = (text.count(" all-reduce(")
                    + text.count(" all-reduce-start("))
    assert n_all_reduce == 1, n_all_reduce


# ------------------------------------------- Mellum2 kernels, real widths

@pytest.mark.parametrize("window", [1024, 0])
def test_splash_attention_compiles_with_grad(one_chip, window):
    """Window (1024 keys) and full causal attention of Mellum2-12B-A2.5B
    (32 query and 4 KV heads of 128) at 8192 tokens, forward and
    backward, through the ``kernels.ops`` entry points."""
    q = _shape(one_chip, (1, 8192, 32, 128), jnp.bfloat16)
    kv = _shape(one_chip, (1, 8192, 4, 128), jnp.bfloat16)
    attend = ((lambda q, k, v: ops.attention_window(q, k, v, window))
              if window else ops.attention_full)

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2)), (q, kv, kv))
    # the forward kernel (with the residuals the backward pass reads) and
    # the dq and dkv kernels
    assert txt.count("tpu_custom_call") >= 3


def test_grouped_expert_ffn_compiles_with_grad(one_chip):
    """The held-expert FFN of Mellum2-12B-A2.5B (8 held experts of width
    896 over d 2304) over the dropless row buffer of 16384 tokens, forward
    and backward, through ``ops.moe_grouped_ffn``."""
    rows, d, ff, n = 16384 * 8, 2304, 896, 8
    xs = _shape(one_chip, (rows, d), jnp.bfloat16)
    wi = _shape(one_chip, (n, d, ff), jnp.bfloat16)
    wo = _shape(one_chip, (n, ff, d), jnp.bfloat16)
    sizes = _shape(one_chip, (n,), jnp.int32)

    def loss(xs, wg, wu, wd, sizes):
        return jnp.sum(ops.moe_grouped_ffn(xs, wg, wu, wd, sizes)
                       .astype(jnp.float32))

    txt = _compile_text(jax.grad(loss, argnums=(0, 1, 2, 3)),
                        (xs, wi, wi, wo, sizes))
    # the two input products forward (a summed output leaves the third
    # unread), three dlhs and three dW products backward
    assert txt.count("tpu_custom_call") >= 8
