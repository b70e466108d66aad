"""Plain reference of one FOLB round of a language model, written from the
algorithm's description and importing nothing of the program under test.

K clients start from w.  Client k takes E prox-SGD steps on
h_k(v) = F_k(v) + mu/2 ||v - w||^2 from v = w with step size lr, all on
its own batch: v <- v - lr (grad F_k(v) + mu (v - w)).  It sends its
update Delta_k = v - w and its gradient g_k = grad F_k(w), which the
stated configuration stores in bfloat16 before they are aggregated.
FOLB (arXiv:2007.13137, Eq. IV-C):

    g1  = (1/K) sum_k g_k
    I_k = <g_k, g1>
    w'  = w + sum_k I_k Delta_k / sum_k |I_k|

``model`` is a configuration's plain reference module (``bench/configs/
<name>.py``) with ``loss(cfg, params, tokens, labels, dtype)``; params
are a float32 pytree on the device.  Everything runs under
``jax.default_matmul_precision("highest")``; the clients' stored updates
and gradients stay on the device for the aggregation.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List


@dataclasses.dataclass
class RoundResult:
    params: object              # w' (float32 pytree on the device)
    losses: List[float]         # F_k(w), one per client
    scores: List[float]         # I_k


def run(model, cfg: dict, params, tokens, labels, lr: float, mu: float,
        local_steps: int, dtype: str = "float32",
        storage: str = "bfloat16") -> RoundResult:
    """One round over the client batches ``tokens``/``labels`` (K, b, S).
    ``dtype`` is the precision of the model's matrix products (float32,
    or a lower one for the control); ``storage`` the dtype Delta_k and
    g_k are held in for the aggregation.  Returns w' on the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    with jax.default_matmul_precision("highest"):
        vg = jax.jit(jax.value_and_grad(functools.partial(
            model.loss, cfg, dtype=dtype)))
        step = jax.jit(lambda v, g, w: jax.tree.map(
            lambda vl, gl, wl: vl - lr * (gl + mu * (vl - wl)), v, g, w))
        first = jax.jit(lambda w, g: (
            jax.tree.map(lambda wl, gl: wl - lr * gl, w, g),
            jax.tree.map(lambda gl: gl.astype(storage), g)))
        delta = jax.jit(lambda v, w: jax.tree.map(
            lambda vl, wl: (vl - wl).astype(storage), v, w))
        losses, grads, deltas = [], [], []
        for k in range(tokens.shape[0]):
            tok, lab = jnp.asarray(tokens[k]), jnp.asarray(labels[k])
            l, g = vg(params, tok, lab)
            v, g_store = first(params, g)
            del g
            for _ in range(local_steps - 1):
                v = step(v, vg(v, tok, lab)[1], params)
            losses.append(float(l))
            grads.append(g_store)
            deltas.append(delta(v, params))
            del v
        new, scores = jax.jit(aggregate)(params, grads, deltas)
    return RoundResult(new, losses, [float(x) for x in np.asarray(scores)])


def aggregate(w, grads, deltas):
    """FOLB over the stored gradients and updates: w' and the K scores,
    in float32."""
    import jax
    import jax.numpy as jnp
    f32 = lambda x: x.astype(jnp.float32)
    K = len(grads)
    g1 = jax.tree.map(lambda *gs: sum(f32(g) for g in gs) / K, *grads)
    scores = jnp.stack([
        sum(jnp.sum(f32(a) * b) for a, b in zip(jax.tree.leaves(g),
                                                jax.tree.leaves(g1)))
        for g in grads])
    wts = scores / jnp.sum(jnp.abs(scores))
    new = jax.tree.map(
        lambda wl, *ds: wl + sum(c * f32(d) for c, d in zip(wts, ds)),
        w, *deltas)
    return new, scores
