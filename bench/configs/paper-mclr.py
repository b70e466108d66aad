"""Plain reference of ``paper-mclr`` (``paper-mclr.json``): multinomial
logistic regression, logits = x W + b, W (n_features, n_classes) and b
(n_classes,) starting at zero (arXiv:2007.13137, Sec. VI)."""
from __future__ import annotations


def init(cfg: dict, dtype):
    import jax.numpy as jnp
    return {"w": jnp.zeros((cfg["n_features"], cfg["n_classes"]), dtype),
            "b": jnp.zeros((cfg["n_classes"],), dtype)}


def logits(cfg: dict, params, x):
    return x @ params["w"] + params["b"]
