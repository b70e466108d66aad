"""Input generators of the benchmark, independent of the program.

``synthetic_alpha_beta`` is Synthetic(alpha, beta) of Shamir et al. and
Li et al. (FedProx), the paper's synthetic task: for device k,
u_k ~ N(0, alpha), W_k ~ N(u_k, 1), b_k ~ N(u_k, 1), B_k ~ N(0, beta),
v_k ~ N(B_k, 1), x ~ N(v_k, diag(j^-1.2)), y = argmax(W_k x + b_k).
Device sizes follow a power law drawn once from a fixed stream, so every
seed gives the same shapes (and the same compiled programs) and only the
values change.
"""
from __future__ import annotations

import numpy as np

SIZE_STREAM = 0x51_2E5


def seed_ints(seed: int, tag: int, n: int = 1) -> list:
    """``n`` 32-bit integers derived from any whole ``seed`` and a tag."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), int(tag)])
    return [int(v) for v in ss.generate_state(n)]


def power_law_sizes(n_devices: int, mean_size: int, alpha: float = 1.5,
                    min_size: int = 10) -> np.ndarray:
    rng = np.random.default_rng(SIZE_STREAM)
    raw = rng.pareto(alpha, n_devices) + 1.0
    sizes = (raw / raw.mean() * mean_size).astype(int)
    return np.maximum(sizes, min_size)


def synthetic_alpha_beta(seed: int, n_devices: int, alpha: float, beta: float,
                         n_features: int, n_classes: int, mean_size: int,
                         test_frac: float):
    """Padded resident arrays: train x (N, M, F) f32, y (N, M) i32,
    mask (N, M) f32, test x/y/mask likewise, and size weights p (N,)."""
    sizes = power_law_sizes(n_devices, mean_size)
    rng = np.random.default_rng(seed_ints(seed, 1, 4))
    diag = np.array([(j + 1) ** -1.2 for j in range(n_features)])
    n_test = np.maximum(1, (sizes * test_frac).astype(int))
    n_train = sizes - n_test
    M, T = int(n_train.max()), int(n_test.max())
    out = {"x": np.zeros((n_devices, M, n_features), np.float32),
           "y": np.zeros((n_devices, M), np.int32),
           "mask": np.zeros((n_devices, M), np.float32),
           "test_x": np.zeros((n_devices, T, n_features), np.float32),
           "test_y": np.zeros((n_devices, T), np.int32),
           "test_mask": np.zeros((n_devices, T), np.float32)}
    for k in range(n_devices):
        u = rng.normal(0, alpha)
        W = rng.normal(u, 1, (n_features, n_classes))
        b = rng.normal(u, 1, (n_classes,))
        v = rng.normal(rng.normal(0, beta), 1, n_features)
        x = rng.normal(v, np.sqrt(diag), (int(sizes[k]), n_features))
        y = np.argmax(x @ W + b, axis=1)
        a, t = int(n_train[k]), int(n_test[k])
        out["x"][k, :a], out["y"][k, :a], out["mask"][k, :a] = x[:a], y[:a], 1
        out["test_x"][k, :t] = x[a:]
        out["test_y"][k, :t] = y[a:]
        out["test_mask"][k, :t] = 1
    out["p"] = (n_train / n_train.sum()).astype(np.float32)
    return out

