"""Federated dataset container: pads per-device data to a common size so a
whole cohort can live in one stacked array (vmap simulator), with masks for
correctness, plus train/test splitting and device-weighted global metrics
(p_k = |D_k| / |D|, Sec. II-A).

``FederatedData`` is the resident form — all N devices stacked into
``(N, M, ...)`` arrays.  ``LazyFederatedData`` is the population-scale
form: every device's examples are a pure function of
``(population_seed, device_id)``, synthesized on demand, so a round
gathers ``(K, M, ...)`` batches for the selected cohort and per-round
data cost is O(K·M) no matter how large the fleet is.
``LazyFederatedData.materialize()`` produces the equivalent resident
``FederatedData`` by gathering ``arange(N)`` — the same computation, so
lazy cohort rows are bit-for-bit rows of the materialized stack (the
foundation of the lazy-vs-materialized engine equivalence tests).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import numpy as np

from repro.data import partition
from repro.sysmodel import population as _pop


@dataclasses.dataclass
class FederatedData:
    """Stacked devices: x (N, M, ...), y (N, M), mask (N, M) with M = max
    device size.  p (N,) are the dataset-size weights."""
    x: np.ndarray
    y: np.ndarray
    mask: np.ndarray
    p: np.ndarray
    test_x: np.ndarray
    test_y: np.ndarray
    test_mask: np.ndarray

    @property
    def n_devices(self) -> int:
        return self.x.shape[0]


def stack_devices(devices: List[Dict[str, np.ndarray]], test_frac: float = 0.2,
                  seed: int = 0, x_key: str = "x", y_key: str = "y"
                  ) -> FederatedData:
    rng = np.random.default_rng(seed)
    train, test = [], []
    for d in devices:
        n = d[x_key].shape[0]
        idx = rng.permutation(n)
        n_test = max(1, int(n * test_frac)) if n > 1 else 0
        test_idx, train_idx = idx[:n_test], idx[n_test:]
        train.append({"x": d[x_key][train_idx], "y": d[y_key][train_idx]})
        test.append({"x": d[x_key][test_idx], "y": d[y_key][test_idx]})

    def pad_stack(parts):
        m = max(1, max(p["x"].shape[0] for p in parts))
        feat = parts[0]["x"].shape[1:]
        xs = np.zeros((len(parts), m) + feat, parts[0]["x"].dtype)
        ys = np.zeros((len(parts), m), np.int32)
        mk = np.zeros((len(parts), m), np.float32)
        for i, p in enumerate(parts):
            n = p["x"].shape[0]
            xs[i, :n] = p["x"]
            ys[i, :n] = p["y"]
            mk[i, :n] = 1.0
        return xs, ys, mk

    x, y, mask = pad_stack(train)
    tx, ty, tmask = pad_stack(test)
    sizes = mask.sum(axis=1)
    p = sizes / sizes.sum()
    return FederatedData(x=x, y=y, mask=mask, p=p.astype(np.float32),
                         test_x=tx, test_y=ty, test_mask=tmask)


def minibatch_indices(rng: np.random.Generator, mask_row: np.ndarray,
                      batch: int) -> np.ndarray:
    """Sample `batch` valid indices (with replacement if needed)."""
    valid = np.flatnonzero(mask_row > 0)
    return rng.choice(valid, size=batch, replace=len(valid) < batch)


# --------------------------------------------------------------------------
# lazy population data
# --------------------------------------------------------------------------

# hash channel for per-device dataset sizes (vectorized, loop-free: the
# plan builders gather R·K sizes without synthesizing any examples)
_CH_SIZE = 7


@functools.lru_cache(maxsize=32)
def _class_prototypes(seed: int, n_classes: int, n_features: int,
                      proto_scale: float):
    """Shared class means of the gaussian mixture (population-level, O(C·F):
    independent of both N and the cohort)."""
    rng = np.random.default_rng(
        np.random.SeedSequence([0x9107_0CA5, int(seed)]))
    return rng.normal(0.0, proto_scale,
                      (n_classes, n_features)).astype(np.float32)


class SizesView:
    """Lazy per-device train-size vector: supports exactly the fancy
    indexing the plan builders use (``sizes[ids]``) but synthesizes only
    the requested rows — the O(K) stand-in for ``mask.sum(axis=1)``."""

    def __init__(self, data: "LazyFederatedData"):
        self._data = data

    def __getitem__(self, ids) -> np.ndarray:
        return self._data.gather_sizes(ids)


@dataclasses.dataclass(frozen=True)
class LazyFederatedData:
    """Generative federated dataset: gaussian mixture features around
    shared class prototypes, labels from a non-IID partitioner
    (``dirichlet`` / ``shard`` / ``iid``), sizes from a counter hash.

    Device k's examples come from its own ``(seed, k)``-keyed stream
    (``partition.device_rng``): identical across processes, independent
    of fleet size and of which cohort requests them.

    ``eval_cohort`` bounds global-eval cost at population scale: when
    set, compiled engines evaluate on a deterministic stride sample of
    that many devices instead of all N (leave ``None`` — evaluate
    everyone — for small-N equivalence runs).
    """
    n_devices: int
    seed: int = 0
    partition: str = "dirichlet"     # "dirichlet" | "shard" | "iid"
    alpha: float = 0.5               # dirichlet concentration
    shards_per_device: int = 2
    n_classes: int = 10
    n_features: int = 60
    min_size: int = 10
    max_size: int = 30
    test_size: int = 5
    noise: float = 0.5
    proto_scale: float = 1.0
    eval_cohort: Optional[int] = None

    def __post_init__(self):
        if self.n_devices <= 0:
            raise ValueError(f"n_devices must be positive, got "
                             f"{self.n_devices}")
        if self.partition not in ("dirichlet", "shard", "iid"):
            raise ValueError(f"unknown partition {self.partition!r}")
        if not (0 < self.min_size <= self.max_size):
            raise ValueError("need 0 < min_size <= max_size")

    # ------------------------------------------------------------ sizes
    def gather_sizes(self, ids) -> np.ndarray:
        """Train-set sizes for ``ids`` (any shape) — vectorized hash
        draw, no example synthesis."""
        u = _pop.hash_uniform(self.seed, _CH_SIZE, np.asarray(ids))
        span = self.max_size - self.min_size + 1
        return (self.min_size + np.floor(u * span)).astype(np.int64)

    @property
    def sizes(self) -> SizesView:
        return SizesView(self)

    # --------------------------------------------------------- synthesis
    def gather(self, ids) -> Dict[str, np.ndarray]:
        """Cohort batch for ``ids`` (any shape): dict with
        x (..., M, F) f32 / y (..., M) i32 / mask (..., M) f32 and the
        test_* equivalents, rows bit-for-bit equal to the materialized
        stack's rows.  Cost O(#unique ids · M); duplicate ids (a device
        selected in many rounds) are synthesized once.

        A device of train size n draws from its own stream, in order:
        its class proportions (``dirichlet`` only), its n + T labels
        (``iid``: bounded integers; ``dirichlet``: uniforms through the
        CDF of the proportions, ``Generator.choice(C, p=pi)`` written
        out; ``shard``: no draw, the labels cycle through its owned
        shards), then (n + T, F) standard normals: n train rows, then T
        test rows.  Each of these is one call where the train and test
        parts could be two: a uniform or a normal takes whole 64-bit
        words of the bit generator, and the half word a bounded integer
        leaves over stays in the bit generator between calls, so one
        draw of n + T values is the same bits as a draw of n and then a
        draw of T.  The rest is vectorized over the cohort."""
        ids = np.asarray(ids, dtype=np.int64)
        flat = ids.reshape(-1)
        uniq, inv = np.unique(flat, return_inverse=True)
        M, T, F, C = (self.max_size, self.test_size, self.n_features,
                      self.n_classes)
        proto = _class_prototypes(self.seed, C, F, self.proto_scale)
        sizes = self.gather_sizes(uniq)
        U, W = len(uniq), M + T
        # device i's draws fill [i, :n_i + T]: train rows, then test rows
        lab = np.zeros((U, W), np.int64)
        u = np.zeros((U, W))
        pi = np.ones((U, C))
        z = np.zeros((U, W, F), np.float32)
        for i, (did, n) in enumerate(zip(uniq.tolist(), sizes.tolist())):
            rng = partition.device_rng(self.seed, did)
            k = n + T
            if self.partition == "dirichlet":
                pi[i] = partition.dirichlet_proportions(rng, C, self.alpha)
                rng.random(out=u[i, :k])
            elif self.partition == "iid":
                lab[i, :k] = rng.integers(0, C, size=k)
            z[i, :k] = rng.standard_normal((k, F))
        pos = np.arange(W)
        if self.partition == "dirichlet":
            cdf = pi.cumsum(axis=1)
            cdf /= cdf[:, -1:]
            # searchsorted(cdf, u, side="right"): the CDF entries <= u
            for c in range(C):
                lab += cdf[:, c, None] <= u
        elif self.partition == "shard":
            owned = partition.shard_labels(self.seed, uniq, self.n_devices,
                                           self.shards_per_device, C)
            rank = np.where(pos < sizes[:, None], pos, pos - sizes[:, None])
            lab = np.take_along_axis(owned, rank % owned.shape[1], axis=1)
        lab = lab.astype(np.int32)
        xs = self.noise * z
        xs += np.take(proto, lab, axis=0)
        xs = xs.astype(np.float32, copy=False)
        rows = np.arange(U)[:, None]
        test_pos = sizes[:, None] + pos[:T]
        tx, ty = xs[rows, test_pos], lab[rows, test_pos]
        valid = pos[:M] < sizes[:, None]
        x, y = xs[:, :M], lab[:, :M]
        x[~valid] = 0.0
        y[~valid] = 0
        mask = valid.astype(np.float32)
        lead = ids.shape
        out = {"x": x[inv], "y": y[inv], "mask": mask[inv],
               "test_x": tx[inv], "test_y": ty[inv],
               "test_mask": np.ones(flat.shape + (T,), np.float32)}
        return {k: v.reshape(lead + v.shape[1:]) for k, v in out.items()}

    # ------------------------------------------------------------- eval
    def eval_ids(self) -> np.ndarray:
        """Deterministic global-eval cohort: everyone when small/unset, a
        stride sample (unbiased — device streams are iid in id) when
        ``eval_cohort`` bounds it."""
        n = self.n_devices
        if self.eval_cohort is None or self.eval_cohort >= n:
            return np.arange(n, dtype=np.int64)
        e = int(self.eval_cohort)
        return (np.arange(e, dtype=np.int64) * n) // e

    def materialize(self) -> FederatedData:
        """Resident ``FederatedData`` over the full fleet — one gather of
        ``arange(N)``; rows are bit-for-bit the lazy cohort gathers."""
        d = self.gather(np.arange(self.n_devices, dtype=np.int64))
        sizes = d["mask"].sum(axis=1)
        p = sizes / sizes.sum()
        return FederatedData(x=d["x"], y=d["y"], mask=d["mask"],
                             p=p.astype(np.float32),
                             test_x=d["test_x"], test_y=d["test_y"],
                             test_mask=d["test_mask"])
