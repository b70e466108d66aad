"""Plain reference of a federated FOLB run of multinomial logistic
regression, written from the algorithm's description and the run's stated
inputs, importing nothing of the program under test.

One run: R rounds.  Round t draws K device ids (uniform, with
replacement) from the run key's split chain and a local-step budget per
device from a round-indexed stream.  Each device runs its budget of
full-batch prox-gradient steps on h_k(w) = F_k(w) + mu/2 ||w - w^t||^2
from w^t and sends its update Delta_k and its gradient grad F_k(w^t).
The stated configuration stores both in bfloat16 before they are
aggregated.  FOLB (arXiv:2007.13137, Eq. IV-C, with the staleness
discount of the deadline mode):

    g1  = sum_k m_k g_k / sum_k m_k
    I_k = <g_k, g1> (1 + tau_k)^-alpha m_k
    w   = w + sum_k I_k Delta_k / sum_k |I_k|

In deadline mode a device's upload lands at dispatch + latency, where
latency = steps * examples * flops_per_example_step / device_flops +
model_bytes / down_bw + 2 * model_bytes / up_bw.  The server waits for
every dispatched device or until ``deadline`` seconds after the round
started, whichever comes first; an update that misses its round joins the
first later round that closes after it lands, with tau = rounds late.

The population (device speeds and dataset sizes) and the per-device data
are pure functions of (seed, device id), as the lazy population is stated:
splitmix64 counter hashes and one numpy stream per device.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

# ------------------------------------------------- population and data

_U64 = np.uint64
_MASK = _U64(0xFFFFFFFFFFFFFFFF)
CH_FLOPS_U1, CH_FLOPS_U2, CH_BW_U1, CH_BW_U2, CH_STRAGGLER = 0, 1, 2, 3, 4
CH_SIZE = 7
DATA_STREAM = 0x5EED_DA7A
PROTO_STREAM = 0x9107_0CA5


def _splitmix64(x):
    with np.errstate(over="ignore"):
        x = (x + _U64(0x9E3779B97F4A7C15)) & _MASK
        x = ((x ^ (x >> _U64(30))) * _U64(0xBF58476D1CE4E5B9)) & _MASK
        x = ((x ^ (x >> _U64(27))) * _U64(0x94D049BB133111EB)) & _MASK
        return x ^ (x >> _U64(31))


def hash_uniform(seed: int, channel: int, ids) -> np.ndarray:
    with np.errstate(over="ignore"):
        key = _splitmix64(np.asarray(
            (_U64(seed & 0xFFFFFFFFFFFFFFFF) * _U64(0xD1342543DE82EF95)
             + _U64(channel) * _U64(0x9E3779B97F4A7C15)) & _MASK))
        h = _splitmix64(np.asarray(ids).astype(np.uint64) ^ key)
    return (h >> _U64(11)).astype(np.float64) * (2.0 ** -53)


def hash_normal(seed, ch1, ch2, ids):
    u1, u2 = hash_uniform(seed, ch1, ids), hash_uniform(seed, ch2, ids)
    return np.sqrt(-2.0 * np.log1p(-u1)) * np.cos(2.0 * np.pi * u2)


def device_caps(pop: dict, ids):
    """(flops, up_bw, down_bw) of each device, as the population states."""
    s = pop["seed"]
    flops = pop["flops_median"] * np.exp(
        pop["flops_sigma"] * hash_normal(s, CH_FLOPS_U1, CH_FLOPS_U2, ids))
    up = pop["up_bw_median"] * np.exp(
        pop["bw_sigma"] * hash_normal(s, CH_BW_U1, CH_BW_U2, ids))
    strag = hash_uniform(s, CH_STRAGGLER, ids) < pop["straggler_frac"]
    flops = np.where(strag, flops / pop["straggler_slowdown"], flops)
    up = np.where(strag, up / pop["straggler_slowdown"], up)
    return flops, up, up * pop["down_up_ratio"]


def lazy_sizes(data: dict, ids) -> np.ndarray:
    u = hash_uniform(data["seed"], CH_SIZE, ids)
    span = data["max_size"] - data["min_size"] + 1
    return (data["min_size"] + np.floor(u * span)).astype(np.int64)


def lazy_device(data: dict, did: int):
    """Device ``did``'s train (x, y) and test (x, y): Dirichlet(alpha)
    label proportions, Gaussian features around shared class means."""
    C, F, T = data["n_classes"], data["n_features"], data["test_size"]
    proto = np.random.default_rng(np.random.SeedSequence(
        [PROTO_STREAM, int(data["seed"])])).normal(
            0.0, data["proto_scale"], (C, F)).astype(np.float32)
    n = int(lazy_sizes(data, np.asarray([did]))[0])
    rng = np.random.default_rng(np.random.SeedSequence(
        [DATA_STREAM, int(data["seed"]), int(did)]))
    pi = rng.dirichlet(np.full(C, float(data["alpha"])))
    y = rng.choice(C, size=n, p=pi).astype(np.int32)
    ty = rng.choice(C, size=T, p=pi).astype(np.int32)
    x = proto[y] + data["noise"] * rng.standard_normal((n, F)).astype(
        np.float32)
    tx = proto[ty] + data["noise"] * rng.standard_normal((T, F)).astype(
        np.float32)
    return x, y, tx, ty


def lazy_arrays(data: dict, ids, width: int):
    """Padded (len(ids), width, F) train arrays of ``ids``."""
    F = data["n_features"]
    x = np.zeros((len(ids), width, F), np.float32)
    y = np.zeros((len(ids), width), np.int32)
    m = np.zeros((len(ids), width), np.float32)
    for i, did in enumerate(ids):
        xi, yi, _, _ = lazy_device(data, int(did))
        x[i, :len(yi)], y[i, :len(yi)], m[i, :len(yi)] = xi, yi, 1.0
    return x, y, m


def eval_cohort_ids(n_devices: int, cohort) -> np.ndarray:
    if cohort is None or cohort >= n_devices:
        return np.arange(n_devices, dtype=np.int64)
    return (np.arange(cohort, dtype=np.int64) * n_devices) // cohort


# ----------------------------------------------------------- the run


@dataclasses.dataclass
class Run:
    """What one run returns: history at the eval points and the final
    parameters."""
    rounds: list
    train_loss: list
    wall_clock: list
    n_arrived: list
    params: dict
    timed: bool = False

    def result(self):
        """This run in the shape of a program result (``history``,
        ``params``), to stand in the program's place."""
        import types
        hist = {"train_loss": self.train_loss}
        if self.timed:
            hist.update(wall_clock=self.wall_clock, n_arrived=self.n_arrived)
        return types.SimpleNamespace(history=hist, params=self.params)


def _round_keys(key, rounds: int):
    """Selection keys: the run key's split chain, each round's subkey split
    once more (first half selects)."""
    import jax
    sel = []
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        sel.append(jax.random.split(sub)[0])
    return sel


def _select(k_sel, n: int, k: int, sampler: str) -> np.ndarray:
    import jax
    import jax.numpy as jnp
    if sampler == "indexed":
        ids = jax.random.randint(k_sel, (k,), 0, n, dtype=jnp.int32)
    else:
        logits = jnp.log(jnp.maximum(jnp.full((n,), 1.0 / n), 1e-30))
        ids = jax.random.categorical(k_sel, logits, shape=(k,))
    return np.asarray(ids, np.int64)


def step_draws(t: int, k: int, max_steps: int) -> np.ndarray:
    return np.random.default_rng(10_000 + t).integers(1, max_steps + 1, k)


class _Math:
    """The jitted pieces of the reference, in one working dtype, for the
    model of the config's reference file (``init``, ``logits``)."""

    def __init__(self, model, cfg: dict, dtype: str, store_dtype: str):
        import jax
        import jax.numpy as jnp
        from jax.flatten_util import ravel_pytree
        dt = jnp.dtype(dtype)
        st = jnp.dtype(store_dtype)
        prec = "highest" if dt == jnp.float32 else None

        def loss(p, x, y, m):
            z = model.logits(cfg, p, x)
            lp = jax.nn.log_softmax(z.astype(jnp.float32), axis=-1)
            ll = jnp.take_along_axis(lp, y[:, None], axis=-1)[:, 0]
            return -jnp.sum(ll * m) / jnp.maximum(jnp.sum(m), 1.0)

        grad = jax.grad(loss)

        def solve(p0, x, y, m, n, lr, mu):
            with jax.default_matmul_precision(prec):
                x = x.astype(dt)
                g0 = grad(p0, x, y, m)

                def body(_, p):
                    g = grad(p, x, y, m)
                    return jax.tree.map(
                        lambda a, ga, a0: a - (lr * (ga + mu * (a - a0))
                                               ).astype(dt), p, g, p0)
                p = jax.lax.fori_loop(0, n, body, p0)
                up = ravel_pytree(jax.tree.map(jnp.subtract, p, p0))[0]
                gr = ravel_pytree(g0)[0]
                return up.astype(st).astype(dt), gr.astype(st).astype(dt)

        def aggregate(p, ups, grs, tau, alpha, mask):
            with jax.default_matmul_precision(prec):
                flat, unravel = ravel_pytree(p)
                m = mask.astype(dt)
                g1 = (m @ grs) / jnp.maximum(jnp.sum(m), 1)
                s = (grs @ g1) * (1.0 + tau.astype(dt)) ** (-alpha) * m
                upd = (s / jnp.maximum(jnp.sum(jnp.abs(s)), 1e-30)) @ ups
                return unravel((flat + upd).astype(dt))

        def evaluate(p, x, y, m, w):
            with jax.default_matmul_precision(prec):
                x = x.astype(dt)
                ls = jax.vmap(lambda xi, yi, mi: loss(p, xi, yi, mi))(x, y, m)
                return jnp.sum(ls.astype(jnp.float32) * w)

        self.dt = dt
        self.init = lambda: model.init(cfg, dt)
        self.solve = jax.jit(solve)
        self.aggregate = jax.jit(aggregate)
        self.evaluate = jax.jit(evaluate)


def run(spec: dict, key, dtype: str = "float32", half: bool = False) -> Run:
    """One reference run.  ``spec``: the model's reference module
    (``model``) and configuration (``config``), ``fl`` (K, lr, mu,
    max_local_steps, staleness_alpha, sampler, deadline, agg_dtype, rounds,
    eval_every) and either ``resident`` data arrays or ``lazy`` data and
    ``population`` descriptions.  With ``half`` every device trains on
    the first half of its examples only (a fault for the tests of the
    comparison)."""
    import jax.numpy as jnp
    fl = spec["fl"]
    cfg = spec["config"]
    F, C = cfg["n_features"], cfg["n_classes"]
    K, R = fl["n_selected"], fl["rounds"]
    mth = _Math(spec["model"], cfg, dtype,
                fl["agg_dtype"] if dtype == "float32" else dtype)
    dt = mth.dt
    params = mth.init()
    lr, mu = jnp.asarray(fl["lr"], dt), jnp.asarray(fl["mu"], dt)
    alpha = jnp.asarray(fl.get("staleness_alpha", 0.0), dt)

    res = spec.get("resident")
    if res is not None:
        N = res["x"].shape[0]
        ev_x, ev_y, ev_m = res["x"], res["y"], res["mask"]
        ev_p = res["p"]
        sizes = res["mask"].sum(axis=1)
    else:
        data, pop = spec["lazy"], spec["population"]
        N = data["n_devices"]
        eids = eval_cohort_ids(N, data.get("eval_cohort"))
        ev_x, ev_y, ev_m = lazy_arrays(data, eids, data["max_size"])
        es = ev_m.sum(axis=1)
        ev_p = (es / es.sum()).astype(np.float32)
    ev = [jnp.asarray(a) for a in (ev_x, ev_y, ev_m, ev_p)]

    deadline = fl.get("deadline", math.inf)
    pb = 4.0 * sum(v.size for v in params.values())
    fpe = 3.0 * 2.0 * F * C
    pending = []        # (arrival, t0, update, grad)
    clock = 0.0
    out = Run([], [], [], [], {}, math.isfinite(deadline))
    for t, k_sel in enumerate(_round_keys(_key(key), R)):
        ids = _select(k_sel, N, K, fl["sampler"])
        steps = step_draws(t, K, fl["max_local_steps"])
        if res is not None:
            x, y, m = res["x"][ids], res["y"][ids], res["mask"][ids]
        else:
            x, y, m = lazy_arrays(spec["lazy"], ids, spec["lazy"]["max_size"])
        if half:
            m = m * (np.cumsum(m, axis=1) <= np.maximum(
                m.sum(axis=1, keepdims=True) // 2, 1))
        if math.isfinite(deadline):
            ex = (lazy_sizes(spec["lazy"], ids) if res is None
                  else sizes[ids]).astype(np.float64)
            fl_, up_, dn_ = device_caps(spec["population"], ids)
            lat = steps * ex * fpe / fl_ + (pb / dn_ + 2 * pb / up_)
            arr = clock + lat
            ok = arr <= clock + deadline
            clock = float(arr.max()) if ok.all() else clock + deadline
        else:
            arr = np.full(K, clock)
            ok = np.ones(K, bool)
        ups, grs = [], []
        for i in range(K):
            u_, g_ = mth.solve(params, x[i], y[i], m[i], int(steps[i]), lr,
                               mu)
            ups.append(u_)
            grs.append(g_)
        due = [p for p in pending if p[0] <= clock]
        pending = [p for p in pending if p[0] > clock]
        rows_u = [ups[i] for i in range(K) if ok[i]]
        rows_g = [grs[i] for i in range(K) if ok[i]]
        taus = [0.0] * len(rows_u)
        for p in due:
            rows_u.append(p[2])
            rows_g.append(p[3])
            taus.append(float(t - p[1]))
        pending += [(arr[i], t, ups[i], grs[i]) for i in range(K)
                    if not ok[i]]
        if rows_u:
            params = mth.aggregate(params, jnp.stack(rows_u),
                                   jnp.stack(rows_g), jnp.asarray(taus, dt),
                                   alpha, jnp.ones((len(rows_u),)))
        if t % fl["eval_every"] == 0 or t == R - 1:
            out.rounds.append(t)
            out.train_loss.append(float(mth.evaluate(params, *ev)))
            out.wall_clock.append(clock)
            out.n_arrived.append(int(ok.sum()) + len(due))
    out.params = {k: np.asarray(v, np.float32) for k, v in params.items()}
    return out


def _key(key):
    import jax
    return jax.random.PRNGKey(key) if isinstance(key, int) else key
