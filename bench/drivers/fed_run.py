"""Driver: whole federated runs through ``repro.fed.run``, one researcher's
call after another (a closed loop with one caller).

The traffic file says which kind of run:

- ``"data": "lazy"``: a ``PopulationSpec`` fleet and ``LazyFederatedData``
  of ``n_devices`` devices, an async ``AsyncFLConfig``.  The deadline
  plan's pool shapes depend on the run key, so the window cycles over a
  fixed set of ``n_keys`` run keys, every one warmed up in set-up, in an
  order drawn from the seed.
- ``"data": "resident"``: Synthetic(alpha, beta) devices made by the
  benchmark from the seed (fixed sizes), a sync ``FLConfig``; every call
  gets a fresh run key from the seed and the call index.

A unit of work is one call of ``rounds`` rounds, timed until its params
and history are on the host.  After the window a sample of the calls,
drawn from the seed, is compared with the plain reference
(``bench/reference/fedsim.py``).
"""
from __future__ import annotations

import time

import numpy as np

from bench.core import counts, gen, refs
from bench.reference import fedsim

ROUND_PROGRAMS = ("jit_scan_rounds", "jit_scan_deadline_cohort",
                  "jit_scan_fedbuff_cohort")


class Profiler:
    """``profiler=`` for ``fed.run``: host seconds per phase, each phase
    also a ``phase:<name>`` span on the profiler's trace.  A phase ends
    only when the device work it dispatched has finished (it waits for
    every live array), so its time is its own; used in traced runs only."""

    def __init__(self):
        import jax
        self._jax = jax
        self._ann = jax.profiler.TraceAnnotation
        self.seconds = {}

    def phase(self, name):
        return _Phase(self, name)

    def finish(self):
        return None


class _Phase:
    def __init__(self, prof, name):
        self.prof, self.name = prof, name

    def __enter__(self):
        self.ann = self.prof._ann(f"phase:{self.name}")
        self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        jax = self.prof._jax
        jax.block_until_ready(jax.live_arrays())
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        s = self.prof.seconds
        s[self.name] = s.get(self.name, 0.0) + dt
        return False


class Session:
    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg = ctx.config
        self.tr = ctx.traffic
        self.calls = []          # (key index, key, result) of window calls
        self.profiler = None

    # ------------------------------------------------------------ set-up
    def setup_inputs(self):
        import jax
        from repro.configs.paper_models import SmallModelConfig
        from repro.data.federated import FederatedData, LazyFederatedData
        from repro.fed.async_engine import AsyncFLConfig
        from repro.fed.simulator import FLConfig
        from repro.sysmodel import PopulationSpec
        tr, seed = self.tr, self.ctx.seed
        self.model = SmallModelConfig(
            name=self.cfg["name"], kind=self.cfg["kind"],
            n_features=self.cfg["n_features"],
            n_classes=self.cfg["n_classes"])
        fl = dict(tr["fl"])
        self.rounds, self.eval_every = tr["rounds"], tr["eval_every"]
        if tr["data"] == "lazy":
            self.fleet = PopulationSpec(**tr["population"])
            self.data = LazyFederatedData(**tr["lazy"])
            self.flcfg = AsyncFLConfig(**fl)
            base = tr["key_base"]
            self.keys = [jax.random.PRNGKey(base + i)
                         for i in range(tr["n_keys"])]
            self.order = np.random.default_rng(
                gen.seed_ints(seed, 2, 2)).permutation(tr["n_keys"])
            self.sizes_of = lambda ids: fedsim.lazy_sizes(tr["lazy"], ids)
        else:
            syn = tr["synthetic"]
            self.arrays = gen.synthetic_alpha_beta(
                seed, syn["n_devices"], syn["alpha"], syn["beta"],
                self.cfg["n_features"], self.cfg["n_classes"],
                syn["mean_size"], syn["test_frac"])
            self.data = FederatedData(**self.arrays)
            self.fleet = None
            self.flcfg = FLConfig(**fl)
            self.keys = None
            sizes = self.arrays["mask"].sum(axis=1)
            self.sizes_of = lambda ids: sizes[ids]
        self.fpe = counts.mclr_flops_per_example_step(
            self.cfg["n_features"], self.cfg["n_classes"])

    def setup(self):
        t0 = time.perf_counter()
        self.setup_inputs()
        t1 = time.perf_counter()
        n_warm = len(self.keys) if self.keys is not None else 2
        for i in range(n_warm):
            self._call(i)
        self.n = 0
        self.setup_phases = {"inputs_s": t1 - t0,
                             "warm_s": time.perf_counter() - t1}

    def _key(self, i):
        import jax
        if self.keys is not None:
            j = int(self.order[i % len(self.order)])
            return j, self.keys[j]
        return i, jax.random.PRNGKey(gen.seed_ints(self.ctx.seed, 100 + i)[0])

    def _call(self, i):
        import jax
        from repro import fed
        j, key = self._key(i)
        res = fed.run(self.model, self.data, self.flcfg, self.rounds,
                      fleet=self.fleet, eval_every=self.eval_every, key=key,
                      profiler=self.profiler)
        jax.block_until_ready(res.params)
        return j, key, res

    def trace_on(self):
        self.profiler = Profiler()

    # ------------------------------------------------------------ window
    def unit(self):
        rec = self._call(self.n)
        self.n += 1
        self.calls.append(rec)
        return rec

    def work(self, records) -> dict:
        """Rounds, model FLOPs and aggregations of the given calls: every
        dispatched device's local steps over its own examples."""
        flops = 0.0
        for _, _, res in records:
            ids = np.asarray(res.ids)
            steps = np.stack([fedsim.step_draws(
                t, ids.shape[1], self.flcfg.max_local_steps)
                for t in range(ids.shape[0])])
            flops += float(np.sum(steps * self.sizes_of(ids))) * self.fpe
        return {"rounds": len(records) * self.rounds, "flops": flops}

    def finish(self):
        pass

    # ------------------------------------------------------------ check
    def check(self) -> dict:
        """Compare a seeded sample of the window's calls with the plain
        reference: the worst relative gap of the train loss over the eval
        points, the worst leaf's relative distance of the final params,
        and (deadline runs) the eval points whose arrivals or clock differ.
        """
        tr = self.tr
        n_check = min(tr["check_calls"], len(self.calls))
        pick = np.random.default_rng(gen.seed_ints(self.ctx.seed, 3, 2)) \
            .choice(len(self.calls), n_check, replace=False)
        worst = {}
        spec = self.reference_spec()
        for c in sorted(pick):
            _, key, res = self.calls[int(c)]
            ref = fedsim.run(spec, key)
            for k, v in compare(res, ref).items():
                worst[k] = max(worst.get(k, 0.0), v)
        return worst

    # ------------------------------------------------------ calibration
    def _cal_keys(self):
        if self.keys is not None:
            return list(range(len(self.keys)))
        return [0, 1]

    def calibration_program(self) -> dict:
        """The comparison over every fixed run key (lazy) or two fresh ones
        (resident), without a window."""
        spec = self.reference_spec()
        worst = {}
        for i in self._cal_keys():
            _, key, res = self._call(i) if self.keys is None else \
                self._call(list(self.order).index(i))
            ref = fedsim.run(spec, key)
            for k, v in compare(res, ref).items():
                worst[k] = max(worst.get(k, 0.0), v)
        return worst

    def calibration_upper(self):
        """(kind, numbers) with the control (the reference in bfloat16) and
        the half-batch fault in the program's place, on one run key."""
        import jax
        self.setup_inputs()
        spec = self.reference_spec()
        key = (self.keys[self.ctx.seed % len(self.keys)] if self.keys
               is not None else jax.random.PRNGKey(
                   gen.seed_ints(self.ctx.seed, 100)[0]))
        ref = fedsim.run(spec, key)
        yield "control", compare(fedsim.run(spec, key, "bfloat16").result(),
                                 ref)
        yield "half_batch", compare(fedsim.run(spec, key, half=True).result(),
                                    ref)

    def reference_spec(self) -> dict:
        tr = self.tr
        spec = {"model": refs.config_module(self.ctx), "config": self.cfg,
                "fl": dict(tr["fl"], rounds=self.rounds,
                           eval_every=self.eval_every)}
        if tr["data"] == "lazy":
            spec["lazy"] = tr["lazy"]
            spec["population"] = tr["population"]
        else:
            spec["resident"] = self.arrays
        return spec


def compare(res, ref) -> dict:
    """Numbers compared between one program call and its reference run:
    the worst relative gap of the train loss over every eval point, the
    worst leaf's distance of the final params (over the larger of that
    leaf's and the median leaf's norm), and for deadline runs the eval
    points whose arrivals or clock differ."""
    lp = np.asarray(res.history["train_loss"], np.float64)
    lr = np.asarray(ref.train_loss, np.float64)
    out = {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr)))}
    norms = {k: float(np.linalg.norm(r)) for k, r in ref.params.items()}
    med = float(np.median(list(norms.values())))
    out["param_gap"] = max(
        float(np.linalg.norm(np.asarray(res.params[k], np.float64) - r))
        / max(norms[k], med, 1e-30) for k, r in ref.params.items())
    if "wall_clock" in res.history:
        cp = np.asarray(res.history["wall_clock"])
        cr = np.asarray(ref.wall_clock)
        ap = np.asarray(res.history["n_arrived"])
        ar = np.asarray(ref.n_arrived)
        bad = (ap != ar) | (np.abs(cp - cr) > 1e-9 * np.maximum(cr, 1e-9))
        out["plan_mismatch"] = float(np.sum(bad))
    return out
