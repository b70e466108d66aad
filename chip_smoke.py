#!/usr/bin/env python3
"""Smoke run of the FOLB round on a TPU, through the entry points users call.

  python chip_smoke.py               # phases paper, flat100m, fed100m; 1 chip
  python chip_smoke.py --four-chips  # only the D-sharded flat100m round on 4
                                     # chips, against the same round on chip 0

Phases:

  paper     ``repro.fed.run`` on Synthetic(1,1) with MCLR at the paper's
            widths (30 devices, K=10): the sync scan engine and the async
            deadline engine on a seeded heterogeneous fleet.  One round's
            fp32 flat buffers go through ``ops.folb_aggregate_buffers`` and
            ``ops.folb_staleness_buffers`` and are checked against
            ``kernels.ref``.
  flat100m  two compiled sync FOLB rounds of the ~1.0e8-parameter MLP with
            bf16 (K, D) buffers, and one aggregation at that D checked
            against ``kernels.ref`` on the same buffers upcast to fp32.
  fed100m   the production round (``fed.distributed.folb_round``, driven as
            ``repro.launch.train`` drives it) of ``configs/fed100m`` at its
            published widths: K=4 clients, E=2, 4x512 tokens per client,
            3 rounds on the flat route; one round of each aggregation route
            from the same params must agree.

Every phase's round program must contain the Mosaic kernel
(``tpu_custom_call``) in its lowered text, so a kernel that fell back to
einsum or to the interpreter fails the run.  Lines before the last are
information (shapes, compile and round seconds, peak device memory, each
check with its error and tolerance), not benchmark metrics.  The last line
of stdout is one JSON object, ``{"ok": ..., "device": {"platform", "kind",
"count"}}``.  Exit code 0 only when every phase ran and every check
passed; 2, with no result printed, when JAX finds no TPU.

Set ``JAX_COMPILATION_CACHE_DIR`` to place the persistent compile cache;
otherwise it lives in ``.jax_cache`` of this checkout.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import re
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

KERNEL_MARKER = "tpu_custom_call"

PAPER_ROUNDS = 10
FLAT_HIDDEN = 10_000        # the ~1.0e8-parameter MLP of the sharded tests
FLAT_K = 8                  # sized by the described-v5e compile (PERF.md)
FLAT_ROUNDS = 2
# the four-chip round compares with fp32 buffers against one chip, where
# K=8 would need 15.4 GiB (described-v5e compile, PERF.md)
FOUR_K = 4
FED_K, FED_E, FED_SEQS, FED_SEQ_LEN, FED_ROUNDS = 4, 2, 4, 512, 3

# fp32 reduction-order tolerances, relative to the reference's largest
# magnitude.  The score tolerance at D ~ 1e8 allows for ~3000 sequentially
# accumulated tile partial sums.
PAPER_TOL = 1e-5
FLAT_W_TOL, FLAT_SCORE_TOL = 1e-5, 1e-4
# tests/test_sharded_agg.py: flat route with bf16 buffers vs the scan route,
# and the 2-shard vs 1-device aggregation
ROUTE_ATOL = 5e-3
SHARDED_ATOL = 1e-5
# The whole sharded round against the one-device round (abs).  Only the
# aggregation is sharded (the local solves run whole on every chip), but
# its per-shard score sums round in another order, and the second round's
# solves carry that difference on: 2.26e-5 on four v5e chips at hidden
# 10 000, K=4, fp32 buffers (PERF.md), so 1e-4 leaves a margin of 4.4x.
# A missing shard or psum puts the scores off by O(10%) and the params by
# O(1e-2).
ROUND_ATOL = 1e-4


class Smoke:
    """Collects check results and the lowered program text of a run.

    ``expect_kernel`` asks each phase's round program for the Mosaic
    kernel; it is off only for CPU rehearsals, where Pallas interprets.
    """

    def __init__(self, expect_kernel: bool = True):
        import jax
        self.expect_kernel = expect_kernel
        self.failures: list = []
        # JAX's backend-compile duration also covers loading a program
        # from the persistent cache (cache_load_s); cache_writes counts
        # programs compiled and stored, the only misses JAX reports
        self.compile_s = 0.0
        self.cache_load_s = 0.0
        self.cache_hits = 0
        self.cache_writes = 0
        self._at_last_peak = self._counters()
        self._dump_dir = None
        self._prev_dump = None
        self._jax = jax

    def _counters(self) -> dict:
        return {"compile_s": self.compile_s, "cache_load_s": self.cache_load_s,
                "cache_hits": self.cache_hits,
                "cache_writes": self.cache_writes}

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_load_s += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def __enter__(self):
        mon = self._jax.monitoring
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        self._dump_dir = tempfile.mkdtemp(prefix="chip_smoke_ir_")
        self._prev_dump = self._jax.config.values["jax_dump_ir_to"]
        self._jax.config.update("jax_dump_ir_to", self._dump_dir)
        return self

    def __exit__(self, *exc):
        self._jax.config.update("jax_dump_ir_to", self._prev_dump)
        shutil.rmtree(self._dump_dir, ignore_errors=True)
        mon = self._jax.monitoring
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)
        return False

    def info(self, phase: str, **kv) -> None:
        print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
              flush=True)

    def check(self, phase: str, name: str, passed: bool, detail: str) -> None:
        print(f"[{phase}] check {name}: {'pass' if passed else 'FAIL'} "
              f"({detail})", flush=True)
        if not passed:
            self.failures.append(f"{phase}/{name}")

    def close(self, phase, name, err, tol) -> None:
        self.check(phase, name, bool(err <= tol), f"err={err!r} tol={tol!r}")

    @contextlib.contextmanager
    def programs(self, phase: str, name: str, jit_name: str):
        """Check that the programs named ``jit_name`` lowered inside the
        block carry the Mosaic kernel; yields the list their text fills."""
        before = set(os.listdir(self._dump_dir))
        texts: list = []
        yield texts
        pat = re.compile(rf"_jit_{re.escape(jit_name)}_compile\.mlir$")
        for f in sorted(set(os.listdir(self._dump_dir)) - before):
            if pat.search(f):
                with open(os.path.join(self._dump_dir, f)) as fh:
                    texts.append(fh.read())
        if not self.expect_kernel:
            self.check(phase, f"{name} lowered", bool(texts),
                       f"{len(texts)} jit_{jit_name} program(s)")
            return
        n = sum(KERNEL_MARKER in t for t in texts)
        self.check(phase, f"{name} has {KERNEL_MARKER}",
                   bool(texts) and n == len(texts),
                   f"{n}/{len(texts)} jit_{jit_name} program(s)")

    def peak(self, phase: str) -> None:
        """Peak device memory so far, and the compile counters since the
        previous call."""
        stats = self._jax.devices()[0].memory_stats() or {}
        now = self._counters()
        since = {k: now[k] - self._at_last_peak[k] for k in now}
        self._at_last_peak = now
        self.info(phase, peak_bytes_in_use=stats.get("peak_bytes_in_use",
                                                     "not reported"),
                  **since)


def _rel_err(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def _finite_tree(tree) -> bool:
    import jax
    import jax.numpy as jnp
    return all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(tree))


def _ref_aggregate(w, deltas, grads, pg):
    """``kernels.ref.folb_aggregate_ref`` on the buffers upcast to fp32,
    with fp32 matmuls (the TPU's default precision would round the
    reference's einsums to bf16)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref

    @jax.jit
    def go(w, deltas, grads, pg):
        g32 = grads.astype(jnp.float32)
        g1 = jnp.mean(g32, axis=0)
        return ref.folb_aggregate_ref(w, deltas.astype(jnp.float32), g32,
                                      g1, pg, jnp.sum(g1 * g1))

    with jax.default_matmul_precision("highest"):
        return go(w, deltas, grads, pg)


def _ref_stale(w, deltas, grads, tau, alpha, pg, mask):
    import jax
    from repro.kernels import ref
    with jax.default_matmul_precision("highest"):
        return jax.jit(ref.folb_aggregate_stale_ref)(w, deltas, grads, tau,
                                                     alpha, pg, mask)


def _timed(fn):
    """(fn(), seconds) with the result's arrays ready; a ``fed.run``
    result is timed to its final params."""
    import jax
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(getattr(out, "params", out))
    return out, time.perf_counter() - t0


# ------------------------------------------------------------------ phases

def phase_paper(s: Smoke, rounds: int = PAPER_ROUNDS) -> None:
    """The front door at the paper's widths, sync and async deadline."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import fed as fed_api
    from repro.configs.paper_models import MCLR
    from repro.core import flat as flat_lib
    from repro.data.federated import stack_devices
    from repro.data.synthetic import synthetic_alpha_beta
    from repro.fed import simulator
    from repro.fed.async_engine import AsyncFLConfig
    from repro.kernels import ops
    from repro.models import small
    from repro.sysmodel import (expected_latencies, heterogeneous_fleet,
                                round_cost_for)
    ph = "paper"
    data = stack_devices(synthetic_alpha_beta(
        seed=0, n_devices=30, alpha=1.0, beta=1.0, mean_size=120), seed=0)
    fl = simulator.FLConfig(algo="folb", n_selected=10, mu=1.0, lr=0.05,
                            seed=0)
    s.info(ph, devices=data.n_devices, x=tuple(data.x.shape), K=10,
           rounds=rounds)

    with s.programs(ph, "sync round", "scan_rounds"):
        _, first_s = _timed(lambda: fed_api.run(MCLR, data, fl, rounds,
                                                engine="scan"))
    res, warm_s = _timed(lambda: fed_api.run(MCLR, data, fl, rounds,
                                             engine="scan"))
    loss = res["train_loss"]
    s.info(ph, sync_first_run_s=first_s, sync_warm_run_s=warm_s,
           loss_first=loss[0], loss_last=loss[-1])
    s.check(ph, "sync loss finite", bool(np.isfinite(loss).all()),
            f"{len(loss)} evals")
    s.check(ph, "sync loss falls", loss[-1] < loss[0],
            f"{loss[0]!r} -> {loss[-1]!r}")

    params0 = small.init_small(MCLR, jax.random.PRNGKey(0))
    fleet = heterogeneous_fleet(0, data.n_devices, straggler_frac=0.3,
                                straggler_slowdown=25.0)
    lat = expected_latencies(fleet, round_cost_for(MCLR, params0),
                             mean_steps=10.5,
                             n_examples=np.asarray(data.mask.sum(1)))
    afl = AsyncFLConfig(mode="deadline", algo="folb", n_selected=10, mu=1.0,
                        lr=0.05, deadline=float(np.quantile(lat, 0.9)),
                        staleness_alpha=0.5, seed=0)
    with s.programs(ph, "deadline round", "scan_async_deadline"):
        ares, async_s = _timed(lambda: fed_api.run(MCLR, data, afl, rounds,
                                                   fleet=fleet))
    aloss = ares["train_loss"]
    s.info(ph, deadline_first_run_s=async_s, deadline_s=afl.deadline,
           stale_mean=ares["stale_mean"], n_arrived=ares["n_arrived"])
    s.check(ph, "deadline loss finite", bool(np.isfinite(aloss).all()),
            f"{len(aloss)} evals")
    s.check(ph, "deadline loss falls", aloss[-1] < aloss[0],
            f"{aloss[0]!r} -> {aloss[-1]!r}")
    s.check(ph, "deadline rounds saw stale arrivals",
            max(ares["stale_mean"]) > 0.0, f"stale_mean={ares['stale_mean']}")

    # one round's fp32 flat buffers through the kernels vs kernels.ref
    train = {"x": jnp.asarray(data.x), "y": jnp.asarray(data.y),
             "mask": jnp.asarray(data.mask)}
    ids = jax.random.choice(jax.random.PRNGKey(1), data.n_devices, (10,))
    deltas, grads, _ = simulator._local_updates(
        MCLR, params0, train, ids, simulator.local_step_draws(0, 10, fl), fl)
    spec = flat_lib.spec_of(params0)
    w = flat_lib.ravel(spec, params0)
    d = flat_lib.ravel_stacked(spec, deltas)
    g = flat_lib.ravel_stacked(spec, grads)
    pg = jnp.zeros((10,), jnp.float32)
    s.info(ph, flat_buffers=tuple(d.shape), dtype=str(d.dtype))
    wk, sk = ops.folb_aggregate_buffers(w, d, g, pg)
    wr, sr = _ref_aggregate(w, d, g, pg)
    s.close(ph, "folb_aggregate_buffers w vs ref", _rel_err(wk, wr),
            PAPER_TOL)
    s.close(ph, "folb_aggregate_buffers scores vs ref", _rel_err(sk, sr),
            PAPER_TOL)
    tau = jnp.asarray([0., 0., 1., 0., 2., 0., 3., 1., 0., 5.])
    mask = jnp.asarray([1., 1., 1., 0., 1., 1., 1., 0., 1., 1.])
    wk, sk = ops.folb_staleness_buffers(w, d, g, tau, 0.5, pg, mask)
    wr, sr = _ref_stale(w, d, g, tau, jnp.float32(0.5), pg, mask)
    s.close(ph, "folb_staleness_buffers w vs ref", _rel_err(wk, wr),
            PAPER_TOL)
    s.close(ph, "folb_staleness_buffers scores vs ref", _rel_err(sk, sr),
            PAPER_TOL)
    s.peak(ph)


def _flat_setup(hidden: int, k: int):
    from repro.configs.paper_models import SmallModelConfig
    from repro.data.federated import stack_devices
    from repro.data.synthetic import synthetic_alpha_beta
    from repro.fed.simulator import FLConfig
    big = SmallModelConfig(name="fed100m-mlp", kind="mlp", n_features=60,
                           n_classes=10, hidden=hidden)
    data = stack_devices(synthetic_alpha_beta(0, 10, 1.0, 1.0, mean_size=20),
                         seed=0)
    fl = FLConfig(algo="folb", n_selected=k, max_local_steps=1, seed=0)
    return big, data, fl


def _random_buffers(D: int, k: int):
    """Seeded (D,) fp32 params and (K, D) bf16 deltas/grads whose grads
    share a common direction, as clients of one round do."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 5)
        w = jax.random.normal(ks[0], (D,), jnp.float32)
        common = jax.random.normal(ks[1], (D,), jnp.float32)
        grads = (common + 0.5 * jax.random.normal(ks[2], (k, D))).astype(
            jnp.bfloat16)
        deltas = (0.01 * jax.random.normal(ks[3], (k, D))).astype(
            jnp.bfloat16)
        pg = 0.05 * jnp.abs(jax.random.normal(ks[4], (k,)))
        return w, deltas, grads, pg

    return make(jax.random.PRNGKey(7))


def phase_flat100m(s: Smoke, hidden: int = FLAT_HIDDEN, k: int = FLAT_K,
                   rounds: int = FLAT_ROUNDS) -> None:
    """Compiled sync FOLB at D ~ 1e8 on one device, bf16 buffers."""
    import jax
    import numpy as np
    from repro import fed as fed_api
    from repro.kernels import ops
    ph = "flat100m"
    big, data, fl = _flat_setup(hidden, k)
    with s.programs(ph, "sync round", "scan_rounds"):
        res, first_s = _timed(lambda: fed_api.run(big, data, fl, rounds,
                                                  engine="scan"))
    del res
    res, warm_s = _timed(lambda: fed_api.run(big, data, fl, rounds,
                                             engine="scan"))
    n_params = sum(x.size for x in jax.tree.leaves(res.params))
    loss = res["train_loss"]
    s.info(ph, n_params=n_params, K=k, rounds=rounds, buf_dtype=fl.agg_dtype,
           first_run_s=first_s, warm_run_s=warm_s, loss=loss)
    s.check(ph, "loss finite", bool(np.isfinite(loss).all()), f"{loss}")
    s.check(ph, "params finite", _finite_tree(res.params),
            f"{n_params} params")
    del res

    from repro.kernels.folb_aggregate import TILE_D
    D = -(-n_params // TILE_D) * TILE_D
    w, d, g, pg = _random_buffers(D, k)
    wk, sk = ops.folb_aggregate_buffers(w, d, g, pg)
    wr, sr = _ref_aggregate(w, d, g, pg)
    s.info(ph, agg_buffers=tuple(d.shape), dtype=str(d.dtype))
    s.close(ph, "folb_aggregate_buffers w vs ref", _rel_err(wk, wr),
            FLAT_W_TOL)
    s.close(ph, "folb_aggregate_buffers scores vs ref", _rel_err(sk, sr),
            FLAT_SCORE_TOL)
    s.peak(ph)


def phase_fed100m(s: Smoke, cfg=None, k: int = FED_K, local_steps: int = FED_E,
                  seqs: int = FED_SEQS, seq_len: int = FED_SEQ_LEN,
                  rounds: int = FED_ROUNDS) -> None:
    """The production round of fed100m, as ``launch.train`` drives it."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.fed.distributed import RoundConfig
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import init_placed, make_round_batches, make_step
    ph = "fed100m"
    cfg = cfg or get_config("fed100m")
    rc_flat = RoundConfig(algo="folb", n_clients=k, local_steps=local_steps,
                          lr=0.05, mu=0.01, remat=True, agg_backend="flat")
    rc_scan = dataclasses.replace(rc_flat, agg_backend="scan")
    mesh = make_host_mesh(1)
    params, p_shard = init_placed(cfg, mesh, seed=0)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    batches = make_round_batches(cfg, k, seqs, seq_len, rounds, seed=0)
    s.info(ph, arch=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
           vocab=cfg.vocab, n_params=n_params, K=k, E=local_steps,
           tokens=tuple(batches[0]["tokens"].shape), rounds=rounds)

    step_flat = make_step(cfg, rc_flat, mesh, p_shard)
    step_scan = make_step(cfg, rc_scan, mesh, p_shard)
    with s.programs(ph, "flat round", "step"):
        (p_flat, m_flat), first_s = _timed(
            lambda: step_flat(params, batches[0]))
    (p_scan, m_scan), scan_first_s = _timed(
        lambda: step_scan(params, batches[0]))
    err = max(float(np.max(np.abs(np.asarray(a, np.float32)
                                  - np.asarray(b, np.float32))))
              for a, b in zip(jax.tree.leaves(p_scan),
                              jax.tree.leaves(p_flat)))
    s.info(ph, flat_first_round_s=first_s, scan_first_round_s=scan_first_s,
           loss_flat=float(m_flat["client_loss"]),
           loss_scan=float(m_scan["client_loss"]))
    s.close(ph, "flat route vs scan route params (abs)", err, ROUTE_ATOL)
    del p_flat, p_scan

    losses, times = [], []
    for batch in batches:
        (params, m), dt = _timed(lambda: step_flat(params, batch))
        losses.append(float(m["client_loss"]))
        times.append(dt)
    s.info(ph, round_s=times, client_loss=losses)
    s.check(ph, "loss finite", bool(np.isfinite(losses).all()), f"{losses}")
    s.check(ph, "params finite", _finite_tree(params), f"{n_params} params")
    s.peak(ph)


def phase_four_chips(s: Smoke, hidden: int = FLAT_HIDDEN, k: int = FOUR_K,
                     rounds: int = FLAT_ROUNDS, n_chips: int = 4) -> None:
    """The flat100m round with its aggregation D-sharded over ``n_chips``
    devices, against the same round with ``mesh=None`` on device 0."""
    import jax
    import numpy as np
    from repro import fed as fed_api
    from repro.kernels import ops
    from repro.kernels.folb_aggregate import shard_alignment
    from repro.sharding.specs import folb_mesh
    ph = "four_chips"
    mesh = folb_mesh(n_chips)
    big, data, fl = _flat_setup(hidden, k)
    # fp32 buffers: the two programs may round a client's fp32 delta one
    # ulp apart, and a bf16 buffer can turn that into one bf16 ulp
    fl = dataclasses.replace(fl, agg_dtype="float32")
    with s.programs(ph, "sharded round", "scan_rounds") as texts:
        sharded, sharded_s = _timed(lambda: fed_api.run(
            big, data, fl, rounds, engine="scan", mesh=mesh))
    n_ar = [len(re.findall(r"stablehlo\.all_reduce|all-reduce", t))
            for t in texts]
    single, single_s = _timed(lambda: fed_api.run(
        big, data, fl, rounds, engine="scan"))
    single, sharded = single.params, sharded.params
    err = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
              for a, b in zip(jax.tree.leaves(single),
                              jax.tree.leaves(sharded)))
    n_params = sum(x.size for x in jax.tree.leaves(single))
    s.info(ph, mesh=dict(mesh.shape), n_params=n_params, K=k, rounds=rounds,
           buf_dtype=fl.agg_dtype,
           sharded_first_run_s=sharded_s, single_first_run_s=single_s,
           lowered_all_reduces=n_ar)
    s.close(ph, "sharded vs device-0 params (abs)", err, ROUND_ATOL)
    s.check(ph, "params finite", _finite_tree(sharded), f"{n_params} params")
    del single, sharded

    # the sharded aggregation alone at the round's (K, D) with bf16
    # buffers: one kernel sweep per phase per shard and one (K+1,)
    # all-reduce between them in the compiled text; equal to the
    # single-device kernel on the same buffers
    D = -(-n_params // shard_alignment(mesh)) * shard_alignment(mesh)
    w, d, g, pg = _random_buffers(D, k)
    text = ops.folb_aggregate_buffers.lower(
        w, d, g, pg, mesh=mesh).compile().as_text()
    n_all_reduce = len(re.findall(r"\ball-reduce(?:-start)?\(", text))
    has_kernel = KERNEL_MARKER in text
    s.check(ph, "compiled aggregation: kernel and one all-reduce",
            (has_kernel or not s.expect_kernel) and n_all_reduce == 1,
            f"{KERNEL_MARKER}={has_kernel} all-reduce={n_all_reduce}")
    wm, sm = ops.folb_aggregate_buffers(w, d, g, pg, mesh=mesh)
    ws, ss = ops.folb_aggregate_buffers(w, d, g, pg)
    s.close(ph, "sharded vs device-0 aggregation w (abs)",
            float(np.max(np.abs(np.asarray(wm) - np.asarray(ws)))),
            SHARDED_ATOL)
    s.close(ph, "sharded vs device-0 aggregation scores",
            _rel_err(sm, ss), FLAT_SCORE_TOL)
    s.peak(ph)


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the D-sharded flat100m round on 4 chips "
                         "and its comparison with chip 0")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, "
              f"{len(jax.devices())} device(s))", file=sys.stderr)
        return 2
    if args.four_chips and len(jax.devices()) < 4:
        print(f"chip_smoke: --four-chips needs 4 TPU chips, found "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    print(f"[setup] device={dev.device_kind} count={len(jax.devices())} "
          f"jax={jax.__version__} compile_cache={cache_dir}", flush=True)

    phases = ([phase_four_chips] if args.four_chips
              else [phase_paper, phase_flat100m, phase_fed100m])
    t_start = time.perf_counter()
    with Smoke() as s:
        for phase in phases:
            t0 = time.perf_counter()
            try:
                phase(s)
            except Exception as e:  # noqa: BLE001 - report, go on, fail
                import traceback
                traceback.print_exc()
                s.failures.append(f"{phase.__name__}: {e!r}")
            print(f"[{phase.__name__}] wall_s={time.perf_counter() - t0!r}",
                  flush=True)
    print(f"[done] wall_s={time.perf_counter() - t_start!r} "
          + " ".join(f"{k}={v!r}" for k, v in s._counters().items())
          + f" failures={s.failures}", flush=True)
    ok = not s.failures
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
