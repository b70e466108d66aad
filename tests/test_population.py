"""Lazy-population tests: PopulationSpec gather/materialize parity, the
partitioners' determinism and non-IID shape, fleet-construction speed at
100k devices, and the headline equivalence contract — a lazy run over
``(PopulationSpec, LazyFederatedData)`` is bit-for-bit the materialized
run of the same config at small N, for sync / deadline / fedbuff and
both aggregation dtypes."""
import hashlib
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro import fed
from repro.configs.paper_models import MCLR
from repro.data import partition
from repro.data.federated import LazyFederatedData, _class_prototypes
from repro.fed.async_engine import AsyncFLConfig, build_plan, plan_digest
from repro.fed.simulator import FLConfig
from repro.models import small
from repro.sysmodel import (PopulationSpec, ScenarioConfig,
                            heterogeneous_fleet, round_cost_for)

N = 24
SPEC = PopulationSpec(n_devices=N, seed=7, straggler_frac=0.4,
                      straggler_slowdown=20.0, avail_frac=0.3)
DATA = LazyFederatedData(n_devices=N, seed=3)


# --------------------------------------------------------------------------
# PopulationSpec: lazy gathers == materialized fancy indexing
# --------------------------------------------------------------------------

class TestPopulationSpec:
    def _ids(self):
        rng = np.random.default_rng(0)
        # duplicates and a 2-D shape on purpose: gathers must be pure
        # elementwise functions of the id
        return rng.integers(0, 500, size=(3, 7))

    def test_gather_caps_matches_materialize(self):
        spec = PopulationSpec(n_devices=500, seed=11, straggler_frac=0.3)
        fleet = spec.materialize()
        ids = self._ids()
        flops, up_bw, down_bw = spec.gather_caps(ids)
        assert np.array_equal(flops, fleet.flops[ids])
        assert np.array_equal(up_bw, fleet.up_bw[ids])
        assert np.array_equal(down_bw, fleet.down_bw[ids])

    def test_gather_avail_matches_materialize(self):
        spec = PopulationSpec(n_devices=500, seed=11, avail_frac=0.5)
        fleet = spec.materialize()
        ids = self._ids()
        period, duty, phase = spec.gather_avail(ids)
        assert np.array_equal(period, fleet.avail_period[ids])
        assert np.array_equal(duty, fleet.avail_duty[ids])
        assert np.array_equal(phase, fleet.avail_phase[ids])
        assert not spec.always_on
        # some but not all devices cycle at avail_frac=0.5
        assert 0 < (period > 0).sum() < period.size

    @pytest.mark.parametrize("t", [0.0, 137.5, 4242.0])
    def test_online_windows_match_fleet(self, t):
        spec = PopulationSpec(n_devices=500, seed=11, avail_frac=0.5)
        fleet = spec.materialize()
        ids = self._ids().reshape(-1)
        assert np.array_equal(spec.online_at(ids, t), fleet.online_at(ids, t))
        assert np.array_equal(spec.next_online(ids, t),
                              fleet.next_online(ids, t))

    def test_always_on_skips_cycling(self):
        spec = PopulationSpec(n_devices=100, seed=1)
        assert spec.always_on
        ids = np.arange(100)
        assert spec.online_at(ids, 999.0).all()
        assert np.array_equal(spec.next_online(ids, 7.0), np.full(100, 7.0))

    def test_gathers_deterministic_across_instances(self):
        a = PopulationSpec(n_devices=10**6, seed=5)
        b = PopulationSpec(n_devices=10**6, seed=5)
        ids = np.array([0, 1, 999_999, 123_456])
        assert all(np.array_equal(x, y) for x, y in
                   zip(a.gather_caps(ids), b.gather_caps(ids)))

    def test_seed_changes_fleet(self):
        ids = np.arange(64)
        f5 = PopulationSpec(n_devices=64, seed=5).gather_caps(ids)[0]
        f6 = PopulationSpec(n_devices=64, seed=6).gather_caps(ids)[0]
        assert not np.array_equal(f5, f6)


class TestFleetConstructionSpeed:
    """The satellite bar: 100k-device fleets build in milliseconds —
    fully vectorized, no per-device python objects."""

    BUDGET_S = 2.0  # generous CI headroom; measured ~50ms

    def test_materialize_100k(self):
        spec = PopulationSpec(n_devices=100_000, seed=3, avail_frac=0.2)
        t0 = time.perf_counter()
        fleet = spec.materialize()
        dt = time.perf_counter() - t0
        assert fleet.n_devices == 100_000
        assert dt < self.BUDGET_S, f"materialize took {dt:.2f}s"

    def test_heterogeneous_fleet_100k(self):
        t0 = time.perf_counter()
        fleet = heterogeneous_fleet(0, 100_000, avail_frac=0.2)
        dt = time.perf_counter() - t0
        assert fleet.n_devices == 100_000
        assert dt < self.BUDGET_S, f"heterogeneous_fleet took {dt:.2f}s"


# --------------------------------------------------------------------------
# partitioners
# --------------------------------------------------------------------------

class TestPartitioners:
    def test_feistel_is_bijection(self):
        for domain in (10, 48, 1000):
            perm = partition.feistel_permutation(9, np.arange(domain), domain)
            assert np.array_equal(np.sort(perm), np.arange(domain))

    def test_shard_labels_bounded_classes(self):
        labels = partition.shard_labels(3, np.arange(200), 200,
                                        shards_per_device=2, n_classes=10)
        assert labels.shape == (200, 2)
        assert labels.min() >= 0 and labels.max() < 10
        # pool is label-sorted: every class appears across the fleet
        assert len(np.unique(labels)) == 10

    def test_device_rng_deterministic_in_process(self):
        a = partition.device_rng(3, 17).standard_normal(8)
        b = partition.device_rng(3, 17).standard_normal(8)
        assert np.array_equal(a, b)
        c = partition.device_rng(3, 18).standard_normal(8)
        assert not np.array_equal(a, c)

    def test_gather_deterministic_across_processes(self):
        """Same (seed, alpha) must give identical partitions in a fresh
        interpreter — the property that lets two hosts of a simulation
        agree on any device's data without coordination."""
        code = (
            "import numpy as np, hashlib, sys\n"
            "from repro.data.federated import LazyFederatedData\n"
            "d = LazyFederatedData(n_devices=64, seed=3, alpha=0.5)\n"
            "g = d.gather([0, 7, 63])\n"
            "h = hashlib.sha256()\n"
            "for k in sorted(g):\n"
            "    h.update(np.ascontiguousarray(g[k]).tobytes())\n"
            "sys.stdout.write(h.hexdigest())\n"
        )
        import os
        import pathlib
        src = str(pathlib.Path(__file__).resolve().parents[1] / "src")
        env = dict(os.environ, PYTHONPATH=src)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, check=True,
                             env=env).stdout.strip()
        import hashlib
        d = LazyFederatedData(n_devices=64, seed=3, alpha=0.5)
        g = d.gather([0, 7, 63])
        h = hashlib.sha256()
        for k in sorted(g):
            h.update(np.ascontiguousarray(g[k]).tobytes())
        assert out == h.hexdigest()

    def test_dirichlet_concentration_controls_skew(self):
        """Small alpha -> near-single-class devices; large alpha -> flat
        label histograms.  Checked via the mean max-class share."""
        def mean_top_share(alpha):
            d = LazyFederatedData(n_devices=40, seed=3, alpha=alpha)
            shares = []
            for dev in range(40):
                g = d.gather([dev])
                y, m = g["y"][0], g["mask"][0] > 0
                counts = np.bincount(y[m], minlength=d.n_classes)
                shares.append(counts.max() / counts.sum())
            return float(np.mean(shares))

        skewed = mean_top_share(0.1)
        mid = mean_top_share(0.5)
        flat = mean_top_share(100.0)
        # with 10-30 samples/device the multinomial noise floor for a
        # uniform π is ~0.2; Dir(0.1) concentrates most mass on 1-2
        # classes per device
        assert skewed > 0.55, skewed
        assert flat < 0.3, flat
        assert skewed > mid > flat

    def test_shard_partition_bounded_classes_per_device(self):
        d = LazyFederatedData(n_devices=30, seed=3, partition="shard",
                              shards_per_device=2)
        for dev in range(30):
            g = d.gather([dev])
            y, m = g["y"][0], g["mask"][0] > 0
            assert len(np.unique(y[m])) <= 2

    def test_sizes_view_matches_materialize(self):
        mat = DATA.materialize()
        ids = np.array([0, 5, 23, 5])
        assert np.array_equal(DATA.sizes[ids],
                              mat.mask.sum(axis=1)[ids].astype(np.int64))
        sizes = DATA.gather_sizes(np.arange(N))
        assert sizes.min() >= DATA.min_size
        assert sizes.max() <= DATA.max_size

    def test_gather_matches_materialize(self):
        mat = DATA.materialize()
        ids = [2, 19, 7]
        g = DATA.gather(ids)
        assert np.array_equal(g["x"], mat.x[ids])
        assert np.array_equal(g["y"], mat.y[ids])
        assert np.array_equal(g["mask"], mat.mask[ids])

    def test_eval_cohort_strides_population(self):
        d = LazyFederatedData(n_devices=1000, seed=3, eval_cohort=10)
        ids = d.eval_ids()
        assert len(ids) == 10
        assert len(np.unique(ids)) == 10
        full = LazyFederatedData(n_devices=50, seed=3)
        assert np.array_equal(full.eval_ids(), np.arange(50))


# --------------------------------------------------------------------------
# gather == the per-device definition of a device's stream
# --------------------------------------------------------------------------

def _gather_per_device(data, ids):
    """The lazy cohort by its definition: one device at a time from its
    own stream, labels by ``rng.choice`` / ``rng.integers`` / its owned
    shards, train then test, and one normal draw for the train rows and
    another for the test rows."""
    ids = np.asarray(ids, dtype=np.int64)
    M, T, F, C = data.max_size, data.test_size, data.n_features, \
        data.n_classes
    proto = _class_prototypes(data.seed, C, F, data.proto_scale)
    out = {"x": np.zeros(ids.shape + (M, F), np.float32),
           "y": np.zeros(ids.shape + (M,), np.int32),
           "mask": np.zeros(ids.shape + (M,), np.float32),
           "test_x": np.zeros(ids.shape + (T, F), np.float32),
           "test_y": np.zeros(ids.shape + (T,), np.int32),
           "test_mask": np.ones(ids.shape + (T,), np.float32)}
    for at in np.ndindex(ids.shape):
        did = int(ids[at])
        n = int(data.gather_sizes(np.asarray([did]))[0])
        rng = partition.device_rng(data.seed, did)
        if data.partition == "dirichlet":
            pi = rng.dirichlet(np.full(C, float(data.alpha)))
            y_tr = rng.choice(C, size=n, p=pi)
            y_te = rng.choice(C, size=T, p=pi)
        elif data.partition == "shard":
            owned = partition.shard_labels(
                data.seed, np.asarray([did]), data.n_devices,
                data.shards_per_device, C)[0]
            y_tr = owned[np.arange(n) % len(owned)]
            y_te = owned[np.arange(T) % len(owned)]
        else:
            y_tr = rng.integers(0, C, size=n)
            y_te = rng.integers(0, C, size=T)
        out["x"][at][:n] = proto[y_tr] + data.noise * rng.standard_normal(
            (n, F)).astype(np.float32)
        out["y"][at][:n] = y_tr
        out["mask"][at][:n] = 1.0
        out["test_x"][at][:] = proto[y_te] + data.noise * \
            rng.standard_normal((T, F)).astype(np.float32)
        out["test_y"][at][:] = y_te
    return out


@pytest.fixture(params=[
    pytest.param({}, id="default"),
    # n below the shard count, 3 test rows, 4 classes
    pytest.param(dict(min_size=1, max_size=4, test_size=3,
                      shards_per_device=3, n_classes=4, alpha=0.1),
                 id="small")])
def stream_shape(request):
    return request.param


@pytest.mark.parametrize("part", ["dirichlet", "shard", "iid"])
class TestGatherStream:
    """``gather`` is bit for bit the per-device definition of a device's
    stream (``_gather_per_device``) for every partition, whatever the
    shape of the ids and the sizes of the devices."""

    N = 10**6

    def _data(self, part, shape):
        return LazyFederatedData(n_devices=self.N, seed=3, partition=part,
                                 **shape)

    def _check(self, data, ids):
        got, want = data.gather(ids), _gather_per_device(data, ids)
        assert set(got) == set(want)
        for k in want:
            assert got[k].dtype == want[k].dtype, k
            assert got[k].shape == want[k].shape, k
            assert np.array_equal(got[k], want[k]), k

    def test_duplicate_ids(self, part, stream_shape):
        self._check(self._data(part, stream_shape),
                    [5, 900_001, 5, 42, 900_001, 5])

    def test_round_by_cohort_ids(self, part, stream_shape):
        ids = np.random.default_rng(1).integers(0, self.N, (6, 5))
        ids[3, 2] = ids[0, 0]
        self._check(self._data(part, stream_shape), ids)

    def test_single_id(self, part, stream_shape):
        self._check(self._data(part, stream_shape), [self.N - 1])

    def test_devices_at_min_and_max_size(self, part, stream_shape):
        data = self._data(part, stream_shape)
        cand = np.arange(2000)
        sizes = data.gather_sizes(cand)
        ids = [cand[sizes == data.min_size][0],
               cand[sizes == data.max_size][0]]
        self._check(data, ids)


def test_gather_digest_is_pinned():
    """The lazy cohort's bits for seed 3, alpha 0.5: a change to any
    device's stream (the order or the kind of its draws) changes them."""
    g = LazyFederatedData(n_devices=10**6, seed=3, alpha=0.5).gather(
        [0, 7, 63, 10**6 - 1])
    h = hashlib.sha256()
    for k in sorted(g):
        h.update(f"{k}:{g[k].dtype.str}:{g[k].shape}".encode())
        h.update(np.ascontiguousarray(g[k]).tobytes())
    assert h.hexdigest() == \
        "e6847d9c3c980871317ca757401d314675dcc66722e21eef30744c3b04021ef3"


# --------------------------------------------------------------------------
# lazy run == materialized run, bit for bit
# --------------------------------------------------------------------------

def _assert_runs_equal(a, b):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    assert set(a.history) == set(b.history)
    for k in a.history:
        assert a.history[k] == b.history[k], k
    assert np.array_equal(a.ids, b.ids)


@pytest.mark.parametrize("agg_dtype", ["bfloat16", "float32"])
class TestLazyEquivalence:
    """Same seeds, same config, sampler='indexed' on both sides: the lazy
    cohort engines must replay the materialized run exactly — params,
    every history series (including wall clock), id timeline, and (for
    the async modes) the event-plan digest."""

    def test_sync(self, agg_dtype):
        fl = FLConfig(algo="folb", n_selected=6, sampler="indexed",
                      agg_dtype=agg_dtype)
        lazy = fed.run(MCLR, DATA, fl, rounds=8, fleet=SPEC)
        mat = fed.run(MCLR, DATA.materialize(), fl, rounds=8,
                      fleet=SPEC.materialize())
        _assert_runs_equal(lazy, mat)
        assert "wall_clock" in lazy.history

    def test_deadline(self, agg_dtype):
        # deadline=40.0 with the 20x straggler tail forces a mix of
        # fast and slow rounds, exercising the pending-pool path
        afl = AsyncFLConfig(mode="deadline", algo="folb", n_selected=6,
                            deadline=40.0, staleness_alpha=0.5,
                            sampler="indexed", agg_dtype=agg_dtype)
        lazy = fed.run(MCLR, DATA, afl, rounds=8, fleet=SPEC)
        mat = fed.run(MCLR, DATA.materialize(), afl, rounds=8,
                      fleet=SPEC.materialize())
        _assert_runs_equal(lazy, mat)
        n_arr = np.asarray(lazy.history["n_arrived"])
        assert (n_arr < 6).any(), "deadline never bound — test too easy"

    def test_fedbuff(self, agg_dtype):
        afl = AsyncFLConfig(mode="fedbuff", algo="folb", buffer_size=5,
                            concurrency=8, staleness_alpha=0.3,
                            sampler="indexed", agg_dtype=agg_dtype)
        lazy = fed.run(MCLR, DATA, afl, rounds=6, fleet=SPEC)
        mat = fed.run(MCLR, DATA.materialize(), afl, rounds=6,
                      fleet=SPEC.materialize())
        _assert_runs_equal(lazy, mat)

    def test_plan_digest_matches(self, agg_dtype):
        params = small.init_small(MCLR, jax.random.PRNGKey(0))
        cost = round_cost_for(MCLR, params, uploads_gradient=True)
        mat_sizes = np.asarray(DATA.materialize().mask.sum(axis=1))
        key = jax.random.PRNGKey(0)
        for afl in (
                AsyncFLConfig(mode="deadline", algo="folb", n_selected=6,
                              deadline=40.0, sampler="indexed",
                              agg_dtype=agg_dtype),
                AsyncFLConfig(mode="fedbuff", algo="folb", buffer_size=5,
                              concurrency=8, sampler="indexed",
                              agg_dtype=agg_dtype)):
            lazy_plan = build_plan(afl, SPEC, cost, DATA.sizes, 6, key)
            mat_plan = build_plan(afl, SPEC.materialize(), cost,
                                  mat_sizes, 6, key)
            assert plan_digest(lazy_plan) == plan_digest(mat_plan)


# --------------------------------------------------------------------------
# front-door validation
# --------------------------------------------------------------------------

class TestLazyApiValidation:
    def test_categorical_sampler_rejected(self):
        fl = FLConfig(algo="folb", n_selected=6)  # default categorical
        with pytest.raises(ValueError, match="indexed"):
            fed.run(MCLR, DATA, fl, rounds=2, fleet=SPEC)

    def test_loop_engine_rejected(self):
        fl = FLConfig(algo="folb", n_selected=6, sampler="indexed")
        with pytest.raises(ValueError, match="loop"):
            fed.run(MCLR, DATA, fl, rounds=2, fleet=SPEC, engine="loop")

    def test_sweep_rejected(self):
        fl = FLConfig(algo="folb", n_selected=6, sampler="indexed")
        with pytest.raises(ValueError, match="sweep"):
            fed.run(MCLR, DATA, fl, rounds=2, fleet=SPEC,
                    sweep={"lr": (0.01, 0.1)})

    def test_scenario_rejected(self):
        fl = FLConfig(algo="folb", n_selected=6, sampler="indexed")
        with pytest.raises(ValueError, match="scenario"):
            fed.run(MCLR, DATA, fl, rounds=2, fleet=SPEC,
                    scenario=ScenarioConfig(drop_prob=0.1))

    def test_scenario_grid_rejected(self):
        from repro.sysmodel import ScenarioGrid
        fl = FLConfig(algo="folb", n_selected=6, sampler="indexed")
        grid = ScenarioGrid((ScenarioConfig(drop_prob=0.1),))
        with pytest.raises(ValueError, match="scenario grids"):
            fed.run(MCLR, DATA, fl, rounds=2, fleet=SPEC, scenario=grid)

    def test_null_scenario_accepted_bit_invisible(self):
        """A ScenarioConfig with every channel off is normalized away
        BEFORE the lazy-engine rejection: it must run, and take the
        exact scenario=None program."""
        fl = FLConfig(algo="folb", n_selected=6, sampler="indexed")
        h_none = fed.run(MCLR, DATA, fl, rounds=3, fleet=SPEC)
        h_null = fed.run(MCLR, DATA, fl, rounds=3, fleet=SPEC,
                         scenario=ScenarioConfig(seed=42))
        _assert_runs_equal(h_none, h_null)

    def test_sel_probs_rejected(self):
        fl = FLConfig(algo="folb", n_selected=6, sampler="indexed")
        with pytest.raises(ValueError, match="sel_probs"):
            fed.run(MCLR, DATA, fl, rounds=2, fleet=SPEC,
                    sel_probs=np.full(N, 1.0 / N))

    def test_async_needs_fleet(self):
        afl = AsyncFLConfig(mode="deadline", algo="folb", n_selected=6,
                            sampler="indexed")
        with pytest.raises(ValueError, match="fleet"):
            fed.run(MCLR, DATA, afl, rounds=2)

    def test_indexed_sampler_excludes_latency_aware(self):
        with pytest.raises(ValueError, match="latency_aware"):
            AsyncFLConfig(mode="deadline", algo="folb", n_selected=6,
                          sampler="indexed", latency_aware=True)

    def test_indexed_sampler_excludes_fednu(self):
        # fednu's selection distribution is built from per-device
        # gradients — inherently O(N), so the config itself refuses
        with pytest.raises(ValueError, match="fednu"):
            FLConfig(algo="fednu_direct", n_selected=6, sampler="indexed")
