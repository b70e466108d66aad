"""The trace reduction (``bench.core.trace``): hand-made traces with
known answers, and a small trace recorded on a TPU v5e chip
(``data/tpu_v5e.xplane.pb``: one 6-round ``fed.run`` call of the
deadline cell at a small size, traced as a benchmark window)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench.core import trace as T

K = 'x = f32[1] custom-call(), custom_call_target="tpu_custom_call"'
FOLB = "jit(a)/while/body/jit(folb_aggregate_buffers)/cond/pallas_call:"
OTHER = "jit(a)/jit(flash_attention)/pallas_call:"
RECORDED = Path(__file__).with_name("data") / "tpu_v5e.xplane.pb"


def test_union_overlap_total():
    u = T.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert T.total(u) == 6
    assert T.overlap(u, [(2, 6)]) == 2


def hand_trace():
    # window 0..100 ns; program A runs 10..50 with ops 10..20, 20..30
    # (the FOLB kernel), 40..50; program B runs 60..70 with one op; host
    # spans: phase:eval 55..100, unit:work 0..100
    ops = [(10, 20, "%a = f32[] add()", ""), (20, 30, K, FOLB),
           (40, 50, "%m = f32[] multiply()", ""),
           (60, 70, "%c = f32[] copy()", "")]
    mods = [(10, 50, "jit_a"), (60, 70, "jit_b")]
    spans = [(0, 100, "window"), (0, 100, "unit:work"),
             (55, 100, "phase:eval")]
    return T.Trace([T.DeviceOps(ops, mods)], spans)


def test_reduce_hand_trace():
    r = T.reduce(hand_trace())
    ns = 1e-9
    assert r.window_s == pytest.approx(100 * ns)
    assert r.busy_s == pytest.approx(40 * ns)
    assert r.program_s["jit_a"] == pytest.approx(30 * ns)
    assert r.program_s["jit_b"] == pytest.approx(10 * ns)
    assert r.kernel_s == pytest.approx(10 * ns)
    assert r.kernel_calls == 1
    assert r.kernel_s_by == {
        ("jit_a", "folb_aggregate_buffers"): pytest.approx(10 * ns)}
    assert r.folb_kernel_s(["jit_a"]) == pytest.approx(10 * ns)
    assert r.folb_kernel_s(["jit_b"]) == 0
    assert r.span_s["phase:eval"] == pytest.approx(45 * ns)
    assert r.span_device_s["phase:eval"] == pytest.approx(10 * ns)
    # gaps 0..10, 30..40, 50..60, 70..100; the longest is under eval
    assert r.idle_gaps[0] == ("phase:eval", pytest.approx(30 * ns))
    assert sorted(v for _, v in r.idle_gaps) == pytest.approx(
        [10 * ns, 10 * ns, 10 * ns, 30 * ns])
    assert {k for k, _ in r.top_ops} == {
        "jit_a/a", "jit_a/x (kernel folb_aggregate_buffers)", "jit_a/m",
        "jit_b/c"}


def test_reduce_clips_to_window():
    t = hand_trace()
    r = T.reduce(t, window=(15, 45))
    assert r.busy_s == pytest.approx(20e-9)
    assert r.window_s == pytest.approx(30e-9)


def test_other_kernels_are_not_the_folb_kernel():
    """A Mosaic kernel launched from another entry (attention, here) is
    kept out of ``agg_kernel_ms`` and counted in ``round_program_ms``."""
    import types

    from bench import run
    t = hand_trace()
    s, e, n, _ = t.devices[0].ops[1]
    t.devices[0].ops[1] = (s, e, n, OTHER)
    r = T.reduce(t)
    assert r.kernel_s == pytest.approx(10e-9)
    assert r.kernel_s_by == {("jit_a", "flash_attention"): pytest.approx(
        10e-9)}
    assert r.folb_kernel_s(["jit_a"]) == 0
    metrics = Path(run.BENCH) / "metrics"
    m = types.SimpleNamespace(reduced=r, work={"rounds": 1},
                              driver=types.SimpleNamespace(
                                  ROUND_PROGRAMS=("jit_a",)))
    agg = run.load_module(metrics / "agg_kernel_ms.py", "t_agg")
    prog = run.load_module(metrics / "round_program_ms.py", "t_prog")
    assert agg.read(m) is None
    assert prog.read(m) == pytest.approx(30e-6)


def test_kernel_entry():
    assert T.kernel_entry(FOLB) == "folb_aggregate_buffers"
    assert T.kernel_entry(OTHER) == "flash_attention"
    assert T.kernel_entry("") == ""


def test_decoder_agrees_with_jax_profile_data():
    from jax.profiler import ProfileData
    mine = T.read_xspace(str(RECORDED))
    theirs = ProfileData.from_file(str(RECORDED))
    n = 0
    for plane in theirs.planes:
        lines = {ln.name: ln for ln in mine[plane.name]}
        for line in plane.lines:
            got = lines[line.name].events
            want = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            assert [g[2] for g in got] == [w[2] for w in want]
            # jax rounds to whole ns; the decoder keeps the picoseconds
            assert all(abs(g[i] - w[i]) < 2.0 for g, w in zip(got, want)
                       for i in (0, 1))
            n += len(want)
    assert n > 1000


def test_recorded_chip_trace():
    t = T.load(str(RECORDED))
    assert len(t.devices) == 1
    r = T.reduce(t)
    assert 0 < r.busy_s < r.window_s
    progs = {k for k, v in r.program_s.items() if v > 0}
    assert "jit_scan_deadline_cohort" in progs
    # one call of 6 rounds, two kernel sweeps per aggregation
    assert r.kernel_calls == 6 * 2
    # the fresh and the stale aggregation: FOLB kernels both
    assert {k[1] for k in r.kernel_s_by} == set(T.FOLB_ENTRIES)
    assert r.folb_kernel_s(["jit_scan_deadline_cohort"]) == \
        pytest.approx(r.kernel_s)
    assert r.busy_s <= sum(r.program_s.values()) + 1e-12
    assert {"phase:plan_build", "phase:gather", "phase:scan",
            "phase:eval"} <= set(r.span_s)
    assert r.idle_gaps and all(v > 0 for _, v in r.idle_gaps)
