"""The language-model round cell (``mellum2-folb-8k``) at a test size on the
CPU through ``bench.run.main`` (the look for a chip skipped): the driver,
the plain reference and the comparison, and the new metric readers on a
synthetic reduced trace.

  python -m pytest bench/tests/test_lm_round.py
"""
from __future__ import annotations

import copy
import json
import types

from bench.core import lm_counts
from bench.tests import small

CELL = "mellum2-folb-8k"
SEED = 3141592653589


def small_lm():
    """(config, traffic) of the cell at a test size: d 128, one period of
    3 window layers (16 keys) and 1 full layer with YaRN over 32 original
    positions, 4 of 8 experts held, 2 clients a round on 64-token
    sequences."""
    spec = json.loads((small.ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}[CELL]
    cfgs = {c["name"]: c for c in spec["configs"]}
    cfg = copy.deepcopy(json.loads(
        (small.ROOT / cfgs[wl["config"]]["file"]).read_text()))
    cfg.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
               head_dim=32, vocab_size=256, num_experts=4,
               num_experts_router=8, num_experts_per_tok=4,
               moe_intermediate_size=128, sliding_window=16)
    cfg["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 32
    tr = json.loads((small.ROOT / "bench" / "traffic" /
                     f"{wl['traffic']}.json").read_text())
    tr.update(seq_len=64, n_clients=8, clients_per_round=2,
              trace_seconds=1.0)
    return cfg, tr


def run_cell(capsys, trace: int, seconds: float = 1.0):
    import jax
    from bench import run
    jax.clear_caches()
    cfg, tr = small_lm()
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   str(seconds), "--trace", str(trace)],
                  overrides={"config": cfg, "traffic": tr},
                  require_tpu=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_sound_run_is_correct(capsys):
    res = run_cell(capsys, trace=1)
    assert res["correct"], res["checks"]
    assert res["checks"]["tokens_dropped"]["value"] == 0
    assert res["checks"]["replay_mismatch"]["value"] == 0
    assert res["attempted"] >= 1
    # the program's counters reach the reader; the CPU trace has no device
    # plane, so the device readers report nothing
    assert 1.0 <= res["metrics"]["expert_load_max_ratio"]["value"] <= 4.0
    assert "expert_ffn_ms" not in res["metrics"]


def test_calibration_brackets_the_limits():
    """Through ``bench/calibrate.py``, as on the chip: the program's round
    1 is within every limit; the control (the reference in float8), the
    reference on half of each client's data and each fault planted in the
    program (capacity routing, full mask, no YaRN) exceed at least one."""
    import jax
    from bench import calibrate
    jax.clear_caches()
    cfg, tr = small_lm()
    limits = json.loads((small.ROOT / "bench" / "limits" / f"{CELL}.json")
                        .read_text())
    out, _ = calibrate.readings(CELL, [SEED], [SEED + 1], require_tpu=False,
                                overrides={"config": cfg, "traffic": tr})
    by = {r["kind"]: r for r in out}
    assert set(by) == {"program", "control", "half_batch", "capacity",
                       "full_mask", "no_yarn"}
    assert all(by["program"].get(k, 0) <= v for k, v in limits.items()), by
    for kind in set(by) - {"program"}:
        assert any(by[kind][k] > v for k, v in limits.items()
                   if k in by[kind]), (kind, by)


def _measure(cfg, tr, rounds, load, kernel_s, folb_s):
    """A metric reader's view of a traced window whose reduced trace holds
    the given kernel times (seconds by entry) and FOLB kernel time."""
    from bench.drivers import lm_round
    recs = [(r, {"moe_load": load}) for r in range(rounds)]
    work = lm_round.round_work(cfg, tr, recs)
    by = {("jit_train_round", e): t for e, t in kernel_s.items()}
    by[("jit_train_round", "folb_aggregate_buffers")] = folb_s
    reduced = types.SimpleNamespace(
        kernel_s_by=by,
        folb_kernel_s=lambda progs: folb_s if "jit_train_round" in progs
        else 0.0)
    return types.SimpleNamespace(
        reduced=reduced, work=work, driver=lm_round,
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        ctx=types.SimpleNamespace(config=cfg))


def _reader(name):
    import importlib.util
    path = small.ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_roofline_shares_stay_under_100_at_the_bounds():
    """At the cell's own shapes, a kernel time equal to its count over the
    peak reads exactly 100%; any longer time reads less."""
    import numpy as np
    spec = json.loads((small.ROOT / "BENCHMARK.json").read_text())
    cfgs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((small.ROOT / cfgs["mellum2-12b-a2.5b"]["file"])
                     .read_text())
    tr = json.loads((small.ROOT / "bench" / "traffic" / "lm-folb-8k.json")
                    .read_text())
    load = np.full((cfg["num_hidden_layers"], cfg["num_experts"]), 4096)
    c = lm_counts.round_counts(cfg, tr, float(load.sum()))
    peak, bw = 197e12, 819e9
    attn = sum(c["attn_kernel_flops"].values())
    for slow in (1.0, 1.7):
        m = _measure(cfg, tr, 3, load, {
            "moe_grouped_ffn": 3 * c["expert_kernel_flops"] / peak * slow,
            "attention_window": 3 * c["attn_kernel_flops"][
                "sliding_attention"] / peak * slow,
            "attention_full": 3 * c["attn_kernel_flops"]["full_attention"]
            / peak * slow}, 3 * c["agg_bytes"] / bw * slow)
        for name in ("expert_ffn_roofline_pct", "attn_roofline_pct",
                     "agg_hbm_pct"):
            v = _reader(name)(m)
            assert 0 < v <= 100.0 + 1e-9, (name, v)
            assert abs(v - 100.0 / slow) < 1e-6, (name, v)
        assert abs(_reader("expert_ffn_ms")(m) - c["expert_kernel_flops"]
                   / peak * slow * 1e3) < 1e-9
        assert attn > 0


def test_counts_of_the_masks():
    """The attended pairs and computed blocks of the causal masks."""
    assert lm_counts.attended_pairs(8, 0) == 36
    assert lm_counts.attended_pairs(8, 3) == 6 + 5 * 3
    assert lm_counts.active_blocks(8192, 0, 512) == 16 * 17 // 2
    # a 1024-key window reaches back into two earlier 512-blocks
    assert lm_counts.active_blocks(8192, 1024, 512) == 16 * 3 - 3
