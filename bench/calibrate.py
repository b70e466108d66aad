#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from, on the
chip, at the cell's own size, in one process.

  python3 bench/calibrate.py --workload NAME --seeds 1,2,... \
      --control-seeds 7,8,9 [--out FILE]

For each seed of ``--seeds`` the program runs as in a benchmark run (set-up
and the calls or rounds that the comparison reads, no measured window) and
is compared with the plain reference: the lower readings.  For each seed
of ``--control-seeds`` the reference computed in bfloat16 (the control)
and the reference with a fault planted (each client trains on half of its
data) take the program's place: the upper readings.  A state left
unchanged reads 1 in every gap by construction and needs no run.  Prints
one JSON object per reading and, last, the largest program reading and the
smallest control and fault readings of each number.  The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(workload: str, seeds, control_seeds, require_tpu=True,
             overrides=None):
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    import jax
    from bench import run
    if require_tpu:
        if jax.devices()[0].platform != "tpu":
            raise SystemExit("calibrate: needs a TPU")
        jax.config.update("jax_compilation_cache_dir", str(run.CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in spec["workloads"]}[workload]
    cfgs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / cfgs[wl["config"]]["file"]).read_text())
    traffic = json.loads((ROOT / "bench" / "traffic" /
                          f"{wl['traffic']}.json").read_text())
    if overrides:
        config = overrides.get("config", config)
        traffic = overrides.get("traffic", traffic)
    driver = run.load_module(ROOT / "bench" / "drivers" /
                             f"{traffic['driver']}.py",
                             f"bench_driver_{traffic['driver']}")
    out = []

    def session(seed):
        ctx = run.Ctx(ROOT, wl, config, traffic, {}, seed, 0.0, False)
        return driver.Session(ctx)

    for seed in seeds:
        t0 = time.perf_counter()
        s = session(seed)
        s.setup()
        nums = s.calibration_program()
        out.append({"kind": "program", "seed": seed, **nums,
                    "seconds": time.perf_counter() - t0})
        print(json.dumps(out[-1]), flush=True)
        del s
    for seed in control_seeds:
        t0 = time.perf_counter()
        s = session(seed)
        for kind, nums in s.calibration_upper():
            out.append({"kind": kind, "seed": seed, **nums,
                        "seconds": time.perf_counter() - t0})
            print(json.dumps(out[-1]), flush=True)
        del s
    names = [k for k, v in out[0].items() if isinstance(v, (int, float))
             and k not in ("kind", "seed", "seconds")]
    summary = {"lower": {}, "upper": {}}
    for n in names:
        prog = [r[n] for r in out if r["kind"] == "program"]
        if prog:
            summary["lower"][n] = max(prog)
        for kind in {r["kind"] for r in out if r["kind"] != "program"}:
            vals = [r[n] for r in out if r["kind"] == kind]
            summary["upper"].setdefault(kind, {})[n] = min(vals)
    return out, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    ints = lambda s: [int(v) for v in s.split(",") if v]
    out, summary = readings(args.workload, ints(args.seeds),
                            ints(args.control_seeds))
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(
            {"readings": out, "summary": summary}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
