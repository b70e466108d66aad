"""Benchmark harness — one function per paper table/figure plus kernel
micro-benchmarks, the roofline summary, and the time-to-accuracy sweep.

  PYTHONPATH=src python -m benchmarks.run [--quick] [--only PREFIX]

Prints ``name,us_per_call,derived`` CSV (us_per_call = mean wall time of
one federated round / one kernel call / roofline step-time bound in us).
The `tta` suite additionally writes a ``BENCH_fed.json`` artifact
(rounds- and seconds-to-target-accuracy per algorithm, plus the
``dispatch`` section's sync AND async scan-vs-loop engine speedups) so
the perf trajectory is tracked across PRs.

The persistent compile cache is on (``repro.launch.compile_cache``): a
run that finds its programs in the cache loads them instead of compiling,
so the artifact's first-call fields (``scan_first_call_seconds``,
``sweep_first_call_seconds``, ``grid_first_call_seconds``, the profile's
``first_call_compile_s``) measure a compile only on a cold cache.  Point
``JAX_COMPILATION_CACHE_DIR`` at an empty directory to measure compiles.

NEVER run this concurrently with pytest or another bench in the same
container: CPU contention collapses the CI-gated speedup ratios.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer rounds (CI-speed smoke)")
    ap.add_argument("--only", default=None,
                    help="run only benchmarks whose name starts with this")
    ap.add_argument("--reports", default="reports")
    ap.add_argument("--bench-json", default="BENCH_fed.json",
                    help="path of the cross-PR perf artifact")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (dispatch_bench, fleet_scale, kernel_bench,
                            paper_tables, resilience, roofline,
                            scenario_matrix, time_to_accuracy)

    rounds = 30 if args.quick else 100
    fig_rounds = 20 if args.quick else 60

    # fixed round budget regardless of --quick: the artifact must be
    # comparable across PRs, and fedbuff needs ~50 aggregations to target
    tta_rounds = 60

    def kernel_rows():
        """Kernel micro-benches + the calibration-relative `kernel` section
        merged into the BENCH_fed.json artifact (the tta suite writes the
        artifact fresh and runs first, so merge-into-existing is safe both
        in a full run and in CI's two-invocation flow)."""
        import json
        import os
        rows = kernel_bench.bench_kernels()
        payload = kernel_bench.kernel_payload(rows)
        data = {}
        if os.path.exists(args.bench_json):
            with open(args.bench_json) as f:
                data = json.load(f)
        data["kernel"] = payload
        with open(args.bench_json, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
        print(f"# merged kernel section into {args.bench_json} "
              f"(calibration_us={payload['calibration_us']})",
              file=sys.stderr)
        return rows

    def tta_rows():
        results = time_to_accuracy.time_to_accuracy_results(tta_rounds)
        network = time_to_accuracy.network_payload(results)
        # persist the TTA sweep before the dispatch bench runs, so a
        # dispatch failure can't discard the multi-minute sweep results
        time_to_accuracy.write_bench_json(results, args.bench_json,
                                          extra={"network": network})
        d_rows, dispatch = dispatch_bench.dispatch_rows()
        time_to_accuracy.write_bench_json(
            results, args.bench_json,
            extra={"network": network, "dispatch": dispatch})
        s_rows, sweep = dispatch_bench.sweep_rows()
        path = time_to_accuracy.write_bench_json(
            results, args.bench_json,
            extra={"network": network, "dispatch": dispatch, "sweep": sweep})
        print(f"# wrote {path}", file=sys.stderr)
        return [(f"tta/{r['name']}",
                 r["host_seconds"] / tta_rounds * 1e6,
                 f"rounds_to_{r['target_acc']}={r['rounds_to_acc']};"
                 f"secs_to_{r['target_acc']}={r['secs_to_acc']:.2f};"
                 f"final_acc={r['final_acc']:.3f};"
                 f"bytes_up={r['bytes_up_total']:.0f};"
                 f"bytes_down={r['bytes_down_total']:.0f};"
                 f"bytes_to_acc={r['bytes_to_acc']:.0f}") for r in results] \
            + d_rows + s_rows

    def scenario_rows():
        """Failure-scenario matrix, merged into the artifact's
        ``scenario`` section (same merge-into-existing contract as
        kernel_rows, so CI can run it as its own invocation)."""
        import json
        import os
        rows, payload = scenario_matrix.scenario_rows()
        data = {}
        if os.path.exists(args.bench_json):
            with open(args.bench_json) as f:
                data = json.load(f)
        data["scenario"] = payload
        with open(args.bench_json, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
        print(f"# merged scenario section into {args.bench_json} "
              f"({len(payload['cells'])} cells x "
              f"{len(next(iter(payload['cells'].values()))['runs'])} algos)",
              file=sys.stderr)
        return rows

    def grid_rows():
        """Scenario-grid engine solo-vs-grid comparison, merged into the
        artifact's ``scenario_grid`` section (same merge-into-existing
        contract as kernel_rows, so CI can run it as its own
        invocation).  Suite prefix is ``grid`` — NOT ``scenario_grid``
        — because --only does prefix matching and ``--only scenario``
        must keep selecting only the failure-matrix suite."""
        import json
        import os
        rows, payload = scenario_matrix.grid_rows()
        data = {}
        if os.path.exists(args.bench_json):
            with open(args.bench_json) as f:
                data = json.load(f)
        data["scenario_grid"] = payload
        with open(args.bench_json, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
        print(f"# merged scenario_grid section into {args.bench_json} "
              f"({payload['n_programs_solo']} solo programs -> "
              f"{payload['n_programs_grid']} grid programs)",
              file=sys.stderr)
        return rows

    def resilience_rows():
        """Guarded-vs-unguarded corruption matrix, merged into the
        artifact's ``resilience`` section (same merge-into-existing
        contract as kernel_rows, so CI can run it as its own
        invocation)."""
        import json
        import os
        rows, payload = resilience.resilience_rows()
        data = {}
        if os.path.exists(args.bench_json):
            with open(args.bench_json) as f:
                data = json.load(f)
        data["resilience"] = payload
        with open(args.bench_json, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
        print(f"# merged resilience section into {args.bench_json} "
              f"(baseline_final_acc="
              f"{payload['baseline_final_acc']:.3f})", file=sys.stderr)
        return rows

    def profile_rows():
        """Host-phase profile + trace export, merged into the artifact's
        ``profile`` section (same merge-into-existing contract as
        kernel_rows, so CI can run it as its own invocation)."""
        import json
        import os
        rows, payload = dispatch_bench.profile_rows(
            reports_dir=args.reports)
        data = {}
        if os.path.exists(args.bench_json):
            with open(args.bench_json) as f:
                data = json.load(f)
        data["profile"] = payload
        with open(args.bench_json, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
        print(f"# merged profile section into {args.bench_json} "
              f"(coverage={payload['coverage']}, "
              f"trace={payload['trace_path']})", file=sys.stderr)
        return rows

    def fleet_rows():
        """Population-scale host-cost comparison, merged into the
        artifact's ``fleet_scale`` section (same merge-into-existing
        contract as kernel_rows, so CI can run it as its own
        invocation).  NOT named ``fleet`` — that key already describes
        the tta suite's 30-device fleet."""
        import json
        import os
        rows, payload = fleet_scale.fleet_rows(quick=args.quick)
        data = {}
        if os.path.exists(args.bench_json):
            with open(args.bench_json) as f:
                data = json.load(f)
        data["fleet_scale"] = payload
        with open(args.bench_json, "w") as f:
            json.dump(data, f, indent=2)
            f.write("\n")
        print(f"# merged fleet_scale section into {args.bench_json} "
              f"(host_ratio_vs_reference="
              f"{payload['host_ratio_vs_reference']})", file=sys.stderr)
        return rows

    suites = [
        ("table1", lambda: paper_tables.table1_rounds_to_accuracy(rounds)),
        ("fig2", lambda: paper_tables.fig2_naive_baselines(
            max(fig_rounds // 2, 10))),
        ("fig3", lambda: paper_tables.fig3_aggregation_vs_mu(fig_rounds)),
        ("fig5", lambda: paper_tables.fig5_device_count(fig_rounds)),
        ("fig6", lambda: paper_tables.fig6_noniid_level(fig_rounds)),
        ("fig11", lambda: paper_tables.fig11_heterogeneity_psi(fig_rounds)),
        ("beyond", lambda: paper_tables.beyond_server_opt(fig_rounds)),
        ("tta", tta_rows),
        ("kernel", kernel_rows),
        ("scenario", scenario_rows),
        ("grid", grid_rows),
        ("resilience", resilience_rows),
        ("profile", profile_rows),
        ("fleet", fleet_rows),
        ("roofline", lambda: roofline.bench_rows(args.reports)),
    ]

    print("name,us_per_call,derived")
    failed = []
    for prefix, fn in suites:
        if args.only and not prefix.startswith(args.only):
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{prefix}/SUITE_ERROR,0,{e!r}", flush=True)
            failed.append(prefix)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}", flush=True)
        print(f"# suite {prefix}: {time.time()-t0:.1f}s", file=sys.stderr)
    if failed:
        # nonzero exit so CI can't silently skip the regression gate with a
        # stale BENCH_fed.json (a crashed tta suite would leave the
        # committed artifact in place and the gate would pass it against
        # itself)
        print(f"# FAILED suites: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
