#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

The cell is the entry of ``workloads`` in ``BENCHMARK.json`` named NAME.
Its configuration is ``bench/configs/<config>.json``, its traffic
``bench/traffic/<traffic>.json``, whose ``driver`` names the module in
``bench/drivers/`` that drives the program, and the limits of its
comparison with the plain reference are ``bench/limits/<workload>.json``.
Each metric of ``BENCHMARK.json`` is computed by ``bench/metrics/<name>.py``.

Set-up (imports, inputs and weights from the seed, warm-up of every shape
the window uses, and compilation, served from the persistent cache in
``.bench_cache/`` of this checkout after the first run) is timed as
``setup_s``.  Then units of work run back to back for S seconds; with
``--trace 1`` the window is traced and the per-layer metrics are read from
the trace.  After the window the program's state is freed and what it
produced is compared with the plain reference.

Standard output ends with one JSON line: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced).
Standard error ends with each compared number beside its limit.  Exit
code 2, with no result, when JAX finds no TPU or fewer chips than the cell
asks for, or when this checkout does not hold the program.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".bench_cache" / "jax"


@dataclasses.dataclass
class Ctx:
    root: Path
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


class CompileCounter:
    """Backend compilations and persistent-cache hits, counted through
    ``jax.monitoring`` (a cache hit also reports a compile duration)."""

    def __init__(self, jax):
        self.compiles = 0
        self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def peak_bytes(device):
    """Peak device memory: the buffer allocator's peak plus the peak the
    runtime reserved for programs' temporaries, which ``peak_bytes_in_use``
    leaves out.  None where the backend keeps no memory statistics."""
    stats = device.memory_stats()
    if not stats:
        return None
    return stats.get("peak_bytes_in_use", 0) + \
        stats.get("peak_bytes_reserved", 0)


def main(argv=None, overrides=None, require_tpu: bool = True) -> int:
    """Run a cell.  ``overrides`` replaces loaded ``config``, ``traffic``
    or ``limits`` dicts, and ``require_tpu=False`` skips the look for a
    chip and the persistent cache: both only for the benchmark's own
    tests, which drive a run at a small size on the CPU."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_file = ROOT / "BENCHMARK.json"
    if not bench_file.is_file():
        return fail(f"no {bench_file.name} at {ROOT}")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"this checkout holds no program (src/repro) at {ROOT}")
    spec = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if args.workload not in cells:
        return fail(f"no workload {args.workload!r} in {bench_file.name}")
    wl = cells[args.workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[wl["config"]]["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{wl['traffic']}.json")
                         .read_text())
    limits_file = BENCH / "limits" / f"{wl['name']}.json"
    limits = json.loads(limits_file.read_text()) if limits_file.is_file() \
        else {}

    over = overrides or {}
    config = over.get("config", config)
    traffic = over.get("traffic", traffic)
    limits = over.get("limits", limits)

    # the TPU runtime's logs go inside the checkout, not to a fixed /tmp path
    os.environ.setdefault("TPU_LOG_DIR",
                          str(ROOT / ".bench_cache" / "tpu_logs"))
    import jax
    devices = jax.devices()
    jax_init_s = time.perf_counter() - T_START
    if require_tpu:
        if devices[0].platform != "tpu":
            return fail(f"needs a TPU; JAX found {devices[0].platform!r} "
                        f"({devices[0].device_kind}, {len(devices)} "
                        f"device(s))")
        if len(devices) < wl["chips"]:
            return fail(f"{wl['name']} needs {wl['chips']} chips, JAX "
                        f"found {len(devices)}")
        # cache every program, however fast it compiled: a warm process
        # otherwise compiles its sub-second programs again
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    counter = CompileCounter(jax)

    from bench.core import counts, trace as trace_lib
    ctx = Ctx(ROOT, wl, config, traffic, limits, args.seed, args.seconds,
              bool(args.trace))
    driver = load_module(BENCH / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    session = driver.Session(ctx)
    session.setup()
    setup_s = time.perf_counter() - T_START
    print(f"[setup] workload={wl['name']} seed={args.seed} "
          f"device={devices[0].device_kind} count={len(devices)} "
          f"setup_s={setup_s!r} compiles={counter.compiles} "
          f"cache_hits={counter.hits} compile_s={counter.compile_s!r} "
          f"jax_init_s={jax_init_s!r} "
          f"phases={getattr(session, 'setup_phases', {})}", flush=True)

    # ------------------------------------------------------------ window
    tdir = None
    seconds = args.seconds
    if ctx.trace:
        # a short traced window: traces are large and tracing slows the host
        seconds = min(seconds, traffic["trace_seconds"])
        session.trace_on()
        (ROOT / ".bench_cache").mkdir(exist_ok=True)
        tdir = tempfile.mkdtemp(prefix="trace_", dir=str(
            ROOT / ".bench_cache"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # Python calls: costly, unread
        jax.profiler.start_trace(tdir, profiler_options=opts)
    compiles0 = counter.compiles
    records, unit_s = [], []
    t0 = time.perf_counter()
    end = t0 + seconds
    ann = jax.profiler.TraceAnnotation
    with ann("window"):
        while True:
            u0 = time.perf_counter()
            if ctx.trace:
                with ann("unit:work"):
                    records.append(session.unit())
            else:
                records.append(session.unit())
            u1 = time.perf_counter()
            unit_s.append(u1 - u0)
            if u1 >= end:
                break
    window_s = time.perf_counter() - t0
    if ctx.trace:
        jax.profiler.stop_trace()
    window_compiles = counter.compiles - compiles0
    peak = max((peak_bytes(d) or 0) for d in devices[:wl["chips"]]) or None
    work = session.work(records)
    slowest = sorted(range(len(unit_s)), key=lambda i: -unit_s[i])[:5]
    print(f"[window] units={len(records)} rounds={work['rounds']} "
          f"window_s={window_s!r} compiles_in_window={window_compiles} "
          f"memory_peak_bytes={peak} slowest_units="
          f"{[(i, round(unit_s[i] * 1e3, 3)) for i in slowest]}", flush=True)
    session.finish()

    # ------------------------------------------------------------ metrics
    m = Measure(ctx=ctx, setup_s=setup_s, window_s=window_s,
                unit_s=unit_s, work=work, driver=driver,
                peaks=counts.peaks(devices[0].device_kind) if require_tpu
                else {"bf16_flops_per_s": 1.0},
                profile=getattr(session, "profiler", None))
    traced = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": wl["chips"],
              "memory_peak_bytes": peak}
    if ctx.trace:
        path = trace_lib.find_trace_file(tdir)
        m.reduced = trace_lib.reduce(trace_lib.load(path))
        device["busy_s"] = m.reduced.busy_s
        device["window_s"] = m.reduced.window_s
        traced["breakdown"] = {
            "device_ops": [[k, v] for k, v in m.reduced.top_ops],
            "idle_gaps": [[k, v] for k, v in m.reduced.idle_gaps]}
        print(f"[trace] file={path} busy_s={m.reduced.busy_s!r} "
              f"window_s={m.reduced.window_s!r} "
              f"programs={m.reduced.program_s} "
              f"kernel_s={m.reduced.kernel_s!r} "
              f"kernel_s_by={m.reduced.kernel_s_by} "
              f"kernel_calls={m.reduced.kernel_calls} "
              f"spans={m.reduced.span_s} "
              f"span_device={m.reduced.span_device_s}", flush=True)
        shutil.rmtree(tdir, ignore_errors=True)
    kind = "per_layer" if ctx.trace else "end_to_end"
    metrics = {}
    for entry in spec[kind]:
        if not applies(entry, wl["name"]):
            continue
        reader = load_module(BENCH / "metrics" / f"{entry['name']}.py",
                             f"bench_metric_{entry['name'].replace('.', '_')}")
        value = reader.read(m)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    # ------------------------------------------------------------ correct
    # every number with a limit is judged; the others are information
    numbers = session.check()
    for name, value in numbers.items():
        if name not in limits:
            print(f"[check] {name}={value!r} (information, no limit)",
                  flush=True)
    checks = {name: {"value": numbers.get(name), "limit": lim}
              for name, lim in limits.items()}
    checks["compiles_in_window"] = {"value": window_compiles, "limit": 0}
    correct = bool(limits) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    result = {"correct": correct, "attempted": len(records),
              "failed": 0, "metrics": metrics, "device": device, **traced,
              "checks": checks}
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


@dataclasses.dataclass
class Measure:
    """What a metric reader sees."""
    ctx: Ctx
    setup_s: float
    window_s: float
    unit_s: list
    work: dict
    driver: object
    peaks: dict
    profile: object = None
    reduced: object = None

    def quantile(self, values, q: int, n: int = 100) -> float:
        """The ``q``-th of ``n`` quantiles, as ``statistics.quantiles``
        (exclusive method) gives them."""
        if len(values) < 2:
            return values[0]
        return statistics.quantiles(values, n=n)[q - 1]


if __name__ == "__main__":
    sys.exit(main())
