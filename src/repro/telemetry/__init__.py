"""Observability layer for the federated engines.

Three parts, riding the execution machinery that already exists instead
of adding dispatches:

  * ``telemetry.metrics`` — structured per-round metrics.  The in-scan
    half (FOLB score stats, aggregation-weight entropy, grad/delta/update
    norms, staleness histogram) is computed inside the SAME jitted round
    steps every engine shares and emitted as extra scan outputs — zero
    extra dispatches, and traced only when ``telemetry=True`` so the off
    path stays bit-for-bit identical.  The host half (modeled network
    bytes, arrivals vs cut stragglers, slot-pool occupancy) is derived
    from the event plans, which already know the whole timeline.
  * ``telemetry.trace`` — converts deadline/fedbuff event plans into
    Chrome trace-event JSON (per-device download/compute/upload spans,
    round barriers, deadline cuts, flush instants) loadable in
    ``ui.perfetto.dev``.
  * ``telemetry.profiler`` — the program's span-and-counter recorder:
    context-manager host-phase timers (setup / plan-build / scan / eval)
    attached to run results and written into the ``profile`` section of
    ``BENCH_fed.json``, and, while recording is on (``recording()``,
    ``enable()``, or the length of a ``fed.run`` call given
    ``profiler=``), the engines' ``<phase>/<step>`` spans and the
    ``d2h_fetches`` / ``h2d_bytes`` counters of its ``fetch`` /
    ``to_device`` transfer helpers, each span a ``phase:<name>``
    annotation on the JAX profiler trace; ``snapshot()`` sums them, and
    a run's ``profile`` carries its own ``spans`` and ``counters``.
"""
from repro.telemetry.metrics import (METRIC_KEYS, STALE_BINS,  # noqa: F401
                                     round_metrics, selection_entropy,
                                     stack_metrics)
from repro.telemetry.profiler import (NULL_PROFILER,  # noqa: F401
                                      PhaseProfiler, profiler_for)
from repro.telemetry.trace import (validate_trace,  # noqa: F401
                                   write_trace)
