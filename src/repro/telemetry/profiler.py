"""Host-time spans and counters for the run engines.

One recorder, two views.  While recording is on, every ``span(name)``
opens a ``jax.profiler.TraceAnnotation`` named ``phase:<name>`` (so a
profiler trace carries it on the same clock as the device ops) and
records its start, end, parent span (per thread) and the ``fed.run``
call it belongs to; ``count(name, n)`` adds to the innermost open span.
The recorder keeps per-name totals (seconds, self seconds, count) and
counters, which ``snapshot()`` returns, and the last ``SPAN_LOG`` spans;
``reset()`` drops both.

Recording is off by default.  It is on while the process-wide switch is
(``recording()``, a context manager, or ``enable()`` / ``disable()``),
and for the length of every ``repro.fed.run`` call given a
``profiler=`` — process-wide too, so other threads' spans of that time
are recorded as well.  While it is off, ``span`` hands back one shared
no-op context manager and ``count`` returns at once — one global read,
no annotation, no clock.

Spans are named ``<phase>/<step>`` inside the engines' phases:

  ============================  ==========================================
  ``plan_build/key_chain``      the round key chain and id draws, fetched
  ``plan_build/step_draws``     the round-indexed local-step draws
  ``plan_build/timeline``       ``sysmodel.plan_deadline_run``
  ``plan_build/pool``           the deadline plan's straggler-slot pool
  ``gather/synthesize``         ``LazyFederatedData.gather`` of a cohort
  ``gather/to_device``          the cohort batches moved to the device
  ``gather/pool_init``          the pending-update pool's zero rows
  ``setup/to_device``           resident data moved to the device
  ``eval/cohort``               the lazy evaluation cohort, gathered
  ``eval/device``               eval rows, ``eval_traj`` dispatches, stack
  ``eval/fetch``                the whole history read to the host, once
  ============================  ==========================================

and three counters, counted by the helpers the engines move data through:
``d2h_fetches`` (``fetch`` / ``fetch_float``: one per device array read
to the host), ``h2d_transfers`` (``to_device``: one per device array made
from a host array) and ``h2d_bytes`` (``to_device``: the ``nbytes`` of
each such device array); and one the lazy engines count themselves,
``synth_devices`` under ``gather/synthesize`` (the unique devices a
cohort gather synthesized).

`PhaseProfiler` breaks one run's host time into named contiguous phases
(`setup` / `plan_build` / `scan` / `eval` for the compiled engines;
`rounds` instead of `scan` for the python-loop engines).  While recording
is on each phase is also a span, the parent of the engines' sub-spans.
`summary()` reports per-phase seconds, the total since construction,
coverage — the fraction of total time the phases account for (the
engines keep phases contiguous, so coverage stays near 1.0; the
acceptance bar is ≥ 0.9) — and the ``spans`` and ``counters`` closed
inside its phases, kept by the profiler itself (empty when recording is
off).

First-call jit compilation is not a separate timer — it lands inside the
first run's `scan` phase.  `dispatch_bench.profile_results` estimates it
as cold-run scan minus warm-run scan, which is how the `profile` section
of BENCH_fed.json reports `first_call_compile_s`.

When telemetry is off the engines use `NULL_PROFILER`, whose phase() is
the same shared no-op context manager — zero timers, zero allocation, and
no change to host-time behavior (the profiled path may block on device
results inside a phase; the null path never does).
"""
from __future__ import annotations

import collections
import contextlib
import threading
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


class _NullPhase:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_PHASE = _NullPhase()
# read by span/count/fetch/to_device: on while enable()d or while a
# fed.run call given a profiler is open
_recording = False
_enabled = False
_open_calls = 0
_lock = threading.Lock()
SPAN_LOG = 4096   # closed spans the recorder keeps, newest last


class _Thread(threading.local):
    """One thread's open spans (innermost last), the totals of the
    PhaseProfilers whose phases are open, and its ``fed.run`` call id."""

    def __init__(self):
        self.stack = []
        self.sinks = []
        self.call: Optional[int] = None


_THREAD = _Thread()


class Span:
    """One recorded span: ``start`` / ``end`` (``perf_counter`` seconds),
    ``parent`` (the enclosing Span of its thread, or None), ``call`` (the
    ``fed.run`` call id or None), ``child_s`` (seconds its child spans
    cover) and its own ``counters``."""

    __slots__ = ("name", "parent", "call", "start", "end", "child_s",
                 "counters", "_ann")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        th = _THREAD
        self.parent = th.stack[-1] if th.stack else None
        self.call = th.call
        self.child_s = 0.0
        self.counters: Dict[str, float] = {}
        self._ann = jax.profiler.TraceAnnotation(f"phase:{self.name}")
        self._ann.__enter__()
        th.stack.append(self)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        th = _THREAD
        th.stack.pop()
        self._ann.__exit__(*exc)
        if self.parent is not None:
            self.parent.child_s += self.end - self.start
        RECORDER.close(self, th.sinks)
        return False


class Totals:
    """Per-name ``{"seconds", "self_seconds", "count"}`` of closed spans,
    and their counters per span name (``""`` holds those added outside
    every span)."""

    def __init__(self):
        self.spans: Dict[str, Dict[str, float]] = {}
        self.counters: Dict[str, Dict[str, float]] = {}

    def add(self, s: Span) -> None:
        dur = s.end - s.start
        t = self.spans.setdefault(s.name, {"seconds": 0.0,
                                           "self_seconds": 0.0, "count": 0})
        t["seconds"] += dur
        t["self_seconds"] += dur - s.child_s
        t["count"] += 1
        if s.counters:
            self.count(s.name, s.counters)

    def count(self, span_name: str, counters: Dict[str, float]) -> None:
        c = self.counters.setdefault(span_name, {})
        for k, v in counters.items():
            c[k] = c.get(k, 0) + v

    def as_dict(self) -> Dict[str, object]:
        return {"spans": {k: dict(v) for k, v in self.spans.items()},
                "counters": {k: dict(v) for k, v in self.counters.items()}}


class Recorder:
    """The process's records: the totals of every closed span, the last
    ``SPAN_LOG`` spans (in the order they closed), and the number of
    ``fed.run`` calls recorded."""

    def __init__(self):
        self.reset()

    def reset(self) -> None:
        with _lock:
            self.totals = Totals()
            self.spans = collections.deque(maxlen=SPAN_LOG)
            self.n_calls = 0

    def close(self, s: Span, sinks) -> None:
        with _lock:
            self.spans.append(s)
            self.totals.add(s)
            for t in sinks:
                t.add(s)

    def add(self, name: str, n) -> None:
        stack = _THREAD.stack
        if stack:
            c = stack[-1].counters
            c[name] = c.get(name, 0) + n
        else:
            with _lock:
                self.totals.count("", {name: n})


RECORDER = Recorder()


def span(name: str):
    """Context manager recording the span ``name`` (shared no-op while
    recording is off)."""
    if not _recording:
        return _NULL_PHASE
    return Span(name)


def count(name: str, n=1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span."""
    if _recording:
        RECORDER.add(name, n)


def fetch(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``: a device array read to the host counts
    one ``d2h_fetches``."""
    out = np.asarray(x, dtype)
    if _recording and isinstance(x, jax.Array):
        RECORDER.add("d2h_fetches", 1)
    return out


def fetch_float(x) -> float:
    """``float(x)``: a device scalar read to the host counts one
    ``d2h_fetches``."""
    if _recording and isinstance(x, jax.Array):
        RECORDER.add("d2h_fetches", 1)
    return float(x)


def to_device(x, dtype=None) -> jax.Array:
    """``jnp.asarray(x, dtype)``: a host array moved to the device counts
    one ``h2d_transfers`` and adds the device array's ``nbytes`` to
    ``h2d_bytes``."""
    out = jnp.asarray(x, dtype)
    if _recording and not isinstance(x, jax.Array):
        RECORDER.add("h2d_transfers", 1)
        RECORDER.add("h2d_bytes", out.nbytes)
    return out


def _switch() -> None:
    global _recording
    _recording = _enabled or _open_calls > 0


def enable() -> None:
    global _enabled
    with _lock:
        _enabled = True
        _switch()


def disable() -> None:
    global _enabled
    with _lock:
        _enabled = False
        _switch()


def is_recording() -> bool:
    return _recording


@contextlib.contextmanager
def recording():
    """Record spans and counters inside the block (and restore the
    switch after it)."""
    global _enabled
    with _lock:
        was, _enabled = _enabled, True
        _switch()
    try:
        yield RECORDER
    finally:
        with _lock:
            _enabled = was
            _switch()


@contextlib.contextmanager
def call(profiler=None):
    """One ``fed.run`` call: while recording, its spans share a fresh call
    id.  Given a ``profiler``, the call records for its length even while
    the switch is off."""
    global _open_calls
    own = profiler is not None
    if not (own or _recording):
        yield
        return
    with _lock:
        RECORDER.n_calls += 1
        call_id = RECORDER.n_calls
        if own:
            _open_calls += 1
            _switch()
    th = _THREAD
    prev, th.call = th.call, call_id
    try:
        yield
    finally:
        th.call = prev
        if own:
            with _lock:
                _open_calls -= 1
                _switch()


def snapshot() -> Dict[str, object]:
    """Totals of everything recorded since the last ``reset()``: ``spans``
    and ``counters`` as ``Totals`` holds them, and ``calls``, the number
    of ``fed.run`` calls recorded."""
    with _lock:
        return {**RECORDER.totals.as_dict(), "calls": RECORDER.n_calls}


def reset() -> None:
    """Drop every record (recording stays as it is)."""
    RECORDER.reset()


class _Phase:
    """Reusable context manager accumulating wall time into a profiler,
    and a recorder span while recording is on."""

    __slots__ = ("_prof", "_name", "_t0", "_span")

    def __init__(self, prof: "PhaseProfiler", name: str):
        self._prof = prof
        self._name = name
        self._t0 = 0.0

    def __enter__(self):
        self._span = span(self._name)
        if self._span is not _NULL_PHASE:
            # the phase's spans, its own included, are also the run's
            _THREAD.sinks.append(self._prof._totals)
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        if self._span is not _NULL_PHASE:
            _THREAD.sinks.pop()
        self._prof._add(self._name, dt)
        return False


class PhaseProfiler:
    """Accumulates named host-time phases from construction to finish()."""

    def __init__(self):
        self._start = time.perf_counter()
        self._end: Optional[float] = None
        self._phases: Dict[str, float] = {}
        self._totals = Totals()   # spans closed inside its phases

    def _add(self, name: str, seconds: float) -> None:
        self._phases[name] = self._phases.get(name, 0.0) + seconds

    def phase(self, name: str) -> _Phase:
        """Context manager timing one (re-enterable) phase."""
        return _Phase(self, name)

    def finish(self) -> Dict[str, object]:
        """Stamp the end time (first call wins) and return `summary()`."""
        if self._end is None:
            self._end = time.perf_counter()
        return self.summary()

    def summary(self) -> Dict[str, object]:
        end = self._end if self._end is not None else time.perf_counter()
        total = max(end - self._start, 1e-12)
        attributed = sum(self._phases.values())
        return {
            "phases": dict(self._phases),
            "total_s": total,
            "unattributed_s": max(total - attributed, 0.0),
            "coverage": min(attributed / total, 1.0),
            **self._totals.as_dict(),
        }


class _NullProfiler:
    """Do-nothing stand-in so engine code has no `if telemetry` timer
    branches: phase() hands back one shared no-op context manager."""

    _PHASE = _NULL_PHASE

    def phase(self, name: str) -> _NullPhase:
        return self._PHASE

    def finish(self) -> None:
        return None

    def summary(self) -> None:
        return None


NULL_PROFILER = _NullProfiler()


def profiler_for(enabled: bool, profiler=None):
    """The engines' profiler hook: an explicit `profiler` wins (callers
    can share one across runs); otherwise a fresh PhaseProfiler when
    telemetry is on, the shared null profiler when off."""
    if profiler is not None:
        return profiler
    return PhaseProfiler() if enabled else NULL_PROFILER
