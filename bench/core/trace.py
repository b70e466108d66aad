"""Reduction of a JAX profiler trace to the benchmark's device numbers.

A trace is the ``.xplane.pb`` (an ``XSpace`` protocol buffer) that
``jax.profiler.start_trace`` writes; ``read_xspace`` decodes the part of
it used here.  On a TPU each chip is a plane ``/device:TPU:<n>`` whose
line ``XLA Ops`` holds one event per executed HLO op and whose line ``XLA
Modules`` holds one event per executed program (``jit_<name>(<id>)``).
An op's metadata carries its JAX name stack (stat ``tf_op``), such as
``jit(scan_rounds)/while/body/jit(folb_aggregate_buffers)/pallas_call``.
Host threads are lines of the plane ``/host:CPU``; the benchmark's own
``jax.profiler.TraceAnnotation`` spans appear there by name, on the same
clock as the device events.

Everything is reduced inside one host span, the traced window:

- busy: the union of the device op intervals, averaged over the chips;
- per program: the exclusive op time inside each program's executions,
  by jit name (control-flow ops span the ops of their bodies, so each op
  counts its own time only);
- kernels: op time of Mosaic kernels (``tpu_custom_call``), by program
  and by kernel entry: the innermost ``jit(<name>)`` scope of the op's
  name stack, the jitted function of the program's ``kernels/ops`` that
  launched it.  The FOLB aggregation kernels are those launched from
  ``FOLB_ENTRIES``; any other kernel (attention, scans) is not theirs;
- idle gaps: holes in the busy union, each labelled by the innermost
  benchmark span open at its middle (``host`` when none is).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN_PREFIXES = ("phase:", "unit:", "window")
JIT_SCOPE = re.compile(r"jit\(([^()]+)\)")
# the program's jitted entry points (repro.kernels.ops) that launch the
# FOLB aggregation kernels
FOLB_ENTRIES = ("folb_aggregate_buffers", "folb_staleness_buffers")

Interval = Tuple[float, float]


@dataclasses.dataclass
class DeviceOps:
    """One chip's events in ns: ops (start, end, name, name stack) sorted
    by start, and program executions (start, end, jit name)."""
    ops: List[Tuple[float, float, str, str]]
    modules: List[Tuple[float, float, str]]


@dataclasses.dataclass
class Trace:
    devices: List[DeviceOps]
    spans: List[Tuple[float, float, str]]     # benchmark host spans


def module_name(event_name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return event_name.split("(", 1)[0]


def op_label(event_name: str) -> str:
    """``%fusion.9 = f32[..] fusion(..)`` -> ``fusion.9``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def kernel_entry(scope: str) -> str:
    """The innermost ``jit(<name>)`` of a name stack, or ``""``."""
    found = JIT_SCOPE.findall(scope)
    return found[-1] if found else ""


# ------------------------------------------------------------ XSpace
# Field numbers of tsl/profiler/protobuf/xplane.proto: XSpace.planes 1;
# XPlane name 2, lines 3, event_metadata 4, stat_metadata 5; XLine name 2,
# timestamp_ns 3, events 4, display_name 11; XEvent metadata_id 1,
# offset_ps 2, duration_ps 3; XEventMetadata name 2, stats 5; XStat
# metadata_id 1, str_value 5, ref_value 7; XStatMetadata name 2; a map
# entry holds its key in 1 and its value in 2.

def _varint(b: bytes, i: int):
    v = shift = 0
    while True:
        c = b[i]
        i += 1
        v |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return v, i


def _fields(b: bytes):
    """(field number, value) of each field of one message: an int for a
    varint, bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclasses.dataclass
class XLine:
    name: str
    events: List[Tuple[float, float, str, str]]   # start, end ns, name, tf_op


def _event_names(meta: Dict[int, bytes], stat_names: Dict[int, str]):
    """{metadata id: (name, tf_op)} of a plane's event metadata."""
    out = {}
    for mid, raw in meta.items():
        name, tf_op = "", ""
        for f, v in _fields(raw):
            if f == 2:
                name = v.decode(errors="replace")
            elif f == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) != "tf_op":
                    continue
                if 5 in stat:
                    tf_op = stat[5].decode(errors="replace")
                elif 7 in stat:
                    tf_op = stat_names.get(stat[7], "")
        out[mid] = (name, tf_op)
    return out


def _line(raw: bytes, names) -> XLine:
    name = display = ""
    ts, events = 0, []
    for f, v in _fields(raw):
        if f == 2:
            name = v.decode()
        elif f == 11:
            display = v.decode()
        elif f == 3:
            ts = _signed(v)
        elif f == 4:
            mid = off = dur = 0
            for ef, ev in _fields(v):
                if ef == 1:
                    mid = ev
                elif ef == 2:
                    off = _signed(ev)
                elif ef == 3:
                    dur = _signed(ev)
            start = ts + off * 1e-3
            events.append((start, start + dur * 1e-3, *names.get(mid, ("", ""))))
    return XLine(name or display, events)


def read_xspace(path: str) -> Dict[str, List[XLine]]:
    """{plane name: lines} of an ``.xplane.pb``; each event with its start
    and end in ns, its metadata's name and its ``tf_op`` stat (or "")."""
    with open(path, "rb") as f:
        data = f.read()
    planes: Dict[str, List[XLine]] = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, raw_lines, meta, stat_names = "", [], {}, {}
        for f, v in _fields(plane):
            if f == 2:
                name = v.decode()
            elif f == 3:
                raw_lines.append(v)
            elif f in (4, 5):
                entry = dict(_fields(v))
                if f == 4:
                    meta[entry.get(1, 0)] = entry.get(2, b"")
                else:
                    stat_names[entry.get(1, 0)] = dict(
                        _fields(entry.get(2, b""))).get(2, b"").decode()
        names = _event_names(meta, stat_names)
        planes[name] = [_line(raw, names) for raw in raw_lines]
    return planes


def find_trace_file(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def load(path: str) -> Trace:
    devices, spans = [], []
    for pname, lines in read_xspace(path).items():
        if DEVICE_PLANE.match(pname):
            ops, mods = [], []
            for line in lines:
                if line.name == "XLA Ops":
                    ops = list(line.events)
                elif line.name == "XLA Modules":
                    mods = [(s, e, module_name(n)) for s, e, n, _ in
                            line.events]
            ops.sort(key=lambda o: (o[0], -o[1]))   # parents first
            mods.sort()
            devices.append(DeviceOps(ops, mods))
        elif pname == "/host:CPU":
            spans += [(s, e, n) for line in lines for s, e, n, _ in
                      line.events if n.startswith(SPAN_PREFIXES)]
    spans.sort()
    return Trace(devices, spans)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def self_times(ops) -> List[float]:
    """Exclusive time of each op of a start-sorted list: its duration less
    that of the ops nested in it (a ``while`` or ``conditional`` op spans
    the ops of its body)."""
    own = [op[1] - op[0] for op in ops]
    stack: List[int] = []
    for i, (s, e) in enumerate(op[:2] for op in ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [max(v, 0.0) for v in own]


@dataclasses.dataclass
class Reduced:
    """Seconds, averaged over the chips, inside the traced window."""
    window_s: float
    busy_s: float
    program_s: Dict[str, float]        # op time inside each jit program
    kernel_s: float                    # op time of Mosaic kernels
    kernel_calls: int                  # kernel executions (per chip)
    kernel_s_by: Dict[Tuple[str, str], float]   # by (program, entry)
    span_s: Dict[str, float]           # host span time by name
    span_device_s: Dict[str, float]    # device busy time under each span
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    def folb_kernel_s(self, programs) -> float:
        """Op time of the FOLB aggregation kernels inside ``programs``."""
        return sum(v for (prog, entry), v in self.kernel_s_by.items()
                   if prog in programs and entry in FOLB_ENTRIES)


def window_of(trace: Trace, name: str = "window") -> Interval:
    for s, e, n in trace.spans:
        if n == name:
            return s, e
    raise ValueError(f"no host span {name!r} in the trace")


def reduce(trace: Trace, window: Optional[Interval] = None,
           n_top: int = 10) -> Reduced:
    lo, hi = window if window is not None else window_of(trace)
    n_dev = max(len(trace.devices), 1)
    spans = [(max(s, lo), min(e, hi), n) for s, e, n in trace.spans
             if e > lo and s < hi and n != "window"]
    busy = kern_s = 0.0
    kern_calls = 0
    programs: Dict[str, float] = {}
    kern_by: Dict[Tuple[str, str], float] = {}
    op_time: Dict[str, float] = {}
    span_dev: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for dev in trace.devices:
        ops = [(max(s, lo), min(e, hi), n, scope)
               for s, e, n, scope in dev.ops if e > lo and s < hi]
        merged = union([op[:2] for op in ops])
        busy += total(merged)
        prev = lo
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if hi > prev:
            gaps.append((prev, hi))
        starts = [m[0] for m in dev.modules]
        for (s, e, n, scope), own in zip(ops, self_times(ops)):
            i = bisect.bisect_right(starts, s) - 1
            mod = dev.modules[i][2] if i >= 0 and dev.modules[i][1] >= s \
                else "unattributed"
            programs[mod] = programs.get(mod, 0.0) + own
            label = f"{mod}/{op_label(n)}"
            if KERNEL_MARK in n:
                kern_s += own
                kern_calls += 1
                key = (mod, kernel_entry(scope))
                kern_by[key] = kern_by.get(key, 0.0) + own
                label += f" (kernel {key[1]})"
            op_time[label] = op_time.get(label, 0.0) + own
        for name in {n for _, _, n in spans}:
            mine = union([(s, e) for s, e, n in spans if n == name])
            span_dev[name] = span_dev.get(name, 0.0) + overlap(mine, merged)
    span_s: Dict[str, float] = {}
    for name in {n for _, _, n in spans}:
        span_s[name] = total(union([(s, e) for s, e, n in spans
                                    if n == name])) * 1e-9

    def label_gap(s, e):
        mid = 0.5 * (s + e)
        open_ = [(se - ss, n) for ss, se, n in spans if ss <= mid <= se]
        return min(open_)[1] if open_ else "host"

    gaps.sort(key=lambda g: g[0] - g[1])
    top_gaps = [(label_gap(s, e), (e - s) * 1e-9) for s, e in gaps[:n_top]]
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:n_top]
    scale = 1e-9 / n_dev
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy * scale,
        program_s={k: v * scale for k, v in programs.items()},
        kernel_s=kern_s * scale, kernel_calls=kern_calls // n_dev,
        kernel_s_by={k: v * scale for k, v in kern_by.items()},
        span_s=span_s,
        span_device_s={k: v * scale for k, v in span_dev.items()},
        top_ops=[(k, v * scale) for k, v in top],
        idle_gaps=top_gaps)
