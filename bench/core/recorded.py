"""What the program's own span recorder (``repro.telemetry.profiler``)
holds after a traced window.

``repro.fed.run`` records every call it is given a ``profiler=`` for, as
the calls of a traced window are: spans and counters of the window's
calls, in this process.  A program without the recorder gives
``None``, so a reader built on it reports nothing there."""
from __future__ import annotations

import sys
from typing import Optional


def snapshot() -> Optional[dict]:
    """The recorder's totals, or None when the program has no recorder or
    recorded no span."""
    rec = sys.modules.get("repro.telemetry.profiler")
    snap = getattr(rec, "snapshot", None)
    if snap is None:
        return None
    out = snap()
    return out if out["spans"] else None


def counter(snap: dict, name: str, prefix: str = "") -> float:
    """Counter ``name`` summed over the spans whose name starts with
    ``prefix`` (every span, and counts outside them, for ``""``)."""
    return sum(c.get(name, 0) for span, c in snap["counters"].items()
               if span.startswith(prefix))
