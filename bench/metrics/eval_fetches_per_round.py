"""Device-to-host reads per round in the program's ``eval/*`` spans (its
``d2h_fetches`` counter: one per device value read to the host) over the
traced window's runs, from the program's span recorder."""
from bench.core import recorded


def read(m):
    snap = recorded.snapshot()
    if snap is None or not any(s.startswith("eval/") for s in snap["spans"]):
        return None
    return recorded.counter(snap, "d2h_fetches", "eval/") / m.work["rounds"]
