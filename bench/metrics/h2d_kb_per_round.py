"""Kilobytes per round the program moved from the host to the device (its
``h2d_bytes`` counter: the ``nbytes`` of every numpy array it transfers)
over the traced window's runs, from the program's span recorder."""
from bench.core import recorded


def read(m):
    snap = recorded.snapshot()
    if snap is None:
        return None
    return recorded.counter(snap, "h2d_bytes") / m.work["rounds"] / 1e3
