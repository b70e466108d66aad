"""Device round trips per round in the program's ``plan_build/*`` spans:
its ``h2d_transfers`` (one per device array made from a host array) plus
its ``d2h_fetches`` (one per device value read to the host) there, over
the traced window's runs, from the program's span recorder.  A program
that does not count ``h2d_transfers`` reports nothing."""
from bench.core import recorded


def read(m):
    snap = recorded.snapshot()
    if snap is None or not any("h2d_transfers" in c
                               for c in snap["counters"].values()):
        return None
    moves = (recorded.counter(snap, "h2d_transfers", "plan_build/")
             + recorded.counter(snap, "d2h_fetches", "plan_build/"))
    return moves / m.work["rounds"]
