"""Milliseconds per communication round: the whole window over the rounds
completed in it (all the work over all the time)."""


def read(m):
    return m.window_s / m.work["rounds"] * 1e3
