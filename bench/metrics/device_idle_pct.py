"""Share of the traced window in which no operation ran on the device:
1 - (union of the device op intervals) / window."""


def read(m):
    r = m.reduced
    if r.busy_s <= 0:
        return None
    return (1.0 - r.busy_s / r.window_s) * 100.0
