"""Device milliseconds per round of the FOLB aggregation kernel's events:
the Mosaic kernels in the round programs launched from the program's FOLB
entry points (``bench.core.trace.FOLB_ENTRIES``), and no other kernel."""


def read(m):
    t = m.reduced.folb_kernel_s(m.driver.ROUND_PROGRAMS)
    if t <= 0:
        return None
    return t / m.work["rounds"] * 1e3
