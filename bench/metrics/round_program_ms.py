"""Device milliseconds per round inside the round programs (the driver's
``ROUND_PROGRAMS`` jit names), less the FOLB aggregation kernel's events.
Any other Mosaic kernel in those programs counts here."""


def read(m):
    r = m.reduced
    names = m.driver.ROUND_PROGRAMS
    t = sum(r.program_s.get(n, 0.0) for n in names) - r.folb_kernel_s(names)
    if t <= 0:
        return None
    return t / m.work["rounds"] * 1e3
