"""Device milliseconds per round of the window layers' attention: the
Mosaic kernels in the round programs launched from the program's
``attention_window`` entry point (forward and backward)."""
from bench.core import lm_counts


def read(m):
    t = lm_counts.entry_kernel_s(m, "attention_window")
    if t <= 0:
        return None
    return t / m.work["rounds"] * 1e3
