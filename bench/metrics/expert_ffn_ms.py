"""Device milliseconds per round of the grouped expert FFN: the Mosaic
kernels in the round programs launched from the program's
``moe_grouped_ffn`` entry point (forward and backward)."""
from bench.core import lm_counts


def read(m):
    t = lm_counts.entry_kernel_s(m, "moe_grouped_ffn")
    if t <= 0:
        return None
    return t / m.work["rounds"] * 1e3
