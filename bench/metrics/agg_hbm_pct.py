"""The FOLB aggregation kernel's share of its HBM roofline at this D: its
bytes (``bench.core.lm_counts``: (K, D) bf16 grads and g1 read by the
scores pass, (K, D) bf16 deltas and the float32 params read and written
by the apply pass) over its device time x the chip's HBM bandwidth."""
from bench.core import lm_counts


def read(m):
    t = m.reduced.folb_kernel_s(m.driver.ROUND_PROGRAMS)
    if t <= 0 or not m.work.get("agg_bytes"):
        return None
    return m.work["agg_bytes"] / (t * m.peaks["hbm_bytes_per_s"]) * 100.0
