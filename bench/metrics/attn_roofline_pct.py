"""The attention kernels' share of their roofline: the matrix products of
the blocks the splash kernels compute (``bench.core.lm_counts``: every
block its mask reaches, window and full layers), forward, recomputed
forward and backward, over their device time x the chip's bf16 peak."""
from bench.core import lm_counts


def read(m):
    t = (lm_counts.entry_kernel_s(m, "attention_window")
         + lm_counts.entry_kernel_s(m, "attention_full"))
    flops = m.work.get("attn_window_flops", 0) + m.work.get(
        "attn_full_flops", 0)
    if t <= 0 or not flops:
        return None
    return flops / (t * m.peaks["bf16_flops_per_s"]) * 100.0
