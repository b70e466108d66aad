"""The grouped expert FFN's share of its roofline: the matrix products it
executes over the round's routed rows (``bench.core.lm_counts``: three a
row forward, again under recomputation, and two per product backward) over
its device time x the chip's bf16 peak.  Bound by its FLOPs: at the
cell's shapes its bytes (the held experts' weights and the routed rows of
each product) take about half as long at the chip's bandwidth."""
from bench.core import lm_counts


def read(m):
    t = lm_counts.entry_kernel_s(m, "moe_grouped_ffn")
    if t <= 0 or not m.work.get("expert_kernel_flops"):
        return None
    return m.work["expert_kernel_flops"] / (t * m.peaks["bf16_flops_per_s"]) \
        * 100.0
