"""Model FLOPs of the rounds completed in the traced window (every local
gradient evaluation of every client, forward and backward, from shapes)
over the traced window times the chip's bf16 peak."""


def read(m):
    r = m.reduced
    if not m.work["flops"] or r.window_s <= 0:
        return None
    n = m.ctx.workload["chips"]
    return m.work["flops"] / (r.window_s * n * m.peaks["bf16_flops_per_s"]) \
        * 100.0
