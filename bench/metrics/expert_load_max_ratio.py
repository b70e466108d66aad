"""Load of the busiest held expert over the mean held expert: the
program's ``moe_tokens_max_expert`` (each layer's largest held-expert row
count, summed) over its ``moe_tokens_held`` (all held rows) divided by
the number of held experts, over the traced window's rounds, from the
program's span recorder.  1 is even; the held experts' kernel waits on
the largest."""
from bench.core import recorded


def read(m):
    snap = recorded.snapshot()
    if snap is None:
        return None
    held = recorded.counter(snap, "moe_tokens_held")
    top = recorded.counter(snap, "moe_tokens_max_expert")
    if held <= 0:
        return None
    return top * m.ctx.config["num_experts"] / held
