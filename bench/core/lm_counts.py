"""Operations and bytes of the language-model FOLB round, counted from the
configuration's shapes (``bench/configs/<name>.json``), the traffic and the
round's routed token counts.  Kept with the benchmark so that every later
change is measured against the same arithmetic.

A round makes K x E gradient evaluations (each client: its gradient at
w^t, reused as the first step, then E - 1 more), each over ``seqs_per_client``
sequences of ``seq_len`` tokens.  A gradient evaluation is a forward and a
backward pass: model FLOPs are 3x the forward's.  With ``remat`` every
layer's forward runs twice (once more in the backward pass): the kernels'
executed FLOPs count that, the model FLOPs do not.
"""
from __future__ import annotations

# splash attention's blocks along both sequences (program:
# kernels/splash_attn.BLOCK for sequences that are a multiple of it)
ATTN_BLOCK = 512
LANES = 128


def param_count(cfg: dict) -> int:
    """Parameters this chip holds: the flat buffer's D before padding."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    experts = cfg["num_experts"] * 3 * d * cfg["moe_intermediate_size"]
    layer = attn + experts + d * cfg["num_experts_router"] + 2 * d
    vocab = (1 if cfg["tie_word_embeddings"] else 2) * V * d
    return cfg["num_hidden_layers"] * layer + vocab + d


def _window(cfg: dict, kind: str) -> int:
    return cfg["sliding_window"] if kind == "sliding_attention" else 0


def attended_pairs(seq: int, window: int) -> int:
    """(query, key) pairs a causal mask of ``window`` keys (0 = all earlier
    keys) leaves, over one head of one sequence."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def active_blocks(seq: int, window: int, block: int) -> int:
    """(query block, key block) pairs of a ``seq``-long causal mask with
    ``window`` that hold any attended pair: those the kernel computes."""
    nb = seq // block
    n = 0
    for i in range(nb):
        q_lo, q_hi = i * block, (i + 1) * block - 1
        for j in range(i + 1):
            k_lo, k_hi = j * block, (j + 1) * block - 1
            if window and q_lo - k_hi >= window:
                continue
            n += 1
    return n


def _padded(seq: int) -> int:
    return -(-seq // LANES) * LANES


def _block(seq: int) -> int:
    return ATTN_BLOCK if seq % ATTN_BLOCK == 0 else LANES


def evals(traffic: dict) -> int:
    return traffic["clients_per_round"] * traffic["local_steps"]


def round_counts(cfg: dict, traffic: dict, load_sum: float) -> dict:
    """Per round: ``model_flops`` (forward and backward of every gradient
    evaluation, attention over the attended pairs only),
    ``expert_kernel_flops`` (the grouped FFN's executed matrix products over
    the routed rows), ``attn_kernel_flops`` by kind (executed products of
    the blocks the kernel computes) and ``agg_bytes`` (the FOLB kernel's HBM
    traffic).  ``load_sum`` is the round's routed rows over every layer,
    held expert and gradient evaluation."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ff = cfg["moe_intermediate_size"]
    S, b = traffic["seq_len"], traffic["seqs_per_client"]
    n_eval = evals(traffic)
    tokens = b * S * n_eval
    remat = 2 if traffic["remat"] else 1
    proj = d * H * hd + 2 * d * KV * hd + H * hd * d \
        + d * cfg["num_experts_router"]
    dense_fwd = 2 * tokens * (cfg["num_hidden_layers"] * proj + d * V)
    expert_fwd = 2 * 3 * d * ff * load_sum
    attn_fwd, attn_kernel = 0.0, {"sliding_attention": 0.0,
                                  "full_attention": 0.0}
    Sp = _padded(S)
    blk = _block(Sp)
    for kind in cfg["layer_types"]:
        w = _window(cfg, kind)
        attn_fwd += 4.0 * hd * H * attended_pairs(S, w) * b * n_eval
        # forward: QK^T, PV (x remat); backward: dq kernel 3 products, dkv
        # kernel 4
        products = 2 * remat + 3 + 4
        attn_kernel[kind] += 2.0 * products * blk * blk * hd * H \
            * active_blocks(Sp, w, blk) * b * n_eval
    K = traffic["clients_per_round"]
    D = param_count(cfg)
    return {
        "model_flops": 3.0 * (dense_fwd + expert_fwd + attn_fwd),
        # three products forward (x remat), two backward per product
        "expert_kernel_flops": expert_fwd * (remat + 2),
        "attn_kernel_flops": attn_kernel,
        # scores pass: grads (K, D) bf16 and g1 (D,) f32; apply pass:
        # deltas (K, D) bf16, w (D,) f32 in and out
        "agg_bytes": 2.0 * K * D * 2 + 3.0 * D * 4,
    }


def entry_kernel_s(m, entry: str) -> float:
    """Device seconds, in the traced window, of the Mosaic kernels that the
    program's kernel entry point ``entry`` launched inside the driver's round
    programs (``Reduced.kernel_s_by``)."""
    return sum(v for (prog, ent), v in m.reduced.kernel_s_by.items()
               if prog in m.driver.ROUND_PROGRAMS and ent == entry)
