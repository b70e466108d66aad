"""Mellum2-12B-A2.5B — sparse experts in every layer, sliding-window and
full attention mixed 3:1
[huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, config.json].

Published: 28L d_model=2304 32H (GQA kv=4) head_dim=128 vocab=98304,
untied embeddings, RMSNorm eps 1e-6; every layer sparse: 64 experts of
width 896, top-8 with the top-k probabilities renormalised, no shared
expert.  ``layer_types`` repeats three ``sliding_attention`` layers
(window 1024, default RoPE, theta 500000) and one ``full_attention`` layer
(YaRN: theta 500000, factor 16 over 8192 original positions, beta_fast 32,
beta_slow 1, attention_factor 1.2772588722239782).

``CONFIG`` is one chip's share of a stated deployment: each layer's 64
experts are spread over 8 chips by expert parallelism, so this chip holds
8 (experts 0-7) while the router keeps its 64 outputs and top-8; the
vocabulary is sliced to an eighth (12288 ids, embedding and head alike);
the depth is one whole period (3 window layers, then 1 full layer), the
other layers lying on further pipeline stages.  No width is cut.

Assumed where the config is silent: softmax router scores; no QK-norm
and no attention bias; no router auxiliary loss; the multi-token
prediction head is left out.  ``intermediate_size`` 7168 is unused: no
layer is dense.
"""
from repro.configs.base import ArchConfig, AttnKind, MoEConfig, RopeConfig

THETA = 500000.0
WINDOW = AttnKind("window", 1024, RopeConfig(theta=THETA))
FULL = AttnKind("full", 0, RopeConfig(
    theta=THETA, yarn_factor=16.0, original_max_position=8192,
    beta_fast=32.0, beta_slow=1.0,
    attention_factor=1.2772588722239782))

PUBLISHED_LAYERS = 28
PUBLISHED_VOCAB = 98304

CONFIG = ArchConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=4,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=0,
    vocab=PUBLISHED_VOCAB // 8,
    act="silu",
    rope_theta=THETA,
    attn_period=(WINDOW, WINDOW, WINDOW, FULL),
    moe=MoEConfig(n_experts=64, top_k=8, expert_d_ff=896,
                  router_aux_weight=0.0, sharding="expert",
                  n_held=8, held_offset=0),
    source="huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct "
           "(config.json): one period, 8 of 64 experts, 1/8 vocabulary",
    param_dtype="bfloat16",
)
