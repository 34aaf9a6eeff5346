"""Granite-4.0-H-Micro (3B): a hybrid of Mamba-2 and GQA attention, 9
Mamba-2 layers to one attention layer, with muP multipliers
[hf:ibm-granite/granite-4.0-h-micro, config.json].

Layer pattern (period 10, as published in ``layer_types``): attention at
layers 5, 15, 25 and 35, Mamba-2 elsewhere; a SwiGLU MLP of 8192 on
every layer, no experts, no positional encoding (NoPE).  The residual
branches are scaled by 0.22, the embeddings by 12, the attention scores
by 1/64 and the logits by 1/8.
"""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    source="hf:ibm-granite/granite-4.0-h-micro",
    n_layers=40,
    d_model=2048,
    n_heads=32,
    n_kv_heads=8,
    d_head=64,
    d_ff=8192,                 # shared_intermediate_size
    vocab_size=100352,
    attn_every=10,
    attn_offset=5,
    pos_scheme="none",
    ssm_kind="mamba2",
    d_state=128,
    d_conv=4,
    expand=2,
    ssm_heads=64,
    ssm_head_dim=64,
    ssm_groups=1,
    ssm_chunk=256,
    act="swiglu",
    norm="rmsnorm",
    norm_eps=1e-5,
    tie_embeddings=True,
    residual_scale=0.22,
    embed_scale=12.0,
    attn_multiplier=1.0 / 64,
    logits_scaling=8.0,
)
