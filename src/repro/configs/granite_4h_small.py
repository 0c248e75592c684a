"""granite-4.0-h-small (Granite 4.0-H Small, 32B-A9B) — hybrid Mamba-2 + GQA,
MoE on every layer: 72 experts top-10 with one shared expert.
[hf:ibm-granite/granite-4.0-h-small config.json]

40 layers in a period of 10 (Mamba x 5, attention, Mamba x 4); Mamba-2 with
128 heads of 64 and d_state 128; attention without positional embedding,
scaled by ``attention_multiplier`` 1/128; embeddings times 12, every
sublayer's output times 0.22 before the residual add, logits over 16.
The experts run as an expert share (``moe_impl="share"``, models/moe.py),
which holds every expert here; a chip's share sets ``experts_held``.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="granite-4.0-h-small",
    family="hybrid",
    n_layers=40,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    head_dim=128,
    d_ff=0,
    vocab_size=100352,
    mlp_type="swiglu",
    norm="rmsnorm",
    pos_emb="none",
    tie_embeddings=True,
    moe=True,
    n_experts=72,
    n_experts_per_tok=10,
    moe_d_ff=768,
    shared_expert_d_ff=1536,
    moe_impl="share",
    ssm=True,
    ssm_state=128,
    ssm_expand=2,
    ssm_headdim=64,
    ssm_ngroups=1,
    conv_width=4,
    ssd_chunk=256,
    ssm_norm_eps=1e-5,
    attn_period=10,
    attn_offset=5,
    attn_scale=0.0078125,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=16.0,
)

SMOKE = CONFIG.replace(
    name="granite-4.0-h-small-smoke",
    n_layers=10, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    vocab_size=512, n_experts=8, n_experts_per_tok=3, moe_d_ff=32,
    shared_expert_d_ff=64, ssm_state=16, ssm_headdim=16, ssd_chunk=16,
    attn_scale=1.0 / 16,
)
