"""Nominal operations and bytes of an LM decode step, counted from the
configuration file's sizes (HF keys), whatever implements them.

A step decodes one token for each of ``batch`` sessions. Its flops are 2 x
the parameters one token touches on this chip, times the batch, plus the
attention layers' 4 x ctx x (heads x head_dim) a token. The parameters a token
touches: every mixer, each layer's router and shared expert, the tied head,
and the held experts at the share of the top-k evaluations that land on
them (k x held / experts). Norms, the conv and the SSM's own update are left
out; the state update has its own count below.
"""
from __future__ import annotations

F32_BYTES = 4


def _sizes(c: dict) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    din = c["mamba_n_heads"] * c["mamba_d_head"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    hd = d // H
    return {
        "mamba": d * (2 * din + 2 * gn + c["mamba_n_heads"]) + din * d,
        "attention": 2 * d * H * hd + 2 * d * c["num_key_value_heads"] * hd,
        "router": d * c["deployment"]["experts_total"],
        "shared": 3 * d * c["shared_intermediate_size"],
        "expert": 3 * d * c["intermediate_size"],
        "head": d * c["vocab_size"],
        "q_width": H * hd,
    }


def token_params(c: dict) -> float:
    """Parameters one token touches on this chip (see the module's doc)."""
    s = _sizes(c)
    per_expert = (c["num_experts_per_tok"] * c["num_local_experts"]
                  / c["deployment"]["experts_total"])
    moe = s["router"] + s["shared"] + per_expert * s["expert"]
    mixers = sum(s[kind] for kind in c["layer_types"])
    return float(mixers + len(c["layer_types"]) * moe + s["head"])


def decode_flops(c: dict, batch: int, steps: int, ctx_sum: float) -> float:
    """Flops of ``steps`` decode steps of ``batch`` sessions, whose attended
    context lengths (the KV entries a token reads, its own included) add up
    to ``ctx_sum`` over the steps."""
    n_attn = sum(kind == "attention" for kind in c["layer_types"])
    return (2.0 * token_params(c) * batch * steps
            + 4.0 * batch * ctx_sum * _sizes(c)["q_width"] * n_attn)


def ssm_state_update(batch: int, heads: int, head_dim: int, d_state: int) -> tuple:
    """(flops, bytes) of one layer's decode update of a float32 SSM state of
    (batch, heads, head_dim, d_state): the decay, the outer product added and
    the read-out against C, 5 flops an element; the state read and written
    once, and x (batch, heads, head_dim), B and C (batch, d_state) and dt
    (batch, heads) read once, in float32."""
    n = batch * heads * head_dim * d_state
    flops = 5.0 * n
    bytes_ = F32_BYTES * (2 * n + batch * (heads * head_dim + 2 * d_state
                                           + heads))
    return flops, bytes_
