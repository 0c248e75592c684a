"""Plain float32 reference of the granite-4.0-h hybrid stack (HF
``GraniteMoeHybrid``) for the output check of the LM decode cells. It imports
nothing of the program.

The weights are plain arrays (any float dtype; each product is taken in
float32 at ``precision="highest"``):

* ``embed`` (V, d), tied to the head; ``final_norm`` (d,);
* ``layers``: one dict a layer, in order, with ``norm1`` and then
  - a Mamba-2 layer: ``in_proj`` (d, 2 d_inner + 2 G N + H) giving z, x, B,
    C, dt; ``conv_w`` (W, d_inner + 2 G N) and ``conv_b``; ``A_log``, ``D``,
    ``dt_bias`` (H,); ``norm`` (d_inner,); ``out_proj`` (d_inner, d);
  - an attention layer: ``wq`` (d, H, hd), ``wk`` and ``wv`` (d, K, hd),
    ``wo`` (H, hd, d);
  and ``norm2``, ``router`` (d, E), the held experts' ``gate`` and ``up``
  (n, d, ff) and ``down`` (n, ff, d), the shared expert's ``shared_gate``,
  ``shared_up`` (d, f) and ``shared_down`` (f, d).

The equations:

* Mamba-2, token by token from a zero state (heads, head_dim, d_state):
  after the causal depthwise conv with bias and SiLU of (x, B, C),
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T`` and ``y_t = h_t C_t + D x_t``
  with ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; then
  ``rmsnorm(y * silu(z))`` and the out projection;
* attention over every earlier position, no positional embedding, scores
  times ``attention_multiplier``, KV head ``h // (H / K)`` for query head h;
* MoE: softmax over the top-k router logits of all E experts; each held
  expert adds its SwiGLU output at its gate (0 where it was not picked); the
  shared expert is added once;
* embeddings times ``embedding_multiplier``, every sublayer's output times
  ``residual_multiplier`` before the residual add, RMSNorm with
  ``rms_norm_eps``, logits over ``logits_scaling``.

The control (``low``, a tuple of parts) computes one precision below the
configuration's in the parts it names: ``"weights"`` rounds the weights to
float8 e4m3 (stated bfloat16), ``"state"`` carries the SSM state in bfloat16
(stated float32).

It is computed in blocks so that it fits on the chip: one jitted call a
layer, attention in blocks of query rows, the experts one at a time.
Departures from HF: experts outside the share are left out, as the program
leaves them out; the SwiGLU halves of HF's fused ``input_linear`` are the
separate gate and up matrices.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
Q_BLOCK = 512
LOW_WEIGHTS = jnp.float8_e4m3fn      # the control's: one below bfloat16
LOW_STATE = jnp.bfloat16             # the control's: one below float32
LOW_PARTS = ("weights", "state")


@dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    m_heads: int
    m_head_dim: int
    d_state: int
    groups: int
    conv: int
    top_k: int
    expert_offset: int
    attn_scale: float
    emb_mult: float
    res_mult: float
    logits_scaling: float
    eps: float
    layer_types: tuple


def dims_of(config: dict) -> Dims:
    """The sizes and multipliers of a configuration file (HF keys)."""
    c = config
    return Dims(c["hidden_size"], c["num_attention_heads"],
                c["num_key_value_heads"], c["mamba_n_heads"], c["mamba_d_head"],
                c["mamba_d_state"], c["mamba_n_groups"], c["mamba_d_conv"],
                c["num_experts_per_tok"], c["deployment"]["expert_offset"],
                float(c["attention_multiplier"]),
                float(c["embedding_multiplier"]),
                float(c["residual_multiplier"]), float(c["logits_scaling"]),
                float(c["rms_norm_eps"]), tuple(c["layer_types"]))


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _f32(w):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), w)


def _mamba(D: Dims, w, x, state_dtype):
    din, G, N = D.m_heads * D.m_head_dim, D.groups, D.d_state
    H, Pd, W = D.m_heads, D.m_head_dim, D.conv
    b, S, _ = x.shape
    zxbcdt = x @ w["in_proj"]
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din:2 * din + 2 * G * N]
    dt = jax.nn.softplus(zxbcdt[..., 2 * din + 2 * G * N:] + w["dt_bias"])
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, i:i + S] * w["conv_w"][i] for i in range(W))
                      + w["conv_b"])
    xs = xbc[..., :din].reshape(b, S, H, Pd)
    group = jnp.arange(H) // (H // G)
    Bm = xbc[..., din:din + G * N].reshape(b, S, G, N)[:, :, group]
    Cm = xbc[..., din + G * N:].reshape(b, S, G, N)[:, :, group]
    A = -jnp.exp(w["A_log"])

    def step(h, inp):
        x_t, B_t, C_t, dt_t = inp
        h = (h.astype(F32) * jnp.exp(dt_t * A)[:, :, None, None]
             + dt_t[:, :, None, None] * x_t[..., :, None] * B_t[..., None, :])
        h = h.astype(state_dtype)
        y = jnp.einsum("bhpn,bhn->bhp", h.astype(F32), C_t) + w["D"][:, None] * x_t
        return h, y

    h0 = jnp.zeros((b, H, Pd, N), state_dtype)
    h, y = lax.scan(step, h0, [a.swapaxes(0, 1) for a in (xs, Bm, Cm, dt)])
    y = y.swapaxes(0, 1).reshape(b, S, din)
    return (_rmsnorm(y * jax.nn.silu(z), w["norm"], D.eps) @ w["out_proj"],
            h.astype(F32))


def _attention(D: Dims, w, x):
    H, K = D.heads, D.kv_heads
    b, S, _ = x.shape
    kv = jnp.arange(H) // (H // K)
    q = jnp.einsum("bsd,dhk->bshk", x, w["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, w["wk"])[:, :, kv]
    v = jnp.einsum("bsd,dhk->bshk", x, w["wv"])[:, :, kv]
    out = []
    for r0 in range(0, S, Q_BLOCK):
        rows = jnp.arange(r0, min(r0 + Q_BLOCK, S))
        s = jnp.einsum("bqhk,bshk->bhqs", q[:, rows], k) * D.attn_scale
        s = jnp.where(jnp.arange(S)[None, :] <= rows[:, None], s, -jnp.inf)
        out.append(jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v))
    return jnp.einsum("bqhk,hkd->bqd", jnp.concatenate(out, axis=1), w["wo"])


def _moe(D: Dims, w, x):
    xf = x.reshape(-1, D.d)
    top, idx = lax.top_k(xf @ w["router"], D.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    y = (jax.nn.silu(xf @ w["shared_gate"]) * (xf @ w["shared_up"])) @ w["shared_down"]
    for e in range(w["gate"].shape[0]):
        g = jnp.sum(jnp.where(idx == D.expert_offset + e, gates, 0.0), axis=-1)
        h = jax.nn.silu(xf @ w["gate"][e]) * (xf @ w["up"][e])
        y = y + g[:, None] * (h @ w["down"][e])
    return y.reshape(x.shape)


@partial(jax.jit, static_argnames=("D", "kind", "low"))
def _layer(D: Dims, kind: str, w, x, low=()):
    state_dtype = LOW_STATE if "state" in low else F32
    with jax.default_matmul_precision("highest"):
        if "weights" in low:
            w = jax.tree.map(lambda a: jnp.asarray(a, F32).astype(LOW_WEIGHTS), w)
        w = _f32(w)
        h = _rmsnorm(x, w["norm1"], D.eps)
        state = None
        if kind == "mamba":
            mix, state = _mamba(D, w, h, state_dtype)
        else:
            mix = _attention(D, w, h)
        x = x + D.res_mult * mix
        return x + D.res_mult * _moe(D, w, _rmsnorm(x, w["norm2"], D.eps)), state


def _table(embed, low):
    e = jnp.asarray(embed, F32)
    return e.astype(LOW_WEIGHTS).astype(F32) if "weights" in low else e


@partial(jax.jit, static_argnames=("D", "low"))
def _embed(D: Dims, embed, tokens, low=()):
    return _table(embed, low)[tokens] * D.emb_mult


@partial(jax.jit, static_argnames=("D", "low"))
def _head(D: Dims, embed, final_norm, x, low=()):
    with jax.default_matmul_precision("highest"):
        x = _rmsnorm(x, jnp.asarray(final_norm, F32), D.eps)
        return (x @ _table(embed, low).T) / D.logits_scaling


def logits_at(D: Dims, weights: dict, tokens, positions, low: tuple = ()):
    """Float32 logits (b, len(positions), V) of the whole stack over
    ``tokens`` (b, L), at the given positions, and each Mamba layer's SSM
    state after the last token, (b, heads, head_dim, d_state) in float32;
    ``low`` names the parts the control computes one precision down."""
    low = tuple(low)
    if set(low) - set(LOW_PARTS):
        raise ValueError(f"control parts {low}, not of {LOW_PARTS}")
    x = _embed(D, weights["embed"], jnp.asarray(tokens, jnp.int32), low=low)
    states = []
    for kind, w in zip(D.layer_types, weights["layers"]):
        x, state = _layer(D, kind, w, x, low=low)
        if state is not None:
            states.append(np.asarray(state))
    x = x[:, np.asarray(positions)]
    return (np.asarray(_head(D, weights["embed"], weights["final_norm"], x,
                             low=low)), states)
