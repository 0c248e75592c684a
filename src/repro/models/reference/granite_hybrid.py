"""Plain float32 forward pass of the granite-4.0-h hybrid stack
(HF ``GraniteMoeHybrid``), for the tests.

Straightforward ``jax.numpy`` at ``jax.default_matmul_precision("highest")``:

* Mamba-2 as the per-step recurrence of its published equations, state
  (heads, head_dim, d_state) carried token by token from zero:
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``,
  after the causal depthwise conv (with bias) and SiLU of x, B, C; then the
  gated RMSNorm ``rmsnorm(y * silu(z))`` and the out projection;
* attention over every earlier position (no positional embedding), KV head
  ``h // (H / K)`` for query head ``h``, scaled by ``attn_scale``;
* the MoE token by token: softmax over the top-k router logits, and for each
  chosen expert that the layer holds, its SwiGLU output at its gate, then the
  shared expert once;
* embeddings times ``embedding_multiplier``, each sublayer's output times
  ``residual_multiplier`` before the residual add, the tied head's logits
  over ``logits_scaling``.

It reads the weights of ``repro.models`` (the same seeded values), nothing
else of it. Departures from HF: the experts outside the share are left out,
as they are in the program; the router's top-k breaks exact ties by index;
the SwiGLU halves of HF's fused ``input_linear`` are the separate gate and
up matrices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, F32), tree)


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _layers(cfg, params):
    """(kind, weights) of each layer in order: layer s * period + j is the
    s-th of the weights stacked at period position j."""
    blocks = params["blocks"]
    P = len(blocks)
    for i in range(cfg.n_layers):
        s, j = divmod(i, P)
        kind = "attention" if cfg.is_attn_layer(i) else "mamba"
        yield kind, jax.tree.map(lambda a: a[s], blocks[j])


def mamba2(cfg, p, x):
    """x (B, S, d) -> (B, S, d)."""
    din, G, N = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state
    H, Pd, W = cfg.ssm_nheads, cfg.ssm_headdim, cfg.conv_width
    Bsz, S, _ = x.shape
    zxbcdt = x @ p["w_in"]
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din:2 * din + 2 * G * N]
    dt = zxbcdt[..., 2 * din + 2 * G * N:]
    padded = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + S] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs = xbc[..., :din].reshape(Bsz, S, H, Pd)
    Bm = xbc[..., din:din + G * N].reshape(Bsz, S, G, N)
    Cm = xbc[..., din + G * N:].reshape(Bsz, S, G, N)
    group = jnp.arange(H) // (H // G)
    Bm, Cm = Bm[:, :, group], Cm[:, :, group]                  # (B, S, H, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # (B, S, H)
    A = -jnp.exp(p["A_log"])

    def step(h, inp):
        x_t, B_t, C_t, dt_t = inp
        h = (h * jnp.exp(dt_t * A)[:, :, None, None]
             + dt_t[:, :, None, None] * x_t[..., :, None] * B_t[..., None, :])
        y = jnp.einsum("bhpn,bhn->bhp", h, C_t) + p["D"][:, None] * x_t
        return h, y

    h0 = jnp.zeros((Bsz, H, Pd, N), F32)
    seq = [a.swapaxes(0, 1) for a in (xs, Bm, Cm, dt)]
    _, y = lax.scan(step, h0, seq)
    y = y.swapaxes(0, 1).reshape(Bsz, S, din)
    y = _rmsnorm(y * jax.nn.silu(z), p["norm"], cfg.ssm_norm_eps)
    return y @ p["w_out"]


def attention(cfg, p, x):
    """Causal GQA over every earlier position; x (B, S, d) -> (B, S, d)."""
    H, K = cfg.n_heads, cfg.n_kv_heads
    S = x.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])[:, :, jnp.arange(H) // (H // K)]
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])[:, :, jnp.arange(H) // (H // K)]
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) * cfg.attn_scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v)
    return jnp.einsum("bqhk,hkd->bqd", o, p["wo"])


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe(cfg, p, x):
    """Routed experts held here plus the shared expert, token by token."""
    lo = cfg.expert_offset
    n = cfg.experts_held or cfg.n_experts

    def token(x_t):
        top, idx = lax.top_k(x_t @ p["router"], cfg.n_experts_per_tok)
        gates = jax.nn.softmax(top)
        y = jnp.zeros_like(x_t)
        for slot in range(cfg.n_experts_per_tok):
            e = idx[slot] - lo
            mine = (e >= 0) & (e < n)
            e = jnp.clip(e, 0, n - 1)
            out = _swiglu(x_t, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
            y = y + jnp.where(mine, gates[slot], 0.0) * out
        if "shared" in p:
            s = p["shared"]
            y = y + _swiglu(x_t, s["w_gate"], s["w_up"], s["w_down"])
        return y

    Bsz, S, d = x.shape
    return jax.vmap(token)(x.reshape(-1, d)).reshape(Bsz, S, d)


def forward(cfg, params, tokens):
    """Logits (B, S, V) of the whole stack over ``tokens`` (B, S)."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        eps, rm = 1e-5, cfg.residual_multiplier
        emb = params["embed"]["tok"]
        x = emb[tokens] * cfg.embedding_multiplier
        for kind, p in _layers(cfg, params):
            h = _rmsnorm(x, p["norm1"]["scale"], eps)
            mix = mamba2 if kind == "mamba" else attention
            x = x + rm * mix(cfg, p["mixer"], h)
            h = _rmsnorm(x, p["norm2"]["scale"], eps)
            x = x + rm * moe(cfg, p["ffn"], h)
        x = _rmsnorm(x, params["final_norm"]["scale"], eps)
        return (x @ emb.T) / cfg.logits_scaling
