"""Model building blocks: norms, RoPE, GQA attention (train/prefill/decode/cross),
MLP variants, embeddings. Pure-functional: init returns a Box tree (value + logical
axes); apply takes the plain-value tree.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.distributed.sharding import Box, ShardingRules

F32 = jnp.float32


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, axes, in_axis=0, scale=1.0, dtype=F32) -> Box:
    fan_in = math.prod(shape[i] for i in range(len(shape)) if i <= in_axis)
    std = scale / math.sqrt(max(fan_in, 1))
    return Box(jax.random.normal(key, shape, dtype) * std, tuple(axes))


def zeros_init(shape, axes, dtype=F32) -> Box:
    return Box(jnp.zeros(shape, dtype), tuple(axes))


def ones_init(shape, axes, dtype=F32) -> Box:
    return Box(jnp.ones(shape, dtype), tuple(axes))


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ArchConfig, dim: int, axes=("act_embed",)):
    p = {"scale": ones_init((dim,), axes)}
    if cfg.norm == "layernorm":
        p["bias"] = zeros_init((dim,), axes)
    return p


def apply_norm(cfg: ArchConfig, p, x, eps: float = 1e-5):
    xf = x.astype(F32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps)
        y = y * p["scale"].astype(F32) + p["bias"].astype(F32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + eps) * p["scale"].astype(F32)
    return y.astype(x.dtype)


def rms_norm_nohead(x, scale, eps=1e-6):
    """RMS norm over the last dim (used for qk-norm and SSD gated norm)."""
    xf = x.astype(F32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * scale.astype(F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (supports partial rotary — chatglm3 "RoPE 2d" == fraction 0.5)
# ---------------------------------------------------------------------------

def apply_rope(x, positions, theta: float, fraction: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    rot = int(hd * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    half = rot // 2
    freqs = jnp.exp(-jnp.arange(0, half, dtype=F32) * (math.log(theta) / half))
    ang = positions[..., None].astype(F32) * freqs          # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]                        # (..., S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = x_rot[..., :half].astype(F32), x_rot[..., half:].astype(F32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([out.astype(x.dtype), x_pass], axis=-1)


def sinusoidal_pos_emb(positions, dim: int, dtype):
    half = dim // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=F32) * (math.log(10_000.0) / max(half - 1, 1)))
    ang = positions[..., None].astype(F32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ArchConfig, key):
    d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H, hd), ("embed", "heads", "head_dim")),
        "wk": dense_init(ks[1], (d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wv": dense_init(ks[2], (d, K, hd), ("embed", "kv_heads", "head_dim")),
        "wo": dense_init(ks[3], (H, hd, d), ("heads", "head_dim", "embed"), in_axis=1),
    }
    if cfg.qk_norm:
        p["q_norm"] = ones_init((hd,), ("head_dim",))
        p["k_norm"] = ones_init((hd,), ("head_dim",))
    return p


def _qkv(cfg: ArchConfig, p, x, kv_x, positions, kv_positions, rope: bool):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", kv_x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", kv_x, p["wv"].astype(dt))
    if cfg.qk_norm:
        q = rms_norm_nohead(q, p["q_norm"])
        k = rms_norm_nohead(k, p["k_norm"])
    if rope and cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, kv_positions, cfg.rope_theta, cfg.rope_fraction)
    return q, k, v


def _sdpa(cfg: ArchConfig, q, k, v, *, causal: bool, q_offset=0, kv_valid_len=None,
          q_chunk: int = 1024, rules: Optional[ShardingRules] = None,
          layout: str = "heads"):
    """Grouped-query scaled-dot-product attention, query-chunked to bound the
    live score buffer. fp32 softmax.

    KV heads are expanded to the full head count BEFORE the einsums so scores
    stay head-sharded under TP even when n_kv_heads < TP size (GQA/MQA): the
    grouped (B, K, G, ...) layout defeats GSPMD sharding propagation and
    replicates the score tensor (verified 16x HBM-traffic regression).

    q: (B, Sq, H, hd);  k/v: (B, Skv, K, hd).
    q_offset: absolute position of q[0] (for causal masking in prefill chunks).
    kv_valid_len: mask kv positions >= this (decode with pre-allocated cache).
    """
    B, Sq, H, hd = q.shape
    scale = cfg.attn_scale or 1.0 / math.sqrt(hd)

    if layout == "seq":
        # Decode layout: k/v are (B, K, Skv, hd), SEQ-sharded (the KV cache
        # layout); scores inherit the seq sharding and GSPMD turns the softmax +
        # PV contraction into a two-pass partial reduction (tiny collectives)
        # instead of re-sharding the whole cache to heads (full-cache
        # all-gather, verified 20x step-time regression).
        K = k.shape[1]
        G = H // K
        Skv = k.shape[2]
        kv_pos = jnp.arange(Skv)
        qg = q.reshape(B, Sq, K, G, hd)
        s = jnp.einsum("bqkgh,bksh->bkgqs", qg, k).astype(F32) * scale
        if rules is not None:
            s = rules.constrain(s, ("batch", None, None, None, "cache_seq"))
        if kv_valid_len is not None:
            s = jnp.where((kv_pos < kv_valid_len)[None, None, None, None, :],
                          s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        o = jnp.einsum("bkgqs,bksh->bqkgh", w, v)
        return o.reshape(B, Sq, H, hd)

    K = k.shape[2]
    # TP layout: shard attention over heads when H divides the model axis;
    # otherwise fall back to SEQUENCE parallelism on the q dimension (minitron /
    # granite-moe have H=24 vs TP=16 — head sharding would silently replicate
    # the score tensors 16x).
    tp = 1
    if rules is not None and rules.mesh is not None:
        tp = dict(zip(rules.mesh.axis_names,
                      rules.mesh.devices.shape)).get("model", 1)
    sp = tp > 1 and (H % tp != 0)
    if K != H:
        k = jnp.repeat(k, H // K, axis=2)       # (B, Skv, H, hd), head-shardable
        v = jnp.repeat(v, H // K, axis=2)
    kv_axes = ("batch", "act_seq", None if sp else "act_heads", None)
    if rules is not None:
        k = rules.constrain(k, kv_axes)
        v = rules.constrain(v, kv_axes)
    Skv = k.shape[1]
    kv_pos = jnp.arange(Skv)

    def chunk_attn(qc, row0, kc, vc, kvp):
        Qc = qc.shape[1]
        if rules is not None and sp:
            qc = rules.constrain(qc, ("batch", "sp_seq", None, None))
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, kc).astype(F32) * scale
        if rules is not None:
            s = rules.constrain(s, ("batch", None, "sp_seq", None) if sp
                                else ("batch", "act_heads", None, None))
        mask = None
        if causal:
            rows = row0 + jnp.arange(Qc)
            mask = kvp[None, :] <= rows[:, None]
        if kv_valid_len is not None:
            vm = (kvp < kv_valid_len)[None, :]
            mask = vm if mask is None else (mask & vm)
        if mask is not None:
            s = jnp.where(mask[None, None], s, -1e30)
        w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqk,bkhd->bqhd", w, vc)

    if Sq <= q_chunk:
        return chunk_attn(q, q_offset, k, v, kv_pos)
    n = Sq // q_chunk
    assert Sq % q_chunk == 0, f"seq {Sq} not divisible by q_chunk {q_chunk}"
    qs = q.reshape(B, n, q_chunk, H, hd).swapaxes(0, 1)     # (n, B, Qc, H, hd)

    def run_chunks(qs_n, row0, kc, vc, kvp):
        """Scan (or unroll) chunk_attn over a block of q chunks with fixed kv."""
        if cfg.unroll:
            outs = [chunk_attn(qs_n[i], row0 + i * q_chunk, kc, vc, kvp)
                    for i in range(qs_n.shape[0])]
            out = jnp.stack(outs, axis=0)
        else:
            def body(_, qc_i):
                qc, i = qc_i
                return None, chunk_attn(qc, row0 + i * q_chunk, kc, vc, kvp)
            _, out = lax.scan(body, None, (qs_n, jnp.arange(qs_n.shape[0])))
        return out

    if causal and cfg.causal_block_skip and q_offset == 0 and kv_valid_len is None:
        # bucketed block-causal: bucket b's q chunks read only kv[0:(b+1)*S/nb]
        # (static slices; scan within a bucket keeps one chunk live). nb=8
        # buckets skip ~44% of the full rectangle's flops+bytes.
        nb = min(8, n)
        while n % nb:
            nb -= 1
        per = n // nb
        outs = []
        for b in range(nb):
            hi = (b + 1) * per * q_chunk
            out_b = run_chunks(qs[b * per:(b + 1) * per], b * per * q_chunk,
                               k[:, :hi], v[:, :hi], kv_pos[:hi])
            outs.append(out_b)
        out = jnp.concatenate(outs, axis=0)
        return out.swapaxes(0, 1).reshape(B, Sq, H, hd)

    out = run_chunks(qs, q_offset, k, v, kv_pos)
    return out.swapaxes(0, 1).reshape(B, Sq, H, hd)


def attention(cfg: ArchConfig, p, x, rules: ShardingRules, *, mode: str,
              positions=None, cache=None, pos=None, kv_x=None, q_chunk: int = 1024):
    """mode: 'causal' | 'bidir' (encoder) | 'cross' | 'decode' | 'cross_decode'.

    decode: cache = {'k': (B, Smax, K, hd), 'v': ...}, pos = scalar position.
    cross_decode: cache holds fixed projected cross k/v.
    Returns (out, new_cache_or_None).
    """
    dt = x.dtype
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    if mode in ("causal", "bidir", "cross"):
        kvx = x if kv_x is None else kv_x
        kv_positions = positions if kv_x is None else jnp.arange(kvx.shape[1])
        q, k, v = _qkv(cfg, p, x, kvx, positions, kv_positions, rope=(mode != "cross"))
        q = rules.constrain(q, ("batch", "act_seq", "act_heads", None))
        k = rules.constrain(k, ("batch", "act_seq", "act_heads", None))
        out = _sdpa(cfg, q, k, v, causal=(mode == "causal"), q_chunk=q_chunk,
                    rules=rules)
        new_cache = None
        if mode == "causal":
            # prefill cache layout: (B, K, S, hd) — seq minor-adjacent so the
            # decode contractions need no transposed copies
            new_cache = {"k": k.swapaxes(1, 2), "v": v.swapaxes(1, 2)}
        out = rules.constrain(out, ("batch", "act_seq", "act_heads", None))
        o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
        return o, new_cache

    if mode == "decode":
        posv = jnp.asarray(pos)
        q, k, v = _qkv(cfg, p, x, x, posv[None, None], posv[None, None], rope=True)
        kt = k.swapaxes(1, 2).astype(cache["k"].dtype)      # (B, K, 1, hd)
        vt = v.swapaxes(1, 2).astype(cache["v"].dtype)
        ck = lax.dynamic_update_slice(cache["k"], kt, (0, 0, posv, 0))
        cv = lax.dynamic_update_slice(cache["v"], vt, (0, 0, posv, 0))
        ck = rules.constrain(ck, ("cache_batch", "cache_heads", "cache_seq", None))
        cv = rules.constrain(cv, ("cache_batch", "cache_heads", "cache_seq", None))
        out = _sdpa(cfg, q, ck.astype(dt), cv.astype(dt), causal=False,
                    kv_valid_len=posv + 1, rules=rules, layout="seq")
        o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
        return o, {"k": ck, "v": cv}

    if mode == "cross_decode":
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        if cfg.qk_norm:
            q = rms_norm_nohead(q, p["q_norm"])
        out = _sdpa(cfg, q, cache["ck"].astype(dt), cache["cv"].astype(dt),
                    causal=False, rules=rules, layout="seq")
        o = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(dt))
        return o, cache

    raise ValueError(f"unknown attention mode {mode!r}")


def cross_kv(cfg: ArchConfig, p, enc_out):
    """Project encoder output once into cross-attention K/V (decode setup).
    Layout (B, K, S, hd), matching the self-attention cache."""
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, p["wv"].astype(dt))
    return {"ck": k.swapaxes(1, 2), "cv": v.swapaxes(1, 2)}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ArchConfig, key, d_ff: Optional[int] = None):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[0], (d, ff), ("embed", "mlp")),
        "w_down": dense_init(ks[1], (ff, d), ("mlp", "embed")),
    }
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = dense_init(ks[2], (d, ff), ("embed", "mlp"))
    return p


def apply_mlp(cfg: ArchConfig, p, x, rules: ShardingRules):
    dt = x.dtype
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(dt))
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(dt))
        h = jax.nn.silu(g) * h
    elif cfg.mlp_type == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    h = rules.constrain(h, ("batch", "act_seq", "act_mlp"))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(dt))


# ---------------------------------------------------------------------------
# embeddings
# ---------------------------------------------------------------------------

def init_embed(cfg: ArchConfig, key):
    ks = jax.random.split(key, 2)
    p = {"tok": dense_init(ks[0], (cfg.vocab_size, cfg.d_model),
                           ("vocab", "embed"), scale=1.0)}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size),
                                  ("embed", "vocab"))
    return p


def embed_tokens(cfg: ArchConfig, p, tokens, rules: ShardingRules):
    x = jnp.take(p["tok"], tokens, axis=0)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    x = x.astype(jnp.dtype(cfg.dtype))
    return rules.constrain(x, ("batch", "act_seq", "act_embed"))


def unembed(cfg: ArchConfig, p, x, rules: ShardingRules):
    dt = x.dtype
    w = p["tok"].T if cfg.tie_embeddings else p["unembed"]
    logits = jnp.einsum("bsd,dv->bsv", x, w.astype(dt))
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return rules.constrain(logits, ("batch", "act_seq", "act_vocab"))
