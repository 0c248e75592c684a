"""Mamba2 / SSD (state-space duality) block.

Train/prefill use the chunked SSD algorithm (intra-chunk quadratic on the MXU +
inter-chunk linear state scan); decode uses the O(1) recurrent update. State math
in fp32; projections in the model dtype.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.distributed.sharding import Box, ShardingRules
from repro.models import layers

F32 = jnp.float32


def conv_channels(cfg: ArchConfig) -> int:
    return cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state


def init_ssd(cfg: ArchConfig, key):
    d, din = cfg.d_model, cfg.d_inner
    H, G, N = cfg.ssm_nheads, cfg.ssm_ngroups, cfg.ssm_state
    proj_out = 2 * din + 2 * G * N + H           # z, x, B, C, dt
    cch = conv_channels(cfg)
    ks = jax.random.split(key, 5)
    dt_bias = jnp.log(jnp.expm1(jnp.exp(jax.random.uniform(
        ks[3], (H,), F32, math.log(1e-3), math.log(1e-1)))))
    a_init = jax.random.uniform(ks[4], (H,), F32, 1.0, 16.0)
    return {
        "w_in": layers.dense_init(ks[0], (d, proj_out), ("embed", "ssm_inner")),
        "conv_w": layers.dense_init(ks[1], (cfg.conv_width, cch), ("conv", "ssm_inner"),
                                    scale=1.0),
        "conv_b": layers.zeros_init((cch,), ("ssm_inner",)),
        "A_log": Box(jnp.log(a_init), ("ssm_heads",)),
        "D": layers.ones_init((H,), ("ssm_heads",)),
        "dt_bias": Box(dt_bias, ("ssm_heads",)),
        "norm": layers.ones_init((din,), ("ssm_inner",)),
        "w_out": layers.dense_init(ks[2], (din, d), ("ssm_inner", "embed")),
    }


def _split_proj(cfg: ArchConfig, zxbcdt):
    din, G, N, H = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    z = zxbcdt[..., :din]
    xbc = zxbcdt[..., din: 2 * din + 2 * G * N]
    dt = zxbcdt[..., 2 * din + 2 * G * N:]
    return z, xbc, dt


def _causal_conv(cfg: ArchConfig, p, xbc):
    """Depthwise causal conv over (B, S, C) with width W."""
    W = cfg.conv_width
    pad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    acc = None
    for i in range(W):
        term = pad[:, i: i + xbc.shape[1], :] * p["conv_w"][i].astype(xbc.dtype)
        acc = term if acc is None else acc + term
    return jax.nn.silu(acc + p["conv_b"].astype(xbc.dtype))


def ssd_chunked(cfg: ArchConfig, xh, dt, A, Bm, Cm, init_state=None):
    """Chunked SSD scan.

    xh: (B, S, H, P) inputs per head; dt: (B, S, H) softplus'd step sizes;
    A: (H,) negative decay rates; Bm/Cm: (B, S, G, N).
    Returns y (B, S, H, P) and final state (B, H, P, N) in fp32.
    """
    Bsz, S, H, Pd = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(cfg.ssd_chunk, S)
    S_orig = S
    if S % Q != 0:
        # pad with dt=0 steps: zero contribution, unit decay => exact
        pad = Q - S % Q
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S = S + pad
    nc = S // Q
    rep = H // G

    xf = xh.astype(F32).reshape(Bsz, nc, Q, H, Pd)
    dtf = dt.astype(F32).reshape(Bsz, nc, Q, H)
    Bf = Bm.astype(F32).reshape(Bsz, nc, Q, G, N)
    Cf = Cm.astype(F32).reshape(Bsz, nc, Q, G, N)

    dA = dtf * A[None, None, None, :]                       # (B, nc, Q, H) (negative)
    ca = jnp.cumsum(dA, axis=2)                             # within-chunk cumsum
    ca_last = ca[:, :, -1:, :]                              # (B, nc, 1, H)

    # ---- intra-chunk (quadratic within Q; MXU einsums) ----
    Bh = jnp.repeat(Bf, rep, axis=3)                        # (B, nc, Q, H, N)
    Ch = jnp.repeat(Cf, rep, axis=3)
    gates = jnp.einsum("bcqhn,bckhn->bchqk", Ch, Bh)        # q=i, k=j
    # L[i,j] = exp(ca_i - ca_j) for i >= j else 0
    ci = ca.transpose(0, 1, 3, 2)                           # (B, nc, H, Q)
    ldiff = ci[..., :, None] - ci[..., None, :]             # (B, nc, H, Q, Q)
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    # zero masked exponents BEFORE exp: upper-triangle ldiff is large-positive
    # (ca is decreasing), exp -> inf, and where()'s VJP would turn inf*0 into
    # NaN gradients (reproduced at full 130M scale; see test_property.py)
    L = jnp.where(mask, jnp.exp(jnp.where(mask, ldiff, 0.0)), 0.0)
    M = gates * L * dtf.transpose(0, 1, 3, 2)[..., None, :]  # * dt_j
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", M, xf)

    # ---- chunk states: S_c = sum_j exp(ca_last - ca_j) dt_j B_j x_j^T ----
    w = jnp.exp(ca_last - ca) * dtf                         # (B, nc, Q, H)
    states = jnp.einsum("bcqh,bcqhn,bcqhp->bchnp", w, Bh, xf)  # (B, nc, H, N, P)

    # ---- inter-chunk scan ----
    chunk_decay = jnp.exp(ca_last[:, :, 0, :])              # (B, nc, H)

    def scan_body(carry, inp):
        s_c, d_c = inp                                       # (B,H,N,P), (B,H)
        new = carry * d_c[..., None, None] + s_c
        return new, carry                                    # emit state BEFORE chunk

    s0 = jnp.zeros((Bsz, H, N, Pd), F32) if init_state is None else init_state
    states_t = states.transpose(1, 0, 2, 3, 4)
    decay_t = chunk_decay.transpose(1, 0, 2)
    if cfg.unroll:
        carry, prevs = s0, []
        for c in range(nc):
            carry, prev = scan_body(carry, (states_t[c], decay_t[c]))
            prevs.append(prev)
        final, prev_states = carry, jnp.stack(prevs, axis=0)
    else:
        final, prev_states = lax.scan(scan_body, s0, (states_t, decay_t))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)       # (B, nc, H, N, P)

    # ---- inter-chunk contribution: y_i += C_i · (exp(ca_i) * state_prev) ----
    inter_w = jnp.exp(ca)                                    # (B, nc, Q, H)
    y_inter = jnp.einsum("bcqhn,bchnp,bcqh->bcqhp", Ch, prev_states, inter_w)

    y = (y_intra + y_inter).reshape(Bsz, S, H, Pd)[:, :S_orig]
    return y, final


def apply_ssd(cfg: ArchConfig, p, x, rules: ShardingRules, cache=None, pos=None):
    """Full SSD block. Train/prefill when cache is None or pos is None is handled
    by the caller convention:
      * cache is None           -> train path, returns (y, None)
      * cache given, pos None   -> prefill: run chunked scan, return final caches
      * cache given, pos given  -> single-token decode
    """
    dt_m = x.dtype
    din, H, G, N = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_ngroups, cfg.ssm_state
    Pd = cfg.ssm_headdim
    W = cfg.conv_width

    zxbcdt = jnp.einsum("bsd,de->bse", x, p["w_in"].astype(dt_m))
    zxbcdt = rules.constrain(zxbcdt, ("batch", "act_seq", "act_mlp"))
    z, xbc, dtr = _split_proj(cfg, zxbcdt)

    if cache is not None and pos is not None:
        # ---- decode: recurrent update ----
        # conv cache: (B, W-1, cch) rolling window of pre-activation inputs
        xbc_t = xbc[:, 0, :]                                 # (B, cch)
        window = jnp.concatenate([cache["conv"], xbc_t[:, None, :]], axis=1)  # (B, W, cch)
        conv_out = jnp.einsum("bwc,wc->bc", window.astype(F32),
                              p["conv_w"].astype(F32)) + p["conv_b"].astype(F32)
        conv_out = jax.nn.silu(conv_out)
        xh = conv_out[:, :din].reshape(-1, H, Pd)            # (B, H, P)
        Bm = conv_out[:, din:din + G * N].reshape(-1, G, N)
        Cm = conv_out[:, din + G * N:].reshape(-1, G, N)
        rep = H // G
        Bh = jnp.repeat(Bm, rep, axis=1)                     # (B, H, N)
        Ch = jnp.repeat(Cm, rep, axis=1)
        dtv = jax.nn.softplus(dtr[:, 0, :].astype(F32) + p["dt_bias"][None])  # (B, H)
        A = -jnp.exp(p["A_log"])                             # (H,)
        dA = jnp.exp(dtv * A[None])                          # (B, H)
        state = cache["state"]                               # (B, H, N, P) fp32
        upd = jnp.einsum("bh,bhn,bhp->bhnp", dtv, Bh, xh)
        state = state * dA[..., None, None] + upd
        y = jnp.einsum("bhn,bhnp->bhp", Ch, state)
        y = y + p["D"].astype(F32)[None, :, None] * xh
        y = y.reshape(-1, 1, din).astype(dt_m)
        y = layers.rms_norm_nohead(y * jax.nn.silu(z.astype(F32)).astype(dt_m), p["norm"],
                                   cfg.ssm_norm_eps)
        out = jnp.einsum("bse,ed->bsd", y, p["w_out"].astype(dt_m))
        new_cache = {"conv": window[:, 1:, :], "state": state}
        return out, new_cache

    # ---- train / prefill: chunked scan ----
    xbc = _causal_conv(cfg, p, xbc)
    xh = xbc[..., :din].reshape(*xbc.shape[:2], H, Pd)
    Bm = xbc[..., din:din + G * N].reshape(*xbc.shape[:2], G, N)
    Cm = xbc[..., din + G * N:].reshape(*xbc.shape[:2], G, N)
    xh = rules.constrain(xh, ("batch", "act_seq", "ssm_heads", None))
    dtv = jax.nn.softplus(dtr.astype(F32) + p["dt_bias"][None, None])
    A = -jnp.exp(p["A_log"])
    y, final_state = ssd_chunked(cfg, xh, dtv, A, Bm, Cm)
    y = y.astype(dt_m)
    y = y + (p["D"].astype(dt_m)[None, None, :, None] * xh)
    y = y.reshape(*x.shape[:2], din)
    y = layers.rms_norm_nohead(y * jax.nn.silu(z.astype(F32)).astype(dt_m), p["norm"],
                               cfg.ssm_norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"].astype(dt_m))

    new_cache = None
    if cache is not None:
        # prefill: stash conv window (last W-1 pre-conv inputs) + final state
        _, xbc_raw, _ = _split_proj(cfg, zxbcdt)
        new_cache = {"conv": xbc_raw[:, -(W - 1):, :], "state": final_state}
    return out, new_cache


def cache_spec(cfg: ArchConfig, batch: int):
    """ShapeDtypeStruct Box tree for SSD decode cache."""
    cch = conv_channels(cfg)
    H, N, Pd = cfg.ssm_nheads, cfg.ssm_state, cfg.ssm_headdim
    return {
        "conv": Box(jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, cch), jnp.dtype(cfg.dtype)),
                    ("cache_batch", None, "ssm_inner")),
        "state": Box(jax.ShapeDtypeStruct((batch, H, N, Pd), F32),
                     ("cache_batch", "ssm_heads", None, None)),
    }
