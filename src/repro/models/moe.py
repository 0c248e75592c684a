"""Mixture-of-Experts FFN: the three implementations ``cfg.moe_impl`` names.
``dispatch`` alone picks the one a layer runs: ``share`` in every mode;
otherwise ``gather`` in decode, ``ep`` where a mesh is set, else ``gather``.
``apply_moe`` runs the one it picked.

* ``gather`` (the default) — global sort-free grouped dispatch via cumsum-ranked
  scatter into an (E, C) buffer; capacity-bounded, so it drops the tokens an
  expert gets beyond C. GSPMD shards the expert einsum over the ``model`` axis.
* ``ep`` — expert parallelism via ``shard_map``: tokens are split over the
  (pod·data) batch axes *and* the ``model`` axis (sequence split), routed locally,
  exchanged with ``all_to_all`` to the expert-owner shards, computed, and returned.
  This is the train/prefill path on a mesh and makes the MoE collective
  schedule (2× all_to_all + all_gather) explicit in the HLO.
* ``share`` — one chip's expert share: the layer holds experts [a, a + n) of the
  ``n_experts`` its router scores, routes over all of them, and computes only its
  own experts' part of the result. Dropless: each held expert takes every token
  at its gate, which is 0 where the router did not pick it, so no capacity bounds
  it. At decode batch sizes that costs what grouping would (a token picks up to k
  of the held experts); in prefill it computes held / (k x held / n_experts)
  times the flops a grouped kernel would.

Experts are padded to a multiple of the model-axis size when necessary
(granite-moe: 40 -> 48); the router never selects padded experts. A shared
expert (``cfg.shared_expert_d_ff``) is added once, whatever the dispatch.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed.sharding import ShardingRules
from repro.models import layers

F32 = jnp.float32

IMPLS = ("gather", "ep", "share")
EP_SIZE = 16  # production model-axis size: "ep" pads to it when no mesh says


def dispatch(cfg: ArchConfig, decode: bool, mesh: bool) -> str:
    """The implementation a layer of ``cfg`` runs, in a decode step or not,
    with a mesh set or not."""
    impl = cfg.moe_impl
    if impl not in IMPLS:
        raise ValueError(f"moe_impl {impl!r}; known: {IMPLS}")
    if impl == "share" or (impl == "ep" and mesh and not decode):
        return impl
    return "gather"


def padded_experts(cfg: ArchConfig, ep_size: Optional[int]) -> int:
    e = cfg.n_experts
    if ep_size is None and cfg.moe_impl == "ep":
        ep_size = EP_SIZE
    if ep_size and e % ep_size != 0:
        e = math.ceil(e / ep_size) * ep_size
    return e


def held_experts(cfg: ArchConfig) -> tuple[int, int]:
    """(first, count) of the experts an expert-share layer holds."""
    return cfg.expert_offset, cfg.experts_held or cfg.n_experts


def init_moe(cfg: ArchConfig, key, ep_size: Optional[int] = None):
    d, ff = cfg.d_model, cfg.moe_d_ff
    # an expert share draws each expert at its own fan-in, so that the
    # weights do not depend on how many experts a chip holds
    if cfg.moe_impl == "share":
        e_pad = held_experts(cfg)[1]
        scale = math.sqrt(e_pad)
    else:
        e_pad, scale = padded_experts(cfg, ep_size), 1.0
    ks = jax.random.split(key, 4)
    p = {
        "router": layers.dense_init(ks[0], (d, cfg.n_experts), ("embed", None)),
        "w_up": layers.dense_init(ks[1], (e_pad, d, ff),
                                  ("experts", "embed", "expert_mlp"), in_axis=1,
                                  scale=scale),
        "w_down": layers.dense_init(ks[2], (e_pad, ff, d),
                                    ("experts", "expert_mlp", "embed"), in_axis=1,
                                    scale=scale),
    }
    if cfg.mlp_type == "swiglu":
        p["w_gate"] = layers.dense_init(ks[3], (e_pad, d, ff),
                                        ("experts", "embed", "expert_mlp"), in_axis=1,
                                        scale=scale)
    if cfg.shared_expert_d_ff:
        p["shared"] = layers.init_mlp(cfg, jax.random.fold_in(key, 1),
                                      cfg.shared_expert_d_ff)
    return p


def _act(cfg: ArchConfig, h, g):
    if cfg.mlp_type == "swiglu":
        return jax.nn.silu(g) * h
    if cfg.mlp_type == "relu2":
        return jnp.square(jax.nn.relu(h))
    return jax.nn.gelu(h)


def _expert_ffn(cfg: ArchConfig, p, xg):
    """xg: (E, C, d) -> (E, C, d) through per-expert FFN."""
    dt = xg.dtype
    h = jnp.einsum("ecd,edf->ecf", xg, p["w_up"].astype(dt))
    g = None
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", xg, p["w_gate"].astype(dt))
    return jnp.einsum("ecf,efd->ecd", _act(cfg, h, g), p["w_down"].astype(dt))


def _route(cfg: ArchConfig, logits):
    """top-k routing. logits: (T, E_real). Returns (expert_idx (T,k), probs (T,k), aux)."""
    k = cfg.n_experts_per_tok
    probs_full = jax.nn.softmax(logits.astype(F32), axis=-1)
    top_p, top_i = lax.top_k(probs_full, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    # load-balancing auxiliary loss (Switch-style): E * sum_e f_e * p_e
    E = logits.shape[-1]
    me = jnp.mean(probs_full, axis=0)                                   # (E,)
    onehot = jax.nn.one_hot(top_i, E, dtype=F32)                        # (T,k,E)
    ce = jnp.mean(jnp.sum(onehot, axis=1), axis=0)                      # fraction routed
    aux = E * jnp.sum(me * ce) / k
    return top_i, top_p.astype(logits.dtype), aux


def _group(token_e, token_w, T: int, E: int, C: int):
    """Rank tokens within their expert and build (E, C) index/weight buffers.

    token_e/token_w: (T*k,) expert id / combine weight per (token, slot).
    Returns idx (E, C) into [0, T] (T == pad sentinel), w (E, C).
    """
    Tk = token_e.shape[0]
    k = Tk // T
    onehot = jax.nn.one_hot(token_e, E, dtype=jnp.int32)                # (Tk, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                                # 0-based rank
    rank = jnp.sum(pos * onehot, axis=-1)                               # (Tk,)
    keep = rank < C
    slot = jnp.where(keep, token_e * C + rank, E * C)                   # drop -> OOB
    tok_ids = jnp.arange(Tk, dtype=jnp.int32) // k                      # token of slot
    tok_of_slot = jnp.full((E * C + 1,), T, jnp.int32).at[slot].set(tok_ids, mode="drop")
    w_of_slot = jnp.zeros((E * C + 1,), token_w.dtype).at[slot].set(token_w, mode="drop")
    return (tok_of_slot[: E * C].reshape(E, C),
            w_of_slot[: E * C].reshape(E, C))


def _moe_gather(cfg: ArchConfig, p, x, rules: ShardingRules):
    """Global grouped dispatch (no shard_map). x: (B, S, d)."""
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xf, p["router"].astype(x.dtype))
    top_i, top_w, aux = _route(cfg, logits)
    E = p["w_up"].shape[0]                                              # padded
    k = cfg.n_experts_per_tok
    C = max(1, int(math.ceil(T * k / cfg.n_experts * cfg.capacity_factor)))
    C = min(C, T)
    idx, w = _group(top_i.reshape(-1), top_w.reshape(-1), T, E, C)
    x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xg = x_pad[idx]                                                     # (E, C, d)
    xg = rules.constrain(xg, ("experts", None, "act_embed"))
    yg = _expert_ffn(cfg, p, xg) * w[..., None].astype(xf.dtype)
    y = jnp.zeros((T + 1, d), xf.dtype).at[idx.reshape(-1)].add(
        yg.reshape(E * C, d))[:T]
    return y.reshape(B, S, d), aux


def _moe_ep(cfg: ArchConfig, p, x, rules: ShardingRules):
    """Expert-parallel dispatch with shard_map + all_to_all over the model axis."""
    mesh = rules.mesh
    assert mesh is not None, "EP MoE requires a mesh"
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in names)
    ep_axis = "model"
    ep = dict(zip(names, mesh.devices.shape))[ep_axis]
    E = p["w_up"].shape[0]
    assert E % ep == 0, f"padded experts {E} not divisible by ep={ep}"
    E_l = E // ep
    k = cfg.n_experts_per_tok
    B, S, d = x.shape
    assert S % ep == 0, f"seq {S} not divisible by model axis {ep}"

    def local(x_l, router, w_up, w_gate, w_down):
        # x_l: (B_l, S, d) — replicated over model; take this member's seq slice.
        # w_up/w_gate/w_down arrive as the LOCAL expert slice (E_l, d, ff).
        m = lax.axis_index(ep_axis)
        B_l = x_l.shape[0]
        S_l = S // ep
        xs = lax.dynamic_slice_in_dim(x_l, m * S_l, S_l, axis=1)        # (B_l, S_l, d)
        T_l = B_l * S_l
        xf = xs.reshape(T_l, d)
        logits = jnp.einsum("td,de->te", xf, router.astype(xf.dtype))
        top_i, top_w, aux = _route(cfg, logits)
        C = max(1, int(math.ceil(T_l * k / cfg.n_experts * cfg.capacity_factor)))
        C = min(C, T_l)
        idx, w = _group(top_i.reshape(-1), top_w.reshape(-1), T_l, E, C)  # (E, C)
        x_pad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
        xg = x_pad[idx]                                                 # (E, C, d)
        # (E, C, d) -> (ep, E_l, C, d) -> exchange -> (ep, E_l, C, d) recv
        send = xg.reshape(ep, E_l, C, d)
        recv = lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=0, tiled=False)
        # recv[p]: peer-p's tokens destined for my local experts
        xr = recv.transpose(1, 0, 2, 3).reshape(E_l, ep * C, d)
        pe = {"w_up": w_up, "w_down": w_down}
        if cfg.mlp_type == "swiglu":
            pe["w_gate"] = w_gate
        yr = _expert_ffn(cfg, pe, xr)                                   # (E_l, ep*C, d)
        back = yr.reshape(E_l, ep, C, d).transpose(1, 0, 2, 3)          # (ep, E_l, C, d)
        ybuf = lax.all_to_all(back, ep_axis, split_axis=0, concat_axis=0, tiled=False)
        yg = ybuf.reshape(E, C, d) * w[..., None].astype(xf.dtype)
        y = jnp.zeros((T_l + 1, d), xf.dtype).at[idx.reshape(-1)].add(
            yg.reshape(E * C, d))[:T_l]
        y = y.reshape(B_l, S_l, d)
        # restore the full sequence on every member (SP -> replicated)
        y_full = lax.all_gather(y, ep_axis, axis=1, tiled=True)          # (B_l, S, d)
        aux = lax.pmean(aux, ep_axis)
        for a in batch_axes:
            aux = lax.pmean(aux, a)
        return y_full, aux

    bspec = P(batch_axes if len(batch_axes) != 1 else batch_axes[0], None, None)
    wspec_r = P(None, None)
    wspec_e = P(ep_axis, None, None)                                    # local experts
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(bspec, wspec_r, wspec_e,
                  wspec_e if cfg.mlp_type == "swiglu" else P(), wspec_e),
        out_specs=(bspec, P()),
        check_vma=False,
    )
    w_gate = p.get("w_gate")
    if cfg.mlp_type != "swiglu":
        w_gate = jnp.zeros((), x.dtype)
    y, aux = fn(x, p["router"], p["w_up"], w_gate, p["w_down"])
    return y, aux


def _moe_share(cfg: ArchConfig, p, x, rules: ShardingRules):
    """The held experts' part of the layer, dropless. x: (B, S, d).
    Returns (y, aux, held): held counts the (token, expert) assignments the
    router gave to experts this layer holds."""
    B, S, d = x.shape
    T = B * S
    xf = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xf, p["router"].astype(x.dtype))
    top_i, top_w, aux = _route(cfg, logits)
    lo, n = held_experts(cfg)
    mine = jax.nn.one_hot(top_i - lo, n, dtype=top_w.dtype)           # (T, k, n)
    gate = jnp.sum(mine * top_w[..., None], axis=1)                   # (T, n)
    held = jnp.sum((top_i >= lo) & (top_i < lo + n), dtype=jnp.int32)
    dt = xf.dtype
    h = jnp.einsum("td,edf->etf", xf, p["w_up"].astype(dt))
    g = None
    if cfg.mlp_type == "swiglu":
        g = jnp.einsum("td,edf->etf", xf, p["w_gate"].astype(dt))
    h = _act(cfg, h, g) * gate.T[..., None].astype(dt)
    h = rules.constrain(h, ("experts", None, "expert_mlp"))
    y = jnp.einsum("etf,efd->td", h, p["w_down"].astype(dt))
    return y.reshape(B, S, d), aux, held


def apply_moe(cfg: ArchConfig, p, x, rules: ShardingRules, impl: Optional[str] = None):
    """Returns (y, aux, held): the layer's output with the shared expert's
    added, the load-balancing loss, and the (token, expert) assignments that
    went to experts held here (all of them, but for an expert share).
    ``impl`` is what ``dispatch`` gave for the step; by default, for one
    that does not decode."""
    impl = impl or dispatch(cfg, False, rules.mesh is not None)
    if impl == "share":
        y, aux, held = _moe_share(cfg, p, x, rules)
    else:
        y, aux = (_moe_ep if impl == "ep" else _moe_gather)(cfg, p, x, rules)
        held = jnp.int32(x.shape[0] * x.shape[1] * cfg.n_experts_per_tok)
    if "shared" in p:
        y = y + layers.apply_mlp(cfg, p["shared"], x, rules)
    return y, aux, held
