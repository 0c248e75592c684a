"""Unified layer-stack engine.

Every architecture is described by a *block program*: the periodic pattern of
(mixer, ffn, cross) sublayers. The stack is `n_layers = n_stack * period` deep;
parameters for each position-in-period are stacked over `n_stack` and the stack is
executed with `lax.scan` (compact HLO, fast compiles, remat per block).

  dense LMs     period=1:  [attn + dense ffn]
  MoE LMs       period=1:  [attn + moe ffn]
  mamba2 (ssm)  period=1:  [ssd]
  jamba (hybrid)period=8:  [ssd+ffn]*4 … attn at index 4, moe at odd indices
  enc-dec       two stacks: encoder [bidir attn + ffn], decoder [attn + cross + ffn]
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig
from repro.distributed.sharding import Box, ShardingRules, is_box, unbox_values
from repro.models import layers, mamba, moe

F32 = jnp.float32


# ---------------------------------------------------------------------------
# block program
# ---------------------------------------------------------------------------

def block_period(cfg: ArchConfig) -> int:
    p = 1
    if cfg.attn_period:
        p = math.lcm(p, cfg.attn_period)
    if cfg.moe and cfg.moe_period > 1:
        p = math.lcm(p, cfg.moe_period)
    return p


def block_program(cfg: ArchConfig, decoder: bool = True) -> list[dict]:
    P = block_period(cfg)
    prog = []
    for j in range(P):
        if cfg.family == "ssm":
            mixer, ffn = "ssm", None
        elif cfg.attn_period:
            mixer = "attn" if cfg.is_attn_layer(j) else "ssm"
            ffn = "moe" if cfg.is_moe_layer(j) else ("dense" if cfg.d_ff else None)
        else:
            mixer = "attn"
            ffn = "moe" if cfg.is_moe_layer(j) else ("dense" if cfg.d_ff else None)
        prog.append({
            "mixer": mixer,
            "ffn": ffn,
            "cross": bool(cfg.encdec and decoder),
        })
    return prog


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(init_fn, key, n: int):
    """Stack `init_fn(key)` over a leading layer dim, prefixing axes with 'stack'."""
    template = init_fn(key)
    keys = jax.random.split(key, n)
    values = jax.vmap(lambda k: unbox_values(init_fn(k)))(keys)
    return jax.tree.map(lambda b, v: Box(v, ("stack",) + b.axes),
                        template, values, is_leaf=is_box)


def _init_block_pos(cfg: ArchConfig, key, entry: dict, ep_size: Optional[int]):
    ks = jax.random.split(key, 6)
    p: dict[str, Any] = {"norm1": layers.init_norm(cfg, cfg.d_model)}
    if entry["mixer"] == "attn":
        p["mixer"] = layers.init_attention(cfg, ks[0])
    else:
        p["mixer"] = mamba.init_ssd(cfg, ks[0])
    if entry["cross"]:
        p["norm_cross"] = layers.init_norm(cfg, cfg.d_model)
        p["cross"] = layers.init_attention(cfg, ks[1])
    if entry["ffn"] == "dense":
        p["norm2"] = layers.init_norm(cfg, cfg.d_model)
        p["ffn"] = layers.init_mlp(cfg, ks[2])
    elif entry["ffn"] == "moe":
        p["norm2"] = layers.init_norm(cfg, cfg.d_model)
        p["ffn"] = moe.init_moe(cfg, ks[2], ep_size)
    return p


def init_lm(cfg: ArchConfig, key, ep_size: Optional[int] = None):
    prog = block_program(cfg)
    P = block_period(cfg)
    assert cfg.n_layers % P == 0, f"{cfg.n_layers} layers, period {P}"
    n_stack = cfg.n_layers // P
    k_embed, k_blocks, k_enc = jax.random.split(key, 3)
    pos_keys = jax.random.split(k_blocks, P)
    params: dict[str, Any] = {
        "embed": layers.init_embed(cfg, k_embed),
        "final_norm": layers.init_norm(cfg, cfg.d_model),
        "blocks": tuple(
            _stack_init(lambda k, j=j: _init_block_pos(cfg, k, prog[j], ep_size),
                        pos_keys[j], n_stack)
            for j in range(P)),
    }
    if cfg.encdec:
        enc_prog = block_program(cfg, decoder=False)
        assert cfg.n_enc_layers % len(enc_prog) == 0
        enc_keys = jax.random.split(k_enc, len(enc_prog))
        params["enc_blocks"] = tuple(
            _stack_init(lambda k, j=j: _init_block_pos(cfg, k, enc_prog[j], ep_size),
                        enc_keys[j], cfg.n_enc_layers // len(enc_prog))
            for j in range(len(enc_prog)))
        params["enc_norm"] = layers.init_norm(cfg, cfg.d_model)
    return params


# ---------------------------------------------------------------------------
# sublayer application
# ---------------------------------------------------------------------------

def _apply_block_pos(cfg: ArchConfig, entry: dict, p, x, rules: ShardingRules, *,
                     mode: str, positions, cache_entry=None, pos=None,
                     enc_out=None):
    """One sublayer-group. mode: 'train' | 'prefill' | 'decode'.
    Returns (x, new_cache_entry, stats): stats as ``_no_stats`` gives them."""
    stats = _no_stats()
    new_cache: dict[str, Any] = {}

    h = layers.apply_norm(cfg, p["norm1"], x)
    if entry["mixer"] == "attn":
        if mode == "decode":
            out, nc = layers.attention(cfg, p["mixer"], h, rules, mode="decode",
                                       cache=cache_entry["attn"], pos=pos)
            new_cache["attn"] = nc
        else:
            attn_mode = ("bidir" if (cfg.encdec and enc_out is None
                                     and not entry["cross"]) else "causal")
            out, nc = layers.attention(cfg, p["mixer"], h, rules, mode=attn_mode,
                                       positions=positions)
            if mode == "prefill" and nc is not None:
                new_cache["attn"] = nc
    else:
        if mode == "decode":
            out, nc = mamba.apply_ssd(cfg, p["mixer"], h, rules,
                                      cache=cache_entry["ssm"], pos=pos)
            new_cache["ssm"] = nc
        else:
            out, nc = mamba.apply_ssd(cfg, p["mixer"], h, rules,
                                      cache=({} if mode == "prefill" else None))
            if mode == "prefill":
                new_cache["ssm"] = nc
    x = x + _residual(cfg, out)
    x = rules.constrain(x, ("batch", "act_seq", "act_embed"))

    if entry["cross"]:
        h = layers.apply_norm(cfg, p["norm_cross"], x)
        if mode == "decode":
            out, _ = layers.attention(cfg, p["cross"], h, rules, mode="cross_decode",
                                      cache=cache_entry["cross"])
            new_cache["cross"] = cache_entry["cross"]
        else:
            out, _ = layers.attention(cfg, p["cross"], h, rules, mode="cross",
                                      positions=positions, kv_x=enc_out)
            if mode == "prefill":
                new_cache["cross"] = layers.cross_kv(cfg, p["cross"], enc_out)
        x = x + out

    if entry["ffn"]:
        h = layers.apply_norm(cfg, p["norm2"], x)
        if entry["ffn"] == "dense":
            out = layers.apply_mlp(cfg, p["ffn"], h, rules)
        else:
            impl = moe.dispatch(cfg, mode == "decode", rules.mesh is not None)
            out, aux, held = moe.apply_moe(cfg, p["ffn"], h, rules, impl=impl)
            stats = {"moe_aux": aux, "moe_held": held}
        x = x + _residual(cfg, out)
        x = rules.constrain(x, ("batch", "act_seq", "act_embed"))
    return x, new_cache, stats


def _no_stats() -> dict:
    """What a sublayer-group reports: the MoE load-balancing loss and the
    (token, expert) assignments to experts held here, summed over layers."""
    return {"moe_aux": jnp.zeros((), F32), "moe_held": jnp.zeros((), jnp.int32)}


def _residual(cfg: ArchConfig, out):
    """A sublayer's output as it is added to the residual stream."""
    if cfg.residual_multiplier != 1.0:
        out = out * jnp.asarray(cfg.residual_multiplier, out.dtype)
    return out


# ---------------------------------------------------------------------------
# stack execution
# ---------------------------------------------------------------------------

def _run_stack(cfg: ArchConfig, blocks, x, rules: ShardingRules, *, mode: str,
               positions, prog, cache=None, pos=None, enc_out=None,
               remat: bool = False):
    """Scan the stacked blocks. Returns (x, new_cache_or_None, stats summed
    over the layers)."""

    def body(carry, xs):
        xc = carry
        if cache is not None:
            layer_ps, cache_in = xs
        else:
            layer_ps, cache_in = xs, None
        new_caches = []
        total = _no_stats()
        for j, entry in enumerate(prog):
            ce = cache_in[j] if cache_in is not None else None
            xc, nc, stats = _apply_block_pos(
                cfg, entry, layer_ps[j], xc, rules, mode=mode, positions=positions,
                cache_entry=ce, pos=pos, enc_out=enc_out)
            new_caches.append(nc)
            total = jax.tree.map(jnp.add, total, stats)
        out_ys = (tuple(new_caches), total) if mode != "train" else total
        return xc, out_ys

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    xs = (blocks, cache) if cache is not None else blocks
    if cfg.unroll:
        n_stack = jax.tree.leaves(blocks)[0].shape[0]
        ys_list = []
        for i in range(n_stack):
            x, ys_i = body(x, jax.tree.map(lambda a: a[i], xs))
            ys_list.append(ys_i)
        ys = jax.tree.map(lambda *ls: jnp.stack(ls, axis=0), *ys_list)
    else:
        x, ys = lax.scan(body, x, xs)
    if mode == "train":
        return x, None, jax.tree.map(jnp.sum, ys)
    new_cache, stats = ys
    return x, new_cache, jax.tree.map(jnp.sum, stats)


# ---------------------------------------------------------------------------
# public model functions
# ---------------------------------------------------------------------------

def _encode(cfg: ArchConfig, params, batch, rules: ShardingRules, remat=False):
    x = batch["frames"].astype(jnp.dtype(cfg.dtype))
    S = x.shape[1]
    positions = jnp.arange(S)
    if cfg.pos_emb == "sinusoidal":
        x = x + layers.sinusoidal_pos_emb(positions, cfg.d_model, x.dtype)[None]
    enc_prog = block_program(cfg, decoder=False)
    x, _, _ = _run_stack(cfg, params["enc_blocks"], x, rules, mode="train",
                         positions=positions, prog=enc_prog, remat=remat)
    return layers.apply_norm(cfg, params["enc_norm"], x)


def forward_train(cfg: ArchConfig, params, batch, rules: ShardingRules):
    """Returns (logits, aux). batch: {tokens, [frames]}."""
    remat = cfg.remat == "full"
    enc_out = None
    if cfg.encdec:
        enc_out = _encode(cfg, params, batch, rules, remat)
    tokens = batch["tokens"]
    x = layers.embed_tokens(cfg, params["embed"], tokens, rules)
    S = x.shape[1]
    positions = jnp.arange(S)
    if cfg.pos_emb == "sinusoidal":
        x = x + layers.sinusoidal_pos_emb(positions, cfg.d_model, x.dtype)[None]
    prog = block_program(cfg)
    x, _, stats = _run_stack(cfg, params["blocks"], x, rules, mode="train",
                             positions=positions, prog=prog, enc_out=enc_out,
                             remat=remat)
    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = layers.unembed(cfg, params["embed"], x, rules)
    return logits, stats["moe_aux"]


def loss_fn(cfg: ArchConfig, params, batch, rules: ShardingRules,
            z_loss: float = 1e-4, moe_aux_weight: float = 1e-2):
    logits, aux = forward_train(cfg, params, batch, rules)
    targets = batch["targets"]
    if jnp.dtype(cfg.softmax_dtype) == jnp.float32:
        lf = logits.astype(F32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    else:
        # §Perf 'bf16_loss': never materialize f32 logits — subtract the f32
        # row-max, exponentiate in bf16, accumulate the sum in f32 (reduction
        # accumulator, not a tensor), take the log in f32.
        m = jnp.max(logits, axis=-1).astype(F32)
        p = jnp.exp(logits - m[..., None].astype(logits.dtype))
        lse = m + jnp.log(jnp.sum(p, axis=-1, dtype=F32))
        ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0].astype(F32)
    nll = jnp.mean(lse - ll)
    zl = z_loss * jnp.mean(jnp.square(lse))
    total = nll + zl + moe_aux_weight * aux
    return total, {"nll": nll, "z_loss": zl, "moe_aux": aux}


def forward_prefill(cfg: ArchConfig, params, batch, rules: ShardingRules):
    """Returns (cache, last_token_logits)."""
    enc_out = None
    if cfg.encdec:
        enc_out = _encode(cfg, params, batch, rules)
    tokens = batch["tokens"]
    x = layers.embed_tokens(cfg, params["embed"], tokens, rules)
    S = x.shape[1]
    positions = jnp.arange(S)
    if cfg.pos_emb == "sinusoidal":
        x = x + layers.sinusoidal_pos_emb(positions, cfg.d_model, x.dtype)[None]
    prog = block_program(cfg)
    # cache entries are produced by the scan (ys): inject a dummy cache=None path
    x, new_cache, _ = _run_stack(cfg, params["blocks"], x, rules, mode="prefill",
                                 positions=positions, prog=prog, enc_out=enc_out)
    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = layers.unembed(cfg, params["embed"], x[:, -1:, :], rules)
    return new_cache, logits


def _decode(cfg: ArchConfig, params, cache, tokens, pos, rules):
    x = layers.embed_tokens(cfg, params["embed"], tokens, rules)
    if cfg.pos_emb == "sinusoidal":
        pe = layers.sinusoidal_pos_emb(jnp.asarray(pos)[None], cfg.d_model, x.dtype)
        x = x + pe[None]
    prog = block_program(cfg)
    x, new_cache, stats = _run_stack(cfg, params["blocks"], x, rules, mode="decode",
                                     positions=None, prog=prog, cache=cache, pos=pos)
    x = layers.apply_norm(cfg, params["final_norm"], x)
    logits = layers.unembed(cfg, params["embed"], x, rules)
    return new_cache, logits, stats


def decode_step(cfg: ArchConfig, params, cache, tokens, pos, rules: ShardingRules):
    """One decode step. tokens: (B, 1); pos: scalar absolute position.
    Returns (new_cache, logits (B, 1, V))."""
    new_cache, logits, _ = _decode(cfg, params, cache, tokens, pos, rules)
    return new_cache, logits


def greedy_step(cfg: ArchConfig, params, cache, tokens, pos, rules: ShardingRules):
    """One decode step that also picks each sequence's next token on the device.

    Returns (new_cache, logits (B, 1, V), next_tokens (B, 1) int32, report):
    ``report`` is int32 (B + 1,), the next tokens and then the step's
    (token, expert) assignments to experts held here, so that one copy to
    the host brings both (``split_report``)."""
    new_cache, logits, stats = _decode(cfg, params, cache, tokens, pos, rules)
    nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    report = jnp.concatenate([nxt, stats["moe_held"][None]])
    return new_cache, logits, nxt[:, None], report


def split_report(cfg: ArchConfig, report) -> tuple:
    """(next token ids, assignments to held experts, to absent experts) of
    one host copy of ``greedy_step``'s report."""
    ids, held = report[:-1], int(report[-1])
    n_moe = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    return ids, held, len(ids) * cfg.n_experts_per_tok * n_moe - held


# ---------------------------------------------------------------------------
# cache specs (Box tree of ShapeDtypeStructs) — must mirror scan structure
# ---------------------------------------------------------------------------

def cache_specs(cfg: ArchConfig, batch: int, max_seq: int):
    prog = block_program(cfg)
    P = block_period(cfg)
    n_stack = cfg.n_layers // P
    dt = jnp.dtype(cfg.dtype)

    def sds(shape, dtype, axes):
        return Box(jax.ShapeDtypeStruct(tuple(shape), dtype), tuple(axes))

    entries = []
    for entry in prog:
        ce: dict[str, Any] = {}
        if entry["mixer"] == "attn":
            kv_shape = (n_stack, batch, cfg.n_kv_heads, max_seq, cfg.head_dim)
            axes = ("stack", "cache_batch", "cache_heads", "cache_seq", None)
            ce["attn"] = {"k": sds(kv_shape, dt, axes), "v": sds(kv_shape, dt, axes)}
        else:
            base = mamba.cache_spec(cfg, batch)
            ce["ssm"] = jax.tree.map(
                lambda b: Box(jax.ShapeDtypeStruct((n_stack,) + b.value.shape, b.value.dtype),
                              ("stack",) + b.axes),
                base, is_leaf=is_box)
        if entry["cross"]:
            cs = (n_stack, batch, cfg.n_kv_heads, cfg.enc_memory_len, cfg.head_dim)
            axes = ("stack", "cache_batch", "cache_heads", "cache_seq", None)
            ce["cross"] = {"ck": sds(cs, dt, axes), "cv": sds(cs, dt, axes)}
        entries.append(ce)
    return tuple(entries)
