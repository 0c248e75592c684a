"""Batched serving driver: prefill prompts into a KV/state cache, then decode.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --tokens 32
"""
from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import telemetry
from repro.configs import ShapeSpec, get_config
from repro.distributed.sharding import make_rules
from repro.launch.mesh import make_dev_mesh
from repro.launch.steps import StepBuilder
from repro.models.transformer import split_report


def decode_flops_bytes(cfg, batch: int, ctx: int = 512):
    """Analytic per-decode-step cost of batched serving (one token for each of
    ``batch`` sequences at context ``ctx``) — roofline feedstock for the fleet
    scenarios.

    FLOPs: 2 FLOPs/param on the *active* params per token, plus attention
    against the KV cache. Bytes: every weight streamed once per step (the
    decode-bandwidth wall) plus the KV cache read.
    """
    counts = cfg.param_counts()
    dt_bytes = 2 if cfg.dtype in ("bfloat16", "float16") else 4
    q_dim = max(cfg.n_heads, 0) * max(cfg.head_dim, 0)       # query heads
    kv_dim = max(cfg.n_kv_heads, 0) * max(cfg.head_dim, 0)   # cached heads
    flops = 2.0 * counts["active"] * batch
    flops += 4.0 * batch * cfg.n_layers * q_dim * ctx        # QK^T + AV
    bytes_ = counts["total"] * dt_bytes
    bytes_ += 2.0 * batch * cfg.n_layers * kv_dim * ctx * dt_bytes
    return flops, bytes_


class Lockstep:
    """A batch of sequences decoding in lockstep through one serving-length
    cache: prompts prefilled in groups of sequences and written into it, a
    greedy decode step with the cache donated, and a snapshot of each
    sequence's SSM and conv state that ``restore`` puts back.

    Telemetry (``repro.telemetry``, off unless a session is open): spans
    ``lm.prefill``, ``lm.decode`` and ``lm.restore``; counters
    ``lm_tokens_total{phase=prefill|decode}``, ``lm_decode_steps_total`` and
    ``moe_assignments_total{experts=held|absent}``, the last counted from the
    report that ``collect`` brings to the host with the token ids.
    """

    def __init__(self, sb: StepBuilder, params, batch: int, max_len: int):
        self.sb, self.cfg, self.params = sb, sb.cfg, params
        self.batch, self.max_len = batch, max_len
        self._step = sb.jit_decode_step(
            ShapeSpec("serve", "decode", max_len, batch), donate=True, greedy=True)
        self._prefill = {}
        self.cache = sb.model.init_cache(batch, max_len)
        self.snapshot = None

    def prefill(self, batch_in: dict, group: int):
        """Prefill ``batch_in`` (``tokens`` (batch, S), and ``frames`` for an
        encoder-decoder) ``group`` sequences at a time into rows of the cache.
        Returns the logits of each sequence's last position (batch, V) and
        its greedy next token (batch, 1), on the device."""
        B, S = batch_in["tokens"].shape
        if B != self.batch or B % group:
            raise ValueError(f"{B} prompts for {self.batch} rows in groups "
                             f"of {group}")
        key = (S, group)
        if key not in self._prefill:
            self._prefill[key] = self.sb.jit_prefill(
                ShapeSpec("prefill", "prefill", S, group))
        pre, last = self._prefill[key], []
        with telemetry.span("lm.prefill"):
            for r in range(0, B, group):
                part, logits = pre(self.params, {k: v[r:r + group]
                                                 for k, v in batch_in.items()})
                self.cache = _write_rows(self.cache, part, r)
                last.append(logits[:, -1, :])
            logits = jnp.concatenate(last)
        telemetry.counter("lm_tokens_total", B * S, phase="prefill")
        return logits, _argmax(logits)

    def take_snapshot(self) -> None:
        """Keep a copy of every sequence's SSM and conv state as it is now."""
        self.snapshot = _ssm_copy(self.cache)

    def restore(self) -> None:
        """Put the snapshot's SSM and conv state back into the cache; the KV
        entries written since are left to be overwritten."""
        with telemetry.span("lm.restore"):
            self.cache = _restore(self.cache, self.snapshot)

    def decode(self, tokens, pos: int):
        """Dispatch one greedy step of every sequence at position ``pos``.
        Returns (logits (batch, 1, V), next tokens (batch, 1), report), on
        the device; ``collect`` brings the report to the host."""
        with telemetry.span("lm.decode"):
            self.cache, logits, nxt, report = self._step(self.params, self.cache,
                                                         tokens, pos)
        telemetry.counter("lm_decode_steps_total")
        telemetry.counter("lm_tokens_total", self.batch, phase="decode")
        return logits, nxt, report

    def collect(self, report) -> np.ndarray:
        """The next token ids of one step on the host, in one copy with the
        step's expert assignments, which are counted."""
        ids, held, absent = split_report(self.cfg, np.asarray(report))
        telemetry.counter("moe_assignments_total", held, experts="held")
        telemetry.counter("moe_assignments_total", absent, experts="absent")
        return ids


@partial(jax.jit, donate_argnums=(0,))
def _write_rows(cache, part, row):
    """``part``, a prefill's cache of a group of sequences, written into the
    serving cache from batch row ``row`` and sequence position 0."""
    def one(c, p):
        return lax.dynamic_update_slice(c, p.astype(c.dtype),
                                        (0, row) + (0,) * (c.ndim - 2))
    return jax.tree.map(one, cache, part)


@jax.jit
def _ssm_copy(cache):
    return tuple({"ssm": jax.tree.map(jnp.copy, e["ssm"])} if "ssm" in e else {}
                 for e in cache)


# keep_unused: the cache's SSM buffers are not read, and only an argument that
# stays in the program can lend its buffer to the output
@partial(jax.jit, donate_argnums=(0,), keep_unused=True)
def _restore(cache, snapshot):
    return tuple({**e, "ssm": jax.tree.map(jnp.copy, s["ssm"])} if "ssm" in s
                 else e for e, s in zip(cache, snapshot))


@jax.jit
def _argmax(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]


@dataclass
class GenResult:
    tokens: np.ndarray
    prefill_s: float
    decode_s: float
    tokens_per_s: float


def generate(arch: str, *, smoke: bool = True, batch: int = 4, prompt_len: int = 32,
             gen_tokens: int = 16, seed: int = 0) -> GenResult:
    """Greedy generation of ``gen_tokens`` for ``batch`` random prompts,
    through ``Lockstep``: the path the benchmark's LM cells run."""
    cfg = get_config(arch, smoke=smoke)
    mesh = make_dev_mesh() if len(jax.devices()) > 1 else None
    sb = StepBuilder(cfg, make_rules(mesh))

    key = jax.random.PRNGKey(seed)
    params = sb.model.init_values(key)
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    batch_in = {"tokens": prompts}
    if cfg.encdec:
        batch_in["frames"] = jax.random.normal(
            key, (batch, cfg.enc_memory_len, cfg.d_model)).astype(cfg.dtype)

    lm = Lockstep(sb, params, batch, prompt_len + gen_tokens)
    t0 = time.perf_counter()
    _, tok = lm.prefill(batch_in, batch)
    out = [np.asarray(tok)[:, 0]]
    t_prefill = time.perf_counter() - t0

    t0 = time.perf_counter()
    for i in range(gen_tokens - 1):
        _, tok, report = lm.decode(tok, prompt_len + i)
        out.append(lm.collect(report))
    t_decode = time.perf_counter() - t0
    toks = np.stack(out, axis=1)
    return GenResult(toks, t_prefill, t_decode,
                     batch * (gen_tokens - 1) / max(t_decode, 1e-9))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args()
    r = generate(args.arch, smoke=not args.full, batch=args.batch,
                 prompt_len=args.prompt_len, gen_tokens=args.tokens)
    print(f"[serve] prefill {r.prefill_s*1e3:.1f}ms decode {r.decode_s*1e3:.1f}ms "
          f"({r.tokens_per_s:.1f} tok/s) sample: {r.tokens[0][:12]}")


if __name__ == "__main__":
    main()
