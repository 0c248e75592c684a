"""Closed-loop LM decode: a batch of sessions decoding in lockstep, one step
in flight, through the program's serving path (``repro.launch.serve.Lockstep``:
``StepBuilder.jit_prefill`` and the greedy ``jit_decode_step`` with the cache
donated).

The configuration file names the registry architecture (``arch``) and holds
its published config.json keys, cut to one chip's share as ``reduced`` and
``deployment`` say; ``arch_config`` maps them onto the registry's
``ArchConfig``. Weights are random from the seed, held in bfloat16.

Set-up: ``batch`` prompts of ``prompt_len`` random ids from the seed are
prefilled ``prefill_group`` sequences at a time into a serving cache of
``prompt_len + gen_len`` positions, and each session's SSM and conv state is
kept as a snapshot. Every window begins by restoring that snapshot, and so
does the step after position ``prompt_len + gen_len - 1``: the sessions
resume at ``prompt_len`` from the token the prefill picked, and the KV entries
past the prompt are overwritten as they go. A call of the window is one
greedy step of every session, from its dispatch to its token ids on the host,
with the restore before it where there is one; a window covers as many
positions from ``prompt_len`` on as it has steps.

The output check: the logits that the first window itself produced for
``check_sessions`` sessions at ``check_steps`` steps drawn from the seed among
its first ``check_window``, and those of their last prompt position, against
the float32 reference (``reference/granite_ref.py``) over the prompt and the
ids the window fed, holding the same experts; and the SSM state of every
Mamba layer that the window left in the cache for those sessions after the
last checked step, against the reference's state after the same tokens.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from reference import granite_ref, tpss

# config.json keys -> ArchConfig fields, where they differ only by name
HF_FIELDS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size", "intermediate_size": "moe_d_ff",
    "num_experts_per_tok": "n_experts_per_tok",
    "shared_intermediate_size": "shared_expert_d_ff",
    "mamba_d_state": "ssm_state", "mamba_d_head": "ssm_headdim",
    "mamba_n_groups": "ssm_ngroups", "mamba_d_conv": "conv_width",
    "mamba_chunk_size": "ssd_chunk", "mamba_expand": "ssm_expand",
    "attention_multiplier": "attn_scale",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling", "rms_norm_eps": "ssm_norm_eps",
    "num_local_experts": "experts_held",
}


def arch_config(config: dict):
    """The registry's ``ArchConfig`` of ``config["arch"]`` with the file's
    sizes, the router over ``deployment.experts_total`` experts and this
    chip's share of them held. Raises where the registry lacks the arch or
    the file's layer pattern is not the block program's."""
    from repro.configs import get_config

    cfg = get_config(config["arch"])
    dep = config["deployment"]
    cfg = cfg.replace(**{f: config[k] for k, f in HF_FIELDS.items() if k in config},
                      head_dim=config["hidden_size"] // config["num_attention_heads"],
                      n_experts=dep["experts_total"],
                      expert_offset=dep["expert_offset"])
    if cfg.ssm_nheads != config["mamba_n_heads"]:
        raise ValueError(f"{cfg.ssm_nheads} SSM heads, the file says "
                         f"{config['mamba_n_heads']}")
    kinds = ["attention" if cfg.is_attn_layer(i) else "mamba"
             for i in range(cfg.n_layers)]
    if kinds != list(config["layer_types"]):
        raise ValueError(f"layer pattern {kinds} is not the file's")
    return cfg


def make_data(config: dict, traffic: dict, seed: int) -> dict:
    """The prompts, and which sessions and steps the check reads, from the seed."""
    rng = np.random.default_rng(seed)
    B, S = traffic["batch"], traffic["prompt_len"]
    prompts = rng.integers(0, config["vocab_size"], (B, S), dtype=np.int32)
    sessions = np.sort(rng.choice(B, traffic["check_sessions"], replace=False))
    steps = np.sort(rng.choice(traffic["check_window"], traffic["check_steps"],
                               replace=False))
    return {"prompts": prompts, "sessions": sessions, "steps": steps.tolist()}


def init_params(sb, seed: int):
    """The program's seeded weights, made on the device in the model dtype."""
    from repro.launch.steps import cast_tree

    init = jax.jit(lambda k: cast_tree(sb.model.init_values(k), sb.cfg.dtype))
    return init(tpss.seed_key(seed))


def ref_weights(cfg, params) -> dict:
    """The program's weights as ``granite_ref`` names them (host arrays)."""
    blocks = params["blocks"]
    P = len(blocks)
    out = []
    for i in range(cfg.n_layers):
        s, j = divmod(i, P)
        b = jax.tree.map(lambda a: a[s], blocks[j])
        m, f = b["mixer"], b["ffn"]
        w = {"norm1": b["norm1"]["scale"], "norm2": b["norm2"]["scale"],
             "router": f["router"], "gate": f["w_gate"], "up": f["w_up"],
             "down": f["w_down"], "shared_gate": f["shared"]["w_gate"],
             "shared_up": f["shared"]["w_up"],
             "shared_down": f["shared"]["w_down"]}
        if cfg.is_attn_layer(i):
            w.update(wq=m["wq"], wk=m["wk"], wv=m["wv"], wo=m["wo"])
        else:
            w.update(in_proj=m["w_in"], conv_w=m["conv_w"], conv_b=m["conv_b"],
                     A_log=m["A_log"], D=m["D"], dt_bias=m["dt_bias"],
                     norm=m["norm"], out_proj=m["w_out"])
        out.append(w)
    return {"embed": params["embed"]["tok"],
            "final_norm": params["final_norm"]["scale"], "layers": out}


def reference(config: dict, weights: dict, data: dict, fed: np.ndarray,
              low: tuple = ()) -> tuple:
    """The reference (its control in the parts ``low`` names) over the
    checked sessions' prompts and the ids ``fed`` to their first steps,
    (sessions, steps): {("prefill", 0) | ("decode", step): (sessions, V)}
    logits, and each Mamba layer's state after the last checked step,
    (sessions, heads, head_dim, d_state)."""
    D = granite_ref.dims_of(config)
    S = data["prompts"].shape[1]
    tokens = np.concatenate([data["prompts"][data["sessions"]], fed], axis=1)
    positions = [S - 1] + [S + k for k in data["steps"]]
    got, states = granite_ref.logits_at(
        D, weights, tokens[:, :S + max(data["steps"]) + 1], positions, low)
    keys = [("prefill", 0)] + [("decode", k) for k in data["steps"]]
    return {key: got[:, i] for i, key in enumerate(keys)}, states


@partial(jax.jit, static_argnums=(0,))
def program_states(n_layers: int, cache, rows) -> list:
    """Each Mamba layer's SSM state in ``cache`` for batch ``rows``, in the
    reference's layout (rows, heads, head_dim, d_state), on the device."""
    out = []
    for i in range(n_layers):
        s, j = divmod(i, len(cache))
        if "ssm" in cache[j]:
            out.append(jnp.swapaxes(cache[j]["ssm"]["state"][s, rows], -1, -2))
    return out


def _head_rel_rms(got, want) -> np.ndarray:
    """Each head's relative RMS error of states (sessions, heads, ...)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    axes = (0,) + tuple(range(2, want.ndim))
    return np.sqrt(np.mean((got - want) ** 2, axis=axes)
                   / np.mean(want * want, axis=axes))


def compare(got: dict, want: dict, got_states: list, want_states: list) -> list:
    """The numbers compared (0 at best):

    * ``logit_rel_rms``: the RMS of the logits' error over the RMS of the
      reference's logits, over every checked session and position at once;
    * ``logit_max_gap``: the largest gap between logits, over the RMS of the
      reference's logits;
    * ``logit_rel_rms_worst``: the largest of each checked vector's own
      relative RMS error, so that a fault in one session or one step is not
      spread over the others;
    * ``ssm_state_rel_rms``: the largest over the Mamba layers and their
      heads of a head's relative RMS error of the state over the checked
      sessions. A head that keeps a long memory accumulates the rounding of
      a low-precision state over the whole context, while the error that
      the bfloat16 activations bring stays near that of one token's input.

    A position or state the window never produced counts as NaN, which fails
    every number it enters."""
    w = np.stack([np.asarray(v, np.float64) for v in want.values()])
    g = np.stack([np.asarray(got[k], np.float64) if k in got
                  else np.full(w.shape[1:], np.nan) for k in want])
    err, scale = g - w, np.sqrt(np.mean(w * w))
    per_vec = np.sqrt(np.mean(err * err, axis=-1) / np.mean(w * w, axis=-1))
    states = [float(np.max(_head_rel_rms(g_, w_)))
              for g_, w_ in zip(got_states, want_states)]
    if len(states) != len(want_states) or not states:
        states = [float("nan")]
    return [("logit_rel_rms", float(np.sqrt(np.mean(err * err)) / scale)),
            ("logit_max_gap", float(np.max(np.abs(err)) / scale)),
            ("logit_rel_rms_worst", float(np.max(per_vec))),
            ("ssm_state_rel_rms", float(np.max(states)))]


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int):
        from repro.distributed import make_rules
        from repro.launch.serve import Lockstep
        from repro.launch.steps import StepBuilder

        self.config, self.traffic = config, traffic
        self.cfg = arch_config(config)
        self.data = make_data(config, traffic, seed)
        sb = StepBuilder(self.cfg, make_rules(None))
        B, S = traffic["batch"], traffic["prompt_len"]
        self.lm = Lockstep(sb, init_params(sb, seed), B, S + traffic["gen_len"])
        logits, self.first = self.lm.prefill({"tokens": self.data["prompts"]},
                                             traffic["prefill_group"])
        rows = self.data["sessions"]
        self.kept = {("prefill", 0): np.asarray(logits[rows], np.float32)}
        self.lm.take_snapshot()
        n_check = max(self.data["steps"]) + 1
        self.fed = np.zeros((len(rows), n_check), np.int32)
        self.fed[:, 0] = np.asarray(self.first)[rows, 0]
        self.states = []
        self.attempted = self.failed = self.windows = 0
        for _ in range(2):                  # warm every shape of the window
            self.lm.restore()
            logits, tok, report = self.lm.decode(self.first, S)
            self.lm.collect(report)
            np.asarray(logits[rows, 0])
        jax.block_until_ready(program_states(self.cfg.n_layers, self.lm.cache,
                                             rows))

    def run(self, seconds: float) -> None:
        """One window: steps until ``seconds`` have passed and, in the first
        window, every checked step has been through it."""
        lm, S = self.lm, self.traffic["prompt_len"]
        end = S + self.traffic["gen_len"]
        rows, check = self.data["sessions"], set(self.data["steps"])
        last = max(check) if self.windows == 0 else -1
        latencies, ctx_sum = [], 0
        t_start = time.perf_counter()
        pos = end
        k = 0
        while True:
            t0 = time.perf_counter()
            if pos == end:
                with TraceAnnotation("bench.restore"):
                    lm.restore()
                tok, pos = self.first, S
            with TraceAnnotation("bench.decode"):
                logits, tok, report = lm.decode(tok, pos)
            with TraceAnnotation("bench.d2h"):
                ids = lm.collect(report)
            t1 = time.perf_counter()
            latencies.append(t1 - t0)
            ctx_sum += pos + 1
            self.attempted += 1
            if k < last:
                self.fed[:, k + 1] = ids[rows]
            if k <= last and k in check:
                self.kept[("decode", k)] = np.asarray(logits[rows, 0], np.float32)
            if k == last:
                self.states = program_states(self.cfg.n_layers, lm.cache, rows)
            k += 1
            pos += 1
            if t1 - t_start >= seconds and k > last:
                break
        self.windows += 1
        self.window = {"calls": k, "elapsed_s": t1 - t_start,
                       "latencies": latencies, "ctx_sum": ctx_sum}

    def end_to_end(self) -> dict:
        """Of the last window: the 95th percentile of a step's time from its
        dispatch (or the restore before it) to its token ids on the host."""
        return {"batch_p95_ms":
                1e3 * float(np.percentile(self.window["latencies"], 95))}

    def layer(self) -> dict:
        """The steps, their summed context and the seconds of the last window."""
        w = self.window
        return {"calls": w["calls"], "elapsed_s": w["elapsed_s"],
                "ctx_sum": w["ctx_sum"], "batch": self.traffic["batch"]}

    def release(self) -> None:
        """Free the cache and snapshot; the weights go to host memory."""
        params = jax.tree.map(np.asarray, self.lm.params)
        self.weights = ref_weights(self.cfg, params)
        self.states = [np.asarray(a, np.float32) for a in self.states]
        del self.lm, self.first

    def check(self) -> list:
        want, want_states = reference(self.config, self.weights, self.data,
                                      self.fed)
        return compare(self.kept, want, self.states, want_states)


def control(config: dict, traffic: dict, seed: int,
            low: tuple = granite_ref.LOW_PARTS) -> list:
    """The numbers compared, with the reference's control put in the
    program's place, over ids drawn from the seed: ``granite_ref`` one
    precision down in the parts ``low`` names, by default in each part the
    configuration states (float8 weights, a bfloat16 SSM state)."""
    from repro.distributed import make_rules
    from repro.launch.steps import StepBuilder

    cfg = arch_config(config)
    data = make_data(config, traffic, seed)
    sb = StepBuilder(cfg, make_rules(None))
    weights = ref_weights(cfg, jax.tree.map(np.asarray, init_params(sb, seed)))
    fed = np.random.default_rng(seed + 1).integers(
        0, config["vocab_size"], (len(data["sessions"]), max(data["steps"]) + 1),
        dtype=np.int32)
    want, want_states = reference(config, weights, data, fed)
    got, got_states = reference(config, weights, data, fed, low=low)
    return compare(got, want, got_states, want_states)
