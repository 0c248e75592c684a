"""granite-4.0-h-small against its plain float32 reference
(``repro.models.reference.granite_hybrid``) on seeded random weights at SMOKE
widths, period 10: prefill then decoding through the cache, the expert shares
summing to the uncut layer, no drops under skewed routing, the published
sizes, and the serving path's spans and counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.configs import get_config
from repro.distributed import make_rules
from repro.distributed.sharding import unbox_values
from repro.launch.serve import Lockstep
from repro.launch.steps import StepBuilder
from repro.models import build_model, layers, moe
from repro.models.reference import granite_hybrid as ref
from repro.models.transformer import block_program

RULES = make_rules(None)
ARCH = "granite-4.0-h-small"


def _rel_rms(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def _params(cfg, seed=1):
    """Seeded weights, with the vectors that init leaves at 0 or 1 (conv
    bias, D, the norms) drawn too, so that the comparison sees them."""
    params = build_model(cfg).init_values(jax.random.PRNGKey(seed))
    key = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))

    def draw(path, a):
        name = jax.tree_util.keystr(path)
        if any(k in name for k in ("conv_b", "'D'", "scale", "'norm'")):
            return a + 0.3 * jax.random.normal(next(key), a.shape, a.dtype)
        return a
    return jax.tree_util.tree_map_with_path(draw, params)


def _serve(cfg, params, tokens, n_prompt, group):
    """Logits of positions n_prompt - 1 .. S - 1 through the serving path:
    jitted prefill of the prompt in groups, then the donated decode step fed
    the rest of ``tokens``."""
    B, S = tokens.shape
    lm = Lockstep(StepBuilder(cfg, RULES), params, B, S)
    last, _ = lm.prefill({"tokens": tokens[:, :n_prompt]}, group)
    out = [np.asarray(last, np.float32)]
    for pos in range(n_prompt, S):
        logits, _, report = lm.decode(tokens[:, pos:pos + 1], pos)
        lm.collect(report)
        out.append(np.asarray(logits[:, 0], np.float32))
    return np.stack(out, axis=1)


# float32: the program's chunked SSD, blocked attention and grouped experts
# add in another order than the per-step reference, so they differ by
# float32 rounding carried through ten layers: 6.3e-7 read here, limit 1e-4.
# bfloat16 (the published dtype): activations and matrix products round to
# 8 bits; 2.3% read here, limit 8%.
@pytest.mark.parametrize("dtype,limit", [("float32", 1e-4), ("bfloat16", 8e-2)])
def test_prefill_then_decode_matches_reference(dtype, limit):
    cfg = get_config(ARCH, smoke=True).replace(dtype=dtype)
    assert [e["mixer"] for e in block_program(cfg)] == ["ssm"] * 5 + ["attn"] + ["ssm"] * 4
    params = _params(cfg)
    B, S, n_prompt = 2, 40, 32          # two SSD chunks and part of a third
    tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
    got = _serve(cfg, params, tokens, n_prompt, group=1)
    want = np.asarray(ref.forward(cfg, params, tokens))[:, n_prompt - 1:]
    assert _rel_rms(got, want) < limit


def _share_setup(T=48, E=12, k=3):
    cfg = get_config(ARCH, smoke=True).replace(
        dtype="float32", n_experts=E, n_experts_per_tok=k)
    p = unbox_values(moe.init_moe(cfg, jax.random.PRNGKey(0)))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, cfg.d_model))
    return cfg, p, x


def _share(cfg, p, a, n):
    """The params and config of the share that holds experts [a, a + n)."""
    part = {k: v[a:a + n] for k, v in p.items() if k.startswith("w_")}
    return cfg.replace(expert_offset=a, experts_held=n), dict(part, router=p["router"])


def test_expert_shares_sum_to_the_uncut_layer():
    """Three shares of 4 of 12 experts, each with its own experts' part, and
    the shared expert counted once, give the uncut reference layer; their
    held assignments add up to every assignment."""
    cfg, p, x = _share_setup()
    total, held = layers.apply_mlp(cfg, p["shared"], x, RULES), 0
    for a in (0, 4, 8):
        c, pa = _share(cfg, p, a, 4)
        y, _, h = moe._moe_share(c, pa, x, RULES)
        total, held = total + y, held + int(h)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(cfg, p, x)
    assert held == x.shape[1] * cfg.n_experts_per_tok
    # float32 sums of the same products in another order
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_skewed_router_drops_nothing():
    """Every token routed to the same k experts: the share's output equals
    the reference's for every token, where the capacity-bound ``gather``
    path drops most of them."""
    cfg, p, x = _share_setup()
    x = jnp.abs(x)
    k = cfg.n_experts_per_tok
    p["router"] = jnp.where(jnp.arange(cfg.n_experts) < k, 1.0, -1.0)[None].repeat(
        cfg.d_model, 0)
    y, _, held = moe.apply_moe(cfg, p, x, RULES)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(cfg, p, x)
    assert int(held) == x.shape[1] * k
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4, atol=1e-5)
    shared = layers.apply_mlp(cfg, p["shared"], x, RULES)
    gathered, _, _ = moe.apply_moe(cfg.replace(moe_impl="gather"), p, x, RULES)
    assert _rel_rms(gathered - shared, want - shared) > 0.5


def test_published_sizes_and_block_program():
    cfg = get_config(ARCH)
    counts = cfg.param_counts()
    assert abs(counts["total"] / 32.2e9 - 1) < 0.03
    assert abs(counts["active"] / 8.8e9 - 1) < 0.03
    prog = block_program(cfg)
    assert [e["mixer"] for e in prog] == ["ssm"] * 5 + ["attn"] + ["ssm"] * 4
    assert all(e["ffn"] == "moe" for e in prog)
    assert (cfg.d_inner, cfg.ssm_nheads, cfg.ssm_state) == (8192, 128, 128)


def test_serving_spans_counters_and_restore():
    """Under a session the serving path records its spans and counters; a
    restore puts the state back, so the same tokens give the same logits."""
    cfg = get_config(ARCH, smoke=True).replace(experts_held=4, expert_offset=2)
    params = build_model(cfg).init_values(jax.random.PRNGKey(0))
    B, S, n = 4, 16, 3
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab_size)
    with telemetry.session() as tel:
        lm = Lockstep(StepBuilder(cfg, RULES), params, B, S + n)
        _, tok = lm.prefill({"tokens": tokens}, group=2)
        lm.take_snapshot()
        runs = []
        for _ in range(2):
            old = lm.cache
            lm.restore()            # the cache donated whole, in place
            assert all(a.is_deleted() for a in jax.tree.leaves(old))
            t, logits = tok, []
            for i in range(n):
                out, t, report = lm.decode(t, S + i)
                ids = lm.collect(report)
                np.testing.assert_array_equal(ids, np.asarray(t)[:, 0])
                logits.append(np.asarray(out))
            runs.append(logits)
    np.testing.assert_array_equal(runs[0], runs[1])
    names = [s.name for s in tel.tracer.roots]
    assert names.count("lm.prefill") == 1 and names.count("lm.restore") == 2
    assert names.count("lm.decode") == 2 * n
    m = tel.metrics
    assert m.get("lm_decode_steps_total").value == 2 * n
    assert m.get("lm_tokens_total", phase="prefill").value == B * S
    assert m.get("lm_tokens_total", phase="decode").value == 2 * n * B
    held = m.get("moe_assignments_total", experts="held").value
    absent = m.get("moe_assignments_total", experts="absent").value
    assert held + absent == 2 * n * B * cfg.n_experts_per_tok * cfg.n_layers
    assert 0 < held < held + absent


def _bench_driver(monkeypatch):
    """``bench/drivers/lm_decode.py``, whose reference imports nothing of
    the program."""
    import importlib.util
    from pathlib import Path

    bench = Path(__file__).resolve().parents[1] / "bench"
    monkeypatch.syspath_prepend(str(bench))
    spec = importlib.util.spec_from_file_location(
        "bench_lm_decode", bench / "drivers" / "lm_decode.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 2)])
def test_bench_reference_matches_this_reference(monkeypatch, held, offset):
    """The benchmark's standalone reference (blocked, for the chip) and this
    one write the same equations: on the same weights, in float32, their
    logits agree at every position, whole and with a share of the experts."""
    drv = _bench_driver(monkeypatch)
    cfg = get_config(ARCH, smoke=True).replace(
        dtype="float32", experts_held=held, expert_offset=offset)
    config = {hf: getattr(cfg, f) for hf, f in drv.HF_FIELDS.items()}
    config.update(mamba_n_heads=cfg.ssm_nheads,
                  attention_multiplier=cfg.attn_scale,
                  layer_types=["attention" if cfg.is_attn_layer(i) else "mamba"
                               for i in range(cfg.n_layers)],
                  deployment={"expert_offset": offset})
    params = _params(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 24), 0, cfg.vocab_size)
    got, states = drv.granite_ref.logits_at(
        drv.granite_ref.dims_of(config), drv.ref_weights(cfg, params),
        np.asarray(tokens), list(range(24)))
    want = np.asarray(ref.forward(cfg, params, tokens))
    assert len(states) == 9
    # float32 sums of the same products in another order: 5.6e-7 read here
    assert _rel_rms(got, want) < 1e-4
