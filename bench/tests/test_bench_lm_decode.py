"""The LM decode cell at a CPU size: a sound run is correct and reports
exactly its end-to-end metrics; the timed path broken underneath (a held
expert left out, the shared expert left out, the SSM state reset every step),
or the control in its place, by part too, comes out not correct. The experts are cut to 2
held of 4, top-2, so that one held expert is a large part of each layer, and
d_state kept at 64, so that the state is a large part of each read-out."""
import tinyroot

import json

import pytest

CELL = "granite4h-decode-b64"
SIZES = {
    **tinyroot.SMALL,
    "configs/granite-4.0-h-small-l10-e8.json": {
        "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
        "attention_multiplier": 0.0625, "intermediate_size": 128,
        "shared_intermediate_size": 64, "mamba_n_heads": 8, "mamba_d_head": 16,
        "mamba_d_state": 64, "mamba_chunk_size": 16, "vocab_size": 512,
        "num_local_experts": 2, "num_experts_per_tok": 2,
        "deployment": {"experts_total": 4, "expert_offset": 2}},
    "traffic/decode-p4k-g4k-b64.json": {
        "batch": 4, "prompt_len": 48, "gen_len": 24, "prefill_group": 2,
        "check_steps": 4, "check_window": 16, "trace_seconds": 0.5},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tinyroot.make(tmp_path_factory.mktemp("bench"), sizes=SIZES)


def test_sound_run(root, capsys):
    line = tinyroot.run(root, CELL, capsys)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"batch_p95_ms", "setup_s"}
    assert set(line["checks"]) == {"logit_rel_rms", "logit_max_gap",
                                   "logit_rel_rms_worst", "ssm_state_rel_rms"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_run_keys(root, capsys):
    line = tinyroot.run(root, CELL, capsys, trace=1)
    assert line["correct"], line["checks"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def _held_expert_out(share):
    """The first held expert's part left out."""
    def broken(cfg, p, x, rules):
        return share(cfg, dict(p, w_down=p["w_down"].at[0].set(0)), x, rules)
    return "_moe_share", broken


def _shared_expert_out(apply_moe):
    def broken(cfg, p, x, rules, impl=None):
        return apply_moe(cfg, {k: v for k, v in p.items() if k != "shared"},
                         x, rules, impl=impl)
    return "apply_moe", broken


def _state_reset(apply_ssd):
    """Each decode step starts from a zero SSM state."""
    def broken(cfg, p, x, rules, cache=None, pos=None):
        if cache is not None and pos is not None:
            cache = dict(cache, state=cache["state"] * 0)
        return apply_ssd(cfg, p, x, rules, cache=cache, pos=pos)
    return "apply_ssd", broken


@pytest.mark.parametrize("fault,module,target", [
    (_held_expert_out, "moe", "_moe_share"),
    (_shared_expert_out, "moe", "apply_moe"),
    (_state_reset, "mamba", "apply_ssd"),
])
def test_broken_path_is_not_correct(root, capsys, monkeypatch, fault, module,
                                    target):
    from repro.models import mamba, moe

    mod = {"moe": moe, "mamba": mamba}[module]
    name, broken = fault(getattr(mod, target))
    monkeypatch.setattr(mod, name, broken)
    line = tinyroot.run(root, CELL, capsys)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("low", [None, ("weights",)])
def test_control_is_not_correct(root, low):
    """The reference one precision down in the program's place: in each part
    the configuration states (the default), and in its weights alone."""
    from benchlib import harness, spec

    cell = spec.Cell(root, CELL)
    drv = cell.driver()
    args = () if low is None else (low,)
    passed, checks = harness.judge(
        drv.control(cell.config, cell.traffic, 7, *args), cell.limits)
    assert not passed, json.dumps(checks)


# 128 SSM heads a layer, as published, and a 2048-token prompt: enough heads
# and context that some keep a long memory
LONG = {
    **SIZES,
    "configs/granite-4.0-h-small-l10-e8.json": {
        **SIZES["configs/granite-4.0-h-small-l10-e8.json"],
        "hidden_size": 256, "mamba_n_heads": 128, "mamba_d_head": 4},
    "traffic/decode-p4k-g4k-b64.json": {
        **SIZES["traffic/decode-p4k-g4k-b64.json"],
        "prompt_len": 2048, "gen_len": 300, "check_window": 256},
}


def test_bfloat16_state_control_is_not_correct(tmp_path):
    """The reference carrying its SSM state in bfloat16 alone: a head that
    keeps a long memory carries the state's rounding over the whole context,
    which ``ssm_state_rel_rms`` reads (13% to 22% over four seeds here; on
    the chip at the cell's size 16% to 32%, sound runs 5.9% to 6.7%)."""
    from benchlib import harness, spec

    cell = spec.Cell(tinyroot.make(tmp_path, sizes=LONG), CELL)
    passed, checks = harness.judge(
        cell.driver().control(cell.config, cell.traffic, 7, ("state",)),
        cell.limits)
    state = checks["ssm_state_rel_rms"]
    assert not passed and state["value"] > state["limit"], json.dumps(checks)


def test_work_counts_by_hand():
    """The yardstick of `granite_mfu.decode` and `ssm_state_roofline.decode`
    at the published widths, against counts made by hand."""
    from benchlib import lm_work

    config = json.loads((tinyroot.BENCH / "configs"
                         / "granite-4.0-h-small-l10-e8.json").read_text())
    # 9 Mamba layers of 4096 * (2 * 8192 + 2 * 128 + 128) + 8192 * 4096,
    # attention 2 * 4096 * 4096 + 2 * 4096 * 1024, 10 x (router 4096 * 72,
    # shared 3 * 4096 * 1536, 10 * 8 / 72 experts of 3 * 4096 * 768), and
    # the tied head 4096 * 100352
    assert lm_work.token_params(config) == 1669660672.0
    # one step of 64 tokens at a context of 5000: 2 x params x 64 + 4 x 64 x
    # 5000 x 4096 for the one attention layer
    assert lm_work.decode_flops(config, 64, 1, 5000) == (
        2 * 1669660672 * 64 + 4 * 64 * 5000 * 4096)
    n = 64 * 128 * 64 * 128
    assert lm_work.ssm_state_update(64, 128, 64, 128) == (
        5.0 * n, 4 * (2 * n + 64 * (128 * 64 + 2 * 128 + 128)))
