import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import (jax locks the device count
# on first init). Everything below is ordinary.
#
# Multi-pod dry-run: lower + compile every (architecture x input-shape) cell on
# the production meshes and extract the roofline terms.
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch nemotron-4-15b --shape train_4k
#     PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
#
# Artifacts: artifacts/dryrun/<mesh>/<arch>__<shape>.json (read by benchmarks/
# roofline.py and EXPERIMENTS.md generation).

import argparse
import json
import time
import traceback

import jax

from repro.configs import (ARCH_IDS, SHAPES, get_config, input_specs, model_flops,
                           shape_applicable)
from repro.core.cost_model import V5E, roofline
from repro.core.hlo_analysis import analyze_compiled
from repro.distributed.sharding import make_rules
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import StepBuilder
from repro.models.moe import IMPLS


# --- optimization knobs for the §Perf hillclimb (all default-off) -----------
# decode_tp_params : serve params TP-only (drop the FSDP 'embed'->data rule for
#                    decode, killing the per-step weight all-gather)
# causal_skip      : block-causal attention — q-chunk i only reads kv[0:(i+1)Qc]
#                    (unrolled loop; halves attention flops+bytes)
# bf16_loss        : bf16 softmax-xent with f32 reductions (no f32 logits
#                    materialization)
# moe_dense        : force the gather MoE (vs EP shard_map)
KNOWN_OPTS = ("decode_tp_params", "causal_skip", "bf16_loss", "moe_dense")


def tune_cfg(cfg, shape, moe_impl: str | None = None, opts: tuple = ()):
    """Per-cell config adjustments (the dry-run knobs the perf loop turns)."""
    kw = {}
    if cfg.moe:
        kw["moe_impl"] = moe_impl or ("ep" if shape.kind != "decode" else "gather")
        if "moe_dense" in opts:
            kw["moe_impl"] = "gather"
    if "causal_skip" in opts:
        kw["causal_block_skip"] = True
    if "bf16_loss" in opts:
        kw["softmax_dtype"] = "bfloat16"
    if kw:
        cfg = cfg.replace(**kw)
    return cfg


def rule_overrides_for(shape, opts: tuple = ()):
    if "decode_tp_params" in opts and shape.kind == "decode":
        return {"embed": None}       # TP-only serving params; no FSDP gather
    return None


def lower_cell(arch: str, shape_name: str, mesh, *, n_microbatches: int = 8,
               moe_impl: str | None = None, cfg_override: dict | None = None,
               grad_only: bool = False, cfg_base=None, opts: tuple = ()):
    """Returns (lowered, aux_info dict)."""
    cfg = cfg_base or get_config(arch)
    shape = SHAPES[shape_name]
    cfg = tune_cfg(cfg, shape, moe_impl, opts)
    if cfg_override:
        cfg = cfg.replace(**cfg_override)
    rules = make_rules(mesh, rule_overrides_for(shape, opts))
    sb = StepBuilder(cfg, rules, n_microbatches=n_microbatches)
    specs = input_specs(cfg, shape)
    if n_microbatches > 1 and shape.kind == "train" and grad_only:
        raise ValueError("probes must use n_microbatches=1")

    if shape.kind == "train":
        params_abs, boxed = sb.abstract_params()
        if grad_only:
            step = sb.jit_grad_step()
            args = (params_abs, specs)
        else:
            opt_abs = sb.abstract_opt_state(params_abs)
            step = sb.jit_train_step(donate=True)
            args = (params_abs, opt_abs, specs)
    elif shape.kind == "prefill":
        params_abs, boxed = sb.abstract_params(dtype="bfloat16")
        step = sb.jit_prefill(shape)
        args = (params_abs, specs)
    else:  # decode
        params_abs, boxed = sb.abstract_params(dtype="bfloat16")
        cache_abs, _ = sb.cache_abstract(shape)
        step = sb.jit_decode_step(shape, donate=True)
        args = (params_abs, cache_abs, specs["tokens"], specs["pos"])

    lowered = step.lower(*args)
    return lowered, {"cfg": cfg, "shape": shape, "sb": sb, "params_abs": params_abs}


# ---------------------------------------------------------------------------
# Compositional cost probes.
#
# XLA:CPU cost_analysis counts a while-loop body ONCE (verified in
# tests/test_hlo_analysis.py), so the scanned production executable under-counts
# FLOPs/bytes by the trip counts. The probes lower loop-free (unrolled) graphs at
# 1x and 2x the block period; the difference is the exact per-block cost, scaled
# by the stack depth and microbatch count, plus a separate optimizer probe.
# ---------------------------------------------------------------------------

def _scale_cost(c, s: float):
    from repro.core.hlo_analysis import CollectiveStats, CompiledCost
    return CompiledCost(
        n_devices=c.n_devices,
        flops=c.flops * s,
        bytes_accessed=c.bytes_accessed * s,
        collective_bytes=c.collective_bytes * s,
        collectives=CollectiveStats(
            {k: v * s for k, v in c.collectives.bytes_by_kind.items()},
            {k: v * s for k, v in c.collectives.count_by_kind.items()}),
        peak_memory_per_device=c.peak_memory_per_device,
        argument_bytes_per_device=c.argument_bytes_per_device,
        temp_bytes_per_device=c.temp_bytes_per_device,
        output_bytes_per_device=c.output_bytes_per_device,
    )


def _add_cost(a, b, sb: float = 1.0):
    from repro.core.hlo_analysis import CollectiveStats, CompiledCost
    keys = set(a.collectives.bytes_by_kind) | set(b.collectives.bytes_by_kind)
    return CompiledCost(
        n_devices=a.n_devices,
        flops=max(a.flops + sb * b.flops, 0.0),
        bytes_accessed=max(a.bytes_accessed + sb * b.bytes_accessed, 0.0),
        collective_bytes=max(a.collective_bytes + sb * b.collective_bytes, 0.0),
        collectives=CollectiveStats(
            {k: max(a.collectives.bytes_by_kind.get(k, 0)
                    + sb * b.collectives.bytes_by_kind.get(k, 0), 0.0) for k in keys},
            {k: max(a.collectives.count_by_kind.get(k, 0)
                    + sb * b.collectives.count_by_kind.get(k, 0), 0.0) for k in keys}),
        peak_memory_per_device=a.peak_memory_per_device,
        argument_bytes_per_device=a.argument_bytes_per_device,
        temp_bytes_per_device=a.temp_bytes_per_device,
        output_bytes_per_device=a.output_bytes_per_device,
    )


def probe_cost(arch: str, shape_name: str, mesh, *, n_microbatches: int = 8,
               moe_impl: str | None = None, cfg_base=None, verbose: bool = False,
               opts: tuple = ()):
    """Exact (trip-count-aware) global cost for the cell, from unrolled probes."""
    from repro.configs import base as cfgbase
    from repro.models.transformer import block_period

    cfg = cfg_base or get_config(arch)
    shape = SHAPES[shape_name]
    chips = mesh.devices.size
    P = block_period(tune_cfg(cfg, shape, moe_impl, opts))
    n_stack = cfg.n_layers // P
    is_train = shape.kind == "train"
    mb = n_microbatches if is_train else 1

    # thread the microbatch-sized batch through input_specs via a scoped SHAPES
    # patch (lower_cell reads SHAPES[shape_name])
    orig = cfgbase.SHAPES[shape_name]
    probe_shape = orig
    if is_train and mb > 1:
        probe_shape = cfgbase.ShapeSpec(orig.name, orig.kind, orig.seq_len,
                                        orig.global_batch // mb)
    c = {}
    try:
        cfgbase.SHAPES[shape_name] = probe_shape
        for mult in (1, 2):
            over = {"unroll": True, "n_layers": mult * P}
            if cfg.encdec:
                over["n_enc_layers"] = mult * (cfg.n_enc_layers * P // cfg.n_layers)
            t0 = time.time()
            lowered, _ = lower_cell(arch, shape_name, mesh, n_microbatches=1,
                                    moe_impl=moe_impl, cfg_override=over,
                                    grad_only=is_train, cfg_base=cfg_base,
                                    opts=opts)
            c[mult] = analyze_compiled(lowered.compile(), n_devices=chips)
            if verbose:
                print(f"[probe] {arch} {shape_name} x{mult}: {time.time()-t0:.0f}s")
    finally:
        cfgbase.SHAPES[shape_name] = orig

    block = _add_cost(c[2], c[1], sb=-1.0)           # per extra block
    per_mb = _add_cost(c[1], block, sb=float(n_stack - 1))
    total = _scale_cost(per_mb, float(mb))

    if is_train:  # optimizer probe on full-size params, once per step
        rules = make_rules(mesh, rule_overrides_for(shape, opts))
        sb_full = StepBuilder(tune_cfg(cfg, shape, moe_impl, opts), rules, 1)
        total = _add_cost(total, _optimizer_probe(sb_full, chips))
    return total


def _optimizer_probe(sb: StepBuilder, chips: int):
    from repro.optim import AdamWConfig
    from repro.optim.adamw import update as adamw_update

    params_abs, boxed = sb.abstract_params()
    ps = sb.param_shardings(boxed)
    opt_abs = sb.abstract_opt_state(params_abs)
    os_ = sb.opt_shardings(ps)
    oc = AdamWConfig(lr=1e-4)
    fn = jax.jit(lambda g, s, p: adamw_update(oc, g, s, p),
                 in_shardings=(ps, os_, ps), donate_argnums=(1,))
    lowered = fn.lower(params_abs, opt_abs, params_abs)
    return analyze_compiled(lowered.compile(), n_devices=chips)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             n_microbatches: int = 8, moe_impl: str | None = None,
             out_dir: str = "artifacts/dryrun", verbose: bool = True,
             probes: bool = True, opts: tuple = ()) -> dict:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    if not ok:
        rec.update(status="skip", reason=why)
        _save(rec, out_dir, mesh_name, arch, shape_name)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        with mesh:
            # (1) full production-structured compile: proves lowering/sharding,
            # gives the real memory picture + collective schedule
            lowered, aux = lower_cell(arch, shape_name, mesh,
                                      n_microbatches=n_microbatches,
                                      moe_impl=moe_impl, opts=opts)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            sched = analyze_compiled(compiled, n_devices=chips)
            if probes:
                # (2) trip-count-aware cost probes (XLA:CPU counts loop bodies
                # once; see tests/test_hlo_analysis.py)
                cost = probe_cost(arch, shape_name, mesh,
                                  n_microbatches=n_microbatches, moe_impl=moe_impl,
                                  opts=opts)
                # memory picture comes from the production executable
                cost.peak_memory_per_device = sched.peak_memory_per_device
                cost.argument_bytes_per_device = sched.argument_bytes_per_device
                cost.temp_bytes_per_device = sched.temp_bytes_per_device
                cost.output_bytes_per_device = sched.output_bytes_per_device
            else:
                cost = sched  # schedule/memory only (multi-pod compile proof)
    except Exception as e:
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
        _save(rec, out_dir, mesh_name, arch, shape_name)
        if verbose:
            print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: FAILED {e}")
        return rec

    terms = roofline(cost.flops, cost.bytes_accessed, cost.collective_bytes, chips)
    mflops = model_flops(aux["cfg"], aux["shape"])
    rec.update(
        status="ok",
        chips=chips,
        lower_s=round(t_lower, 1),
        compile_s=round(t_compile, 1),
        flops=cost.flops,
        bytes_accessed=cost.bytes_accessed,
        collective_bytes=cost.collective_bytes,
        collective_bytes_by_kind=cost.collectives.bytes_by_kind,
        collective_count_by_kind=cost.collectives.count_by_kind,
        peak_memory_per_device=cost.peak_memory_per_device,
        argument_bytes_per_device=cost.argument_bytes_per_device,
        temp_bytes_per_device=cost.temp_bytes_per_device,
        t_compute=terms.t_compute,
        t_memory=terms.t_memory,
        t_collective=terms.t_collective,
        t_step=terms.t_step,
        dominant=terms.dominant,
        model_flops=mflops,
        useful_flops_ratio=(mflops / cost.flops) if cost.flops else None,
        roofline_fraction=(mflops / (terms.t_step * chips * V5E.peak_flops))
        if terms.t_step > 0 else None,
        n_microbatches=n_microbatches,
        opts=list(opts),
    )
    _save(rec, out_dir, mesh_name, arch, shape_name)
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"t_step={terms.t_step*1e3:.2f}ms dom={terms.dominant} "
              f"mem/dev={cost.peak_memory_per_device/2**30:.2f}GiB "
              f"useful={rec['useful_flops_ratio'] and round(rec['useful_flops_ratio'],3)} "
              f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return rec


def _save(rec: dict, out_dir: str, mesh_name: str, arch: str, shape_name: str):
    d = os.path.join(out_dir, mesh_name)
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{arch}__{shape_name}.json"), "w") as f:
        json.dump(rec, f, indent=1, default=float)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--moe-impl", choices=IMPLS)
    ap.add_argument("--no-probes", action="store_true",
                    help="compile-proof only (no cost probes); used for multi-pod")
    ap.add_argument("--opt", action="append", default=[], choices=list(KNOWN_OPTS),
                    help="perf knobs (repeatable); results tagged in the artifact")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    cells = ([(args.arch, args.shape)] if args.arch and args.shape else
             [(a, s) for a in ([args.arch] if args.arch else ARCH_IDS)
              for s in ([args.shape] if args.shape else list(SHAPES))])
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    n_ok = n_fail = 0
    for mp in meshes:
        for arch, shape in cells:
            rec = run_cell(arch, shape, multi_pod=mp,
                           n_microbatches=args.microbatches,
                           moe_impl=args.moe_impl, out_dir=args.out,
                           probes=not args.no_probes, opts=tuple(args.opt))
            if rec["status"] == "error":
                n_fail += 1
            else:
                n_ok += 1
    print(f"[dryrun] done: {n_ok} ok/skip, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
