import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # fake devices only: never hold a chip
# The lines above MUST run before any other import (jax locks the platform
# and device count at first init).  Everything below is ordinary.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and extract memory/cost/collective evidence.

  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod both
  PYTHONPATH=src python -m repro.launch.dryrun --all --roofline --out runs/dryrun.json

Per cell this produces:
  - single-pod (16x16) and/or multi-pod (2x16x16) full-depth compile:
    memory_analysis (fits/chip?), cost_analysis, collective histogram;
  - with --roofline: two reduced-depth UNROLLED compiles (nb=1,2; naive
    attention; unchunked loss) -> affine extrapolation to full depth ->
    compute/memory/collective roofline terms (see core.roofline docstring).

Results append into a JSON file so the full table builds incrementally.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import (ARCHS, LM_SHAPES, SHAPES_BY_NAME, override,
                           shape_applicable)
from repro.configs.base import DECODE, PREFILL, TRAIN, ModelConfig, ShapeCell
import repro.core.roofline as rl
from repro.core.memmodel import V5E
from repro.dist import POLICIES
from repro.dist.steps import make_decode_step, make_prefill_step, make_train_step
from repro.launch.mesh import make_production_mesh
from repro.models import RuntimeFlags, build
from repro.optim import AdamWConfig, adamw


def default_flags(roofline: bool = False) -> RuntimeFlags:
    if roofline:
        # unrolled + scan-free inner ops so cost_analysis counts everything;
        # remat stays on so the recompute cost is measured like deployment.
        # attention keeps the DEPLOYED block sizes, python-unrolled.
        return RuntimeFlags(attn_impl="unrolled", attn_bq=2048, attn_bkv=2048,
                            unroll_layers=True, loss_chunk=0,
                            moe_impl="sorted", remat="full")
    # attn blocks from core.autotune.tune_attention_blocks (VMEM-budgeted)
    return RuntimeFlags(attn_impl="chunked", attn_bq=2048, attn_bkv=2048,
                        moe_impl="sorted", loss_chunk=512, remat="full")


# optimized-preset microbatch counts (hillclimb iteration 2: grad accumulation
# scales activation memory 1/m; chosen so train cells fit 16GiB — grok-1
# additionally requires the 2-pod mesh: params+opt are 12.3GiB/chip on one)
TRAIN_MICRO = {
    "grok-1-314b": 32, "internlm2-20b": 4, "gemma2-27b": 8, "pixtral-12b": 4,
    "granite-moe-3b-a800m": 4, "recurrentgemma-9b": 8,
    "seamless-m4t-medium": 4, "phi4-mini-3.8b": 2, "gemma-2b": 2,
    "mamba2-130m": 1,
}


def lower_cell(cfg: ModelConfig, cell: ShapeCell, mesh, policy,
               flags: RuntimeFlags, microbatches: int = 1):
    bundle = build(cfg, flags)
    abs_params, _ = bundle.abstract_params()
    inputs = bundle.input_specs(cell)
    with jax.set_mesh(mesh):
        if cell.kind == TRAIN:
            step, p_sh, o_sh, bsh = make_train_step(
                bundle, mesh, policy, AdamWConfig(), microbatches=microbatches)
            opt_abs = jax.eval_shape(adamw.init, abs_params)
            lowered = step.lower(abs_params, opt_abs, inputs)
        elif cell.kind == PREFILL:
            step, _ = make_prefill_step(bundle, mesh, policy, cell)
            lowered = step.lower(abs_params, inputs)
        else:  # decode
            step, _, c_sh = make_decode_step(bundle, mesh, policy, cell)
            cache_abs = bundle.cache_specs(cell)
            lowered = step.lower(abs_params, cache_abs, inputs["tokens"],
                                 inputs["pos"])
        compiled = lowered.compile()
    return compiled


def model_flops_per_chip(cfg: ModelConfig, cell: ShapeCell, chips: int) -> float:
    _, active = cfg.param_count()
    mult = 6 if cell.kind == TRAIN else 2
    return mult * active * cell.tokens / chips


def reduced_cfg(cfg: ModelConfig, nb: int) -> ModelConfig:
    kw = dict(num_layers=cfg.pattern_len * nb + len(cfg.remainder_specs))
    if cfg.enc_dec:
        kw["num_encoder_layers"] = nb
    return override(cfg, **kw)


def preset_for(cfg: ModelConfig, cell: ShapeCell, preset: str):
    """(policy_name, flags, microbatches) for a cell under a preset.

    ``baseline``  — the paper-naive deployable config (hillclimb iteration 0).
    ``opt``       — after the §Perf iterations: sequence-parallel activations
                    + grad-accumulation microbatching for train cells; int8
                    KV caches for decode cells.
    """
    if preset == "baseline":
        return "fsdp_tp", default_flags(), 1
    if cell.kind == TRAIN:
        # iteration 3: loss_chunk 512->128 (CE pipeline holds ~4GiB less)
        return ("fsdp_tp_sp",
                dataclasses.replace(default_flags(), loss_chunk=128),
                TRAIN_MICRO.get(cfg.name, 4))
    if cell.kind == DECODE:
        return ("fsdp_tp",
                dataclasses.replace(default_flags(), kv_dtype="int8"), 1)
    return "fsdp_tp", default_flags(), 1


def run_cell(cfg: ModelConfig, cell: ShapeCell, *, pods: str, roofline: bool,
             policy_name: str = "fsdp_tp", flags=None, preset=None) -> dict:
    if preset is not None:
        policy_name, flags, micro = preset_for(cfg, cell, preset)
    else:
        micro = 1
    rec = dict(arch=cfg.name, shape=cell.name, kind=cell.kind,
               policy=policy_name, status="ok", meshes={},
               preset=preset or "baseline", microbatches=micro)
    policy = POLICIES[policy_name]
    flags = flags or default_flags()
    mesh_list = {"single": False, "multi": True, "both": None}[pods]
    todo = [False, True] if mesh_list is None else [mesh_list]
    for mp in todo:
        mesh = make_production_mesh(multi_pod=mp)
        chips = mesh.size
        t0 = time.time()
        compiled = lower_cell(cfg, cell, mesh, policy, flags, micro)
        dt = time.time() - t0
        mem = rl.memory_summary(compiled)
        cost = rl.cost_of(compiled)
        _, per_coll = rl.collective_stats(compiled.as_text())
        key = "multi_pod" if mp else "single_pod"
        rec["meshes"][key] = dict(
            chips=chips, engines=policy.engines(mesh), compile_s=round(dt, 1),
            peak_gib=round(mem.get("peak_bytes_per_device", 0) / 2**30, 3),
            arg_gib=round(mem.get("argument_size_in_bytes", 0) / 2**30, 3),
            temp_gib=round(mem.get("temp_size_in_bytes", 0) / 2**30, 3),
            out_gib=round(mem.get("output_size_in_bytes", 0) / 2**30, 3),
            hlo_flops_per_dev=cost.flops, hlo_bytes_per_dev=cost.bytes_raw,
            hlo_bytes_fused_per_dev=cost.bytes_fused,
            collective_bytes_per_dev=cost.collective,
            collectives={k: v for k, v in per_coll.items()},
        )
        print(f"  [{key}] chips={chips} compile={dt:.1f}s "
              f"peak/dev={rec['meshes'][key]['peak_gib']:.2f}GiB "
              f"colls={sorted(per_coll)}", flush=True)
        del compiled

    if roofline:
        mesh = make_production_mesh(multi_pod=False)
        chips = mesh.size
        rflags = default_flags(roofline=True)
        costs = {}
        for nb in (1, 2):
            rcfg = reduced_cfg(cfg, nb)
            t0 = time.time()
            compiled = lower_cell(rcfg, cell, mesh, policy, rflags)
            costs[nb] = rl.cost_of(compiled)
            print(f"  [roofline nb={nb}] compile={time.time()-t0:.1f}s "
                  f"flops={costs[nb].flops:.3e}", flush=True)
            del compiled
        nb_t = cfg.num_pattern_blocks
        full = rl.affine_extrapolate(costs[1], costs[2], 1, 2, nb_t)
        mf = model_flops_per_chip(cfg, cell, chips)
        terms = rl.terms_from_cost(full, chips, mf)
        rec["roofline"] = dict(
            chips=chips, engines=policy.engines(mesh),
            hlo_flops=full.flops, hlo_bytes_raw=full.bytes_raw,
            hlo_bytes=full.bytes_fused,
            bytes_flash_inner=full.bytes_flash_inner,
            collective_bytes=full.collective,
            compute_s=terms.compute_s, memory_s=terms.memory_s,
            collective_s=terms.collective_s, dominant=terms.dominant,
            model_flops=mf, useful_ratio=terms.useful_flops_ratio,
            roofline_fraction=terms.roofline_fraction,
        )
        print(f"  [roofline] dominant={terms.dominant} "
              f"compute={terms.compute_s*1e3:.2f}ms "
              f"memory={terms.memory_s*1e3:.2f}ms "
              f"collective={terms.collective_s*1e3:.2f}ms "
              f"useful={terms.useful_flops_ratio:.3f} "
              f"frac={terms.roofline_fraction:.3f}", flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=sorted(ARCHS))
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES_BY_NAME))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", dest="pods", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--policy", default="fsdp_tp", choices=sorted(POLICIES))
    ap.add_argument("--preset", default=None, choices=["baseline", "opt"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    cells = []
    for cfg in ARCHS.values():
        if args.arch and cfg.name != args.arch:
            continue
        for cell in LM_SHAPES:
            if args.shape and cell.name != args.shape:
                continue
            ok, why = shape_applicable(cfg, cell)
            cells.append((cfg, cell, ok, why))
    if not args.all and not args.arch and not args.shape:
        ap.error("pass --all or --arch/--shape")

    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["policy"]) for r in results
            if r.get("status") == "ok" and (not args.roofline or "roofline" in r)
            and (args.pods == "single" or "multi_pod" in r.get("meshes", {}))}

    failures = 0
    for cfg, cell, ok, why in cells:
        tag = f"{cfg.name} x {cell.name}"
        if not ok:
            print(f"SKIP {tag}: {why}", flush=True)
            rec = dict(arch=cfg.name, shape=cell.name, policy=args.policy,
                       status="skip", reason=why)
            results = [r for r in results if not (
                r["arch"] == cfg.name and r["shape"] == cell.name)] + [rec]
            continue
        if (cfg.name, cell.name, args.policy) in done:
            print(f"CACHED {tag}", flush=True)
            continue
        print(f"CELL {tag}", flush=True)
        try:
            rec = run_cell(cfg, cell, pods=args.pods, roofline=args.roofline,
                           policy_name=args.policy, preset=args.preset)
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            rec = dict(arch=cfg.name, shape=cell.name, policy=args.policy,
                       status="fail", error=str(e)[:500])
            failures += 1
        results = [r for r in results if not (
            r["arch"] == cfg.name and r["shape"] == cell.name
            and r["policy"] == args.policy)] + [rec]
        if args.out:
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    print(f"done: {len(results)} records, {failures} failures", flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
