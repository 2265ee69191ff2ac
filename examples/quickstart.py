"""Quickstart: the paper's memory engines + advisor, then 5 training steps.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs import ARCHS, SHAPES_BY_NAME, ShapeCell, smoke_config
from repro.core import advisor, engines
from repro.core.autotune import tune_attention_blocks, tune_pattern
from repro.core.patterns import Pattern
from repro.dist import POLICIES
from repro.models import RuntimeFlags, build
from repro.optim import AdamWConfig
from repro.train import TrainConfig, Trainer
from repro.tune import default_cache, plan_for


def main():
    print("=== 1. the paper's engines: measured vs modeled (v5e) ===")
    for row in (engines.bw_sequential(rows=1024, cols=512),
                engines.bw_random(n_rows=1 << 12, cols=64, n_idx=1 << 11),
                engines.latency_chase(n_entries=1 << 12, steps=1 << 11)):
        print("  " + row.csv())

    print("\n=== 2. per-pattern optimization directions (paper §5/§6) ===")
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    policy = POLICIES["fsdp_tp"]
    n_eng = policy.engines(mesh)
    print(f"  policy={policy.name}: {n_eng} access engine(s) on this mesh")
    reports = advisor.advise_model(ARCHS["gemma2-27b"],
                                   SHAPES_BY_NAME["prefill_32k"],
                                   engines=n_eng,
                                   param_engines=policy.param_engines(mesh))
    print(advisor.render_report(reports))

    print("\n=== 3. autotuned knobs ===")
    print("  sequential:", tune_pattern(Pattern.SEQUENTIAL))
    print("  attention blocks (hd=128):", tune_attention_blocks(128))

    print("\n=== 3b. the applied KernelPlan for this model (repro.tune) ===")
    big = ARCHS["gemma2-27b"]
    cell = SHAPES_BY_NAME["prefill_32k"]
    plan = plan_for("flash_attention",
                    shape_sig=(cell.seq_len, cell.seq_len,
                               big.resolved_head_dim),
                    dtype=big.compute_dtype)
    print(f"  flash_attention @ {big.name}/{cell.name}: "
          f"bq={plan.bq} bkv={plan.bkv} depth={plan.pipeline_depth} "
          f"dtype={plan.dtype} interpret={plan.resolve_interpret()} "
          f"({plan.predicted_gbps:.0f} GB/s predicted, {plan.source})")
    print(f"  cached in {default_cache().path or 'memory'} "
          f"— kernels pick this up when called without blocks")

    print("\n=== 4. five training steps of a reduced gemma2 ===")
    cfg = smoke_config(ARCHS["gemma2-27b"])
    bundle = build(cfg, RuntimeFlags(attn_bq=16, attn_bkv=16, moe_impl="dense",
                                     loss_chunk=16))
    tr = Trainer(bundle, ShapeCell("quick", "train", 64, 4), mesh,
                 policy, AdamWConfig(lr=1e-3),
                 TrainConfig(steps=5, log_every=1, data_kind="markov"))
    with jax.set_mesh(mesh):
        tr.run()
    for h in tr.history:
        print(f"  step {h['step']}: loss {h['loss']:.4f} ({h['tok_s']:.0f} tok/s)")


if __name__ == "__main__":
    main()
