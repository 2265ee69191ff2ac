"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch mamba2-130m \
        --seq 256 --batch 8 --steps 50 --ckpt /tmp/run1

Runs on whatever devices the host exposes (data x model mesh); on a real
TPU pod slice the same entry point runs under ``jax.distributed`` with the
production mesh from ``repro.launch.mesh``.  Fault tolerance: automatic
retry-with-restore (``--max-failures``); deterministic data makes recovery
bit-exact with an uninterrupted run.
"""
import argparse
import logging
import sys

import jax

from repro.configs import ARCHS, ShapeCell, override, smoke_config
from repro.dist import POLICIES
from repro.launch.compile_cache import enable_compile_cache
from repro.models import RuntimeFlags, build
from repro.optim import AdamWConfig, schedule
from repro.train import TrainConfig, Trainer, run_with_recovery


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-friendly)")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--policy", default="fsdp_tp", choices=sorted(POLICIES))
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--data", default="markov", choices=["markov", "uniform"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--max-failures", type=int, default=3)
    ap.add_argument("--mesh-model", type=int, default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = smoke_config(cfg)
    else:
        cfg = override(cfg, param_dtype="float32", compute_dtype="float32")

    n_dev = jax.device_count()
    dm = args.mesh_model
    mesh = jax.make_mesh((n_dev // dm, dm), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    flags = RuntimeFlags(attn_impl="chunked", attn_bq=128, attn_bkv=128,
                         loss_chunk=128, moe_impl="dense")
    bundle = build(cfg, flags)
    cell = ShapeCell("cli", "train", args.seq, args.batch)
    opt = AdamWConfig(lr=args.lr,
                      schedule=schedule.warmup_cosine(10, args.steps))
    tcfg = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt,
                       ckpt_every=max(10, args.steps // 5), log_every=5,
                       data_kind=args.data, microbatches=args.micro)
    tr = Trainer(bundle, cell, mesh, POLICIES[args.policy], opt, tcfg)

    def run(resume):
        with jax.set_mesh(mesh):
            return tr.run(resume if resume is not None
                          else (-1 if args.resume else None))

    final = run_with_recovery(run, max_failures=args.max_failures)
    print(f"finished at step {final}; last metrics: "
          f"{tr.history[-1] if tr.history else {}}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
