#!/usr/bin/env python3
"""The readings the correctness limits are set from, on the chip.

    python3 chipbench/readings.py --workload phi4-decode-closed \
        --seeds 101,102,103 --seconds 51 --out readings.jsonl

One process builds the cell once; for each seed it draws new weights,
serves a window of the cell's traffic at the cell's own load, and compares
a seeded sample of the served requests with the reference, as a run does
(``harness.NUMBERS``): from the gaps of the served tokens below the
reference's best (``f32``, the program's readings), and, at the same
positions, from the gaps of the tokens that the reference computed in int8
or in fp8 puts first (the control's readings).  One JSON line per seed
goes to ``--out`` and to stdout.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench import harness

    seeds = [int(s) for s in args.seeds.split(",")]
    precs = ("f32", "int8", "fp8")
    cell = harness.Cell(ROOT, args.workload, seeds[0])
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        for i, seed in enumerate(seeds):
            t = time.perf_counter()
            if i:
                cell.system.reseed(seed)
            served = cell.serve(seed, args.seconds)
            cmp = cell.check(served, seed, precs)
            cell.system.front.reset()
            line = dict(workload=args.workload, seed=seed,
                        numbers=cmp["numbers"], compared=cmp["compared"],
                        outputs_ok=cmp["outputs_ok"],
                        failed=served["failed"],
                        values={k: v for k, v in served["values"].items()
                                if k != "backlog"},
                        seconds=time.perf_counter() - t)
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
            f.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
