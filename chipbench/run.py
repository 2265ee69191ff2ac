#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload phi4-decode-closed --seed 7 \
        --seconds 51 --trace 0

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1`` runs
the same traffic with the profiler on over a sub-window and reports the
cell's per-layer metrics.  The last line of stdout is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and
``checks`` last: each compared number with its limit, also printed as the
last lines of stderr).  Without a TPU, with fewer chips than the cell asks
for, or without the program beside it, it exits nonzero and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"the system under test is missing: no src/repro under {ROOT}",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    # the persistent compilation cache lives at a fixed path inside the
    # checkout (the path is part of each entry's key), for every program
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from chipbench import harness

    try:
        result = harness.run(ROOT, args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
