#!/usr/bin/env python3
"""Record a short traced run of a cell on the chip and keep a trimmed copy
of its trace as a test fixture.

    python3 chipbench/record_fixture.py --workload phi4-decode-closed \
        --seed 5 --seconds 12 --keep-ms 300 --out chipbench/fixtures

Runs the cell as ``run.py --trace 1`` does, prints the trace's layout
(planes, lines, event counts and the heaviest event names) to stderr, and
writes ``<out>/<workload>.json.gz``: the first ``--keep-ms`` of the traced
window (the ``chipbench.window`` span cut to match) with the host-side
readings of the whole traced window.
"""
import argparse
import collections
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def layout(raw: dict) -> str:
    out = []
    for plane in raw["planes"]:
        out.append(f"plane {plane['name']}")
        for line in plane["lines"]:
            tot = collections.Counter()
            for name, _, dur in line["events"]:
                tot[name] += dur
            top = ", ".join(f"{n} {t * 1e-6:.3f}ms"
                            for n, t in tot.most_common(12))
            out.append(f"  line {line['name']!r}: {len(line['events'])} "
                       f"events; heaviest: {top}")
    return "\n".join(out)


def trim(raw: dict, keep_ns: float) -> dict:
    from chipbench import trace

    t0, t1 = trace.window_span(raw)
    t1 = min(t1, t0 + keep_ns)
    planes = []
    for plane in raw["planes"]:
        lines = []
        for line in plane["lines"]:
            ev = [[n, s, d] for n, s, d in line["events"]
                  if s < t1 and s + d > t0 and n != trace.WINDOW]
            if ev:
                lines.append({"name": line["name"], "events": ev})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    planes.append({"name": "/host:chipbench", "lines": [
        {"name": "window", "events": [[trace.WINDOW, t0, t1 - t0]]}]})
    return {"planes": planes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-ms", type=float, default=300.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench import harness, trace

    kept = {}

    def on_trace(raw, host):
        print(layout(raw), file=sys.stderr)
        kept["raw"], kept["host"] = raw, host

    result = harness.run(ROOT, args.workload, args.seed, args.seconds, True,
                         t_start=T_START, on_trace=on_trace)
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, f"{args.workload}.json.gz")
    trace.save(dict(workload=args.workload, host=kept["host"],
                    raw=trim(kept["raw"], args.keep_ms * 1e6)), path)
    print(f"fixture: {path} ({os.path.getsize(path)} bytes)",
          file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
