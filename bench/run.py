#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip:

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic, metrics and limits are read from
``BENCHMARK.json`` and the files under ``bench/`` (``bench/harness.py``).
Set-up (weights from the seed, crossbar plans and states, compiles and
warm-up) is timed as ``setup_s``; then whole calls run back to back for
``--seconds``; then what the window served is compared with the plain
reference.  With ``--trace 1`` the window runs under the profiler and
the per-layer metrics are reported instead of the end-to-end ones.

With no TPU, or fewer chips than the cell asks for, it exits non-zero
and prints no result.  The last stdout line is one JSON object.
"""
import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def log(*args, file=None):
    print(*args, file=file or sys.stdout, flush=True)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    from bench import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START, log=log)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        sys.exit(2)
    for name, c in result["checks"].items():
        log(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
