#!/usr/bin/env python3
"""Read a cell's control beside the program, for several seeds in one
process (by hand, on the chip; the benchmark's own runs never run it):

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        --control high --control bf16 --control fp8

For each seed it makes a whole run of the cell and judges what the window
served against the reference, as every run does; then, for each
``--control`` (``CONTROLS`` in ``bench/sites.py``), it puts the
reference at that lower precision in the program's place and judges it
by the same comparison and limits.  One JSON line per seed.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="append", default=None,
                    choices=("high", "bf16", "fp8"))
    args = ap.parse_args()
    from bench import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = harness.run(args.workload, seed, args.seconds, False,
                          t_start=t0, controls=tuple(args.control or ()),
                          log=lambda *a, **k: print(*a, file=sys.stderr,
                                                    flush=True))
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"],
                          "controls": res.get("controls", {})}), flush=True)
        gc.collect()


if __name__ == "__main__":
    main()
