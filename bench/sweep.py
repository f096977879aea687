#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip (not run by the
benchmark itself).

    python bench/sweep.py --workload <name> --seconds <s> --seed <n> --rates 1 2 4

Runs the cell's window at each session rate in turn, in one process, and
prints per rate the end-to-end metrics and how many requests were still
queued or unanswered when the window closed.  The knee is the highest rate
whose queue does not grow through the window; the cell's rate is set at
about four fifths of it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload)
    devices = bench_run.require_chips(cell.chips)
    bench_run.use_compile_cache()
    for rate in args.rates:
        r = bench_run.run_cell(cell, args.seed, args.seconds, False, devices,
                               load={"session_rate_per_s": rate})
        print(json.dumps({"rate": rate, "attempted": r["attempted"],
                          "window": r["window"],
                          "correct": r["correct"],
                          "metrics": {k: v["value"] for k, v in r["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
