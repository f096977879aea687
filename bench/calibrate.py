#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from (not run by the
benchmark itself).

    python bench/calibrate.py --workload <name> --seconds <s> --seeds 101 102 ...

For each seed, in one process: a run of the cell at its own size and load
(a window of ``--seconds``, long enough to finish the mix's longest
requests), then the widest logit gap of the served tokens (the program's
reading) and, on the same sample, of the reference's lower-precision
control (``logits_at(..., control=True)`` of the cell's architecture
module, ``reference/<architecture>.py``).  The limit goes above
the largest program reading and below the smallest control reading.  One
JSON line per seed, then a summary line.
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = bench_run.load_cell(args.workload)
    devices = bench_run.require_chips(cell.chips)
    bench_run.use_compile_cache()
    prog, ctrl = [], []
    for seed in args.seeds:
        r = bench_run.run_cell(cell, seed, args.seconds, False, devices, control=True)
        gap = r["checks"]["max_logit_gap"]["value"]
        n = r["checks"]["tokens_compared"]["value"]
        prog.append(gap)
        ctrl.append(r["control_gap"])
        print(json.dumps({"seed": seed, "program_gap": gap, "control_gap": r["control_gap"],
                          "tokens": n, "attempted": r["attempted"],
                          "metrics": {k: v["value"] for k, v in r["metrics"].items()}}),
              flush=True)
    print(json.dumps({"workload": args.workload, "program_max": max(prog),
                      "control_min": min(ctrl), "seeds": len(args.seeds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
