#!/usr/bin/env python3
"""Read the correctness comparison's two readings for a cell, on the
chip, over many seeds in one process: the program's widest logit gap
(its lower reading) and the fp8 control's on the same sample (its upper
reading), with the verdict of the harness's own comparison on each:
``correct`` for the program, ``control_correct`` for the control, which
has to be false.  The benchmark's own runs never run the control.

    python3 bench/control.py --workload <cell> --seconds 10 --seeds 1,2,3

Each seed is a full run of the cell at its own load (weights, traffic,
window, sample), with a short window; prints one JSON line per seed.
The limit in ``bench/checks/<cell>.json`` is set from these readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import correctness
import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", required=True)
    a = ap.parse_args()
    for seed in [int(s) for s in a.seeds.split(",")]:
        args = run.parse(["--workload", a.workload, "--seed", str(seed),
                          "--seconds", str(a.seconds), "--trace", "0"])
        result, checks, ctl = run.run_cell(args, control=True)
        print(json.dumps({
            "seed": seed, "correct": result["correct"],
            "control_correct": correctness.passed(ctl),
            "served_gap": checks["max_logit_gap"]["value"],
            "control_gap": ctl["max_logit_gap"]["value"],
            "tokens": checks["tokens_compared"]["value"],
            "requests": checks["requests_compared"]["value"]}), flush=True)
        del result, checks, ctl
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
