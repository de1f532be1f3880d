#!/usr/bin/env python3
"""Readings from which a cell's correctness limit is set, on the chip.

    python bench/control.py --workload qwen3-1.7b.chat --seconds 10 --seeds 11 12 13

For each seed, in one process: a run of the cell as the benchmark makes it
(a shorter window, the same load, drained until its requests finish), then
the plain reference over the same sample of served tokens, once as is and
once per control precision in the program's place.  The program and each
control go through the cell's one comparison (``check.judge``).  Prints
one JSON line per seed: the program's widest logit gap (the lower
reading) and whether it is correct, and each control's gap (the upper
reading) and whether that is correct, which it must not be.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run

CONTROLS = ("int8", "fp8")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    run.prepare_environment()
    run.enable_compile_cache()
    for seed in args.seeds:
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           t_start=time.perf_counter(), controls=CONTROLS)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "logit_gap": out["compared"]["logit_gap"]["value"],
            "served_tokens": out["compared"]["served_tokens_checked"]["value"],
            "controls": {c: {"correct": ok,
                             "logit_gap": cmp["logit_gap"]["value"]}
                         for c, (ok, cmp) in out["controls"].items()},
            "attempted": out["attempted"], "failed": out["failed"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
