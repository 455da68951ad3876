#!/usr/bin/env python3
"""Readings the correctness limit of a cell is set from (not part of a
benchmark run).

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds <s>

For each seed, in one process: a normal run of the cell (set-up, window,
sampled requests), then the widest logit gap of the program's served
tokens against the float32 reference (the lower reading) and that of the
float8 control's tokens on the same prompts and served tokens (the upper
reading), the control judged by the cell's own comparison in the
program's place: ``correct`` is the control's verdict, and has to come
out false. One JSON line per seed; the cell's limit goes between the
largest lower and the smallest upper reading (PERF.md gives both).
"""
import argparse
import json
import sys
import time

T_PROCESS = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="10")
    a = ap.parse_args()
    t = T_PROCESS
    for seed in a.seeds.split(","):
        res = harness.run_cell(ROOT, ["--workload", a.workload, "--seed",
                                      seed, "--seconds", a.seconds,
                                      "--trace", "0"], t, control=True)
        print(json.dumps({"seed": int(seed), "correct": res["correct"],
                          "control": res["checks"]["max_logit_gap"]["value"],
                          "program": res["program_max_logit_gap"],
                          "limit": res["checks"]["max_logit_gap"]["limit"],
                          "sampled_tokens":
                              res["checks"]["sampled_tokens"]["value"],
                          "metrics": res["metrics"]}), flush=True)
        t = time.perf_counter()


if __name__ == "__main__":
    main()
