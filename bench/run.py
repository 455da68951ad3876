#!/usr/bin/env python3
"""Run one cell of the benchmark named in BENCHMARK.json.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Needs the accelerator the cell asks for; exits nonzero without a result
line where JAX finds none. See ``bench/lib/harness.py``.
"""
import sys
import time

T_PROCESS = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(ROOT, sys.argv[1:], T_PROCESS))
