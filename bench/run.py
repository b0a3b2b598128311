#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells are listed in BENCHMARK.json; see bench/harness.py for the files
that define each one.  Exits non-zero, printing no result, when JAX finds
no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()  # set-up is timed from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:], T_START))
