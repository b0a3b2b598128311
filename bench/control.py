#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, on the chip.

    python3 bench/control.py --workload nell2.sweep --seeds 1 --seconds 4

For each seed: the cell's set-up, a short window at the cell's own load,
then the numbers the cell compares, for the program (``exact``) and for
the controls, the reference at a lower precision put in the program's
place (``bfloat16``, ``bf16x3``).  One JSON line per seed and reading.
At the cell's full size give one seed per process: the program's memos
keep every tensor's device buffers for the life of the process.  The lower reading of a limit is the largest
the program gives over a dozen seeds or more; the upper is the smallest
the control gives.  The benchmark's own runs do not run this.
"""

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

CONTROLS = ("bfloat16", "bf16x3")


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--controls", type=int, default=3,
                    help="run the controls on the first this many seeds")
    args = ap.parse_args(argv)
    harness.enable_cache()
    info = harness.cell(args.workload)
    devices = harness.require_tpu(info["entry"]["chips"])
    driver = harness.load_module(harness.BENCH / "traffic" / f"{info['spec']['kind']}.py")
    with harness.precision(info["config"]):
        readings(args, info, driver, devices)
    return 0


def readings(args, info, driver, devices) -> None:
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = harness.Context(name=args.workload, seed=seed, seconds=args.seconds, trace=False,
                              config=info["config"], params=info["spec"]["params"],
                              limits=info["spec"]["limits"])
        t0 = time.perf_counter()
        state = driver.setup(ctx)
        win = driver.window(ctx, state)
        for contract in ("exact",) + (CONTROLS if i < args.controls else ()):
            got = driver.check(ctx, state, win, contract=contract)
            print(json.dumps({"workload": args.workload, "seed": seed, "contract": contract,
                              "readings": got, "device": devices[0].device_kind,
                              "s": time.perf_counter() - t0}), flush=True)
        del state, win


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
