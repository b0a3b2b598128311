#!/usr/bin/env python
"""Ordering-sweep driver (repro.reorder.bench, DESIGN.md §10).

Builds the correlated synthetic workloads, captures every ordering
strategy's executed nonzero trace, prices it on all four memory stacks
via the DSE evaluator, prints the report and writes ``BENCH_reorder.json``.

Usage:
    python scripts/run_reorder.py                      # make reorder
    python scripts/run_reorder.py --quick --out /tmp/BENCH_reorder_smoke.json

Exits nonzero if the acceptance gate fails: on each correlated tensor at
least one non-lex strategy must beat lex on BOTH the E-SRAM and O-SRAM
stacks — strictly higher exact-LRU hit rate and strictly lower priced
energy.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.compile_cache import enable_compile_cache
from repro.data.frostt import PAPER_RANK
from repro.perf.report import reorder_report_md
from repro.reorder import ORDERINGS
from repro.reorder.bench import run_reorder_sweep


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--strategies",
        default=",".join(ORDERINGS),
        help=f"comma list from {list(ORDERINGS)}",
    )
    ap.add_argument("--rank", type=int, default=PAPER_RANK)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument(
        "--quick",
        action="store_true",
        help="~4x smaller tensors (CI smoke); deltas shrink but keep sign",
    )
    ap.add_argument("--out", default="BENCH_reorder.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    strategies = tuple(s.strip() for s in args.strategies.split(",") if s.strip())
    unknown = [s for s in strategies if s not in ORDERINGS]
    if unknown:
        raise SystemExit(f"unknown strategies {unknown}; known: {list(ORDERINGS)}")
    if "lex" not in strategies:
        raise SystemExit("the lex baseline must be among --strategies")

    t0 = time.perf_counter()
    payload = run_reorder_sweep(
        strategies=strategies, rank=args.rank, quick=args.quick, seed=args.seed
    )
    payload["driver_wall_s"] = time.perf_counter() - t0

    print(reorder_report_md(payload))
    print(f"\ndriver wall time: {payload['driver_wall_s']:.1f}s")
    Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"wrote {args.out}")
    if not payload["acceptance"]["ok"]:
        print("FAIL: no non-lex strategy beats lex on both acceptance stacks")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
