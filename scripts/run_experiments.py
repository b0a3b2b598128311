#!/usr/bin/env python
"""End-to-end experiment driver (repro.experiments, DESIGN.md §7).

Materializes scaled FROSTT tensors, runs measured CP-ALS sweeps through
the requested impls (``sharded`` spawns its own 8-device subprocess),
prices every run on all four memory technologies, prints the measured-vs-
modeled report and writes the ``BENCH_experiments.json`` artifact.

Usage:
    python scripts/run_experiments.py                       # make experiments
    python scripts/run_experiments.py --tensors NELL-2@1e-4 --impls ref \\
        --iters 2 --out /tmp/BENCH_experiments_smoke.json   # CI smoke

Exits nonzero if any priced scenario's exact-trace hit rate disagrees
with the Che approximation beyond the documented 0.10 tolerance.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.compile_cache import enable_compile_cache
from repro.data.frostt import FROSTT_TENSORS, PAPER_RANK
from repro.data.synthetic_tensors import EXPERIMENT_SCALES
from repro.experiments import ExperimentSpec, run_experiments
from repro.perf.report import experiments_report_md


def _parse_tensors(arg: str) -> tuple[tuple[str, float], ...]:
    """``NAME[@SCALE]``, comma-separated; default scales from the catalog."""
    out = []
    for item in arg.split(","):
        item = item.strip()
        if not item:
            continue
        name, _, scale_s = item.partition("@")
        if name not in FROSTT_TENSORS:
            raise SystemExit(
                f"unknown tensor {name!r}; known: {sorted(FROSTT_TENSORS)}"
            )
        if scale_s:
            scale = float(scale_s)
        elif name in EXPERIMENT_SCALES:
            scale = EXPERIMENT_SCALES[name]
        else:
            raise SystemExit(
                f"no default scale for {name!r}; pass {name}@SCALE explicitly"
            )
        out.append((name, scale))
    if not out:
        raise SystemExit("--tensors selected nothing")
    return tuple(out)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--tensors",
        default=",".join(EXPERIMENT_SCALES),
        help="comma list of NAME[@SCALE] (default: the catalog scales, "
        + ", ".join(f"{n}@{s:g}" for n, s in EXPERIMENT_SCALES.items())
        + ")",
    )
    ap.add_argument(
        "--impls",
        default="ref,pallas,sharded",
        help="comma list from {ref,pallas,sharded}",
    )
    ap.add_argument("--rank", type=int, default=PAPER_RANK)
    ap.add_argument("--iters", type=int, default=3, help="CP-ALS iterations")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--no-cost-analysis",
        action="store_true",
        help="skip the HLO cost_analysis lowering (faster smoke runs)",
    )
    ap.add_argument(
        "--no-fused",
        action="store_true",
        help="skip the fused-executor timing path (DESIGN.md §11)",
    )
    ap.add_argument(
        "--fit-every",
        type=int,
        default=1,
        help="fused executor host-sync cadence in sweeps",
    )
    ap.add_argument(
        "--backend",
        default=None,
        choices=("mosaic", "triton", "xla", "interpret"),
        help="pallas-path execution backend (default: the platform's "
        "compiled path — the XLA fallback on CPU; DESIGN.md §13)",
    )
    ap.add_argument(
        "--autotune",
        action="store_true",
        help="tune (tile_nnz, rows_per_block) per tensor through the "
        "closed-loop DSE autotuner before measuring pallas cells",
    )
    ap.add_argument("--out", default="BENCH_experiments.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    impls = tuple(i.strip() for i in args.impls.split(",") if i.strip())
    unknown = [i for i in impls if i not in ("ref", "pallas", "sharded")]
    if unknown:
        raise SystemExit(f"unknown impls {unknown}")

    spec = ExperimentSpec(
        tensors=_parse_tensors(args.tensors),
        impls=impls,
        rank=args.rank,
        n_iters=args.iters,
        seed=args.seed,
        cost_analysis=not args.no_cost_analysis,
        fused=not args.no_fused,
        fit_every=args.fit_every,
        backend=args.backend,
        autotune=args.autotune,
    )
    t0 = time.perf_counter()
    result = run_experiments(spec)
    wall = time.perf_counter() - t0

    payload = result.to_json_dict()
    payload["driver_wall_s"] = wall
    print(experiments_report_md(payload))
    print(f"\ndriver wall time: {wall:.1f}s for {len(result.runs)} runs")
    Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"wrote {args.out}")
    if not result.all_within_tol:
        print("FAIL: trace-vs-Che hit-rate reconciliation out of tolerance")
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
