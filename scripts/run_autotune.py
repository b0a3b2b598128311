#!/usr/bin/env python
"""Closed-loop tile-autotuning benchmark driver (DESIGN.md §13).

Runs the DSE autotuner (``repro.dse.autotune``) over scaled FROSTT
tensors on the platform's compiled MTTKRP backend, compares against the
interpret-mode emulator and the fixed default tile config, prices every
measured config with the analytic model, and writes the
``BENCH_autotune.json`` artifact.

Usage:
    python scripts/run_autotune.py                          # make autotune
    python scripts/run_autotune.py --quick \\
        --out /tmp/BENCH_autotune_smoke.json                # make autotune-smoke

Acceptance gate (exit nonzero on violation):
  * the compiled backend is STRICTLY faster than interpret-mode
    emulation on every bench cell (default config, mode 0);
  * the autotuned config is never slower than the default
    ``(256,256,lex)`` on any tensor (structural — the default is in the
    tune space — but verified against the recorded timings);
  * compiled-vs-oracle parity within ``PARITY_RTOL`` on every mode.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.autotune_bench import PARITY_RTOL, bench_cell
from repro.compile_cache import enable_compile_cache
from repro.data.frostt import FROSTT_TENSORS, PAPER_RANK
from repro.dse.autotune import Autotuner, TuneSpace
from repro.kernels.mttkrp.ops import resolve_backend

DEFAULT_TENSORS = "NELL-2@5e-5,NELL-2@1e-4"
QUICK_TENSORS = "NELL-2@5e-5"
# Quick mode sweeps a 2x2 grid (plus the default member) so the CI smoke
# still exercises cache banding and the tuned<=default gate end to end.
QUICK_SPACE = TuneSpace(tile_nnz=(128, 256), rows_per_block=(64, 256))


def _parse_tensors(arg: str) -> tuple[tuple[str, float], ...]:
    out = []
    for item in arg.split(","):
        item = item.strip()
        if not item:
            continue
        name, _, scale_s = item.partition("@")
        if name not in FROSTT_TENSORS:
            raise SystemExit(f"unknown tensor {name!r}; known: {sorted(FROSTT_TENSORS)}")
        if not scale_s:
            raise SystemExit(f"pass an explicit scale: {name}@SCALE")
        out.append((name, float(scale_s)))
    if not out:
        raise SystemExit("--tensors selected nothing")
    return tuple(out)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tensors", default=None, help="comma list of NAME@SCALE")
    ap.add_argument("--rank", type=int, default=PAPER_RANK)
    ap.add_argument("--reps", type=int, default=3, help="fenced timing reps (median)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--orderings",
        default="lex",
        help="comma list of nonzero orderings to include in the tune space",
    )
    ap.add_argument(
        "--quick",
        action="store_true",
        help=f"CI smoke: tensors {QUICK_TENSORS}, 2x2 tune grid, 2 reps",
    )
    ap.add_argument("--out", default="BENCH_autotune.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tensors = _parse_tensors(
        args.tensors or (QUICK_TENSORS if args.quick else DEFAULT_TENSORS)
    )
    orderings = tuple(o.strip() for o in args.orderings.split(",") if o.strip())
    if args.quick:
        space = TuneSpace(
            tile_nnz=QUICK_SPACE.tile_nnz,
            rows_per_block=QUICK_SPACE.rows_per_block,
            orderings=orderings,
        )
        reps = 2
    else:
        space = TuneSpace(orderings=orderings)
        reps = args.reps

    backend = resolve_backend(None)
    if backend == "interpret":
        # The gate is compiled-vs-interpret; with no compiled path the
        # comparison is vacuous.  REPRO_PALLAS_INTERPRET=1 reaches here.
        print("FAIL: resolved backend is 'interpret' — no compiled path to tune")
        return 1

    tuner = Autotuner(space, reps=reps)
    cells = []
    t_start = time.perf_counter()
    for name, scale in tensors:
        label = f"{name}@{scale:g}"
        print(f"--- {label}  (backend={backend}, {len(space.configs())} configs)")
        cell = bench_cell(
            name, scale, rank=args.rank, tuner=tuner, reps=reps, seed=args.seed
        )
        cells.append(cell)
        print(
            f"    interpret {cell['interpret_mode0_s']*1e3:8.1f} ms | compiled "
            f"{cell['compiled_mode0_s']*1e3:8.1f} ms ({cell['interpret_speedup']:.0f}x) | "
            f"tuned {cell['best_config']} {cell['best_s']*1e3:.1f} ms vs default "
            f"{cell['default_s']*1e3:.1f} ms ({cell['speedup_vs_default']:.2f}x) | "
            f"parity {cell['parity_max_rel_err']:.1e}"
        )

    all_compiled_faster = all(c["compiled_faster"] for c in cells)
    all_tuned_ok = all(c["tuned_ok"] for c in cells)
    all_parity_ok = all(c["parity_ok"] for c in cells)
    payload = {
        "benchmark": "mttkrp_autotune",
        "config": {
            "tensors": [f"{n}@{s:g}" for n, s in tensors],
            "rank": args.rank,
            "reps": reps,
            "seed": args.seed,
            "backend": backend,
            "tune_space": {
                "tile_nnz": list(space.tile_nnz),
                "rows_per_block": list(space.rows_per_block),
                "orderings": list(space.orderings),
            },
            "quick": args.quick,
        },
        "parity_rtol": PARITY_RTOL,
        "all_compiled_faster": all_compiled_faster,
        "all_tuned_ok": all_tuned_ok,
        "all_parity_ok": all_parity_ok,
        "memo": {"hits": tuner.memo.hits, "misses": tuner.memo.misses,
                 "cells": len(tuner.memo)},
        "driver_wall_s": time.perf_counter() - t_start,
        "cells": cells,
    }
    Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"\nwrote {args.out}")

    ok = True
    if not all_compiled_faster:
        slow = [c["tensor"] for c in cells if not c["compiled_faster"]]
        print(f"FAIL: compiled path not strictly faster than interpret on: {slow}")
        ok = False
    if not all_tuned_ok:
        bad = [c["tensor"] for c in cells if not c["tuned_ok"]]
        print(f"FAIL: tuned config slower than default on: {bad}")
        ok = False
    if not all_parity_ok:
        bad = [c["tensor"] for c in cells if not c["parity_ok"]]
        print(f"FAIL: compiled-vs-oracle parity beyond {PARITY_RTOL}: {bad}")
        ok = False
    if ok:
        print(
            f"gate OK: compiled strictly faster than interpret on all "
            f"{len(cells)} cells, tuned <= default everywhere, parity within "
            f"{PARITY_RTOL}"
        )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
