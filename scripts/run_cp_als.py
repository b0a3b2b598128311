#!/usr/bin/env python
"""Fused CP-ALS benchmark driver (repro.core.cp_als_fused, DESIGN.md §11).

Times the eager per-mode CP-ALS driver against the fused device-resident
executor on scaled FROSTT tensors — warm (post-compile) wall per cell,
best of ``--repeats`` — plus the vmap multi-restart throughput of the
fused path, prints the table and writes the ``BENCH_cp_als.json``
artifact.

Usage:
    python scripts/run_cp_als.py                                # make cp-als
    python scripts/run_cp_als.py --quick --restarts 2 --iters 2 \\
        --out /tmp/BENCH_cp_als_smoke.json                      # CI smoke

Acceptance gate (exit nonzero on violation):
  * the fused executor is STRICTLY faster than the eager driver on every
    measured (tensor, impl) cell (warm vs warm);
  * fused fit trajectories match eager within ``FUSED_FIT_TOL``
    (same seeds, documented float-summation tolerance).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.cp_als import cp_als
from repro.core.cp_als_fused import FUSED_FIT_TOL, FusedCPALS
from repro.data.frostt import FROSTT_TENSORS, PAPER_RANK
from repro.data.synthetic_tensors import make_frostt_like
from repro.kernels.mttkrp.ops import resolve_backend

DEFAULT_TENSORS = "NELL-2@1e-4,PATENTS@1e-5"
QUICK_TENSORS = "NELL-2@5e-5"
DEFAULT_IMPLS = "ref,pallas,sharded"
QUICK_IMPLS = "ref"

# Interpret-mode-only guard: the Pallas emulator's per-tile overhead
# scales with nnz_pad, so above this many nonzeros an eager-vs-fused
# comparison measures the emulator rather than the dispatch overhead the
# fused executor removes — the cell is skipped (recorded in the
# artifact), mirroring the engine's PALLAS_MAX_OUTPUT_ROWS guard.  The
# compiled backends (mosaic/triton/xla; DESIGN.md §13) run these cells.
PALLAS_MAX_BENCH_NNZ = 20_000


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


def _best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tensors", default=None, help="comma list of NAME@SCALE")
    ap.add_argument("--impls", default=None, help="comma list from {ref,pallas,sharded}")
    ap.add_argument("--rank", type=int, default=PAPER_RANK)
    ap.add_argument("--iters", type=int, default=3, help="CP-ALS sweeps per run")
    ap.add_argument("--restarts", type=int, default=8, help="vmap restart batch size")
    ap.add_argument("--fit-every", type=int, default=1, help="fused host-sync cadence")
    ap.add_argument("--repeats", type=int, default=3, help="warm timing repeats (best-of)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--quick",
        action="store_true",
        help=f"CI smoke: tensors {QUICK_TENSORS}, impls {QUICK_IMPLS}, 2 repeats",
    )
    ap.add_argument(
        "--backend",
        default=None,
        choices=("mosaic", "triton", "xla", "interpret"),
        help="pallas-path execution backend (default: the platform's "
        "compiled path — the XLA fallback on CPU; DESIGN.md §13)",
    )
    ap.add_argument("--out", default="BENCH_cp_als.json")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tensors = _parse_tensors(
        args.tensors or (QUICK_TENSORS if args.quick else DEFAULT_TENSORS)
    )
    impls = tuple(
        i.strip()
        for i in (args.impls or (QUICK_IMPLS if args.quick else DEFAULT_IMPLS)).split(",")
        if i.strip()
    )
    unknown = [i for i in impls if i not in ("ref", "pallas", "sharded")]
    if unknown:
        raise SystemExit(f"unknown impls {unknown}")
    repeats = 2 if args.quick else args.repeats
    pallas_backend = resolve_backend(args.backend)

    cells = []
    skipped = []
    t_start = time.perf_counter()
    for name, scale in tensors:
        tensor = make_frostt_like(name, scale=scale, seed=args.seed)
        for impl in impls:
            label = f"{name}@{scale:g}/{impl}"
            if (
                impl == "pallas"
                and pallas_backend == "interpret"
                and tensor.nnz > PALLAS_MAX_BENCH_NNZ
            ):
                reason = (
                    f"nnz={tensor.nnz} exceeds PALLAS_MAX_BENCH_NNZ="
                    f"{PALLAS_MAX_BENCH_NNZ} on the interpret backend "
                    "(emulation would dominate the comparison; compiled "
                    "backends run this cell)"
                )
                skipped.append({"tensor": f"{name}@{scale:g}", "impl": impl,
                                "reason": reason})
                print(f"--- {label}  SKIPPED: {reason}")
                continue
            print(f"--- {label}  (nnz={tensor.nnz}, dims={tensor.shape})")

            def eager():
                return cp_als(
                    tensor,
                    args.rank,
                    n_iters=args.iters,
                    tol=0.0,
                    seed=args.seed,
                    impl=impl,
                    backend=args.backend,
                )

            eager_state = eager()  # warmup: compile-cache the per-mode jits
            eager_s = _best_of(eager, repeats)

            executor = FusedCPALS(tensor, args.rank, impl=impl, backend=args.backend)
            t0 = time.perf_counter()
            fused_res = executor.run(
                n_iters=args.iters, tol=0.0, seed=args.seed, fit_every=args.fit_every
            )
            fused_cold_s = time.perf_counter() - t0

            def fused():
                return executor.run(
                    n_iters=args.iters, tol=0.0, seed=args.seed, fit_every=args.fit_every
                )

            fused_s = _best_of(fused, repeats)
            max_fit_delta = float(
                np.max(
                    np.abs(np.asarray(fused_res.state.fits) - np.asarray(eager_state.fits))
                )
            )

            # Multi-restart throughput: R concurrent decompositions per
            # compiled program (vmap over init seeds) vs R sequential runs.
            # Skipped only for pallas on the interpret backend: vmap
            # multiplies the per-tile emulation overhead, measuring the
            # emulator rather than the batching.  The compiled backends
            # (mosaic/triton/xla) batch natively and are timed.
            batched_s = throughput = batch_gain = None
            if impl != "pallas" or pallas_backend != "interpret":
                executor.run(
                    n_iters=args.iters, tol=0.0, seed=args.seed, restarts=args.restarts
                )  # warmup the batched program
                batched_s = _best_of(
                    lambda: executor.run(
                        n_iters=args.iters,
                        tol=0.0,
                        seed=args.seed,
                        restarts=args.restarts,
                    ),
                    repeats,
                )
                throughput = args.restarts / batched_s
                batch_gain = throughput * fused_s  # vs sequential fused singles

            cell = {
                "tensor": f"{name}@{scale:g}",
                "impl": impl,
                "dims": list(tensor.shape),
                "nnz": tensor.nnz,
                "rank": args.rank,
                "iters": args.iters,
                "eager_warm_s": eager_s,
                "fused_cold_s": fused_cold_s,
                "fused_warm_s": fused_s,
                "speedup": eager_s / fused_s,
                "max_fit_delta": max_fit_delta,
                "fit_ok": max_fit_delta <= FUSED_FIT_TOL,
                "faster": fused_s < eager_s,
                "restarts": args.restarts,
                "batched_warm_s": batched_s,
                "restart_throughput_per_s": throughput,
                "restart_batch_gain": batch_gain,
            }
            cells.append(cell)
            restart_note = (
                f"{args.restarts} restarts @ {throughput:.1f}/s "
                f"(batch gain {batch_gain:.2f}x)"
                if throughput is not None
                else "restart timing skipped (pallas interpret backend)"
            )
            print(
                f"    eager {eager_s*1e3:8.1f} ms | fused {fused_s*1e3:8.1f} ms "
                f"(cold {fused_cold_s*1e3:.1f}) | speedup {cell['speedup']:.2f}x | "
                f"max fit delta {max_fit_delta:.2e} | " + restart_note
            )

    if not cells:
        print("FAIL: every requested cell was skipped — nothing was measured")
        return 1
    all_faster = all(c["faster"] for c in cells)
    all_fit_ok = all(c["fit_ok"] for c in cells)
    payload = {
        "benchmark": "cp_als_fused",
        "config": {
            "tensors": [f"{n}@{s:g}" for n, s in tensors],
            "impls": list(impls),
            "rank": args.rank,
            "iters": args.iters,
            "restarts": args.restarts,
            "fit_every": args.fit_every,
            "repeats": repeats,
            "seed": args.seed,
            "backend": args.backend,
            "resolved_backend": pallas_backend,
        },
        "fit_tol": FUSED_FIT_TOL,
        "all_faster": all_faster,
        "all_fit_ok": all_fit_ok,
        "driver_wall_s": time.perf_counter() - t_start,
        "cells": cells,
        "skipped": skipped,
    }
    Path(args.out).write_text(json.dumps(payload, indent=2))
    print(f"\nwrote {args.out}")

    ok = True
    if not all_faster:
        slow = [c["tensor"] + "/" + c["impl"] for c in cells if not c["faster"]]
        print(f"FAIL: fused executor not strictly faster on: {slow}")
        ok = False
    if not all_fit_ok:
        bad = [c["tensor"] + "/" + c["impl"] for c in cells if not c["fit_ok"]]
        print(f"FAIL: fused fit trajectory out of FUSED_FIT_TOL={FUSED_FIT_TOL}: {bad}")
        ok = False
    if ok:
        print(
            f"gate OK: fused strictly faster on all {len(cells)} cells, "
            f"fit deltas within {FUSED_FIT_TOL}"
        )
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
