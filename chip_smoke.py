#!/usr/bin/env python3
"""Smoke test of the main path on a TPU: fused CP-ALS on the Mosaic kernel.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # sharded path only, on four chips

One chip runs three phases:

  1. device  — JAX must report a TPU; there is no CPU fallback;
  2. single job at the NELL-2 deployment (FROSTT dims and skew, nnz cut
     to fit one chip): ``cp_als(..., impl="pallas", fused=True)`` must
     resolve to the Mosaic kernel and compile it into the sweep; each
     mode's kernel MTTKRP is checked against ``mttkrp_ref`` and the fit
     trajectory against the eager ``impl="ref"`` driver;
  3. service — a few synthetic requests through ``DecompositionService``,
     audited against standalone fused runs.

``--four-chips`` runs only ``cp_als(..., impl="sharded", fused=True)``
over ``jax.devices()`` and a per-mode check of the sharded MTTKRP
against ``mttkrp_ref``, asserting that every device holds a shard of the
partitioned operands and of the output.

Every number goes on a line before the last.  The last line of stdout is
one JSON object naming the device; any failed check raises, so the script
exits non-zero and prints no such line.  Timings are smoke timings from
one run, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# The NELL-2 deployment (repro.data.frostt) at its published dims, skew
# and the paper's rank.  Only nnz is cut: the kernel path stages an
# (N-1, nnz_pad, 128) f32 gather, ~4 GB here, where the published 76.9 M
# nonzeros would need ~79 GB (DESIGN.md §13).
NNZ_DRAWN = 4_000_000  # before duplicate coordinates coalesce
SWEEPS = 5
SEED = 0
SERVICE_REQUESTS = 8
MTTKRP_REL_TOL = 1e-4
MAX_PROGRAM_TEXT = 4 << 20  # a sweep that embedded the tensor would be ~100s of MB


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel_err(got, want) -> float:
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def peak_bytes_in_use(devices) -> int:
    return max(d.memory_stats()["peak_bytes_in_use"] for d in devices)


def device_phase(chips: int) -> dict:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU found (JAX reports platform "
            f"{devices[0].platform!r}); this script does not fall back to the CPU"
        )
    if len(devices) != chips:
        sys.exit(f"chip_smoke: expected {chips} TPU device(s), JAX reports {len(devices)}")
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def nell2_tensor():
    from repro.core.sparse_tensor import random_sparse_tensor
    from repro.data.frostt import FROSTT_TENSORS, PAPER_RANK

    ft = FROSTT_TENSORS["NELL-2"]
    t0 = time.perf_counter()
    tensor = random_sparse_tensor(ft.dims, nnz=NNZ_DRAWN, seed=SEED, zipf_a=ft.zipf_alpha)
    print(f"[nell2] dims={ft.dims} zipf_a={ft.zipf_alpha} nnz={tensor.nnz} "
          f"(cut to {tensor.nnz / ft.nnz:.2%} of the published {ft.nnz}; dims, "
          f"skew and rank={PAPER_RANK} unchanged) gen_s={time.perf_counter() - t0:.3f}")
    return tensor, PAPER_RANK


def single_job_phase() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cp_als import cp_als, cp_init
    from repro.core.cp_als_fused import FUSED_FIT_TOL, FusedCPALS
    from repro.core.mttkrp import mttkrp_ref
    from repro.kernels.mttkrp.ops import get_plan, mttkrp_from_plan, resolve_backend

    backend = resolve_backend()
    print(f"[single] resolved backend={backend}")
    check(backend == "mosaic", f"the pallas path resolved to {backend!r}, not 'mosaic'")
    tensor, rank = nell2_tensor()

    t0 = time.perf_counter()
    executor = FusedCPALS(tensor, rank, impl="pallas")
    setup_s = time.perf_counter() - t0
    plans = [get_plan(tensor, mode) for mode in range(tensor.nmodes)]  # memoized
    factors = tuple(cp_init(tensor, rank, seed=SEED))
    weights = jnp.ones((rank,), factors[0].dtype)
    t0 = time.perf_counter()
    lowered = executor.sweep_fn(1, False).lower(executor.operands, factors, weights)
    program_bytes = len(lowered.as_text())
    hlo = lowered.compile().as_text()
    compile_s = time.perf_counter() - t0
    tiles = [p.num_tiles for p in plans]
    print(f"[single] tiles per mode={tiles} stablehlo_bytes={program_bytes} "
          f"tpu_custom_call={'tpu_custom_call' in hlo}")
    check("tpu_custom_call" in hlo, "the compiled sweep holds no Mosaic kernel")
    check(program_bytes < MAX_PROGRAM_TEXT,
          f"sweep program is {program_bytes} bytes: tensor data embedded as constants?")

    t0 = time.perf_counter()
    fused = cp_als(tensor, rank, n_iters=SWEEPS, tol=0.0, seed=SEED, impl="pallas", fused=True)
    first_s = time.perf_counter() - t0
    check(fused.iters == SWEEPS and np.all(np.isfinite(fused.fits)),
          f"fused run: iters={fused.iters} fits={fused.fits}")
    executor.run(n_iters=SWEEPS, tol=0.0, seed=SEED)  # compiles this executor's sweep
    t0 = time.perf_counter()
    executor.run(n_iters=SWEEPS, tol=0.0, seed=SEED)
    sweeps_s = time.perf_counter() - t0
    print(f"[single] smoke timing (one run, not a benchmark): "
          f"plan_build_upload_s={setup_s:.3f} compile_s={compile_s:.3f} "
          f"cp_als_first_call_s={first_s:.3f} warm_run_s={sweeps_s:.3f} "
          f"per_sweep_s={sweeps_s / SWEEPS:.4f}")

    for mode, plan in enumerate(plans):
        got = mttkrp_from_plan(plan, fused.factors, backend=backend)
        want = mttkrp_ref(tensor, fused.factors, mode)
        err = rel_err(got, want)
        print(f"[single] mode {mode} kernel vs mttkrp_ref: max|got-want|/max|want|="
              f"{err:.3e} (tol {MTTKRP_REL_TOL:g})")
        check(err <= MTTKRP_REL_TOL, f"mode {mode} kernel MTTKRP off by {err:.3e}")

    t0 = time.perf_counter()
    eager = cp_als(tensor, rank, n_iters=SWEEPS, tol=0.0, seed=SEED, impl="ref")
    eager_s = time.perf_counter() - t0
    delta = float(np.max(np.abs(np.asarray(fused.fits) - np.asarray(eager.fits))))
    print(f"[single] fits fused/pallas={['%.6f' % f for f in fused.fits]} "
          f"eager/ref={['%.6f' % f for f in eager.fits]}")
    print(f"[single] max fit delta {delta:.3e} (FUSED_FIT_TOL {FUSED_FIT_TOL}) "
          f"eager_ref_s={eager_s:.3f}")
    check(delta <= FUSED_FIT_TOL, f"fused-vs-eager fit delta {delta:.3e}")
    print(f"[single] peak_bytes_in_use={peak_bytes_in_use(jax.devices())}")


def service_phase() -> None:
    import numpy as np

    from repro.core.cp_als import cp_als
    from repro.core.cp_als_fused import FUSED_FIT_TOL
    from repro.serve import DecompositionService, TrafficConfig, synthetic_trace

    trace = synthetic_trace(TrafficConfig(n_requests=SERVICE_REQUESTS, seed=SEED))
    service = DecompositionService()
    t0 = time.perf_counter()
    for _, req in trace:
        check(service.submit(req), f"service rejected {req.request_id}")
    completed = service.run_until_drained()
    drain_s = time.perf_counter() - t0
    print(f"[service] completed {len(completed)}/{len(trace)} requests "
          f"drain_s={drain_s:.3f} (smoke timing)")
    check(len(completed) == len(trace), "service did not answer every request")
    max_delta = 0.0
    for _, req in trace:
        ref = cp_als(req.tensor, req.rank, n_iters=req.n_iters, tol=0.0, seed=req.seed,
                     fused=True)
        got = completed[req.request_id].state
        max_delta = max(max_delta, float(np.max(np.abs(
            np.asarray(got.fits) - np.asarray(ref.fits)))))
    print(f"[service] parity vs standalone fused: max fit delta {max_delta:.3e} "
          f"(tol {FUSED_FIT_TOL})")
    check(max_delta <= FUSED_FIT_TOL, f"served fits differ by {max_delta:.3e}")


def spans_devices(arr, devices) -> bool:
    """Every device holds a proper part (not a full copy) of ``arr``."""
    shards = arr.addressable_shards
    return (
        {s.device for s in shards} == set(devices)
        and all(s.data.shape[0] < arr.shape[0] for s in shards)
    )


def four_chips_phase() -> None:
    import jax
    import numpy as np

    from repro.core.cp_als import cp_als
    from repro.core.cp_als_fused import FusedCPALS
    from repro.core.mttkrp import mttkrp_ref
    from repro.distributed.mttkrp_dist import data_mesh, mttkrp_sharded_apply

    devices = jax.devices()
    tensor, rank = nell2_tensor()
    t0 = time.perf_counter()
    fused = cp_als(tensor, rank, n_iters=SWEEPS, tol=0.0, seed=SEED, impl="sharded", fused=True)
    jax.block_until_ready(fused.factors)
    run_s = time.perf_counter() - t0
    print(f"[sharded] fits={['%.6f' % f for f in fused.fits]} "
          f"run_s={run_s:.3f} (smoke timing, compile included)")
    check(fused.iters == SWEEPS and np.all(np.isfinite(fused.fits)),
          f"sharded run: iters={fused.iters} fits={fused.fits}")

    mesh = data_mesh()
    setups = FusedCPALS(tensor, rank, impl="sharded").operands[0]
    for mode, setup in enumerate(setups):
        for name in ("idx", "val", "row_start"):
            check(spans_devices(getattr(setup, name), devices),
                  f"mode {mode} operand {name} is not split over all {len(devices)} devices")
        got = mttkrp_sharded_apply(setup, fused.factors, mesh=mesh)
        check(spans_devices(got, devices),
              f"mode {mode} output is not split over all {len(devices)} devices "
              f"({got.sharding})")
        err = rel_err(got, mttkrp_ref(tensor, fused.factors, mode))
        print(f"[sharded] mode {mode}: shards on {len(devices)} devices, output "
              f"{got.sharding.spec}; vs mttkrp_ref max|got-want|/max|want|={err:.3e} "
              f"(tol {MTTKRP_REL_TOL:g})")
        check(err <= MTTKRP_REL_TOL, f"mode {mode} sharded MTTKRP off by {err:.3e}")
    print(f"[sharded] max peak_bytes_in_use over devices={peak_bytes_in_use(devices)}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded fused CP-ALS path on four chips")
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
        sys.exit("chip_smoke: REPRO_PALLAS_INTERPRET is set; unset it so the "
                 "kernel runs compiled on the chip")
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: the repro package is not under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    device = device_phase(4 if args.four_chips else 1)
    if args.four_chips:
        four_chips_phase()
    else:
        single_job_phase()
        service_phase()
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
