"""``sweep_jobs`` on a tensor whose coordinates span more than an int64
key: back-to-back single CP-ALS jobs (``FusedCPALS``) on one large
tensor of any number of modes.

The same traffic, window and check as ``sweep_jobs`` (imported, not
copied); only the draw differs: ``draws.zipf_tensor_rows`` coalesces
duplicates on the coordinate rows, so a tensor such as LBNL-network
(five modes, ~3.9e19 coordinates) can be drawn at all.  Parameters and
configuration keys are ``sweep_jobs``'s.
"""

from __future__ import annotations

import time

import numpy as np

from bench import draws, generators
from bench.traffic import sweep_jobs

window, check = sweep_jobs.window, sweep_jobs.check


def setup(ctx):
    """``sweep_jobs.setup`` with ``draws.zipf_tensor_rows`` for the draw."""
    import jax

    from repro.core.cp_als_fused import FusedCPALS
    from repro.core.sparse_tensor import SparseTensor

    cfg = ctx.config
    rng = np.random.default_rng(ctx.seed)
    t0 = time.perf_counter()
    idx, _ = draws.zipf_tensor_rows(cfg["dims"], cfg["nnz"], cfg["zipf_a"],
                                    cfg["structure_seed"])
    vals = rng.standard_normal(idx.shape[0]).astype(np.float32)
    tensor = SparseTensor(idx, vals, tuple(cfg["dims"]))
    ctx.say(f"tensor dims={tensor.shape} nnz={tensor.nnz} draw_s={time.perf_counter() - t0!r}")
    t0 = time.perf_counter()
    executor = FusedCPALS(tensor, cfg["rank"], impl=cfg["impl"], tile_nnz=cfg["tile_nnz"],
                          rows_per_block=cfg["rows_per_block"])
    jax.block_until_ready(executor.operands)
    ctx.say(f"executor build and upload_s={time.perf_counter() - t0!r}")
    t0 = time.perf_counter()
    warm = executor.run(n_iters=sweep_jobs.WARM_ITERS, fit_every=ctx.params["fit_every"], tol=0.0,
                        seed=int(rng.integers(generators.SEED_LIMIT)))
    jax.block_until_ready(warm.state.factors)
    ctx.say(f"warm job_s={time.perf_counter() - t0!r}")
    return {"tensor": tensor, "executor": executor, "rng": rng}
