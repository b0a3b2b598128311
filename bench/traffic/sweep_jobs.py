"""Back-to-back single CP-ALS jobs on one large tensor (``FusedCPALS``).

Set-up draws the tensor, builds the executor once (plans and upload) and
runs one short job so that every program of the window is compiled or
loaded.  The window runs whole jobs, each from a fresh initialisation
seed, until ``ctx.seconds`` have passed; it ends at a
``block_until_ready`` on the last job's factors.

Parameters (the cell's ``params``): ``n_iters``, ``fit_every``.
Configuration keys: ``dims``, ``nnz``, ``zipf_a``, ``structure_seed``,
``rank``, ``impl``, ``tile_nnz``, ``rows_per_block``.

After the window, ``check`` compares the last job with the reference
(see its docstring).

The coordinates come from ``structure_seed`` and the values from the
run's seed: the plans, and so every compiled shape, are the same for
every seed, while each seed brings its own data and initialisations.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench import generators, reference
from bench.harness import Window

WARM_ITERS = 2


def setup(ctx):
    import jax

    from repro.core.cp_als_fused import FusedCPALS
    from repro.core.sparse_tensor import SparseTensor

    cfg = ctx.config
    rng = np.random.default_rng(ctx.seed)
    t0 = time.perf_counter()
    idx, _ = generators.zipf_tensor(cfg["dims"], cfg["nnz"], cfg["zipf_a"], cfg["structure_seed"])
    vals = rng.standard_normal(idx.shape[0]).astype(np.float32)
    tensor = SparseTensor(idx, vals, tuple(cfg["dims"]))
    ctx.say(f"tensor dims={tensor.shape} nnz={tensor.nnz} draw_s={time.perf_counter() - t0!r}")
    t0 = time.perf_counter()
    executor = FusedCPALS(tensor, cfg["rank"], impl=cfg["impl"], tile_nnz=cfg["tile_nnz"],
                          rows_per_block=cfg["rows_per_block"])
    jax.block_until_ready(executor.operands)
    ctx.say(f"executor build and upload_s={time.perf_counter() - t0!r}")
    t0 = time.perf_counter()
    warm = executor.run(n_iters=WARM_ITERS, fit_every=ctx.params["fit_every"], tol=0.0,
                        seed=int(rng.integers(generators.SEED_LIMIT)))
    jax.block_until_ready(warm.state.factors)
    ctx.say(f"warm job_s={time.perf_counter() - t0!r}")
    return {"tensor": tensor, "executor": executor, "rng": rng}


def window(ctx, state):
    import jax

    executor, rng = state["executor"], state["rng"]
    n_iters = ctx.params["n_iters"]
    jobs = bad = 0
    t0 = time.perf_counter()
    while True:
        seed = int(rng.integers(generators.SEED_LIMIT))
        with ctx.span("job"):
            res = executor.run(n_iters=n_iters, fit_every=ctx.params["fit_every"], tol=0.0,
                               seed=seed)
        with ctx.span("block_until_ready"):
            jax.block_until_ready((res.state.factors, res.state.weights))
        jobs += 1
        bad += int(res.state.iters != n_iters or not np.all(np.isfinite(res.state.fits)))
        elapsed = time.perf_counter() - t0
        if elapsed >= ctx.seconds:
            break
    sweeps = jobs * n_iters
    ctx.say(f"jobs={jobs} sweeps={sweeps} window_s={elapsed!r}")
    t = state["tensor"]
    return Window(
        attempted=jobs, failed=bad,
        metrics={"sweep_ms": elapsed / sweeps * 1e3},
        record={"sweeps": sweeps, "dims": t.shape, "nnz": t.nnz, "rank": ctx.config["rank"],
                "last": (seed, res.state)},
    )


def _reference(ctx, tensor, seed, contract, n_iters):
    """The float32 reference on the chip at ``contract``'s precision:
    ``n_iters`` sweeps from the job's initialisation."""
    import jax
    import jax.numpy as jnp

    ar = reference.jax_arrays(contract)
    step = jax.jit(lambda n2, i, v, f, w: reference.sweep(ar, n2, i, v, f, w, contract))
    rank = ctx.config["rank"]
    with jax.default_matmul_precision(reference.MATMUL_PRECISION[contract]):
        idx, vals = jnp.asarray(tensor.indices), jnp.asarray(tensor.values)
        norm2 = jnp.sum(vals * vals)
        factors = [jnp.asarray(f) for f in reference.init_factors(tensor.shape, rank, seed)]
        weights = jnp.ones((rank,), jnp.float32)
        fits = []
        for _ in range(n_iters):
            factors, weights, f = step(norm2, idx, vals, factors, weights)
            fits.append(f)
        return [np.asarray(f) for f in factors], np.asarray(weights), np.asarray(fits)


def check(ctx, state, win, contract="exact"):
    """The window's last job against the reference from its seed.

    ``fit_gap`` and ``weight_gap`` compare the whole job (every sweep's
    fit and the final weights) with the float32 reference.  ``row_gap``
    compares its first sweep's mode-0 factor, as the window's own executor
    gives it again from the job's seed, with a float64 host update
    (``reference.row_gap``): there each row's error comes from the
    MTTKRP's products and sums alone, so a contraction in fewer bf16
    passes shows (the job's later sweeps mix every row's error into every
    other row).
    """
    seed, got = win.record["last"]
    tensor = state["tensor"]
    n_iters = ctx.params["n_iters"]
    if "first" not in state:
        first = state["executor"].run(n_iters=1, fit_every=ctx.params["fit_every"], tol=0.0,
                                      seed=seed)
        state["first"] = np.asarray(first.state.factors[0])
        state["executor"] = None  # the program's state goes before the reference runs
        del first
        gc.collect()
    t0 = time.perf_counter()
    if "want" not in state:
        state["want"] = _reference(ctx, tensor, seed, "exact", n_iters)
        state["first_want"] = reference.first_update_numpy(
            tensor.indices, tensor.values, tensor.shape, ctx.config["rank"], seed)
    if contract != "exact":
        _, got_w, got_fits = _reference(ctx, tensor, seed, contract, n_iters)
        first = _reference(ctx, tensor, seed, contract, 1)[0][0]
    else:
        got_w, got_fits = np.asarray(got.weights), np.asarray(got.fits)
        first = state["first"]
    readings = reference.gaps(got_w, got_fits, *state["want"][1:])
    readings["row_gap"] = reference.row_gap(first, state["first_want"])
    ctx.say(f"reference ({contract}) s={time.perf_counter() - t0!r} readings={readings}")
    return readings
