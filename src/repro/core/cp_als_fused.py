"""Fused, batched, device-resident CP-ALS executor (DESIGN.md §11).

The eager driver (``repro.core.cp_als``) dispatches one MTTKRP per mode
from Python and blocks on ``float(fit)`` every iteration — host overhead
the paper's accelerator never pays, and overhead the measured wall times
of the experiment engine therefore over-charge.  This executor removes
it:

  * **plan residency** — every per-mode ``MTTKRPPlan`` (pallas) /
    ``ShardedModeSetup`` (sharded) / ordered COO view (ref) is built once
    at construction and lives on device for all sweeps and restarts.
    The jitted sweep takes these device buffers as ARGUMENTS: captured
    arrays would be embedded in the program as constants, making the
    executable — and its compile-cache key — as large as the tensor;
  * **fused sweeps** — an entire ALS sweep (all modes' MTTKRP +
    Hadamard-of-Grams solve + column normalization) plus the in-graph fit
    runs as one jitted ``lax.scan`` over iterations.  The per-mode update
    loop unrolls at trace time: factor matrices have heterogeneous shapes
    ``(I_k, R)``, so a traced-index mode loop would force padding every
    factor to the largest mode — unrolling keeps the math identical to
    the eager driver (both call ``cp_als._mode_update`` / ``cp_als._fit``);
  * **sync cadence** — the host syncs fits only every ``fit_every``
    sweeps; convergence is checked against the in-graph fit trajectory at
    each sync point, so ``fit_every=1`` reproduces the eager driver's
    per-iteration early-stop exactly while larger cadences trade up to
    ``fit_every - 1`` extra sweeps for fewer device round-trips;
  * **batched multi-restart** — ``restarts > 1`` vmaps the whole sweep
    over independent ``cp_init`` seeds (one compiled program, factor
    batch leading axis) and returns the best-final-fit restart — the
    "many concurrent decompositions" serving scenario.

Fused and eager trajectories differ only by XLA op scheduling inside the
fused trace; ``FUSED_FIT_TOL`` is the documented float-summation
tolerance that equivalence tests and the ``BENCH_cp_als.json`` gate
enforce (tests/test_cp_als.py, scripts/run_cp_als.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.core.cp_als import CPState, _fit, _mode_update, cp_init
from repro.core.mttkrp import mttkrp_ref
from repro.core.sparse_tensor import SparseTensor

__all__ = [
    "FUSED_FIT_TOL",
    "BatchedCPState",
    "FusedCPALS",
    "MultiTensorCPALS",
    "cp_als_fused",
]

# Documented fused-vs-eager fit tolerance: same seeds, same math, but one
# fused XLA program may re-associate float summations the eager per-op
# dispatch kept separate.  Enforced by tests/test_cp_als.py and the
# BENCH_cp_als.json acceptance gate.
FUSED_FIT_TOL = 2e-3


@dataclasses.dataclass
class BatchedCPState:
    """Result of a fused (possibly multi-restart) CP-ALS run.

    ``state`` is the best-final-fit restart as a plain ``CPState`` (the
    eager driver's return type); ``fits`` keeps every restart's full
    trajectory, ``(restarts, iters)``.  ``sync_count`` is the number of
    device→host fit syncs the run performed — the eager driver pays one
    per iteration, this executor one per ``fit_every`` sweeps.
    """

    state: CPState
    best_restart: int
    seeds: tuple[int, ...]
    fits: np.ndarray  # (restarts, iters)
    sync_count: int

    @property
    def final_fits(self) -> tuple[float, ...]:
        return tuple(float(f) for f in self.fits[:, -1])


class FusedCPALS:
    """Device-resident CP-ALS executor for one (tensor, impl, ordering).

    Construction does all host-side work — plan builds, shard
    partitioning, buffer upload; ``run`` only launches compiled sweeps.
    Reuse one executor across runs (restarts, seeds, iteration budgets):
    the per-block-length jit cache and every device buffer are shared.
    """

    def __init__(
        self,
        tensor: SparseTensor,
        rank: int,
        *,
        impl: str = "ref",
        dtype=jnp.float32,
        tile_nnz: int = 256,
        rows_per_block: int = 256,
        ordering: str | None = None,
        scheme: str = "mode_ordered",
        interpret: bool | None = None,
        backend: str | None = None,
        autotune=None,
    ) -> None:
        # ``autotune`` is duck-typed (``config_for(tensor, rank) -> cfg``
        # with tile_nnz/rows_per_block/ordering fields — in practice
        # ``repro.dse.autotune.Autotuner``) so core never imports the DSE
        # package.  The tuned band winner overrides the plan geometry;
        # an explicitly-passed ``ordering`` still wins over the tuned one.
        if autotune is not None:
            cfg = autotune.config_for(tensor, rank)
            tile_nnz = int(cfg.tile_nnz)
            rows_per_block = int(cfg.rows_per_block)
            if ordering is None and cfg.ordering != "lex":
                ordering = cfg.ordering
        if tensor.nnz == 0:
            raise ValueError(
                "cp_als requires a tensor with at least one nonzero "
                "(an empty tensor has no factorization and an undefined fit)"
            )
        if impl not in ("ref", "pallas", "sharded"):
            raise ValueError(f"unknown impl {impl!r}")
        self.tensor = tensor
        self.rank = int(rank)
        self.impl = impl
        self.dtype = dtype
        self.ordering = ordering
        self.nmodes = tensor.nmodes
        compute_dtype = jnp.promote_types(dtype, jnp.float32)
        # Fit operands (raw COO order, exactly what the eager driver
        # uses), from the per-tensor device memo: executors and serving
        # buckets built over the same tensor re-upload nothing
        # (kernels/mttkrp/ops.tensor_device_operands, DESIGN.md §12).
        from repro.kernels.mttkrp.ops import tensor_device_operands

        ops = tensor_device_operands(tensor, dtype=compute_dtype)
        fit_operands = (ops.norm2, ops.indices, ops.values)
        self._sweep_cache: dict[tuple[int, bool], callable] = {}

        if impl == "ref":
            # Per-mode ordered COO views when a strategy is requested
            # (repro.reorder, DESIGN.md §10); one shared view otherwise.
            if ordering is not None:
                from repro.reorder import nonzero_order

                mode_operands = []
                for m in range(self.nmodes):
                    o = nonzero_order(
                        tensor, m, ordering, rows_per_block=rows_per_block
                    )
                    mode_operands.append(
                        (
                            jnp.asarray(tensor.indices[o]),
                            jnp.asarray(tensor.values[o]).astype(compute_dtype),
                        )
                    )
            else:
                mode_operands = [(ops.indices, ops.values)] * self.nmodes
        elif impl == "pallas":
            from repro.kernels.mttkrp.ops import (
                get_plan,
                plan_device_buffers,
                resolve_backend,
            )

            self._backend = resolve_backend(backend, interpret=interpret)
            self._plans = [
                get_plan(
                    tensor,
                    m,
                    tile_nnz=tile_nnz,
                    rows_per_block=rows_per_block,
                    ordering=ordering if ordering is not None else "lex",
                )
                for m in range(self.nmodes)
            ]
            # Upload once; every sweep of every restart reuses the buffers.
            mode_operands = [plan_device_buffers(p) for p in self._plans]
        else:  # sharded
            from repro.distributed.mttkrp_dist import (
                build_sharded_mode_setup,
                data_mesh,
            )

            self._axis = "data"
            self._mesh = data_mesh(self._axis)
            mode_operands = [
                build_sharded_mode_setup(
                    tensor,
                    m,
                    self._mesh,
                    axis=self._axis,
                    scheme=scheme,
                    ordering=ordering,
                    rows_per_block=rows_per_block,
                )
                for m in range(self.nmodes)
            ]
        # Every device buffer a sweep reads, passed to it as one pytree
        # argument (module docstring, plan residency).
        self.operands = (tuple(mode_operands), fit_operands)

    # -- device-side MTTKRP dispatch (called inside the jitted sweep) -------

    def _mttkrp(self, factors: Sequence[jax.Array], mode: int, operand) -> jax.Array:
        with jax.named_scope("mttkrp"):  # every impl, unpad and cast included
            if self.impl == "ref":
                idx_m, val_m = operand
                return mttkrp_ref((idx_m, val_m, self.tensor.shape), factors, mode)
            if self.impl == "pallas":
                from repro.kernels.mttkrp.ops import mttkrp_from_plan

                return mttkrp_from_plan(
                    self._plans[mode], factors, backend=self._backend, bufs=operand
                )
            from repro.distributed.mttkrp_dist import mttkrp_sharded_apply

            return mttkrp_sharded_apply(
                operand, factors, mesh=self._mesh, axis=self._axis
            )

    # -- fused sweep blocks --------------------------------------------------

    def sweep_fn(self, length: int, batched: bool):
        """Jitted ``length``-sweep block ``(operands, factors, weights) ->
        (factors, weights, fits)``, called with ``self.operands``; cached
        per (length, batched)."""
        key = (length, batched)
        fn = self._sweep_cache.get(key)
        if fn is not None:
            return fn

        def sweep(operands, factors, weights):
            mode_operands, fit_operands = operands

            def body(carry, _):
                factors, weights = carry
                for mode in range(self.nmodes):  # unrolled at trace time
                    # names each mode's device ops in a trace
                    with jax.named_scope(f"als_mode{mode}"):
                        m = self._mttkrp(factors, mode, mode_operands[mode])
                        factors, weights = _mode_update(factors, weights, m, mode)
                fit = _fit(*fit_operands, factors, weights)
                return (factors, weights), fit

            (factors, weights), fits = lax.scan(
                body, (factors, weights), None, length=length
            )
            return factors, weights, fits

        if batched:
            sweep = jax.vmap(sweep, in_axes=(None, 0, 0))
        fn = jax.jit(sweep)
        self._sweep_cache[key] = fn
        return fn

    # -- driver ---------------------------------------------------------------

    def run(
        self,
        *,
        n_iters: int = 20,
        tol: float = 1e-5,
        seed: int = 0,
        seeds: Sequence[int] | None = None,
        restarts: int = 1,
        fit_every: int = 1,
        verbose: bool = False,
    ) -> BatchedCPState:
        """Run CP-ALS; host sync only every ``fit_every`` sweeps.

        ``seeds`` (or ``seed + i`` for ``i < restarts``) select the
        ``cp_init`` draws; with more than one, the sweep is vmapped over
        the restart axis and the run stops early only when EVERY
        restart's fit delta falls below ``tol``.  Convergence is checked
        over the in-graph fit trajectory at each sync point; on a
        mid-block stop the returned fit trace is truncated at the
        converged iteration while factors are from the end of the last
        executed block (``fit_every=1`` matches the eager driver
        exactly, factors included).
        """
        if n_iters < 1:
            raise ValueError(f"n_iters must be >= 1, got {n_iters}")
        if fit_every < 1:
            raise ValueError(f"fit_every must be >= 1, got {fit_every}")
        if restarts < 1:
            raise ValueError(f"restarts must be >= 1, got {restarts}")
        if seeds is None:
            seeds = tuple(seed + i for i in range(restarts))
        seeds = tuple(int(s) for s in seeds)
        batched = len(seeds) > 1

        with TraceAnnotation(
            "cp_als.run", n_iters=n_iters, fit_every=fit_every, restarts=len(seeds)
        ):
            with TraceAnnotation("cp_als.init"):
                inits = [
                    cp_init(self.tensor, self.rank, seed=s, dtype=self.dtype)
                    for s in seeds
                ]
                if batched:
                    factors = tuple(
                        jnp.stack([init[k] for init in inits])
                        for k in range(self.nmodes)
                    )
                    weights = jnp.ones((len(seeds), self.rank), factors[0].dtype)
                else:
                    factors = tuple(inits[0])
                    weights = jnp.ones((self.rank,), factors[0].dtype)

            fit_cols: list[np.ndarray] = []  # one (restarts,) column per iteration
            fit_prev = np.full((len(seeds),), -np.inf)
            it = 0
            syncs = 0
            converged = False
            while it < n_iters and not converged:
                block = min(fit_every, n_iters - it)
                # ``new_program`` is 1 on the block that builds the program
                # for its length: a count of programs built, read off the trace.
                new_program = int((block, batched) not in self._sweep_cache)
                with TraceAnnotation(
                    "cp_als.block", sweeps=block, new_program=new_program
                ):
                    factors, weights, fits = self.sweep_fn(block, batched)(
                        self.operands, factors, weights
                    )
                with TraceAnnotation("cp_als.fit_sync"):
                    # The ONLY device→host sync of the block.
                    block_fits = np.asarray(
                        jax.block_until_ready(fits), dtype=np.float64
                    )
                    syncs += 1
                    cols = block_fits if batched else block_fits[None, :]  # (R, block)
                    for j in range(cols.shape[1]):
                        it += 1
                        fit_cols.append(cols[:, j])
                        if verbose:
                            shown = ", ".join(f"{f:.6f}" for f in cols[:, j])
                            print(f"  fused ALS iter {it:3d}  fit=[{shown}]")
                        if np.all(np.abs(cols[:, j] - fit_prev) < tol):
                            converged = True
                            fit_prev = cols[:, j]
                            break
                        fit_prev = cols[:, j]

            fits_mat = np.stack(fit_cols, axis=1)  # (restarts, iters)
            best = int(np.argmax(fits_mat[:, -1]))
            if batched:
                best_factors = [f[best] for f in factors]
                best_weights = weights[best]
            else:
                best_factors = list(factors)
                best_weights = weights
            state = CPState(
                factors=best_factors,
                weights=best_weights,
                fit=float(fits_mat[best, -1]),
                fits=[float(f) for f in fits_mat[best]],
                iters=it,
            )
            return BatchedCPState(
                state=state,
                best_restart=best,
                seeds=seeds,
                fits=fits_mat,
                sync_count=syncs,
            )


@functools.lru_cache(maxsize=128)
def _multi_tensor_sweep(shape: tuple[int, ...], length: int):
    """Jitted multi-tensor fused sweep program for one padded geometry.

    The FusedCPALS sweep vmapped over a batch of DISTINCT tensors: the
    COO operands (indices, values, norm2) join the factors as batched
    arguments instead of captured constants.  Cached at module level by
    (padded shape, sweep length) — every service instance, bucket and
    test that shares a geometry shares one jit wrapper and therefore one
    XLA compile cache entry per (batch, nnz_pad, rank) shape
    (repro.serve, DESIGN.md §12).
    """
    nmodes = len(shape)

    def sweep(indices, values, norm2, factors, weights):
        def body(carry, _):
            factors, weights = carry
            for mode in range(nmodes):  # unrolled at trace time
                m = mttkrp_ref((indices, values, shape), factors, mode)
                factors, weights = _mode_update(factors, weights, m, mode)
            fit = _fit(norm2, indices, values, factors, weights)
            return (factors, weights), fit

        (factors, weights), fits = lax.scan(
            body, (factors, weights), None, length=length
        )
        return factors, weights, fits

    return jax.jit(jax.vmap(sweep))


class MultiTensorCPALS:
    """Fused CP-ALS over a batch of DISTINCT tensors with one geometry.

    ``FusedCPALS`` batches restarts of ONE tensor (its operands are
    shared, unbatched arguments); this executor batches *different*
    tensors that share a padded geometry — the multi-tenant serving case
    (repro.serve, DESIGN.md §12).  All tensors in a batch must be padded to the same
    ``(shape, nnz_pad)`` and their factors to the same rank; zero-row /
    zero-column / zero-value padding is exactly result-preserving (the
    parity argument is spelled out in DESIGN.md §12 and enforced by
    tests/test_serve.py against standalone ``cp_als(..., fused=True)``).

    Ref-impl math only: the pallas/sharded paths build per-tensor plans
    and partitions, which cannot be batched across distinct tensors.
    """

    def __init__(self, shape: Sequence[int], *, nnz_pad: int, rank: int) -> None:
        if nnz_pad < 1:
            raise ValueError(f"nnz_pad must be >= 1, got {nnz_pad}")
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        self.shape = tuple(int(s) for s in shape)
        self.nmodes = len(self.shape)
        self.nnz_pad = int(nnz_pad)
        self.rank = int(rank)

    def run_batch(
        self,
        indices: jax.Array,  # (B, nnz_pad, nmodes) int32
        values: jax.Array,  # (B, nnz_pad)
        norm2: jax.Array,  # (B,)
        factors: Sequence[jax.Array],  # per mode: (B, I_k_pad, rank)
        *,
        n_iters: int,
    ) -> tuple[tuple[jax.Array, ...], jax.Array, jax.Array]:
        """Run ``n_iters`` fused sweeps on every tensor in the batch.

        Returns ``(factors, weights, fits)`` with ``fits`` of shape
        ``(B, n_iters)``.  Dispatch is asynchronous — nothing blocks
        until the caller reads a result, which is what lets the service
        keep multiple batches in flight (DESIGN.md §12).
        """
        if n_iters < 1:
            raise ValueError(f"n_iters must be >= 1, got {n_iters}")
        if indices.shape[1:] != (self.nnz_pad, self.nmodes):
            raise ValueError(
                f"indices shape {indices.shape} does not match geometry "
                f"(B, {self.nnz_pad}, {self.nmodes})"
            )
        for k, f in enumerate(factors):
            if f.shape[1:] != (self.shape[k], self.rank):
                raise ValueError(
                    f"factor {k} shape {f.shape} does not match geometry "
                    f"(B, {self.shape[k]}, {self.rank})"
                )
        weights = jnp.ones((indices.shape[0], self.rank), factors[0].dtype)
        return _multi_tensor_sweep(self.shape, int(n_iters))(
            indices, values, norm2, tuple(factors), weights
        )


def cp_als_fused(
    tensor: SparseTensor,
    rank: int,
    *,
    n_iters: int = 20,
    tol: float = 1e-5,
    seed: int = 0,
    seeds: Sequence[int] | None = None,
    restarts: int = 1,
    fit_every: int = 1,
    impl: str = "ref",
    dtype=jnp.float32,
    tile_nnz: int = 256,
    rows_per_block: int = 256,
    ordering: str | None = None,
    scheme: str = "mode_ordered",
    interpret: bool | None = None,
    backend: str | None = None,
    autotune=None,
    verbose: bool = False,
) -> BatchedCPState:
    """One-shot fused CP-ALS (build the executor, run once).

    ``cp_als(..., fused=True)`` wraps this and returns ``.state``; call
    this directly (or hold a ``FusedCPALS``) for restart batching,
    per-restart trajectories, and executor reuse across runs.
    """
    executor = FusedCPALS(
        tensor,
        rank,
        impl=impl,
        dtype=dtype,
        tile_nnz=tile_nnz,
        rows_per_block=rows_per_block,
        ordering=ordering,
        scheme=scheme,
        interpret=interpret,
        backend=backend,
        autotune=autotune,
    )
    return executor.run(
        n_iters=n_iters,
        tol=tol,
        seed=seed,
        seeds=seeds,
        restarts=restarts,
        fit_every=fit_every,
        verbose=verbose,
    )
