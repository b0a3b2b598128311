"""CP-ALS (Canonical Polyadic Decomposition via Alternating Least Squares).

The driver that makes spMTTKRP matter: each ALS sweep performs one MTTKRP
per mode (the paper's kernel under study) followed by a rank x rank
Hadamard-of-Grams solve.  Any of the MTTKRP impls (ref / pallas / sharded)
can back it, selected by ``impl=``.

Two execution modes share the per-mode update and fit math below:

  * the eager driver (this module) dispatches one MTTKRP per mode from
    Python and syncs the fit to the host every iteration — simple, and
    the instrumentation surface the experiment engine hooks into;
  * the fused executor (``repro.core.cp_als_fused``, DESIGN.md §11) runs
    whole sweeps as one jitted ``lax.scan`` with device-resident plans,
    syncing only at a configurable cadence; ``cp_als(..., fused=True)``
    selects it without changing this API.

Fit is computed the standard sparse way without materializing the residual:
    ||X - X_hat||^2 = ||X||^2 - 2<X, X_hat> + ||X_hat||^2
    ||X_hat||^2     = lambda^T (hadamard_k A_k^T A_k) lambda
    <X, X_hat>      = sum_r lambda_r * sum_nnz val * prod_k A_k[i_k, r]
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mttkrp import mttkrp, mttkrp_ref
from repro.core.sparse_tensor import SparseTensor

__all__ = ["CPState", "cp_als", "cp_init", "reconstruct_values"]


@dataclasses.dataclass
class CPState:
    factors: list[jax.Array]  # A_k: (I_k, R)
    weights: jax.Array  # lambda: (R,)
    fit: float
    fits: list[float]
    iters: int


def cp_init(tensor: SparseTensor, rank: int, *, seed: int = 0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), tensor.nmodes)
    return [
        jax.random.uniform(keys[k], (tensor.shape[k], rank), dtype=dtype)
        for k in range(tensor.nmodes)
    ]


def reconstruct_values(
    indices: jax.Array, factors: Sequence[jax.Array], weights: jax.Array
) -> jax.Array:
    """X_hat at the given coordinates."""
    rank = factors[0].shape[1]
    prod = jnp.ones((indices.shape[0], rank), factors[0].dtype)
    for k, f in enumerate(factors):
        prod = prod * jnp.take(f, indices[:, k], axis=0)
    return prod @ weights


def _fit(tensor_norm2, indices, values, factors, weights) -> jax.Array:
    with jax.named_scope("als_fit"):  # names the fit's device ops in a trace
        grams = [f.T @ f for f in factors]
        had = grams[0]
        for g in grams[1:]:
            had = had * g
        xhat_norm2 = weights @ had @ weights
        inner = values @ reconstruct_values(indices, factors, weights)
        resid2 = jnp.maximum(tensor_norm2 - 2.0 * inner + xhat_norm2, 0.0)
        # An all-zero tensor has ||X|| = 0; the historical sqrt(0)/sqrt(0)
        # produced a NaN fit that silently poisoned the convergence check.
        # Both `where` branches are evaluated, so the denominator must stay
        # nonzero on the dead branch.
        safe_norm2 = jnp.where(tensor_norm2 > 0.0, tensor_norm2, 1.0)
        fit = 1.0 - jnp.sqrt(resid2) / jnp.sqrt(safe_norm2)
        return jnp.where(tensor_norm2 > 0.0, fit, 0.0)


def _mode_update(
    factors: Sequence[jax.Array], weights: jax.Array, m: jax.Array, mode: int
) -> tuple[tuple[jax.Array, ...], jax.Array]:
    """One ALS mode update from the mode's MTTKRP result ``m``.

    Hadamard-of-Grams normal equations, ridge-stabilized solve, column
    normalization into the CP lambda.  Shared verbatim by the eager driver
    below and the fused executor (``repro.core.cp_als_fused``) so their
    trajectories differ only by XLA op scheduling, never by math.

    The solve runs in ``promote_types(m.dtype, float32)``: reduced-
    precision factor dtypes (bf16/fp16) have no LAPACK kernels and no
    business accumulating normal equations; fp32 inputs are bit-for-bit
    unchanged by the promotion.
    """
    with jax.named_scope("als_update"):  # names the update's device ops in a trace
        rank = m.shape[1]
        solve_dtype = jnp.promote_types(m.dtype, jnp.float32)
        had = jnp.ones((rank, rank), solve_dtype)
        for k in range(len(factors)):
            if k != mode:
                fk = factors[k].astype(solve_dtype)
                had = had * (fk.T @ fk)
        # Solve A_mode @ had = m  (had is SPD up to rank deficiency).
        a_new = jnp.linalg.solve(
            had + 1e-8 * jnp.eye(rank, dtype=solve_dtype), m.T.astype(solve_dtype)
        ).T
        # Column normalization -> weights (standard CP-ALS lambda).
        norms = jnp.maximum(jnp.linalg.norm(a_new, axis=0), 1e-12)
        out = list(factors)
        out[mode] = (a_new / norms).astype(factors[mode].dtype)
        return tuple(out), norms.astype(weights.dtype)


def cp_als(
    tensor: SparseTensor,
    rank: int,
    *,
    n_iters: int = 20,
    tol: float = 1e-5,
    seed: int = 0,
    impl: str = "ref",
    backend: str | None = None,
    mttkrp_fn: Callable | None = None,
    verbose: bool = False,
    dtype=jnp.float32,
    fused: bool = False,
    fit_every: int = 1,
    restarts: int = 1,
) -> CPState:
    """Alternating least squares for CPD.  Returns factors + fit trace.

    ``mttkrp_fn(tensor, factors, mode) -> (I_mode, R)`` overrides the impl
    (used by the distributed driver to inject the sharded path with its
    precomputed plans).

    ``backend`` selects the pallas-path execution backend (``"mosaic"``,
    ``"triton"``, ``"xla"``, ``"interpret"``; DESIGN.md §13).  Ignored for
    the other impls.

    ``dtype`` is the factor storage dtype (``cp_init``'s ``dtype=``,
    previously unreachable from here); values and the tensor norm are kept
    in ``promote_types(dtype, float32)`` so reduced-precision factors still
    accumulate the fit in at least fp32.

    ``fused=True`` delegates to the device-resident fused executor
    (``repro.core.cp_als_fused``, DESIGN.md §11): whole sweeps run as one
    jitted ``lax.scan``, the host syncs only every ``fit_every`` sweeps,
    and ``restarts > 1`` runs a vmap-batched multi-start returning the
    best-fit restart.  The returned ``CPState`` is API-identical.
    """
    if tensor.nnz == 0:
        raise ValueError(
            "cp_als requires a tensor with at least one nonzero "
            "(an empty tensor has no factorization and an undefined fit)"
        )
    if fused:
        if mttkrp_fn is not None:
            raise ValueError(
                "mttkrp_fn injection is an eager-driver hook; the fused "
                "executor owns its MTTKRP dispatch (use impl=)"
            )
        from repro.core.cp_als_fused import cp_als_fused

        return cp_als_fused(
            tensor,
            rank,
            n_iters=n_iters,
            tol=tol,
            seed=seed,
            impl=impl,
            backend=backend,
            dtype=dtype,
            fit_every=fit_every,
            restarts=restarts,
            verbose=verbose,
        ).state
    if restarts != 1:
        raise ValueError("restarts > 1 requires fused=True (vmap batching)")
    if fit_every != 1:
        raise ValueError(
            "fit_every requires fused=True (the eager driver syncs every "
            "iteration by construction)"
        )

    compute_dtype = jnp.promote_types(dtype, jnp.float32)
    factors = tuple(cp_init(tensor, rank, seed=seed, dtype=dtype))
    weights = jnp.ones((rank,), factors[0].dtype)
    indices = jnp.asarray(tensor.indices)
    values = jnp.asarray(tensor.values).astype(compute_dtype)
    tensor_norm2 = jnp.asarray(
        float((tensor.values.astype(np.float64) ** 2).sum()), dtype=compute_dtype
    )

    if mttkrp_fn is None:
        if impl == "ref":
            mttkrp_fn = lambda t, f, m: mttkrp_ref((indices, values, t.shape), f, m)
        else:
            impl_kwargs = {"backend": backend} if impl == "pallas" else {}
            mttkrp_fn = lambda t, f, m: mttkrp(t, f, m, impl=impl, **impl_kwargs)

    fits: list[float] = []
    fit_prev = -jnp.inf
    it = 0
    for it in range(1, n_iters + 1):
        for mode in range(tensor.nmodes):
            m = mttkrp_fn(tensor, factors, mode)  # (I_mode, R)
            factors, weights = _mode_update(factors, weights, m, mode)

        fit = float(_fit(tensor_norm2, indices, values, factors, weights))
        fits.append(fit)
        if verbose:
            print(f"  ALS iter {it:3d}  fit={fit:.6f}")
        if abs(fit - fit_prev) < tol:
            break
        fit_prev = fit

    return CPState(
        factors=list(factors), weights=weights, fit=fits[-1], fits=fits, iters=it
    )
