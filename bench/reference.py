"""Plain CP-ALS reference: MTTKRP, the ALS mode update and the fit.

Written from the textbook method (Kolda & Bader, "Tensor Decompositions
and Applications", §3.4) and independent of the program under test.  The
same code runs on two array modules:

* ``jax.numpy`` in float32 on the chip, after the measured window (the
  whole job, and the controls);
* ``numpy`` in float64 on the host (the first sweep's mode-0 update).

``contract`` sets the precision of the reference: ``"exact"`` is the
reference itself, float32 at ``precision="highest"``; ``"bf16x3"`` keeps
the three largest of the four bf16 partial products of each MTTKRP
product and runs the matrix products at ``Precision.HIGH`` (three bf16
passes); ``"bfloat16"`` rounds each nonzero's value and its Hadamard row
to bfloat16 before they are multiplied (a one-pass bf16 MXU contraction
with float32 accumulation) and runs the matrix products at the default
one-pass precision.  The last two are the controls that the comparison
must refuse.

The initial factors follow the documented CP-ALS initialisation of the
system: ``jax.random.uniform`` over ``[0, 1)`` for mode ``k`` with the
``k``-th key of ``jax.random.split(jax.random.PRNGKey(seed), N)``.  The
mode update solves the Hadamard-of-Grams normal equations with a ridge of
``RIDGE`` and normalises columns into the weights (``λ``).
"""

from __future__ import annotations

import numpy as np

RIDGE = 1e-8
NORM_FLOOR = 1e-12
CONTRACTS = ("exact", "bfloat16", "bf16x3")
# jax.default_matmul_precision of each contract's matrix products.
MATMUL_PRECISION = {"exact": "highest", "bf16x3": "high", "bfloat16": "default"}


def init_factors(dims, rank: int, seed: int) -> list[np.ndarray]:
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), len(dims))
    return [np.asarray(jax.random.uniform(keys[k], (d, rank), dtype=np.float32))
            for k, d in enumerate(dims)]


def _bf16(x):
    """Round to the nearest bfloat16 (ties to even), kept in x's dtype.

    Done on the bits, since a compiler that allows excess precision may
    drop a float32 → bfloat16 → float32 round trip.
    """
    if isinstance(x, np.ndarray):
        u = x.astype(np.float32).view(np.uint32)
        u = (u + np.uint32(0x7FFF) + ((u >> 16) & 1)) & np.uint32(0xFFFF0000)
        return u.view(np.float32).astype(x.dtype)
    import jax.numpy as jnp
    from jax import lax

    u = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = (u + jnp.uint32(0x7FFF) + ((u >> 16) & 1)) & jnp.uint32(0xFFFF0000)
    return lax.bitcast_convert_type(u, jnp.float32).astype(x.dtype)


def _products(values, hadamard, contract: str):
    """values[:, None] * hadamard at the given contraction precision."""
    v = values[:, None]
    if contract == "exact":
        return v * hadamard
    v_hi, h_hi = _bf16(v), _bf16(hadamard)
    if contract == "bfloat16":
        return v_hi * h_hi
    if contract == "bf16x3":
        v_lo, h_lo = _bf16(v - v_hi), _bf16(hadamard - h_hi)
        return v_hi * h_hi + v_hi * h_lo + v_lo * h_hi
    raise ValueError(f"unknown contract {contract!r}; expected one of {CONTRACTS}")


class Arrays:
    """The few operations where numpy and jax.numpy differ."""

    def __init__(self, xp, segment_sum, matmul):
        self.xp, self.segment_sum, self.matmul = xp, segment_sum, matmul


def numpy_arrays() -> Arrays:
    def segment_sum(data, ids, n):
        return np.stack([np.bincount(ids, weights=data[:, r], minlength=n)
                         for r in range(data.shape[1])], axis=1)

    return Arrays(np, segment_sum, np.matmul)


def jax_arrays(contract: str = "exact") -> Arrays:
    """jax.numpy, with matrix products at the contract's precision (use it
    inside ``jax.default_matmul_precision(MATMUL_PRECISION[contract])``,
    which also sets the solve's)."""
    import jax
    import jax.numpy as jnp

    precision = {"highest": jax.lax.Precision.HIGHEST, "high": jax.lax.Precision.HIGH,
                 "default": jax.lax.Precision.DEFAULT}[MATMUL_PRECISION[contract]]

    def segment_sum(data, ids, n):
        return jax.ops.segment_sum(data, ids, num_segments=n)

    def matmul(a, b):
        return jnp.matmul(a, b, precision=precision)

    return Arrays(jnp, segment_sum, matmul)


def mttkrp(ar: Arrays, indices, values, factors, mode: int, contract: str = "exact"):
    """M[i, r] = Σ_{nonzeros with i_mode = i} x · Π_{k≠mode} A_k[i_k, r]."""
    had = None
    for k, f in enumerate(factors):
        if k != mode:
            rows = ar.xp.take(f, indices[:, k], axis=0)
            had = rows if had is None else had * rows
    prod = _products(values, had, contract)
    return ar.segment_sum(prod, indices[:, mode], factors[mode].shape[0])


def fit(ar: Arrays, norm2, indices, values, factors, weights):
    """1 − ‖X − X̂‖/‖X‖, with ‖X̂‖² from the Grams and ⟨X, X̂⟩ over the nonzeros."""
    xp = ar.xp
    had = None
    for f in factors:
        g = ar.matmul(f.T, f)
        had = g if had is None else had * g
    xhat2 = ar.matmul(ar.matmul(weights[None, :], had), weights[:, None])[0, 0]
    rows = None
    for k, f in enumerate(factors):
        r = xp.take(f, indices[:, k], axis=0)
        rows = r if rows is None else rows * r
    inner = xp.sum(values * ar.matmul(rows, weights[:, None])[:, 0])
    resid2 = xp.maximum(norm2 - 2.0 * inner + xhat2, 0.0)
    return 1.0 - xp.sqrt(resid2) / xp.sqrt(norm2)


def mode_update(ar: Arrays, indices, values, factors, mode: int, contract: str = "exact"):
    """The ALS update of one mode; returns (factors, weights)."""
    xp = ar.xp
    factors = list(factors)
    rank = factors[0].shape[1]
    m = mttkrp(ar, indices, values, factors, mode, contract)
    had = xp.ones((rank, rank), m.dtype)
    for k, f in enumerate(factors):
        if k != mode:
            had = had * ar.matmul(f.T, f)
    a = xp.linalg.solve(had + RIDGE * xp.eye(rank, dtype=m.dtype), m.T).T
    weights = xp.maximum(xp.linalg.norm(a, axis=0), NORM_FLOOR)
    factors[mode] = a / weights
    return factors, weights


def sweep(ar: Arrays, norm2, indices, values, factors, weights, contract: str = "exact"):
    """One ALS sweep over every mode; returns (factors, weights, fit)."""
    for mode in range(len(factors)):
        factors, weights = mode_update(ar, indices, values, factors, mode, contract)
    return factors, weights, fit(ar, norm2, indices, values, factors, weights)


def first_update_numpy(indices, values, dims, rank, seed):
    """Float64 host update of mode 0 from the documented initialisation:
    the first sweep's first factor."""
    factors = [f.astype(np.float64) for f in init_factors(dims, rank, seed)]
    factors, _ = mode_update(numpy_arrays(), indices.astype(np.int64),
                             values.astype(np.float64), factors, 0)
    return factors[0]


def gaps(weights, fits, ref_weights, ref_fits) -> dict[str, float]:
    """The widest fit gap over the sweeps and the relative gap of the
    final weights."""
    w = np.asarray(weights, np.float64)
    rw = np.asarray(ref_weights, np.float64)
    fit = np.abs(np.asarray(fits, np.float64) - np.asarray(ref_fits, np.float64))
    return {
        "fit_gap": float(np.max(fit)),
        "weight_gap": float(np.linalg.norm(w - rw) / np.linalg.norm(rw)),
    }


def row_gap(got, want) -> float:
    """Median over the rows of a factor of each row's relative gap, once
    the one linear map that best takes ``want`` to ``got`` is applied.

    Errors of the Grams, the solve's factorisation and the column norms
    act on every row through one R×R map, which the least-squares fit
    takes out; what is left is each row's own error, set by the MTTKRP's
    products and sums over that row's nonzeros.
    """
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    t, *_ = np.linalg.lstsq(want, got, rcond=None)
    fitted = want @ t
    rows = np.linalg.norm(got - fitted, axis=1) / np.maximum(np.linalg.norm(fitted, axis=1), 1e-300)
    return float(np.median(rows))
