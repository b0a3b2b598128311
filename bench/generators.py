"""Seeded inputs of the benchmark: sparse tensors.

The benchmark's own copy of the recipe in
``repro.core.sparse_tensor.random_sparse_tensor`` (bounded-Zipf indices,
duplicates coalesced, standard-normal values), so that the inputs stay
fixed while the program changes.  Only numpy is used.
"""

from __future__ import annotations

import numpy as np

SEED_LIMIT = 2**31  # inner seeds are drawn below this, whatever --seed is


def zipf_indices(dims, nnz: int, zipf_a: float, rng: np.random.Generator) -> np.ndarray:
    """(n, N) int32 coordinates: bounded Zipf per mode (p(rank) ∝ rank^-a,
    rank decorrelated from index by a permutation), duplicates coalesced
    in lexicographic key order.  ``n <= nnz``."""
    cols = []
    for dim in dims:
        p = np.arange(1, dim + 1, dtype=np.float64) ** (-float(zipf_a))
        cdf = np.cumsum(p)
        cdf /= cdf[-1]
        ranks = np.searchsorted(cdf, rng.random(nnz), side="left")
        perm = rng.permutation(dim)
        cols.append(perm[np.clip(ranks, 0, dim - 1)])
    idx = np.stack(cols, axis=1)
    keys = np.ravel_multi_index(tuple(idx.T), tuple(dims), mode="wrap")
    _, first = np.unique(keys, return_index=True)
    return idx[first].astype(np.int32)


def zipf_tensor(dims, nnz: int, zipf_a: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(indices, float32 values), draw for draw the program's
    ``random_sparse_tensor(dims, nnz, seed=seed, zipf_a=zipf_a)``."""
    rng = np.random.default_rng(seed)
    idx = zipf_indices(dims, nnz, zipf_a, rng)
    return idx, rng.standard_normal(idx.shape[0]).astype(np.float32)
