"""Seeded sparse tensors whose coordinates span more than an int64 key.

``generators.zipf_indices`` coalesces duplicates on one flat key over
all modes, which ``np.ravel_multi_index`` refuses once the product of
the dims passes 2**63 (LBNL-network's five modes span ~3.9e19).  This
module makes the same draws and coalesces on the coordinate rows
themselves: consecutive modes packed into int64 keys while they fit,
and a stable sort over the keys, which keeps each row's first
occurrence in lexicographic row order.  Where the flat key fits, the
result is ``generators.zipf_tensor``'s, draw for draw.  Only numpy is
used.
"""

from __future__ import annotations

import numpy as np


def first_of_each_row(idx: np.ndarray, dims) -> np.ndarray:
    """Positions of the first occurrence of each distinct row of ``idx``
    (n, N), in lexicographic row order."""
    n = idx.shape[0]
    keys, key, span = [], np.zeros(n, np.int64), 1
    for col, dim in zip(idx.T, dims):
        if span * int(dim) > np.iinfo(np.int64).max:
            keys.append(key)
            key, span = np.zeros(n, np.int64), 1
        key = key * int(dim) + col
        span *= int(dim)
    keys.append(key)
    order = np.lexsort(keys[::-1])  # np.lexsort sorts by its last key first
    new = np.ones(n, dtype=bool)
    sorted_keys = [k[order] for k in keys]
    new[1:] = np.any([k[1:] != k[:-1] for k in sorted_keys], axis=0)
    return order[new]


def zipf_tensor_rows(dims, nnz: int, zipf_a: float, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(indices, float32 values): ``generators.zipf_tensor``'s draws
    (bounded Zipf per mode, rank decorrelated from index by a
    permutation, then standard-normal values), duplicates coalesced on
    the rows.  ``indices`` is (n, N) int32 with ``n <= nnz``."""
    rng = np.random.default_rng(seed)
    cols = []
    for dim in dims:
        p = np.arange(1, dim + 1, dtype=np.float64) ** (-float(zipf_a))
        cdf = np.cumsum(p)
        cdf /= cdf[-1]
        ranks = np.searchsorted(cdf, rng.random(nnz), side="left")
        perm = rng.permutation(dim)
        cols.append(perm[np.clip(ranks, 0, dim - 1)])
    idx = np.stack(cols, axis=1)
    idx = idx[first_of_each_row(idx, dims)].astype(np.int32)
    return idx, rng.standard_normal(idx.shape[0]).astype(np.float32)
