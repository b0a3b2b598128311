"""Sparse tensor containers and the mode-ordered MTTKRP execution plan.

This module is the executable counterpart of the paper's §IV-A: a sparse
tensor is viewed as a hypergraph H = (V, E) whose vertices are the index
values of every mode and whose hyperedges are the nonzeros.  For each
output mode the nonzeros are *linearized in output-mode order* so that all
hyperedges sharing an output vertex are consecutive — this is exactly the
property the paper exploits to keep partial sums in the on-chip (O-SRAM)
buffer and store each output row exactly once (Algorithm 1, line 11).

On TPU the same linearization lets the Pallas kernel revisit one VMEM
output block across consecutive grid steps, which is the hardware-legal
accumulation pattern.  The plan construction below (sort → block grouping →
tile padding) is host-side numpy, computed once per (tensor, mode) and
amortized over all CP-ALS iterations — mirroring the paper's per-mode
"mapping of X into memory".
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

__all__ = [
    "SparseTensor",
    "HypergraphStats",
    "MTTKRPPlan",
    "build_mttkrp_plan",
    "random_sparse_tensor",
]


@dataclasses.dataclass(frozen=True)
class SparseTensor:
    """COO sparse tensor.

    indices: (nnz, nmodes) int32 coordinates.
    values:  (nnz,) floating values.
    shape:   per-mode dimension sizes ``(I_0, ..., I_{N-1})``.
    """

    indices: np.ndarray
    values: np.ndarray
    shape: tuple[int, ...]

    def __post_init__(self):
        if self.indices.ndim != 2:
            raise ValueError(f"indices must be (nnz, nmodes), got {self.indices.shape}")
        if self.values.ndim != 1 or self.values.shape[0] != self.indices.shape[0]:
            raise ValueError("values must be (nnz,) aligned with indices")
        if self.indices.shape[1] != len(self.shape):
            raise ValueError("indices mode count must match shape")

    @property
    def nnz(self) -> int:
        return int(self.values.shape[0])

    @property
    def nmodes(self) -> int:
        return len(self.shape)

    @property
    def density(self) -> float:
        total = float(np.prod([float(s) for s in self.shape]))
        return self.nnz / total if total > 0 else 0.0

    def mode_sorted(self, mode: int) -> "SparseTensor":
        """Return a copy with nonzeros sorted by the given (output) mode."""
        order = np.argsort(self.indices[:, mode], kind="stable")
        return SparseTensor(self.indices[order], self.values[order], self.shape)

    def to_dense(self) -> np.ndarray:
        """Materialize (tests / tiny tensors only)."""
        out = np.zeros(self.shape, dtype=self.values.dtype)
        np.add.at(out, tuple(self.indices.T), self.values)
        return out

    def hypergraph_stats(self) -> "HypergraphStats":
        """|V|, |E| and per-mode vertex-degree statistics (paper Fig. 3)."""
        degrees = []
        for m in range(self.nmodes):
            counts = np.bincount(self.indices[:, m], minlength=self.shape[m])
            degrees.append(counts)
        return HypergraphStats(
            num_vertices=int(sum(self.shape)),
            num_hyperedges=self.nnz,
            mode_degree_mean=tuple(float(d[d > 0].mean()) if (d > 0).any() else 0.0 for d in degrees),
            mode_degree_max=tuple(int(d.max()) if d.size else 0 for d in degrees),
            mode_nonempty=tuple(int((d > 0).sum()) for d in degrees),
        )


@dataclasses.dataclass(frozen=True)
class HypergraphStats:
    num_vertices: int
    num_hyperedges: int
    mode_degree_mean: tuple[float, ...]
    mode_degree_max: tuple[int, ...]
    mode_nonempty: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class MTTKRPPlan:
    """Mode-ordered, tile-padded execution plan for one output mode.

    All arrays are host numpy; the jit'd op converts them to device arrays.

    sorted_indices : (nnz_pad, nmodes) int32 — nonzeros sorted by output
        mode, grouped by output block, padded per block to a multiple of
        ``tile_nnz`` (padding rows point at the block's first output row).
    sorted_values  : (nnz_pad,) — zeros at padding positions.
    local_row      : (nnz_pad,) int32 — output row *within* its block,
        in [0, rows_per_block).
    tile_block     : (num_tiles,) int32 — output block index per tile;
        non-decreasing, every block in [0, num_blocks) appears >= 1 time.
    """

    mode: int
    shape: tuple[int, ...]
    tile_nnz: int
    rows_per_block: int
    num_blocks: int
    sorted_indices: np.ndarray
    sorted_values: np.ndarray
    local_row: np.ndarray
    tile_block: np.ndarray
    # Nonzero-ordering strategy the linearization used (repro.reorder,
    # DESIGN.md §10).  "lex" is the historical baseline: stable output-mode
    # sort, original COO order within each output row.
    ordering: str = "lex"

    @property
    def num_tiles(self) -> int:
        return int(self.tile_block.shape[0])

    @property
    def nnz_pad(self) -> int:
        return int(self.sorted_values.shape[0])

    @property
    def padding_overhead(self) -> float:
        real = int((self.sorted_values != 0).sum())
        return self.nnz_pad / max(real, 1)

    def executed_row_trace(self, k: int, *, include_padding: bool = True) -> np.ndarray:
        """Factor-``k`` row indices in the order the kernel accesses them.

        This is the trace-capture hook for the experiment engine
        (DESIGN.md §7): the plan's linearization IS the executed nonzero
        order, so column ``k`` of ``sorted_indices`` is exactly the
        access stream the cache subsystem sees for input factor ``k``.
        ``include_padding=True`` keeps the padding rows' gathers (they
        fetch a real factor row — row 0 / the block's first output row —
        so the hardware cache sees them too); ``False`` restricts to real
        nonzeros.
        """
        if not (0 <= k < len(self.shape)):
            raise ValueError(f"factor {k} out of range for {len(self.shape)}-mode plan")
        trace = self.sorted_indices[:, k]
        if include_padding:
            return trace.copy()
        return trace[self.sorted_values != 0]


def build_mttkrp_plan(
    tensor: SparseTensor,
    mode: int,
    *,
    tile_nnz: int = 256,
    rows_per_block: int = 256,
    ordering: str = "lex",
) -> MTTKRPPlan:
    """Linearize nonzeros for mode-ordered execution (paper Algorithm 1).

    Steps:
      1. order hyperedges by the selected ``ordering`` strategy
         (repro.reorder, DESIGN.md §10) — every strategy keeps the output
         mode as the primary key, so steps 2–4 see contiguous ascending
         output blocks; ``"lex"`` is the historical stable output-mode sort;
      2. group by output block (``rows_per_block`` consecutive output rows);
      3. pad every block's nonzero count to a multiple of ``tile_nnz`` so no
         tile spans two output blocks (padding nonzeros carry value 0 and
         point at the block's first row — they contribute nothing);
      4. blocks with no nonzeros get one all-padding tile so the kernel
         still zero-initializes their VMEM output block.
    """
    if not (0 <= mode < tensor.nmodes):
        raise ValueError(f"mode {mode} out of range for {tensor.nmodes}-mode tensor")
    i_out = tensor.shape[mode]
    num_blocks = max(1, -(-i_out // rows_per_block))

    if ordering == "lex":
        order = np.argsort(tensor.indices[:, mode], kind="stable")
    else:
        from repro.reorder import nonzero_order  # circular-import guard

        order = nonzero_order(
            tensor, mode, ordering, rows_per_block=rows_per_block
        )
    idx = tensor.indices[order].astype(np.int32)
    val = tensor.values[order]

    block_of = idx[:, mode] // rows_per_block
    # Nonzeros per block (bincount over all blocks, including empty ones).
    per_block = np.bincount(block_of, minlength=num_blocks)
    padded_per_block = np.maximum(tile_nnz, -(-per_block // tile_nnz) * tile_nnz)

    nnz_pad = int(padded_per_block.sum())
    out_idx = np.zeros((nnz_pad, tensor.nmodes), dtype=np.int32)
    out_val = np.zeros((nnz_pad,), dtype=val.dtype)
    out_local = np.zeros((nnz_pad,), dtype=np.int32)

    block_starts_dst = np.concatenate([[0], np.cumsum(padded_per_block)])[:-1]
    block_starts_src = np.concatenate([[0], np.cumsum(per_block)])[:-1]

    for b in range(num_blocks):
        n = int(per_block[b])
        dst = int(block_starts_dst[b])
        src = int(block_starts_src[b])
        if n:
            out_idx[dst : dst + n] = idx[src : src + n]
            out_val[dst : dst + n] = val[src : src + n]
            out_local[dst : dst + n] = idx[src : src + n, mode] - b * rows_per_block
        # Padding rows: point at the block's first row, value 0, and set
        # non-output coordinates to 0 (a valid row of every factor matrix).
        pad_lo = dst + n
        pad_hi = dst + int(padded_per_block[b])
        if pad_hi > pad_lo:
            out_idx[pad_lo:pad_hi, mode] = b * rows_per_block
            out_local[pad_lo:pad_hi] = 0

    tiles_per_block = padded_per_block // tile_nnz
    tile_block = np.repeat(np.arange(num_blocks, dtype=np.int32), tiles_per_block)

    return MTTKRPPlan(
        mode=mode,
        shape=tensor.shape,
        tile_nnz=tile_nnz,
        rows_per_block=rows_per_block,
        num_blocks=num_blocks,
        sorted_indices=out_idx,
        sorted_values=out_val,
        local_row=out_local,
        tile_block=tile_block,
        ordering=ordering,
    )


def _first_of_each_row(idx: np.ndarray, shape: Sequence[int]) -> np.ndarray:
    """Positions of the first occurrence of each distinct coordinate row
    of ``idx`` (n, N), in lexicographic row order.

    Consecutive modes are packed into one int64 key while their index
    space fits it (LBNL-network's five modes span ~3.9e19 coordinates,
    more than 2**63); a stable sort over the keys then keeps each row's
    first occurrence.  Where every mode fits one key this is
    ``np.unique(flat_key, return_index=True)``, draw for draw.
    """
    n = idx.shape[0]
    keys, key, span = [], np.zeros(n, np.int64), 1
    for col, dim in zip(idx.T, shape):
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


def random_sparse_tensor(
    shape: Sequence[int],
    nnz: int,
    *,
    seed: int = 0,
    dtype=np.float32,
    zipf_a: float | None = None,
    correlation: float = 0.0,
    n_clusters: int = 64,
    shuffle: bool = False,
) -> SparseTensor:
    """Random COO tensor with optionally Zipf-skewed per-mode indices.

    ``zipf_a`` controls mode-index skew (higher → more locality), used to
    emulate the access-locality differences across FROSTT tensors that
    drive the paper's cache-sensitivity results (NELL-2 vs NELL-1).
    Indices are drawn from a TRUE bounded Zipf law (p_rank ∝ rank^-a,
    inverse-CDF sampled) — the same popularity model ``che_hit_rate``
    solves, so executed-trace hit rates on these tensors are directly
    reconcilable with the Che approximation (DESIGN.md §7).
    Duplicate coordinates are coalesced.

    ``correlation`` is the cross-mode hot-row coupling knob
    (DESIGN.md §10): each nonzero draws a shared latent quantile, and
    with probability ``correlation`` a mode's index quantile is sampled
    from that latent's cluster band (one of ``n_clusters`` equal quantile
    bands) instead of independently.  Rows that are hot together in one
    mode are then hot together in every coupled mode — the structure
    real FROSTT tensors have and the reordering strategies exploit
    (repro.reorder).  Per-mode marginals are unchanged (the mixture is
    still uniform over quantiles), so Che reconciliation still holds;
    ``correlation=0`` (default) is draw-for-draw identical to the
    historical generator.

    ``shuffle`` randomizes the COO *storage* order after coalescing.
    The coalescing step (``np.unique``) otherwise leaves the nonzeros
    lexicographically sorted by coordinate — an artifact real FROSTT
    dumps do not have, which silently made the ``lex`` baseline
    coincide with ``secondary-sort`` for every mode (the within-row
    order was already sorted).  Ordering benchmarks should shuffle.
    """
    if not 0.0 <= correlation <= 1.0:
        raise ValueError(f"correlation must be in [0, 1], got {correlation}")
    rng = np.random.default_rng(seed)
    u_shared = rng.random(nnz) if correlation > 0.0 else None
    cols = []
    for dim in shape:
        u = None
        if u_shared is not None:
            # Same cluster band as the shared latent (coarse quantile),
            # fresh fine part — coupled draws agree on the hot/cold band,
            # not the exact row.
            band = np.floor(u_shared * n_clusters)
            coupled = (band + rng.random(nnz)) / n_clusters
            u = np.where(rng.random(nnz) < correlation, coupled, rng.random(nnz))
        if zipf_a is None:
            if u is None:
                cols.append(rng.integers(0, dim, size=nnz, dtype=np.int64))
            else:
                cols.append(np.minimum((u * dim).astype(np.int64), dim - 1))
        else:
            # Bounded Zipf (p ∝ rank^-a) via inverse-CDF sampling.
            p = np.arange(1, dim + 1, dtype=np.float64) ** (-float(zipf_a))
            cdf = np.cumsum(p)
            cdf /= cdf[-1]
            draws = rng.random(nnz) if u is None else u
            ranks = np.searchsorted(cdf, draws, side="left")
            perm = rng.permutation(dim)  # decorrelate rank from index value
            cols.append(perm[np.clip(ranks, 0, dim - 1)])
    idx = np.stack(cols, axis=1)
    idx = idx[_first_of_each_row(idx, shape)].astype(np.int32)  # coalesce duplicates
    vals = rng.standard_normal(idx.shape[0]).astype(dtype)
    if shuffle:
        perm = rng.permutation(idx.shape[0])
        idx, vals = idx[perm], vals[perm]
    return SparseTensor(idx, vals, tuple(int(s) for s in shape))
