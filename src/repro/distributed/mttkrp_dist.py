"""Distributed spMTTKRP — the paper's accelerator parallelism on the mesh.

Two schemes, mirroring DESIGN.md §2's changed-assumptions note:

  * ``allreduce`` (naive baseline): nonzeros block-sharded over the data
    axis; every shard computes a full-height partial MTTKRP; one psum.
    DRAM analog: partial sums cross the interconnect.

  * ``mode_ordered`` (paper-faithful): nonzeros are partitioned by OUTPUT
    ROW RANGE (possible because the plan sorts hyperedges by the output
    mode — Algorithm 1's ordering).  Each shard owns a disjoint output
    block, so the output needs NO reduction — the direct translation of
    the paper's "output factor matrix computed without partial sums",
    with the PE/DRAM-channel pairing becoming shard/mesh-slot pairing.
    Input factor matrices are replicated (the paper streams them through
    shared caches; see §Perf for the sharded-input variant).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.sparse_tensor import SparseTensor

__all__ = [
    "ShardedModeSetup",
    "build_sharded_mode_setup",
    "data_mesh",
    "mttkrp_sharded",
    "mttkrp_sharded_apply",
    "partition_by_output_rows",
]


def partition_by_output_rows(
    tensor: SparseTensor, mode: int, n_shards: int, *, order: np.ndarray | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sort by output mode and pad-split nonzeros into equal shard blocks.

    Returns (indices (n_shards, m, nmodes), values (n_shards, m),
    row_start (n_shards,)) where shard i owns output rows
    [row_start[i], row_start[i+1]).  Shard boundaries are placed at row
    boundaries closest to an even nnz split (the paper's per-PE mapping).

    ``order`` optionally injects a nonzero execution permutation
    (``repro.reorder.nonzero_order``, DESIGN.md §10): shard MEMBERSHIP is
    unchanged (it derives from row ownership), but each shard's nonzeros
    are laid out — and hence gathered/executed — in the given order.  The
    default (and ``order=lex``) reproduces the historical stable
    output-mode sort exactly.
    """
    sort_order = np.argsort(tensor.indices[:, mode], kind="stable")
    idx = tensor.indices[sort_order]
    val = tensor.values[sort_order]
    nnz = idx.shape[0]
    rows = idx[:, mode]
    # even-nnz split points, snapped to row boundaries
    targets = [(nnz * (i + 1)) // n_shards for i in range(n_shards - 1)]
    cuts = []
    for t in targets:
        # advance to the end of the row at position t
        r = rows[min(t, nnz - 1)]
        e = np.searchsorted(rows, r, side="right")
        cuts.append(e)
    bounds = [0] + cuts + [nnz]
    row_start = np.zeros(n_shards, np.int32)
    per = max(b - a for a, b in zip(bounds[:-1], bounds[1:]))
    out_idx = np.zeros((n_shards, per, tensor.nmodes), np.int32)
    out_val = np.zeros((n_shards, per), tensor.values.dtype)
    shard_of = None
    if order is not None:
        shard_of = np.empty(nnz, np.int64)
        for i, (a, b) in enumerate(zip(bounds[:-1], bounds[1:])):
            shard_of[sort_order[a:b]] = i
    for i, (a, b) in enumerate(zip(bounds[:-1], bounds[1:])):
        n = b - a
        if order is None:
            if n:
                out_idx[i, :n] = idx[a:b]
                out_val[i, :n] = val[a:b]
        else:
            members = order[shard_of[order] == i]
            if members.shape[0] != n:  # membership is order-independent
                raise ValueError(
                    f"order is not a permutation of this tensor's nonzeros: "
                    f"shard {i} collected {members.shape[0]} members, "
                    f"row ownership says {n}"
                )
            if n:
                out_idx[i, :n] = tensor.indices[members]
                out_val[i, :n] = tensor.values[members]
        row_start[i] = rows[a] if b > a else (rows[bounds[i] - 1] if a > 0 else 0)
        # padding points at the shard's first (lowest) row with value 0
        if n:
            out_idx[i, n:, mode] = rows[a]
    return out_idx, out_val, row_start


def data_mesh(axis: str = "data") -> Mesh:
    """1-D mesh over every device, with an ``Auto`` axis.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which slicing
    the row-range-sharded output back to ``I_mode`` rows is a sharding
    type error; ``Auto`` lets the compiler reassemble it.
    """
    return jax.make_mesh((jax.device_count(),), (axis,), axis_types=(AxisType.Auto,))


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["idx", "val", "row_start", "leftover_idx", "leftover_val"],
    meta_fields=["mode", "scheme", "nmodes", "i_out", "n_shards", "rows_per"],
)
@dataclasses.dataclass(frozen=True)
class ShardedModeSetup:
    """Host-precomputed, device-resident buffers for one (mode, scheme).

    The O(nnz log nnz) partitioning work of the sharded path, split off
    from the per-call math so callers that run many MTTKRPs per mode —
    the fused CP-ALS executor (DESIGN.md §11) — pay it once.  The
    partitioned arrays are sharded over the mesh's data axis (the
    leftovers are replicated); ``mttkrp_sharded_apply`` is pure jax and
    legal inside a jit trace (including under ``lax.scan`` / ``vmap``).
    The setup is a pytree whose arrays are its leaves, so a jitted
    caller can take it as an argument instead of capturing constants.

    ``leftover_idx``/``leftover_val`` hold the nonzeros masked out of the
    equal-height shard blocks (the block-vs-nnz boundary mismatch); None
    when the partition has no such residue.
    """

    mode: int
    scheme: str
    nmodes: int
    i_out: int
    n_shards: int
    rows_per: int  # mode_ordered: output block height per shard
    idx: jax.Array  # mode_ordered: (n, per, nmodes); allreduce: (n*per, nmodes)
    val: jax.Array
    row_start: jax.Array | None  # mode_ordered only
    leftover_idx: jax.Array | None
    leftover_val: jax.Array | None


def build_sharded_mode_setup(
    tensor: SparseTensor,
    mode: int,
    mesh: Mesh,
    *,
    axis: str = "data",
    scheme: str = "mode_ordered",
    ordering: str | None = None,
    rows_per_block: int = 256,
) -> ShardedModeSetup:
    """Partition ``tensor`` for ``mode`` over ``mesh``'s ``axis`` once;
    see ``mttkrp_sharded``."""
    i_out = tensor.shape[mode]
    n_shards = mesh.shape[axis]

    def put(x: np.ndarray, *spec) -> jax.Array:
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    ord_perm = None
    if ordering is not None:
        from repro.reorder import nonzero_order

        ord_perm = nonzero_order(tensor, mode, ordering, rows_per_block=rows_per_block)

    if scheme == "allreduce":
        # block-shard nonzeros (pad to multiple of n)
        nnz = tensor.nnz
        per = -(-nnz // n_shards)
        idx = np.zeros((n_shards * per, tensor.nmodes), np.int32)
        val = np.zeros((n_shards * per,), tensor.values.dtype)
        idx[:nnz] = tensor.indices if ord_perm is None else tensor.indices[ord_perm]
        val[:nnz] = tensor.values if ord_perm is None else tensor.values[ord_perm]
        return ShardedModeSetup(
            mode=mode,
            scheme=scheme,
            nmodes=tensor.nmodes,
            i_out=i_out,
            n_shards=n_shards,
            rows_per=per,
            idx=put(idx, axis, None),
            val=put(val, axis),
            row_start=None,
            leftover_idx=None,
            leftover_val=None,
        )
    if scheme != "mode_ordered":
        raise ValueError(f"unknown scheme {scheme!r}")

    idx_s, val_s, row_start = partition_by_output_rows(
        tensor, mode, n_shards, order=ord_perm
    )
    rows_per = -(-i_out // n_shards)  # output block height per shard (padded)

    # Nonzeros masked out of the equal-height blocks (row not in the block
    # of their nnz-shard) — typically a tiny boundary fraction; contributed
    # back by a second (sparse, tiny) pass in the apply step.
    rows = idx_s[..., mode]
    shard_of_nnz = np.repeat(np.arange(n_shards)[:, None], idx_s.shape[1], 1)
    owned = (rows >= shard_of_nnz * rows_per) & (rows < (shard_of_nnz + 1) * rows_per)
    leftover = ~owned & (val_s != 0)
    leftover_idx = leftover_val = None
    if leftover.any():
        leftover_idx = put(idx_s[leftover])
        leftover_val = put(val_s[leftover].astype(np.float32))
    return ShardedModeSetup(
        mode=mode,
        scheme=scheme,
        nmodes=tensor.nmodes,
        i_out=i_out,
        n_shards=n_shards,
        rows_per=rows_per,
        idx=put(idx_s, axis, None, None),
        val=put(val_s, axis, None),
        row_start=put(row_start, axis),
        leftover_idx=leftover_idx,
        leftover_val=leftover_val,
    )


def mttkrp_sharded_apply(
    setup: ShardedModeSetup, factors, *, mesh: Mesh, axis: str = "data"
) -> jax.Array:
    """Device math of the sharded MTTKRP over a precomputed partition.

    Pure jax (no host work): safe to call inside a jit trace, so the
    fused executor can run it under ``lax.scan``/``vmap`` (DESIGN.md §11).
    """
    mode, rows_per, i_out = setup.mode, setup.rows_per, setup.i_out
    rank = factors[0].shape[1]
    facs = tuple(jnp.asarray(f) for f in factors)

    if setup.scheme == "allreduce":

        def local(idx_l, val_l, *facs_l):
            acc = val_l.astype(jnp.float32)[:, None] * jnp.ones((1, rank), jnp.float32)
            for k in range(setup.nmodes):
                if k == mode:
                    continue
                acc = acc * jnp.take(facs_l[k], idx_l[:, k], axis=0).astype(jnp.float32)
            out = jax.ops.segment_sum(acc, idx_l[:, mode], num_segments=i_out)
            return jax.lax.psum(out, axis)

        fn = jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis)) + (P(None, None),) * len(facs),
            out_specs=P(None, None),
            check_vma=False,
        )
        return fn(setup.idx, setup.val, *facs)[:i_out].astype(facs[mode].dtype)

    # --- paper-faithful: output-row partitioning, no reduction --------------
    def local(idx_l, val_l, start_l, *facs_l):
        idx_l, val_l, start_l = idx_l[0], val_l[0], start_l[0]
        acc = val_l.astype(jnp.float32)[:, None] * jnp.ones((1, rank), jnp.float32)
        for k in range(setup.nmodes):
            if k == mode:
                continue
            acc = acc * jnp.take(facs_l[k], idx_l[:, k], axis=0).astype(jnp.float32)
        shard = jax.lax.axis_index(axis)
        # local rows relative to this shard's output block origin
        local_rows = idx_l[:, mode] - shard * rows_per
        local_rows = jnp.clip(local_rows, 0, rows_per - 1)
        owned = (idx_l[:, mode] >= shard * rows_per) & (
            idx_l[:, mode] < (shard + 1) * rows_per
        )
        acc = jnp.where(owned[:, None], acc, 0.0)
        out = jax.ops.segment_sum(acc, local_rows, num_segments=rows_per)
        return out[None]

    # NOTE: with row-range partitioning the nnz split follows row ownership
    # of EQUAL-HEIGHT blocks (grid-friendly); nonzeros whose rows fall
    # outside the shard's block are masked (they belong to a neighbor's
    # block boundary, from the even-nnz snapping) — correctness is
    # preserved by the tiny residual pass below.
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None), P(axis)) + (P(None, None),) * len(facs),
        out_specs=P(axis, None, None),
        check_vma=False,
    )
    out = fn(setup.idx, setup.val, setup.row_start, *facs)
    out = out.reshape(setup.n_shards * rows_per, rank)[:i_out]

    # residual pass: the setup's precomputed leftover nonzeros.
    if setup.leftover_idx is not None:
        li, lv = setup.leftover_idx, setup.leftover_val
        accj = lv[:, None] * jnp.ones((1, rank), jnp.float32)
        for k in range(setup.nmodes):
            if k == mode:
                continue
            accj = accj * jnp.take(facs[k], li[:, k], axis=0).astype(jnp.float32)
        out = out + jax.ops.segment_sum(accj, li[:, mode], num_segments=out.shape[0])
    return out.astype(facs[mode].dtype)


def mttkrp_sharded(
    tensor: SparseTensor,
    factors,
    mode: int,
    *,
    mesh: Mesh | None = None,
    axis: str = "data",
    scheme: str = "mode_ordered",
    ordering: str | None = None,
    rows_per_block: int = 256,
):
    """Multi-device MTTKRP.  Returns (I_mode, R) on the host layout.

    ``ordering`` selects the within-shard nonzero execution order
    (repro.reorder, DESIGN.md §10); shard ownership — row ranges under
    ``mode_ordered``, equal blocks under ``allreduce`` — is a hardware
    constraint and stays fixed.  ``None`` keeps the historical layouts
    (raw order for ``allreduce``, stable output-mode sort otherwise).
    ``rows_per_block`` is the blocked strategy's output-tile height; it
    must match the value the trace capture uses
    (``executed_input_traces``) or the measured order is not the
    executed one.

    Repartitions on every call (its documented host-side dispatch cost);
    callers running many MTTKRPs per mode should hold a
    ``build_sharded_mode_setup`` result and call ``mttkrp_sharded_apply``
    — the fused CP-ALS executor does (DESIGN.md §11).
    """
    if mesh is None:
        mesh = data_mesh(axis)
    setup = build_sharded_mode_setup(
        tensor,
        mode,
        mesh,
        axis=axis,
        scheme=scheme,
        ordering=ordering,
        rows_per_block=rows_per_block,
    )
    return mttkrp_sharded_apply(setup, factors, mesh=mesh, axis=axis)
