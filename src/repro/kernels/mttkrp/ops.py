"""jit'd wrapper around the Pallas spMTTKRP kernel.

Responsibilities split exactly as the paper splits them:
  * host-side, once per (tensor, mode): the mode-ordered linearization
    (core.sparse_tensor.build_mttkrp_plan) — the paper's per-mode memory
    mapping, amortized over all CP-ALS iterations;
  * device-side, per call: gather factor rows (TPU DMA engine), run the
    kernel, slice off block padding and lane padding.
"""

from __future__ import annotations

import itertools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.memo import IdentityKeyedCache
from repro.core.sparse_tensor import MTTKRPPlan, SparseTensor, build_mttkrp_plan
from repro.kernels.common import default_interpret, interpret_override
from repro.kernels.mttkrp.kernel import LANE, mttkrp_pallas_call

#: Execution backends accepted by :func:`resolve_backend` (DESIGN.md §13).
#:   * ``"mosaic"``    — native Pallas→Mosaic compile (TPU);
#:   * ``"triton"``    — Pallas→Triton lowering (GPU);
#:   * ``"xla"``       — the jit-compiled XLA fallback
#:                       (``kernels.mttkrp.compiled``, any platform);
#:   * ``"interpret"`` — the pure-Python Pallas emulator (debugging only).
BACKENDS = ("mosaic", "triton", "xla", "interpret")

# Plan memo per source tensor (repro.core.memo documents the
# identity-anchoring soundness requirement — a bare id() key caused
# intermittent stale-plan NaNs in the hypothesis sweep).
_PLAN_CACHE = IdentityKeyedCache()

# Device residency memo per plan: the plan's host numpy arrays are
# uploaded once and every subsequent call — each CP-ALS iteration, each
# fused-executor sweep (DESIGN.md §11) — reuses the same device buffers
# instead of re-staging ~nnz_pad * (nmodes + 3) elements per MTTKRP.
_BUFFER_CACHE = IdentityKeyedCache()

# Device residency memo per SOURCE TENSOR: raw (optionally nnz-padded)
# COO operands, uploaded once per (tensor, nnz_pad, dtype).  This is the
# serving-path analogue of _BUFFER_CACHE — a request stream that
# re-submits the same tensor (retries, repeated decompositions with new
# seeds) re-stages nothing (repro.serve, DESIGN.md §12).
_OPERAND_CACHE = IdentityKeyedCache()


class PlanBuffers(NamedTuple):
    """Device-resident copies of an ``MTTKRPPlan``'s kernel operands."""

    indices: jax.Array  # (nnz_pad, nmodes) int32
    values: jax.Array  # (nnz_pad,)
    local_row: jax.Array  # (nnz_pad,) int32
    tile_block: jax.Array  # (num_tiles,) int32


def plan_device_buffers(plan: MTTKRPPlan) -> PlanBuffers:
    """The plan's operands on device, uploaded once per plan object."""
    bufs = _BUFFER_CACHE.get(plan, ())
    if bufs is None:
        bufs = _BUFFER_CACHE.put(
            plan,
            (),
            PlanBuffers(
                indices=jnp.asarray(plan.sorted_indices),
                values=jnp.asarray(plan.sorted_values),
                local_row=jnp.asarray(plan.local_row),
                tile_block=jnp.asarray(plan.tile_block),
            ),
        )
    return bufs


class TensorOperands(NamedTuple):
    """Device-resident COO operands of one ``SparseTensor``.

    ``indices``/``values`` may be zero-padded past the tensor's real nnz
    (padding rows point at coordinate 0 with value 0 — a no-op for both
    MTTKRP and the CP fit); ``norm2`` is ``||X||^2`` over the REAL values
    only, accumulated in float64 exactly as the CP-ALS drivers do.
    """

    indices: jax.Array  # (nnz_pad, nmodes) int32
    values: jax.Array  # (nnz_pad,)
    norm2: jax.Array  # scalar

    @property
    def nnz_pad(self) -> int:
        return int(self.values.shape[0])


def tensor_device_operands(
    tensor: SparseTensor,
    *,
    nnz_pad: int | None = None,
    dtype=jnp.float32,
) -> TensorOperands:
    """The tensor's COO operands on device, uploaded once per
    (tensor, nnz_pad, dtype).

    ``nnz_pad`` pads the nonzero stream to a fixed length so tensors of
    different nnz can share one compiled bucket program (repro.serve);
    ``None`` keeps the exact length.  Padding entries carry value 0.0 at
    coordinate (0, ..., 0): the gather fetches a real factor row, the
    multiply-accumulate adds an exact IEEE 0.0, so every consumer sees
    the unpadded result bit-for-bit.
    """
    if nnz_pad is None:
        nnz_pad = tensor.nnz
    if nnz_pad < tensor.nnz:
        raise ValueError(f"nnz_pad={nnz_pad} < tensor nnz {tensor.nnz}")
    dtype = jnp.dtype(dtype)
    key = (int(nnz_pad), dtype.name)
    ops = _OPERAND_CACHE.get(tensor, key)
    if ops is None:
        idx = np.zeros((nnz_pad, tensor.nmodes), dtype=np.int32)
        val = np.zeros((nnz_pad,), dtype=dtype)
        idx[: tensor.nnz] = tensor.indices
        val[: tensor.nnz] = tensor.values
        ops = _OPERAND_CACHE.put(
            tensor,
            key,
            TensorOperands(
                indices=jnp.asarray(idx),
                values=jnp.asarray(val),
                norm2=jnp.asarray(
                    float((tensor.values.astype(np.float64) ** 2).sum()), dtype=dtype
                ),
            ),
        )
    return ops


# Kept as an alias so existing importers keep working; the one shared
# definition (env-overridable) lives in repro.kernels.common.
_default_interpret = default_interpret


def _native_compiled_backend() -> str:
    """The platform's compiled lowering: Mosaic/Triton, else the XLA fallback."""
    return {"tpu": "mosaic", "gpu": "triton"}.get(jax.default_backend(), "xla")


def resolve_backend(
    backend: str | None = None, *, interpret: bool | None = None
) -> str:
    """Resolve the MTTKRP execution backend (DESIGN.md §13).

    Precedence: an explicit ``backend`` wins; else an explicit
    ``interpret`` flag (``True`` → the emulator, ``False`` → the
    platform's compiled lowering); else the ``REPRO_PALLAS_INTERPRET``
    env override; else the platform default — which is COMPILED
    everywhere: Mosaic on TPU, Triton on GPU, and the XLA fallback on
    CPU.  (Historically CPU defaulted to interpret mode; now that a
    compiled path exists on every platform the emulator is opt-in.)
    """
    if backend is not None:
        if backend not in BACKENDS:
            raise ValueError(f"backend={backend!r} not in {BACKENDS}")
        return backend
    if interpret is None:
        interpret = interpret_override()
    if interpret:
        return "interpret"
    return _native_compiled_backend()


def get_plan(
    tensor: SparseTensor,
    mode: int,
    *,
    tile_nnz: int = 256,
    rows_per_block: int = 256,
    ordering: str = "lex",
) -> MTTKRPPlan:
    key = (mode, tile_nnz, rows_per_block, ordering)
    plan = _PLAN_CACHE.get(tensor, key)
    if plan is None:
        plan = _PLAN_CACHE.put(
            tensor,
            key,
            build_mttkrp_plan(
                tensor,
                mode,
                tile_nnz=tile_nnz,
                rows_per_block=rows_per_block,
                ordering=ordering,
            ),
        )
    return plan


def mttkrp_from_plan(
    plan: MTTKRPPlan,
    factors: Sequence[jax.Array],
    *,
    backend: str | None = None,
    interpret: bool | None = None,
    bufs: PlanBuffers | None = None,
) -> jax.Array:
    """MTTKRP from a plan alone.  Returns (I_mode, R) for ``plan.mode``.

    The core execution path: everything it needs — output mode, output
    height, kernel operands — lives on the plan, so no ``SparseTensor``
    is constructed (the historical dummy-tensor shim allocated a fresh
    one per call in the distributed per-shard hot loop).  Plan operands
    come from the per-plan device-buffer memo, so repeated calls (the
    CP-ALS hot path) re-upload nothing.

    ``backend``/``interpret`` pick the execution path via
    :func:`resolve_backend`; the XLA fallback consumes the same plan
    buffers, so switching backends re-stages nothing.

    ``bufs`` replaces the memoized device copies of the plan's arrays: a
    jitted caller passes them in as arguments so they stay out of the
    compiled program (the plan then supplies only its static geometry).
    """
    backend = resolve_backend(backend, interpret=interpret)
    if bufs is None:
        bufs = plan_device_buffers(plan)
    if backend == "xla":
        from repro.kernels.mttkrp.compiled import mttkrp_xla_from_plan

        return mttkrp_xla_from_plan(plan, factors, bufs)
    return _mttkrp_pallas_exec(plan, factors, bufs, interpret=backend == "interpret")


# Bytes of lane-padded factor rows one gather table may hold.  XLA:TPU
# stages a gather's table in on-chip VMEM (memory space S(1)) when it is
# small enough, and then gathers at about HBM write speed; a larger table
# stays in HBM and each gathered row is a random HBM read.  Described-v5e
# compiles (jax 0.9.0) of a standalone take of (rows, 128) float32 place
# tables of up to 200,000 rows (102 MB) in S(1), and leave 262,144 rows
# (134 MB) in HBM.  32 MiB sits above NELL-2's largest table (40,900 rows,
# 21 MB) and well under that limit.
TABLE_BYTES = 32 << 20


def _table_factors(factors: Sequence[jax.Array], other: list[int], r_pad: int) -> list[int]:
    """The input factors that go into the one gather table, in ``other``'s
    order: the shortest first, while the table's lane-padded bytes stay
    within ``TABLE_BYTES``, and always the shortest.  Each factor left out
    is gathered apart, from its own lane-padded copy."""
    row_bytes = r_pad * factors[other[0]].dtype.itemsize
    kept, used = set(), 0
    for k in sorted(other, key=lambda k: factors[k].shape[0]):
        used += factors[k].shape[0] * row_bytes
        if kept and used > TABLE_BYTES:
            break
        kept.add(k)
    return [k for k in other if k in kept]


def _mttkrp_pallas_exec(
    plan: MTTKRPPlan,
    factors: Sequence[jax.Array],
    bufs: PlanBuffers,
    *,
    interpret: bool,
) -> jax.Array:
    """The Pallas leg of the dispatch: gather, kernel call, unpad.

    The gather runs under the ``mttkrp_gather`` scope, its table under
    ``mttkrp_gather/mttkrp_table``, and the kernel under
    ``mttkrp_kernel``: each device op's ``op_name`` names its part in a
    profiler trace.

    The kernel's ``(K, nnz_pad, R_pad)`` factor operand is written by one
    gather: the K input factors are lane-padded and concatenated into one
    table, each index column is offset to its factor's first row there,
    and a single ``take`` fetches every row.  Its output is the operand
    itself, so no stack or pad copies it again; ``mode="clip"`` adds no
    fill mask, and clips nothing, since the plan keeps every index in
    range (padding rows point at each factor's row 0).

    A factor too tall for the table (``_table_factors``) is gathered
    apart, under ``mttkrp_gather/mttkrp_gather_apart``: the table gather
    fills its slot with the table's row 0, and its own rows, taken from
    its lane-padded copy, are written over that slot in place.  The
    operand holds the same rows in the same order either way.
    """
    mode = plan.mode
    rank = factors[0].shape[1]
    r_pad = -(-rank // LANE) * LANE

    other = [k for k in range(len(factors)) if k != mode]
    in_table = _table_factors(factors, other, r_pad)
    with jax.named_scope("mttkrp_gather"):
        with jax.named_scope("mttkrp_table"):
            # Pad each factor, not the table: XLA moves a pad of the table
            # past the gather, which brings back a copy of the gathered rows.
            padded = {k: factors[k] for k in other}
            if r_pad != rank:
                padded = {k: jnp.pad(f, ((0, 0), (0, r_pad - rank))) for k, f in padded.items()}
            table = jnp.concatenate([padded[k] for k in in_table])  # (sum I_k, R_pad)
        starts = itertools.accumulate(factors[k].shape[0] for k in in_table)
        first_row = dict(zip(in_table, [0, *starts]))
        # An apart factor's slot gathers the table's row 0 until its own
        # rows overwrite it below.
        rows = jnp.concatenate([
            bufs.indices[:, k] + first_row[k] if k in first_row
            else jnp.zeros_like(bufs.indices[:, k])
            for k in other
        ])
        gathered = jnp.take(table, rows, axis=0, mode="clip").reshape(
            len(other), -1, r_pad
        )  # (K, nnz_pad, R_pad)
        for slot, k in enumerate(other):
            if k not in first_row:
                with jax.named_scope("mttkrp_gather_apart"):
                    own = jnp.take(padded[k], bufs.indices[:, k], axis=0, mode="clip")
                    gathered = jax.lax.dynamic_update_slice(gathered, own[None], (slot, 0, 0))

    with jax.named_scope("mttkrp_kernel"):
        out = mttkrp_pallas_call(
            bufs.tile_block,
            bufs.values,
            bufs.local_row,
            gathered,
            tile_nnz=plan.tile_nnz,
            rows_per_block=plan.rows_per_block,
            num_blocks=plan.num_blocks,
            interpret=interpret,
        )
    i_out = plan.shape[mode]
    return out[:i_out, :rank].astype(factors[mode].dtype)


def mttkrp_pallas_from_plan(
    plan: MTTKRPPlan,
    factors: Sequence[jax.Array],
    *,
    interpret: bool | None = None,
    backend: str | None = None,
) -> jax.Array:
    """Historical name for :func:`mttkrp_from_plan` (kept for callers
    predating the backend dispatch)."""
    return mttkrp_from_plan(plan, factors, backend=backend, interpret=interpret)


def mttkrp_pallas(
    tensor: SparseTensor,
    factors: Sequence[jax.Array],
    mode: int,
    *,
    plan: MTTKRPPlan | None = None,
    tile_nnz: int = 256,
    rows_per_block: int = 256,
    ordering: str = "lex",
    interpret: bool | None = None,
    backend: str | None = None,
) -> jax.Array:
    """MTTKRP for ``mode`` via the plan-based kernel family.
    Returns (I_mode, R).

    ``ordering`` selects the plan's nonzero execution order (repro.reorder,
    DESIGN.md §10); the kernel accumulates per output block, so any
    block-contiguous order is legal and the result is unchanged up to
    float summation order.  ``backend``/``interpret`` select the
    execution path (:func:`resolve_backend`).
    """
    if plan is None:
        plan = get_plan(
            tensor,
            mode,
            tile_nnz=tile_nnz,
            rows_per_block=rows_per_block,
            ordering=ordering,
        )
    return mttkrp_from_plan(plan, factors, backend=backend, interpret=interpret)
