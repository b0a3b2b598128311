"""jit-compiled XLA fallback for plan-based MTTKRP (DESIGN.md §13).

The Pallas kernel only *compiles* for TPU (Mosaic) and GPU (Triton); on
CPU the historical choice was the pure-Python interpreter, which is an
emulation artifact, not an execution path — benches skipped every cell
above 20k nonzeros because interpret-mode wall time is meaningless.

This module is the third leg of the ``kernels.mttkrp.ops`` backend
dispatch: a tiled segment-sum over the SAME ``MTTKRPPlan`` buffers the
Pallas kernel consumes, jit-compiled by stock XLA so a compiled path
exists on every backend (including CPU-only CI).  Same plan, same
gather, same accumulation order up to float re-association — parity
with the ref implementation is tested to float32 tolerance.

Structure: the nonzero stream is processed in fixed-size chunks through
a ``lax.scan`` carrying the output accumulator, with each chunk doing
``acc.at[rows].add(vals · ∘_k F_k[rows_k])``.  Chunking bounds the live
Hadamard-product working set to ``nnz_chunk × rank`` (the analogue of
the kernel's per-tile VMEM footprint) instead of materializing all
``nnz_pad × rank`` products at once.  The scan is vmappable, which the
fused executor's multi-restart path requires.

Correctness leans on a plan invariant (core.sparse_tensor): every
padded entry carries value 0 and points its indices at its block's
first output row — a REAL row in ``[0, I_mode)`` — so padding
contributes an exact IEEE ``+0.0`` and the scatter never writes out of
bounds.  No block/lane padding is needed here at all: the accumulator
is exactly ``(I_mode, rank)``.
"""

from __future__ import annotations

import functools
from typing import TYPE_CHECKING, Sequence

import jax
import jax.numpy as jnp

from repro.core.sparse_tensor import MTTKRPPlan

if TYPE_CHECKING:
    from repro.kernels.mttkrp.ops import PlanBuffers

__all__ = ["DEFAULT_NNZ_CHUNK", "mttkrp_xla_call", "mttkrp_xla_from_plan"]

# Nonzeros per scan step.  Large enough that the per-step gather/multiply
# amortizes scan overhead, small enough that the chunk's Hadamard product
# (nnz_chunk × rank floats) stays cache-resident for typical ranks.
DEFAULT_NNZ_CHUNK = 65_536


@functools.partial(jax.jit, static_argnames=("i_out", "nnz_chunk"))
def mttkrp_xla_call(
    rows: jax.Array,  # (nnz_pad,) int32 output rows, in [0, i_out)
    values: jax.Array,  # (nnz_pad,)
    gathered: jax.Array,  # (K, nnz_pad, R) factor rows for the other modes
    *,
    i_out: int,
    nnz_chunk: int,
) -> jax.Array:
    """Chunked scatter-accumulate; returns (i_out, R) float32."""
    nfac, nnz_pad, rank = gathered.shape
    if rows.shape != (nnz_pad,):
        raise ValueError(
            f"rows shape {rows.shape} does not match gathered nnz_pad={nnz_pad}"
        )
    nchunks = max(1, -(-nnz_pad // nnz_chunk))
    pad = nchunks * nnz_chunk - nnz_pad
    if pad:
        # Padding mirrors the plan's own convention: value 0 at row 0.
        rows = jnp.pad(rows, (0, pad))
        values = jnp.pad(values, (0, pad))
        gathered = jnp.pad(gathered, ((0, 0), (0, pad), (0, 0)))

    rows_c = rows.reshape(nchunks, nnz_chunk)
    vals_c = values.reshape(nchunks, nnz_chunk)
    gath_c = jnp.moveaxis(
        gathered.reshape(nfac, nchunks, nnz_chunk, rank), 1, 0
    )  # (nchunks, K, nnz_chunk, R)

    acc_t = jnp.float32

    def body(acc, xs):
        rr, vv, gg = xs
        prod = gg[0].astype(acc_t)
        for k in range(1, nfac):
            prod = prod * gg[k].astype(acc_t)
        prod = prod * vv.astype(acc_t)[:, None]
        return acc.at[rr].add(prod), None

    acc0 = jnp.zeros((i_out, rank), acc_t)
    acc, _ = jax.lax.scan(body, acc0, (rows_c, vals_c, gath_c))
    return acc


def mttkrp_xla_from_plan(
    plan: MTTKRPPlan,
    factors: Sequence[jax.Array],
    bufs: PlanBuffers,
    *,
    nnz_chunk: int = DEFAULT_NNZ_CHUNK,
) -> jax.Array:
    """MTTKRP for ``plan.mode`` on the compiled XLA path.

    Returns (I_mode, R) in the factor dtype — the same contract as
    ``ops.mttkrp_pallas_from_plan``, from the same device-resident plan
    buffers (so a plan already warmed for the Pallas path re-stages
    nothing when the dispatch layer picks this backend instead).
    ``bufs`` are those buffers (``ops.plan_device_buffers``, or jit
    arguments of a traced caller).
    """
    mode = plan.mode
    other = [k for k in range(len(factors)) if k != mode]
    # The same scopes as ops._mttkrp_pallas_exec: gather, then kernel.
    with jax.named_scope("mttkrp_gather"):
        gathered = jnp.stack(
            [jnp.take(factors[k], bufs.indices[:, k], axis=0) for k in other]
        )  # (K, nnz_pad, R)
    with jax.named_scope("mttkrp_kernel"):
        out = mttkrp_xla_call(
            bufs.indices[:, mode],
            bufs.values,
            gathered,
            i_out=plan.shape[mode],
            nnz_chunk=min(nnz_chunk, int(bufs.values.shape[0])),
        )
    return out.astype(factors[mode].dtype)
