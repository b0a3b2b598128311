"""Pallas TPU kernel for mode-ordered sparse MTTKRP.

TPU-native translation of the paper's accelerator datapath (DESIGN.md §2):

  * the *O-SRAM partial-sum buffer* becomes a VMEM scratch accumulator
    carried across consecutive grid steps (legal because the plan sorts
    nonzeros by output mode — the paper's Algorithm 1 ordering);
  * the *cache subsystem* becomes pre-staged factor rows delivered tile-by-
    tile through the Pallas grid pipeline (automatic HBM→VMEM double
    buffering takes the role of the DMA stream units);
  * the *scatter-accumulate* becomes a one-hot ⋅ MXU matmul
    ``A_blk += onehot(local_row) @ (vals · ∘_k F_k[rows])`` — the irregular
    write pattern is converted into systolic compute, which is the TPU
    replacement for the 200-port concurrent O-SRAM write.

Grid: one step per nonzero tile.  Scalar-prefetched ``tile_block`` drives
the output BlockSpec index map, so each grid step lands on the VMEM block
holding its output rows.

**Streaming accumulation** (DESIGN.md §13): per-output-row partial state
lives in a VMEM scratch accumulator carried through the grid scan — the
AttentionEngine online-softmax structure, where the running (m, l, acc)
state rides in scratch across KV tiles.  First tile of a block
initializes the scratch, interior tiles accumulate into it, and only the
LAST tile of the block writes ``out_ref`` — one output store per block
instead of a read-modify-write of the output block on every tile, which
is both the paper's store-each-row-exactly-once property (Algorithm 1
line 11) and what lets Mosaic keep the output block write-only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128  # TPU lane width — rank is padded to this
SUBLANE = 8


def _kernel(
    tile_block_ref, vals_ref, local_ref, fac_ref, out_ref, acc_ref, *, nfac: int
):
    t = pl.program_id(0)
    num_tiles = pl.num_programs(0)
    blk = tile_block_ref[t]
    # ``logical_or`` evaluates both sides, so the t-1 load is clamped to
    # stay inside SMEM; at t==0 it then reads tile 0 itself, and only the
    # t==0 term makes the first tile initialize.
    first = jnp.logical_or(t == 0, blk != tile_block_ref[jnp.maximum(t - 1, 0)])
    # Last tile of this output block; the t+1 load is clamped so the final
    # tile (flushed unconditionally) never indexes past the grid.
    last = jnp.logical_or(
        t == num_tiles - 1,
        tile_block_ref[jnp.minimum(t + 1, num_tiles - 1)] != blk,
    )

    acc_t = jnp.float32
    prod = fac_ref[0].astype(acc_t)
    for k in range(1, nfac):
        prod = prod * fac_ref[k].astype(acc_t)

    # values/local_row arrive as (1, tile_nnz) lane-major rows, so the
    # value scaling is folded into the one-hot along its nonzero axis
    # instead of being transposed onto prod's sublanes.
    rows_per_block = out_ref.shape[0]
    tile_nnz = prod.shape[0]
    row_iota = jax.lax.broadcasted_iota(jnp.int32, (rows_per_block, tile_nnz), 0)
    scatter = jnp.where(
        row_iota == local_ref[...], vals_ref[...].astype(acc_t), 0.0
    )
    # HIGHEST pins an fp32 contraction instead of leaving the f32 matmul's
    # precision to Mosaic's default.
    contrib = jnp.dot(
        scatter,
        prod,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )

    @pl.when(first)
    def _init():
        acc_ref[...] = contrib

    @pl.when(jnp.logical_not(first))
    def _accum():
        acc_ref[...] += contrib

    @pl.when(last)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(
    jax.jit,
    static_argnames=("tile_nnz", "rows_per_block", "num_blocks", "interpret"),
)
def mttkrp_pallas_call(
    tile_block: jax.Array,  # (num_tiles,) int32, non-decreasing
    values: jax.Array,  # (nnz_pad,)
    local_row: jax.Array,  # (nnz_pad,) int32 in [0, rows_per_block)
    gathered: jax.Array,  # (K, nnz_pad, R_pad)
    *,
    tile_nnz: int,
    rows_per_block: int,
    num_blocks: int,
    interpret: bool = False,
) -> jax.Array:
    """Returns (num_blocks * rows_per_block, R_pad) float32 partial-sum grid."""
    nfac, nnz_pad, r_pad = gathered.shape
    # Geometry checks raise (not assert): they must survive ``python -O``
    # and fail with the offending shapes instead of an opaque Mosaic or
    # scatter error from inside the jit trace.
    if nnz_pad % tile_nnz != 0:
        raise ValueError(
            f"nnz_pad={nnz_pad} is not a multiple of tile_nnz={tile_nnz} "
            "(the plan pads every block to whole tiles — was the gathered "
            "operand built from a different plan?)"
        )
    num_tiles = nnz_pad // tile_nnz
    if tile_block.shape != (num_tiles,):
        raise ValueError(
            f"tile_block shape {tile_block.shape} does not match the "
            f"{num_tiles} tiles implied by nnz_pad={nnz_pad} / "
            f"tile_nnz={tile_nnz}"
        )
    if r_pad % LANE != 0:
        raise ValueError(
            f"gathered rank {r_pad} is not LANE({LANE})-padded"
        )
    if rows_per_block % SUBLANE != 0:
        raise ValueError(
            f"rows_per_block={rows_per_block} is not a multiple of "
            f"SUBLANE({SUBLANE})"
        )

    # values/local_row go in as (1, nnz_pad) rows with (1, tile_nnz)
    # blocks: a 1-D (tile_nnz,) block only matches XLA's T(1024) layout
    # of a 1-D f32/int32 array when tile_nnz is a multiple of 1024.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((1, tile_nnz), lambda t, tb: (0, t)),
            pl.BlockSpec((1, tile_nnz), lambda t, tb: (0, t)),
            pl.BlockSpec((nfac, tile_nnz, r_pad), lambda t, tb: (0, t, 0)),
        ],
        out_specs=pl.BlockSpec((rows_per_block, r_pad), lambda t, tb: (tb[t], 0)),
        scratch_shapes=[pltpu.VMEM((rows_per_block, r_pad), jnp.float32)],
    )
    out_shape = jax.ShapeDtypeStruct((num_blocks * rows_per_block, r_pad), jnp.float32)
    kernel = functools.partial(_kernel, nfac=nfac)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        interpret=interpret,
        name="mttkrp",  # the kernel's name in compiled programs and traces
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(tile_block, values.reshape(1, nnz_pad), local_row.reshape(1, nnz_pad), gathered)
