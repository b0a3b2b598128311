"""Pallas spMTTKRP kernel vs pure-jnp oracle (interpret=True on CPU)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

# Real hypothesis when installed (requirements-dev.txt; CI), else a
# deterministic fallback sampler — the sweep runs either way.
from property_compat import given, settings, st

from repro.core.mttkrp import dense_mttkrp_oracle, mttkrp_ref
from repro.core.sparse_tensor import build_mttkrp_plan, random_sparse_tensor
from repro.kernels.mttkrp import mttkrp_pallas
from repro.kernels.mttkrp.ref import gather_factor_rows, mttkrp_plan_ref


def _factors(shape, rank, seed=0, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shape))
    return [jax.random.normal(k, (s, rank), dtype) for k, s in zip(keys, shape)]


def test_ref_matches_dense_oracle():
    t = random_sparse_tensor((13, 7, 9), nnz=60, seed=1)
    facs = _factors(t.shape, 4)
    for mode in range(3):
        got = np.asarray(mttkrp_ref(t, facs, mode))
        want = dense_mttkrp_oracle(t.to_dense(), [np.asarray(f) for f in facs], mode)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_plan_ref_matches_raw_ref():
    t = random_sparse_tensor((50, 40, 30), nnz=500, seed=2)
    facs = _factors(t.shape, 16)
    for mode in range(3):
        plan = build_mttkrp_plan(t, mode, tile_nnz=64, rows_per_block=32)
        gathered = gather_factor_rows(plan, facs)
        got = mttkrp_plan_ref(
            plan, jnp.asarray(plan.sorted_values), gathered, out_rows=t.shape[mode]
        )
        want = mttkrp_ref(t, facs, mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_pallas_matches_ref_3mode(mode):
    t = random_sparse_tensor((70, 33, 41), nnz=800, seed=3)
    facs = _factors(t.shape, 16)
    got = mttkrp_pallas(t, facs, mode, tile_nnz=128, rows_per_block=64, interpret=True)
    want = mttkrp_ref(t, facs, mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_pallas_4mode_and_5mode():
    for nm, shape in [(4, (20, 15, 10, 8)), (5, (9, 8, 7, 6, 5))]:
        t = random_sparse_tensor(shape, nnz=300, seed=nm)
        facs = _factors(t.shape, 8)
        for mode in range(nm):
            got = mttkrp_pallas(t, facs, mode, tile_nnz=64, rows_per_block=32, interpret=True)
            want = mttkrp_ref(t, facs, mode)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4
            )


def test_pallas_bf16_inputs():
    t = random_sparse_tensor((40, 30, 20), nnz=400, seed=7)
    facs = _factors(t.shape, 16, dtype=jnp.bfloat16)
    got = mttkrp_pallas(t, facs, 0, tile_nnz=128, rows_per_block=64, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = mttkrp_ref(t, [f.astype(jnp.float32) for f in facs], 0)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), rtol=3e-2, atol=3e-2
    )


def test_empty_blocks_are_zeroed():
    # Rows 100..199 of the output mode have no nonzeros -> their block must be 0.
    idx = np.array([[0, 0, 0], [1, 1, 1], [250, 2, 2]], np.int32)
    vals = np.array([1.0, 2.0, 3.0], np.float32)
    from repro.core.sparse_tensor import SparseTensor

    t = SparseTensor(idx, vals, (300, 4, 4))
    facs = _factors(t.shape, 8, seed=9)
    got = mttkrp_pallas(t, facs, 0, tile_nnz=64, rows_per_block=64, interpret=True)
    want = mttkrp_ref(t, facs, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(got)[100:200] == 0.0)


@settings(max_examples=25, deadline=None)
@given(
    i0=st.integers(3, 60),
    i1=st.integers(3, 40),
    i2=st.integers(3, 40),
    rank=st.sampled_from([1, 3, 8, 16, 24]),
    nnz=st.integers(1, 400),
    tile=st.sampled_from([8, 32, 128]),
    rpb=st.sampled_from([8, 32, 128]),
    mode=st.integers(0, 2),
    seed=st.integers(0, 2**16),
)
def test_pallas_property_sweep(i0, i1, i2, rank, nnz, tile, rpb, mode, seed):
    t = random_sparse_tensor((i0, i1, i2), nnz=nnz, seed=seed)
    facs = _factors(t.shape, rank, seed=seed % 97)
    got = mttkrp_pallas(t, facs, mode, tile_nnz=tile, rows_per_block=rpb, interpret=True)
    want = mttkrp_ref(t, facs, mode)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


# --- edge cases every impl must agree on (sharded runs the same cases in
# --- tests/test_distributed.py, which needs its 8-device subprocess) -------


def _assert_pallas_matches_ref(t, rank, *, tile_nnz=256, rows_per_block=64, seed=0):
    facs = _factors(t.shape, rank, seed=seed)
    got = mttkrp_pallas(
        t, facs, 0, tile_nnz=tile_nnz, rows_per_block=rows_per_block, interpret=True
    )
    want = mttkrp_ref(t, facs, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)
    return np.asarray(got)


def test_single_nonzero_tensor():
    from repro.core.sparse_tensor import SparseTensor

    t = SparseTensor(
        np.array([[5, 2, 7]], np.int32), np.array([2.5], np.float32), (11, 6, 9)
    )
    got = _assert_pallas_matches_ref(t, rank=8)
    # exactly one populated output row
    assert (np.abs(got).sum(axis=1) > 0).sum() == 1


def test_rank_one_padded_to_lane():
    # rank 1 stresses the LANE padding (1 -> 128) end to end.
    t = random_sparse_tensor((30, 20, 10), nnz=200, seed=21)
    _assert_pallas_matches_ref(t, rank=1)


def test_all_nonzeros_in_one_output_block():
    # Every output row < rows_per_block: a single VMEM block accumulates all.
    rng = np.random.default_rng(4)
    from repro.core.sparse_tensor import SparseTensor

    idx = np.stack(
        [
            rng.integers(0, 16, size=300),  # output rows all in block 0 (rpb=64)
            rng.integers(0, 40, size=300),
            rng.integers(0, 40, size=300),
        ],
        axis=1,
    ).astype(np.int32)
    t = SparseTensor(idx, rng.standard_normal(300).astype(np.float32), (256, 40, 40))
    got = _assert_pallas_matches_ref(t, rank=16)
    assert np.all(got[16:] == 0.0)


def test_nnz_smaller_than_tile():
    # 5 nonzeros, tile_nnz=256: one mostly-padding tile per touched block.
    t = random_sparse_tensor((40, 30, 20), nnz=5, seed=13)
    _assert_pallas_matches_ref(t, rank=16, tile_nnz=256, rows_per_block=64)


def test_plan_properties():
    t = random_sparse_tensor((100, 50, 50), nnz=1000, seed=11)
    plan = build_mttkrp_plan(t, 0, tile_nnz=32, rows_per_block=16)
    # Non-decreasing tile->block map covering every block.
    assert np.all(np.diff(plan.tile_block) >= 0)
    assert set(plan.tile_block.tolist()) == set(range(plan.num_blocks))
    # Every real nonzero preserved exactly once.
    assert (plan.sorted_values != 0).sum() == (t.values != 0).sum()
    # local_row consistent with sorted_indices and tile_block.
    blk = plan.sorted_indices[:, 0] // plan.rows_per_block
    np.testing.assert_array_equal(
        plan.local_row, plan.sorted_indices[:, 0] - blk * plan.rows_per_block
    )


def test_from_plan_path_builds_no_tensor_and_matches_ref():
    """The plan-only entry point slices from plan.shape[plan.mode] and
    never constructs a SparseTensor (the historical dummy-tensor shim
    allocated one per call in the distributed per-shard hot loop)."""
    from repro.kernels.mttkrp import mttkrp_pallas_from_plan

    t = random_sparse_tensor((40, 30, 20), nnz=400, seed=21)
    rng = np.random.default_rng(0)
    facs = [jnp.asarray(rng.random((s, 8), np.float32)) for s in t.shape]
    for mode in range(3):
        plan = build_mttkrp_plan(t, mode, tile_nnz=64, rows_per_block=8)
        got = np.asarray(mttkrp_pallas_from_plan(plan, facs, interpret=True))
        want = np.asarray(mttkrp_ref(t, facs, mode))
        assert got.shape == (t.shape[mode], 8)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_plan_device_buffers_uploaded_once():
    """Plan operands are device-memoized per plan object: every CP-ALS
    iteration reuses the same buffers instead of re-staging them."""
    from repro.kernels.mttkrp import plan_device_buffers

    t = random_sparse_tensor((40, 30, 20), nnz=200, seed=22)
    plan = build_mttkrp_plan(t, 0, tile_nnz=64, rows_per_block=8)
    a = plan_device_buffers(plan)
    b = plan_device_buffers(plan)
    assert a is b
    for buf, host in [
        (a.indices, plan.sorted_indices),
        (a.values, plan.sorted_values),
        (a.local_row, plan.local_row),
        (a.tile_block, plan.tile_block),
    ]:
        np.testing.assert_array_equal(np.asarray(buf), host)
    # A distinct plan (even with identical contents) gets its own buffers.
    plan2 = build_mttkrp_plan(t, 0, tile_nnz=64, rows_per_block=8)
    assert plan_device_buffers(plan2) is not a


# --- backend dispatch + edge geometry on BOTH execution paths -------------
# (DESIGN.md §13: the interpret emulator and the compiled XLA fallback
# must agree on the exact cases where the streaming-accumulation
# predication is easiest to get wrong.)

EDGE_BACKENDS = ("interpret", "xla")


@pytest.mark.parametrize("backend", EDGE_BACKENDS)
def test_single_tile_single_block(backend):
    # num_tiles == 1: the only tile is simultaneously first (t==0) and
    # last (t==num_tiles-1) — init and flush fire on the same grid step.
    t = random_sparse_tensor((30, 20, 10), nnz=40, seed=31)
    facs = _factors(t.shape, 8, seed=31)
    got = mttkrp_pallas(
        t, facs, 0, tile_nnz=64, rows_per_block=32, backend=backend
    )
    want = mttkrp_ref(t, facs, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", EDGE_BACKENDS)
def test_t0_wrap_predication(backend):
    # Every nonzero lands in output block 0 across MULTIPLE tiles, so the
    # wrapping t-1 load at t==0 sees the LAST tile — which shares block 0.
    # Without the t==0 short-circuit the first tile would accumulate into
    # uninitialized scratch instead of initializing it.
    rng = np.random.default_rng(32)
    from repro.core.sparse_tensor import SparseTensor

    idx = np.stack(
        [
            rng.integers(0, 30, size=300),  # all rows < rows_per_block=32
            rng.integers(0, 25, size=300),
            rng.integers(0, 25, size=300),
        ],
        axis=1,
    ).astype(np.int32)
    t = SparseTensor(idx, rng.standard_normal(300).astype(np.float32), (32, 25, 25))
    facs = _factors(t.shape, 8, seed=32)
    got = mttkrp_pallas(
        t, facs, 0, tile_nnz=64, rows_per_block=32, backend=backend
    )
    want = mttkrp_ref(t, facs, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("backend", EDGE_BACKENDS)
def test_rank_exactly_lane(backend):
    # rank == LANE(128): zero padding columns — the r_pad % LANE check
    # passes on the exact boundary and the full lane width is live data.
    from repro.kernels.mttkrp.kernel import LANE

    t = random_sparse_tensor((20, 15, 10), nnz=100, seed=33)
    facs = _factors(t.shape, LANE, seed=33)
    got = mttkrp_pallas(
        t, facs, 0, tile_nnz=64, rows_per_block=16, backend=backend
    )
    assert got.shape == (t.shape[0], LANE)
    want = mttkrp_ref(t, facs, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_backends_bitwise_consistent_with_ref_tolerance():
    # The two CPU paths must agree with each other at least as tightly as
    # either agrees with the oracle (same f32 accumulation tree per tile).
    t = random_sparse_tensor((37, 29, 23), nnz=500, seed=34)
    facs = _factors(t.shape, 16, seed=34)
    a = np.asarray(mttkrp_pallas(t, facs, 0, backend="interpret"))
    b = np.asarray(mttkrp_pallas(t, facs, 0, backend="xla"))
    np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def _stacked_operand_mttkrp(plan, factors, bufs):
    """The kernel fed its factor operand built the per-factor way: one
    ``take`` per input factor, stacked, then lane-padded."""
    from repro.kernels.mttkrp.kernel import LANE, mttkrp_pallas_call

    rank = factors[0].shape[1]
    r_pad = -(-rank // LANE) * LANE
    other = [k for k in range(len(factors)) if k != plan.mode]
    gathered = jnp.stack([jnp.take(factors[k], bufs.indices[:, k], axis=0) for k in other])
    gathered = jnp.pad(gathered, ((0, 0), (0, 0), (0, r_pad - rank)))
    out = mttkrp_pallas_call(
        bufs.tile_block, bufs.values, bufs.local_row, gathered,
        tile_nnz=plan.tile_nnz, rows_per_block=plan.rows_per_block,
        num_blocks=plan.num_blocks, interpret=True,
    )
    return out[: plan.shape[plan.mode], :rank].astype(factors[plan.mode].dtype)


def _rows_100_to_199_empty():
    from repro.core.sparse_tensor import SparseTensor

    idx = np.array([[0, 0, 0], [1, 1, 1], [250, 2, 2]], np.int32)
    return SparseTensor(idx, np.array([1.0, 2.0, 3.0], np.float32), (300, 4, 4))


@pytest.mark.parametrize(
    "case,rank",
    [("n3", 16), ("n5", 16), ("n3", 128), ("n5", 128), ("empty_blocks", 16),
     ("fused_restarts_2", 16)],
)
def test_single_gather_operand_is_bit_identical(case, rank):
    """The one-table gather hands the kernel the same operand as one
    gather per factor followed by stack and lane pad: the MTTKRP is
    bit for bit the same, padding rows and empty blocks included, and
    under the restart vmap of ``FusedCPALS``."""
    from repro.core.cp_als_fused import FusedCPALS
    from repro.kernels.mttkrp.ops import get_plan, mttkrp_from_plan, plan_device_buffers

    t = {
        "n3": lambda: random_sparse_tensor((70, 33, 41), nnz=500, seed=41),
        "n5": lambda: random_sparse_tensor((9, 8, 7, 6, 5), nnz=300, seed=42),
        "empty_blocks": _rows_100_to_199_empty,
        "fused_restarts_2": lambda: random_sparse_tensor((40, 30, 20), nnz=300, seed=43),
    }[case]()
    if case == "fused_restarts_2":
        executor = FusedCPALS(t, rank, impl="pallas", backend="interpret",
                              tile_nnz=64, rows_per_block=32)
        mode_bufs = executor.operands[0]
        facs = [jnp.stack([f, -f]) for f in _factors(t.shape, rank, seed=43)]
        for mode in range(t.nmodes):
            plan = get_plan(t, mode, tile_nnz=64, rows_per_block=32)
            bufs = mode_bufs[mode]
            got = jax.jit(jax.vmap(
                lambda fs: mttkrp_from_plan(plan, fs, backend="interpret", bufs=bufs)
            ))(facs)
            want = jax.jit(jax.vmap(lambda fs: _stacked_operand_mttkrp(plan, fs, bufs)))(facs)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    facs = _factors(t.shape, rank, seed=rank)
    outs = []
    for mode in range(t.nmodes):
        plan = build_mttkrp_plan(t, mode, tile_nnz=64, rows_per_block=32)
        outs.append(np.asarray(mttkrp_from_plan(plan, facs, backend="interpret")))
        want = _stacked_operand_mttkrp(plan, facs, plan_device_buffers(plan))
        np.testing.assert_array_equal(outs[-1], np.asarray(want))
    if case == "empty_blocks":
        assert np.all(outs[0][100:200] == 0.0)


@pytest.mark.parametrize(
    "shape,mode,table_rows,apart,rank",
    [
        ((70, 12, 40), 2, 20, [0], 16),  # the first slot
        ((40, 12, 70), 0, 0, [2], 16),  # the last slot; a table of the shortest alone
        ((40, 12, 70), 0, 20, [2], 128),  # no lane pad
        ((9, 50, 7, 8), 0, 20, [1], 16),  # the first of three slots
        ((9, 50, 7, 8), 3, 20, [1], 16),  # the middle slot
        ((9, 40, 7, 30, 5), 4, 20, [1, 3], 16),  # two apart, both middle
        ((9, 40, 7, 30, 5), 0, 20, [1, 3], 16),  # two apart, first and third
    ],
)
def test_apart_gather_operand_is_bit_identical(monkeypatch, shape, mode, table_rows, apart, rank):
    """With the table's budget cut so that one or two factors are
    gathered apart, the kernel gets the same factor operand as from the
    one table, bit for bit, and the MTTKRP matches the reference."""
    from repro.kernels.mttkrp import ops
    from repro.kernels.mttkrp.kernel import LANE

    t = random_sparse_tensor(shape, nnz=300, seed=len(shape) + mode)
    facs = _factors(t.shape, rank, seed=mode)
    plan = build_mttkrp_plan(t, mode, tile_nnz=64, rows_per_block=32)
    operands = []

    def kernel(*args, **kwargs):
        operands.append(np.asarray(args[3]))
        return ops_kernel(*args, **kwargs)

    ops_kernel = ops.mttkrp_pallas_call
    monkeypatch.setattr(ops, "mttkrp_pallas_call", kernel)
    one_table = np.asarray(ops.mttkrp_from_plan(plan, facs, backend="interpret"))
    r_pad = -(-rank // LANE) * LANE
    monkeypatch.setattr(ops, "TABLE_BYTES", table_rows * r_pad * 4)
    other = [k for k in range(len(shape)) if k != mode]
    assert [k for k in other if k not in ops._table_factors(facs, other, r_pad)] == apart
    split = np.asarray(ops.mttkrp_from_plan(plan, facs, backend="interpret"))

    np.testing.assert_array_equal(operands[1], operands[0])
    np.testing.assert_array_equal(split, one_table)
    want = mttkrp_ref(t, facs, mode)
    np.testing.assert_allclose(split, np.asarray(want), rtol=1e-4, atol=1e-4)


def test_pallas_call_geometry_valueerrors():
    """Geometry violations raise ValueError with the offending shapes
    (replacing bare asserts that vanish under ``python -O``)."""
    from repro.kernels.mttkrp.kernel import mttkrp_pallas_call

    tile_block = jnp.zeros((4,), jnp.int32)
    values = jnp.zeros((256,), jnp.float32)
    local = jnp.zeros((256,), jnp.int32)
    gathered = jnp.zeros((2, 256, 128), jnp.float32)
    ok = dict(tile_nnz=64, rows_per_block=32, num_blocks=1, interpret=True)

    with pytest.raises(ValueError, match="not a multiple of tile_nnz=96"):
        mttkrp_pallas_call(tile_block, values, local, gathered,
                           **{**ok, "tile_nnz": 96})
    with pytest.raises(ValueError, match="tile_block shape"):
        mttkrp_pallas_call(tile_block[:-1], values, local, gathered, **ok)
    with pytest.raises(ValueError, match=r"not LANE\(128\)-padded"):
        mttkrp_pallas_call(
            tile_block, values, local, jnp.zeros((2, 256, 64), jnp.float32), **ok
        )
    with pytest.raises(ValueError, match=r"SUBLANE\(8\)"):
        mttkrp_pallas_call(tile_block, values, local, gathered,
                           **{**ok, "rows_per_block": 12})


def test_resolve_backend_precedence(monkeypatch):
    from repro.kernels.common import PALLAS_INTERPRET_ENV
    from repro.kernels.mttkrp.ops import resolve_backend

    monkeypatch.delenv(PALLAS_INTERPRET_ENV, raising=False)
    native = resolve_backend(None)
    assert native in ("mosaic", "triton", "xla")  # compiled default everywhere
    if jax.default_backend() == "cpu":
        assert native == "xla"

    # explicit backend beats everything, including the interpret flag
    assert resolve_backend("interpret") == "interpret"
    assert resolve_backend("xla", interpret=True) == "xla"
    with pytest.raises(ValueError, match="backend='cuda'"):
        resolve_backend("cuda")

    # explicit interpret flag
    assert resolve_backend(None, interpret=True) == "interpret"
    assert resolve_backend(None, interpret=False) == native

    # env override (only consulted when neither explicit input is given)
    monkeypatch.setenv(PALLAS_INTERPRET_ENV, "1")
    assert resolve_backend(None) == "interpret"
    assert resolve_backend(None, interpret=False) == native
    monkeypatch.setenv(PALLAS_INTERPRET_ENV, "0")
    assert resolve_backend(None) == native
    monkeypatch.setenv(PALLAS_INTERPRET_ENV, "maybe")
    with pytest.raises(ValueError, match=PALLAS_INTERPRET_ENV):
        resolve_backend(None)
