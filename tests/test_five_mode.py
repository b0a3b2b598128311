"""A 5-mode hyper-sparse tensor (LBNL-network's shape at a CPU size)
through the fused executor, against a plain float64 CP-ALS; and the
draw that coalesces on coordinate rows, so that a tensor whose key space
passes 2**63 (LBNL-network's five modes) can be drawn at all.

The tensor keeps what the published one forces: five modes (four
gathered factor rows per nonzero), one mode with more rows than the
tensor has nonzeros, several output blocks per mode, and blocks of that
mode with no nonzero at all (one all-padding tile each).
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cp_als import cp_init
from repro.core.cp_als_fused import FusedCPALS
from repro.core.mttkrp import mttkrp_ref
from repro.core.sparse_tensor import (
    _first_of_each_row,
    build_mttkrp_plan,
    random_sparse_tensor,
)
from repro.data.frostt import FROSTT_TENSORS
from repro.data.synthetic_tensors import make_frostt_like
from repro.kernels.mttkrp.ops import get_plan, mttkrp_from_plan

DIMS = (6, 9, 6, 9, 4000)
NNZ = 300
RANK = 4
TILE, ROWS = 8, 32
SWEEPS = 4
LBNL = FROSTT_TENSORS["LBNL"]


@pytest.fixture(scope="module")
def tensor():
    return random_sparse_tensor(DIMS, NNZ, seed=11, zipf_a=LBNL.zipf_alpha)


def test_tensor_is_five_mode_and_hyper_sparse(tensor):
    assert tensor.nmodes == 5 and tensor.shape[4] > tensor.nnz
    plan = build_mttkrp_plan(tensor, 4, tile_nnz=TILE, rows_per_block=ROWS)
    per_block = np.bincount(tensor.indices[:, 4] // ROWS, minlength=plan.num_blocks)
    assert plan.num_blocks > 1 and (per_block == 0).any() and (per_block > 0).sum() > 1
    for m in range(4):
        assert build_mttkrp_plan(tensor, m, tile_nnz=TILE, rows_per_block=4).num_blocks > 1


# -- plain float64 CP-ALS (textbook; Kolda & Bader §3.4) -----------------------


def _mttkrp64(idx, vals, factors, mode):
    rows = vals[:, None].copy()
    for k, f in enumerate(factors):
        if k != mode:
            rows = rows * f[idx[:, k]]
    out = np.zeros_like(factors[mode])
    np.add.at(out, idx[:, mode], rows)
    return out


def _cp_als64(tensor, factors, sweeps):
    idx, vals = tensor.indices, tensor.values.astype(np.float64)
    factors = [np.asarray(f, np.float64) for f in factors]
    norm_x = np.sqrt(np.sum(vals**2))
    fits = []
    for _ in range(sweeps):
        for mode in range(len(factors)):
            gram = np.ones((RANK, RANK))
            for k, f in enumerate(factors):
                if k != mode:
                    gram *= f.T @ f
            a = np.linalg.solve(gram + 1e-8 * np.eye(RANK), _mttkrp64(idx, vals, factors, mode).T).T
            weights = np.maximum(np.linalg.norm(a, axis=0), 1e-12)
            factors[mode] = a / weights
        gram = np.ones((RANK, RANK))
        for f in factors:
            gram *= f.T @ f
        rows = np.ones((len(vals), RANK))
        for k, f in enumerate(factors):
            rows *= f[idx[:, k]]
        resid2 = norm_x**2 - 2 * vals @ (rows @ weights) + weights @ gram @ weights
        fits.append(1 - np.sqrt(max(resid2, 0.0)) / norm_x)
    return factors, weights, np.array(fits)


@pytest.mark.parametrize("backend", ["interpret", "xla"])
def test_fused_sweeps_match_plain_float64_cp_als(tensor, backend):
    executor = FusedCPALS(tensor, RANK, impl="pallas", backend=backend, tile_nnz=TILE,
                          rows_per_block=ROWS)
    with jax.default_matmul_precision("highest"):
        got = executor.run(n_iters=SWEEPS, fit_every=1, tol=0.0, seed=5).state
    want_f, want_w, want_fits = _cp_als64(tensor, cp_init(tensor, RANK, seed=5), SWEEPS)
    np.testing.assert_allclose(got.fits, want_fits, rtol=0, atol=2e-5)
    np.testing.assert_allclose(np.asarray(got.weights), want_w, rtol=2e-4)
    for g, w in zip(got.factors, want_f):
        np.testing.assert_allclose(np.asarray(g), w, rtol=0, atol=2e-4)


@pytest.mark.parametrize("backend", ["interpret", "xla"])
@pytest.mark.parametrize("mode", range(len(DIMS)))
def test_each_mode_mttkrp_matches_the_reference(tensor, backend, mode):
    keys = jax.random.split(jax.random.PRNGKey(2), len(DIMS))
    factors = [jax.random.normal(k, (d, RANK)) for k, d in zip(keys, DIMS)]
    plan = get_plan(tensor, mode, tile_nnz=TILE, rows_per_block=ROWS)
    with jax.default_matmul_precision("highest"):
        got = mttkrp_from_plan(plan, factors, backend=backend)
        want = mttkrp_ref((jnp.asarray(tensor.indices), jnp.asarray(tensor.values),
                           tensor.shape), factors, mode)
    assert got.shape == (DIMS[mode], RANK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    exact = _mttkrp64(tensor.indices, tensor.values.astype(np.float64),
                      [np.asarray(f, np.float64) for f in factors], mode)
    np.testing.assert_allclose(np.asarray(got), exact, rtol=1e-5, atol=1e-5)


# -- the draw -----------------------------------------------------------------


def test_draws_lbnl_at_its_published_dims():
    t = random_sparse_tensor(LBNL.dims, 3000, seed=0, zipf_a=LBNL.zipf_alpha)
    assert t.shape == LBNL.dims and 0 < t.nnz <= 3000
    assert (t.indices >= 0).all() and (t.indices < np.array(LBNL.dims)).all()
    rows = [tuple(r) for r in t.indices]
    assert rows == sorted(set(rows))  # coalesced, in lexicographic order


def test_make_frostt_like_draws_the_published_lbnl(monkeypatch):
    """At scale 1.0 the stand-in is the published tensor's shape and
    nonzero count; the draw itself is shrunk here to keep the test fast."""
    import repro.data.synthetic_tensors as st

    seen = {}

    def small(dims, nnz, **kw):
        seen.update(dims=tuple(dims), nnz=nnz)
        return random_sparse_tensor(dims, 3000, **kw)

    monkeypatch.setattr(st, "random_sparse_tensor", small)
    t = make_frostt_like("LBNL", scale=1.0)
    assert seen == {"dims": LBNL.dims, "nnz": LBNL.nnz} and t.shape == LBNL.dims


def test_first_of_each_row_is_the_flat_key_unique_where_the_key_fits():
    rng = np.random.default_rng(4)
    for dims in [(5, 3, 4), (40, 30, 20, 10), (12_100, 9_200, 28_800)]:
        idx = np.stack([rng.integers(0, d, 5000) for d in dims], axis=1)
        _, want = np.unique(np.ravel_multi_index(tuple(idx.T), dims), return_index=True)
        np.testing.assert_array_equal(_first_of_each_row(idx, dims), want)


def test_first_of_each_row_splits_a_key_space_past_int64():
    dims = (2**40, 2**40, 3)
    rows = np.array([[5, 2**39, 1], [5, 2**39, 0], [1, 7, 2], [5, 2**39, 1], [1, 7, 2]])
    np.testing.assert_array_equal(_first_of_each_row(rows, dims), [2, 1, 0])


# Digests of (indices, values) drawn before coalescing moved onto the rows.
GOLDEN = [
    ((20, 14, 18), 240, {"seed": 3}, 236, "e3d0e773180dd33d"),
    ((12_100, 9_200, 28_800), 5000, {"seed": 0, "zipf_a": 0.85}, 5000, "6d917e126925ab79"),
    ((64, 48, 80, 30), 4000, {"seed": 7, "zipf_a": 1.1, "correlation": 0.5}, 3191,
     "96f11fad5fa19e5e"),
    ((30, 40, 50), 6000, {"seed": 1, "shuffle": True}, 5717, "48fdbf324212eabf"),
]


@pytest.mark.parametrize("shape,nnz,kw,n,digest", GOLDEN)
def test_draws_where_the_key_fits_are_byte_identical(shape, nnz, kw, n, digest):
    t = random_sparse_tensor(shape, nnz, **kw)
    assert t.nnz == n
    assert hashlib.sha256(t.indices.tobytes() + t.values.tobytes()).hexdigest()[:16] == digest
