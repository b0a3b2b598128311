"""``lbnl.sweep`` through the harness at a CPU size that keeps its
shape: five modes, and a last mode with more rows than the tensor has
nonzeros (the look for a chip is skipped).

A sound run is ``correct``; a sweep that returns its state unchanged,
factors altered where they are produced, and the ``bfloat16`` control
each turn it false.  Also: the benchmark's row-coalescing draw is
``generators.zipf_tensor``'s wherever that one works, and the
reference's MTTKRP is a dense contraction on a tiny 5-mode tensor.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import draws, generators, harness, reference

NAME = "lbnl.sweep"
# mode 0 keeps more rows than the rank, so that row_gap sees each row's error
SMALL = {"dims": [40, 60, 40, 60, 3000], "nnz": 2500}


def _run(seconds=0.4):
    return harness.run_cell(NAME, 2**31 + 77, seconds, False, 0.0, devices=jax.devices(),
                            overrides=SMALL)


def _patch_sweep(monkeypatch, wrap):
    from repro.core.cp_als_fused import FusedCPALS

    sweep_fn = FusedCPALS.sweep_fn
    monkeypatch.setattr(FusedCPALS, "sweep_fn",
                        lambda self, length, batched: wrap(sweep_fn(self, length, batched)))


def test_the_cell_is_five_mode_and_hyper_sparse():
    dims = {**harness.cell(NAME)["config"], **SMALL}["dims"]
    assert len(dims) == 5 and dims[-1] > SMALL["nnz"]


def test_sound_run_is_correct():
    result = _run()
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_sweep_that_returns_its_state_unchanged_is_caught(monkeypatch):
    _patch_sweep(monkeypatch, lambda fn: lambda ops, factors, weights: (
        factors, weights, fn(ops, factors, weights)[2]))
    assert not _run()["correct"]


def test_altered_factors_are_caught(monkeypatch):
    def wrap(fn):
        def run(ops, factors, weights):
            f, w, fits = fn(ops, factors, weights)
            return (f[0].at[0, 0].add(0.05),) + tuple(f[1:]), w, fits

        return run

    _patch_sweep(monkeypatch, wrap)
    assert not _run()["correct"]


def test_bfloat16_control_fails_the_limits():
    info = harness.cell(NAME)
    config = {**info["config"], **SMALL}
    ctx = harness.Context(name=NAME, seed=5, seconds=0.3, trace=False, config=config,
                          params=info["spec"]["params"], limits=info["spec"]["limits"])
    driver = harness.load_module(harness.BENCH / "traffic" / f"{info['spec']['kind']}.py")
    with harness.precision(config):
        state = driver.setup(ctx)
        win = driver.window(ctx, state)
        readings = driver.check(ctx, state, win, contract="bfloat16")
    assert any(readings[k] > v for k, v in ctx.limits.items()), readings


@pytest.mark.parametrize("dims,nnz,zipf_a,seed", [
    ((64, 48, 80), 3000, 0.85, 0),
    ((12_100, 9_200, 28_800), 20_000, 0.85, 3),
    ((16, 12, 16, 12, 900), 2000, 0.75, 2**31 - 1),
])
def test_row_draw_is_the_flat_key_draw_where_that_works(dims, nnz, zipf_a, seed):
    got = draws.zipf_tensor_rows(dims, nnz, zipf_a, seed)
    want = generators.zipf_tensor(dims, nnz, zipf_a, seed)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def test_row_draw_fits_lbnl_where_the_flat_key_does_not():
    dims = harness.cell(NAME)["config"]["dims"]
    with pytest.raises(ValueError):
        generators.zipf_tensor(dims, 1000, 0.75, 0)
    idx, vals = draws.zipf_tensor_rows(dims, 1000, 0.75, 0)
    assert idx.shape == (vals.shape[0], 5) and 0 < vals.shape[0] <= 1000
    assert len({tuple(r) for r in idx}) == idx.shape[0]
    assert (idx < np.array(dims)).all()


@pytest.mark.parametrize("mode", range(5))
def test_reference_mttkrp_is_the_dense_contraction_on_five_modes(mode):
    dims, rank = (3, 4, 2, 5, 6), 3
    rng = np.random.default_rng(mode)
    dense = rng.standard_normal(dims) * (rng.random(dims) < 0.3)
    idx = np.argwhere(dense)
    vals = dense[tuple(idx.T)]
    factors = [rng.standard_normal((d, rank)) for d in dims]
    letters = "abcde"
    spec = f"{letters}," + ",".join(f"{c}r" for k, c in enumerate(letters) if k != mode)
    want = np.einsum(f"{spec}->{letters[mode]}r", dense,
                     *[f for k, f in enumerate(factors) if k != mode])
    got_np = reference.mttkrp(reference.numpy_arrays(), idx, vals, factors, mode)
    np.testing.assert_allclose(got_np, want, rtol=1e-12, atol=1e-12)
    with jax.default_matmul_precision("highest"):
        got_jax = reference.mttkrp(reference.jax_arrays(), jnp.asarray(idx),
                                   jnp.asarray(vals, jnp.float32),
                                   [jnp.asarray(f, jnp.float32) for f in factors], mode)
    np.testing.assert_allclose(np.asarray(got_jax), want, rtol=1e-5, atol=1e-5)
