"""The comparison that decides ``correct``, driven through the harness
at a small size on the CPU (the look for a chip is skipped).

A sound run passes; with the timed path broken underneath (a sweep that
returns its state unchanged, factors altered where they are produced),
``correct`` comes out false.  The controls, the reference at a lower
precision put in the program's place (``bf16x3``, three bf16 passes, and
``bfloat16``), fail the same limits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference

SMALL = {"nell2.sweep": {"dims": [64, 48, 80], "nnz": 3000}}


def _run(name, seconds=0.4):
    return harness.run_cell(name, 2**31 + 99, seconds, False, 0.0, devices=jax.devices(),
                            overrides=SMALL[name])


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct(name):
    result = _run(name)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"


def test_sweep_that_returns_its_state_unchanged_is_caught(monkeypatch):
    from repro.core.cp_als_fused import FusedCPALS

    sweep_fn = FusedCPALS.sweep_fn

    def frozen(self, length, batched):
        fn = sweep_fn(self, length, batched)
        return lambda ops, factors, weights: (factors, weights, fn(ops, factors, weights)[2])

    monkeypatch.setattr(FusedCPALS, "sweep_fn", frozen)
    assert not _run("nell2.sweep")["correct"]


def test_altered_factors_are_caught(monkeypatch):
    from repro.core.cp_als_fused import FusedCPALS

    sweep_fn = FusedCPALS.sweep_fn

    def altered(self, length, batched):
        fn = sweep_fn(self, length, batched)

        def run(ops, factors, weights):
            f, w, fits = fn(ops, factors, weights)
            return (f[0].at[0, 0].add(0.05),) + tuple(f[1:]), w, fits

        return run

    monkeypatch.setattr(FusedCPALS, "sweep_fn", altered)
    assert not _run("nell2.sweep")["correct"]


def _control_readings(name, contract):
    info = harness.cell(name)
    config = {**info["config"], **SMALL[name]}
    ctx = harness.Context(name=name, seed=5, seconds=0.3, trace=False, config=config,
                          params=info["spec"]["params"], limits=info["spec"]["limits"])
    driver = harness.load_module(harness.BENCH / "traffic" / f"{info['spec']['kind']}.py")
    with harness.precision(config):
        state = driver.setup(ctx)
        win = driver.window(ctx, state)
        return ctx.limits, driver.check(ctx, state, win, contract=contract)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_bfloat16_control_fails_the_limits(name):
    limits, readings = _control_readings(name, "bfloat16")
    assert any(readings[k] > v for k, v in limits.items()), readings


def test_bf16x3_control_fails_the_row_gap():
    """Three bf16 passes, the step below the configuration's float32 at
    highest, show in the first sweep's rows though not in the job's
    final factors."""
    limits, readings = _control_readings("nell2.sweep", "bf16x3")
    assert readings["row_gap"] > limits["row_gap"], readings


def test_row_gap_takes_out_one_shared_map_and_keeps_each_rows_error():
    rng = np.random.default_rng(0)
    want = rng.random((500, 8))
    shared = np.eye(8) + 1e-3 * rng.standard_normal((8, 8))
    assert reference.row_gap(want @ shared, want) < 1e-12
    noisy = want @ shared * (1.0 + 1e-5 * rng.standard_normal(want.shape))
    assert 3e-6 < reference.row_gap(noisy, want) < 1e-4


def test_reference_contracts_order_by_error():
    ar = reference.jax_arrays()
    key = jax.random.PRNGKey(0)
    idx = jax.random.randint(key, (4000, 3), 0, 30)
    vals = jax.random.normal(key, (4000,))
    factors = [jax.random.uniform(jax.random.fold_in(key, k), (30, 8)) for k in range(3)]
    with jax.default_matmul_precision("highest"):
        exact = reference.mttkrp(ar, idx, vals, factors, 0)
        err = {c: float(jnp.max(jnp.abs(reference.mttkrp(ar, idx, vals, factors, 0, c) - exact))
                        / jnp.max(jnp.abs(exact))) for c in ("bfloat16", "bf16x3")}
    assert err["bf16x3"] < err["bfloat16"] / 20
    assert err["bfloat16"] > 1e-3
