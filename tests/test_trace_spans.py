"""The fused executor's host spans and device scopes, read back from a
profiler trace on the CPU (``FusedCPALS.run``, DESIGN.md §11).

``cp_als.run`` holds ``cp_als.init`` and, per block, ``cp_als.block``
(stats ``sweeps`` and ``new_program``) and ``cp_als.fit_sync``; the
sweep's device ops carry the ``mttkrp``, ``mttkrp_gather``,
``mttkrp_kernel``, ``als_update`` and ``als_fit`` scopes, each mode's
under its ``als_mode<m>``, and the gather's table under ``mttkrp_table``.
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core.cp_als import cp_init
from repro.core.cp_als_fused import FusedCPALS
from repro.core.sparse_tensor import random_sparse_tensor

SCOPES = ("mttkrp", "mttkrp_gather", "mttkrp_kernel", "als_update", "als_fit")


def _spans(log_dir) -> list[tuple[str, float, dict]]:
    """(name, start ns, stats) of every ``cp_als.*`` host event, by start."""
    from jax.profiler import ProfileData

    (path,) = log_dir.glob("plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            out.extend((e.name, e.start_ns, dict(e.stats))
                       for e in line.events if e.name.startswith("cp_als."))
    return sorted(out, key=lambda s: s[1])


@pytest.fixture(scope="module")
def tensor():
    return random_sparse_tensor((20, 14, 18), nnz=240, seed=3)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_block_spans_count_syncs_sweeps_and_programs_built(tmp_path, tensor, backend):
    executor = FusedCPALS(tensor, 3, impl="pallas", backend=backend)
    jax.profiler.start_trace(str(tmp_path))
    try:
        first = executor.run(n_iters=5, fit_every=2, tol=0.0, seed=1)  # blocks 2, 2, 1
        again = executor.run(n_iters=5, fit_every=2, tol=0.0, seed=2)
    finally:
        jax.profiler.stop_trace()
    spans = _spans(tmp_path)
    runs = [s for s in spans if s[0] == "cp_als.run"]
    assert [r[2] for r in runs] == [{"n_iters": 5, "fit_every": 2, "restarts": 1}] * 2
    assert sum(s[0] == "cp_als.init" for s in spans) == 2
    blocks = [s[2] for s in spans if s[0] == "cp_als.block"]
    assert sum(s[0] == "cp_als.fit_sync" for s in spans) == first.sync_count + again.sync_count
    assert len(blocks) == first.sync_count + again.sync_count == 6
    assert sum(b["sweeps"] for b in blocks[:3]) == first.state.iters == 5
    assert sum(b["sweeps"] for b in blocks[3:]) == again.state.iters == 5
    # a block length's first run builds its program; every later one reuses it
    assert [b["new_program"] for b in blocks] == [1, 0, 1, 0, 0, 0]


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_lowered_sweep_holds_every_scope(tensor, backend):
    executor = FusedCPALS(tensor, 3, impl="pallas", backend=backend)
    state = executor.run(n_iters=1, tol=0.0).state
    text = executor.sweep_fn(1, False).lower(
        executor.operands, tuple(state.factors), state.weights
    ).as_text(debug_info=True)
    parts = set(re.split(r'[/;"]', text))
    assert set(SCOPES) <= parts


def test_lowered_sweep_names_each_mode_and_the_gather_table():
    """``als_mode<m>`` around each mode's MTTKRP and update, and
    ``mttkrp_table`` inside ``mttkrp_gather`` (the Pallas leg's factor
    table), on a 5-mode tensor."""
    tensor = random_sparse_tensor((6, 9, 6, 9, 400), nnz=300, seed=4)
    executor = FusedCPALS(tensor, 3, impl="pallas", backend="interpret")
    factors = tuple(cp_init(tensor, 3))
    text = executor.sweep_fn(1, False).lower(
        executor.operands, factors, jnp.ones((3,))
    ).as_text(debug_info=True)
    parts = set(re.split(r'[/;"]', text))
    assert {f"als_mode{m}" for m in range(5)} | {"mttkrp_table"} <= parts
    assert "als_mode5" not in parts
    assert re.search(r"als_mode\d/mttkrp/mttkrp_gather/mttkrp_table/", text)
