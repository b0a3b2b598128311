"""Tests for the symbolic traffic interpreter (DESIGN.md §15).

Covers the Laurent polynomial domain, predicate pricing, the closed-form
censuses extracted from the shipped kernels, and the mutation gates: a
deleted t==0 wrap guard and a doubled output store in the real kernel
source must be caught by grid-carry-init / traffic-model-drift.
"""

from __future__ import annotations

from fractions import Fraction
from pathlib import Path

import pytest

from repro.analysis import run_analysis
from repro.analysis.core import SourceFile
from repro.analysis.poly import Poly, poly_sum
from repro.analysis.traffic import Pred, find_traffic_censuses, semantic

REPO = Path(__file__).resolve().parents[1]

KERNEL = "src/repro/kernels/mttkrp/kernel.py"
OPS = "src/repro/kernels/mttkrp/ops.py"
COMPILED = "src/repro/kernels/mttkrp/compiled.py"
FLASH = "src/repro/kernels/flash_attention/kernel.py"


def _census_map():
    files = [SourceFile(REPO / p, REPO) for p in (KERNEL, OPS, COMPILED, FLASH)]
    censuses, skipped = find_traffic_censuses(files)
    return {c.program: c for c in censuses}, skipped


@pytest.fixture(scope="module")
def censuses():
    return _census_map()[0]


nnz = Poly.var("nnz")
rank = Poly.var("rank")
n_inputs = Poly.var("n_inputs")
i_mode = Poly.var("I_mode")


# ---------------------------------------------------------------------------
# the polynomial domain
# ---------------------------------------------------------------------------


def test_poly_arithmetic_is_exact():
    p = (Poly.var("a") + 1) * (Poly.var("a") - 1)
    assert p == Poly.var("a") ** 2 - 1
    assert (Poly.const(6) * Poly.var("a")) / Poly.const(3) == 2 * Poly.var("a")
    # Laurent division by a single term keeps exactness
    q = (Poly.var("a") * Poly.var("b")) / Poly.var("b")
    assert q == Poly.var("a")
    assert poly_sum([Poly.var("a"), Poly.var("a")]) == 2 * Poly.var("a")


def test_poly_substitute_and_evaluate():
    p = Poly.var("num_tiles") * Poly.var("tile_nnz")
    p = p.subs({"num_tiles": Poly.var("nnz_pad") / Poly.var("tile_nnz")})
    assert p == Poly.var("nnz_pad")
    assert p.evaluate({"nnz_pad": 320}) == Fraction(320)


def test_semantic_collapses_padding():
    padded = Poly.var("num_tiles") * Poly.var("tile_nnz")
    assert semantic(padded) == nnz
    blocks = Poly.var("num_blocks") * Poly.var("rows_per_block")
    assert semantic(blocks) == i_mode
    chunks = Poly.var("num_chunks") * Poly.var("nnz_chunk")
    assert semantic(chunks) == nnz


def test_pred_counts():
    grid = Poly.var("num_tiles")
    blocks = Poly.var("num_blocks")
    assert Pred.count(Pred.EVERY, grid, blocks) == grid
    assert Pred.count(Pred.FIRST, grid, blocks) == blocks
    assert Pred.count(Pred.LAST, grid, blocks) == blocks
    assert Pred.count(Pred.NOT_FIRST, grid, blocks) == grid - blocks
    assert Pred.negate(Pred.FIRST) == Pred.NOT_FIRST
    assert Pred.negate(Pred.FIRST_NO_WRAP) == Pred.NOT_FIRST_NO_WRAP


# ---------------------------------------------------------------------------
# shipped-kernel censuses: the proven closed forms
# ---------------------------------------------------------------------------


def test_both_kernels_get_a_census_and_flash_is_skipped():
    census_map, skipped = _census_map()
    assert set(census_map) == {"mttkrp_pallas_call", "mttkrp_xla_call"}
    assert census_map["mttkrp_pallas_call"].kind == "pallas"
    assert census_map["mttkrp_xla_call"].kind == "xla"
    (skip,) = skipped
    assert skip["fn"] == "flash_attention_fwd"
    assert "no scalar-prefetch streaming grid spec" in skip["reason"]


def test_pallas_census_closed_forms(censuses):
    c = censuses["mttkrp_pallas_call"]
    assert c.scratch_refs == ("acc_ref",)
    assert c.grid == Poly.var("nnz_pad") / Poly.var("tile_nnz")
    assert c.semantic_total(op="load", role="value") == nnz
    # one local-row column + one gather index column per input factor
    assert c.semantic_total(op="load", role="index") == nnz + n_inputs * nnz
    assert c.semantic_total(op="load", role="factor_gather") == n_inputs * nnz * rank
    assert c.semantic_total(op="load", role="factor_stream") == n_inputs * nnz * rank
    assert c.semantic_total(op="store", role="output") == i_mode * rank
    # VMEM psum traffic is block-granular: rows_per_block*rank per tile
    psum = nnz * rank * Poly.var("rows_per_block") / Poly.var("tile_nnz")
    assert c.semantic_total(op="load", role="psum") == psum
    assert c.semantic_total(op="store", role="psum") == psum
    # scalar-prefetch metadata is sub-linear (3 loads of tile_block/tile)
    meta = 3 * nnz / Poly.var("tile_nnz")
    assert c.semantic_total(op="load", role="meta_index") == meta


def test_xla_census_closed_forms(censuses):
    c = censuses["mttkrp_xla_call"]
    assert c.semantic_total(op="load", role="value") == nnz
    assert c.semantic_total(op="load", role="index") == nnz + n_inputs * nnz
    assert c.semantic_total(op="load", role="factor_gather") == n_inputs * nnz * rank
    assert c.semantic_total(op="load", role="factor_stream") == n_inputs * nnz * rank
    assert c.semantic_total(op="store", role="output") == i_mode * rank
    # scatter-accumulate: one accumulator-row RMW per nonzero (+ the
    # zero-init store of the whole accumulator)
    assert c.semantic_total(op="load", role="psum") == nnz * rank
    assert c.semantic_total(op="store", role="psum") == i_mode * rank + nnz * rank


def test_census_evaluates_on_a_concrete_plan(censuses):
    c = censuses["mttkrp_pallas_call"]
    padded_rows = c.total(op="load", role="factor_gather") / rank
    assert padded_rows.evaluate({"n_inputs": 2, "nnz_pad": 320}) == Fraction(640)


def test_census_to_dict_is_json_shaped(censuses):
    d = censuses["mttkrp_pallas_call"].to_dict()
    assert d["program"] == "mttkrp_pallas_call"
    assert d["kind"] == "pallas"
    assert isinstance(d["sites"], list) and d["sites"]
    assert all(isinstance(s["total"], str) for s in d["sites"])


# ---------------------------------------------------------------------------
# mutation gates: break the real kernel source, the checkers must notice
# ---------------------------------------------------------------------------


def _mini_repo(tmp_path: Path, kernel_text: str, with_ops: bool = True) -> Path:
    root = tmp_path / "mini"
    pkg = root / "src" / "repro" / "kernels" / "mttkrp"
    pkg.mkdir(parents=True)
    (pkg / "kernel.py").write_text(kernel_text)
    if with_ops:
        (pkg / "ops.py").write_text((REPO / OPS).read_text())
    return root


def test_mutation_deleted_wrap_guard_is_caught(tmp_path):
    src = (REPO / KERNEL).read_text()
    broken = src.replace(
        "jnp.logical_or(t == 0, blk != tile_block_ref[jnp.maximum(t - 1, 0)])",
        "blk != tile_block_ref[jnp.maximum(t - 1, 0)]",
    )
    assert broken != src
    root = _mini_repo(tmp_path, broken, with_ops=False)
    report = run_analysis(root, checks=["grid-carry-init"])
    msgs = "\n".join(f.message for f in report.active)
    assert "without the t==0 wrap guard" in msgs
    assert "uninitialized" in msgs


def test_mutation_doubled_store_is_caught(tmp_path):
    src = (REPO / KERNEL).read_text()
    store = "        out_ref[...] = acc_ref[...]"
    broken = src.replace(store, store + "\n" + store)
    assert broken != src
    root = _mini_repo(tmp_path, broken)
    report = run_analysis(root, checks=["traffic-model-drift"])
    msgs = "\n".join(f.message for f in report.active)
    assert "output stores drift" in msgs
    assert "2*I_mode*rank" in msgs
    # one finding per checked nmodes instantiation
    assert len(report.active) == 2


def test_unmutated_kernel_is_clean_in_the_mini_repo(tmp_path):
    root = _mini_repo(tmp_path, (REPO / KERNEL).read_text())
    report = run_analysis(
        root, checks=["grid-carry-init", "traffic-model-drift"]
    )
    assert report.active == [], "\n".join(f.message for f in report.active)
