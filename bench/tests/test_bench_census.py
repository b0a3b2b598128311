"""Compulsory-work census and the peaks table."""

import json

import pytest

from bench import census

NELL2 = (12_100, 9_200, 28_800)
NNZ = 6_783_976  # the cut NELL-2 tensor after duplicates coalesce
R = 16


def test_mode_census_at_nell2_by_hand():
    # values + 3 indices per nonzero, 4 bytes each; every factor once.
    nbytes, ops = census.mode_census(NELL2, NNZ, R, 0)
    assert nbytes == NNZ * 4 * 4 + (12_100 + 9_200 + 28_800) * 16 * 4
    assert nbytes == 111_750_016
    assert ops == 3 * NNZ * 16


def test_sweep_census_is_n_modes_and_hbm_bound_on_v5e():
    nbytes, ops = census.sweep_census(NELL2, NNZ, R)
    assert nbytes == 3 * 111_750_016
    assert ops == 3 * 3 * NNZ * 16
    t, which = census.roofline_time(nbytes, ops, census.peaks("TPU v5 lite"))
    assert which == "hbm"
    assert t == pytest.approx(3 * 111_750_016 / 819e9)  # ≈0.41 ms per sweep


def test_peaks_are_the_published_v5e_numbers():
    p = census.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in json.loads(census.PEAKS_FILE.read_text())["source"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        census.peaks("TPU v9 imaginary")
