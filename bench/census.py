"""Compulsory work of a CP-ALS sweep, and the chip's peaks.

The census counts what any MTTKRP implementation must move and compute,
from the tensor's shapes alone: tiles, padding, one-hot height and lane
width are not counted, so no change of implementation can push a share
of this roofline over 100%.

Per mode ``m`` of an ``N``-mode tensor with ``nnz`` nonzeros at rank ``R``
(4-byte values, indices and factor entries):

* bytes: each nonzero's value and ``N`` indices read once,
  ``nnz·(1+N)·4``; each input factor read once, ``Σ_{k≠m} I_k·R·4``; the
  output written once, ``I_m·R·4``;
* operations: per nonzero and rank column, ``N−2`` multiplications for the
  Hadamard row of the ``N−1`` input factors, one by the value and one
  addition into the output: ``N·nnz·R``.

This restates the per-nonzero access census (value ``nnz``, index
``N·nnz``, factor rows ``(N−1)·nnz·R``, output ``I·R``) as compulsory
traffic: a factor row that is reused need not be read from HBM again.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
WORD = 4


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path.name}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def mode_census(dims, nnz: int, rank: int, mode: int) -> tuple[int, int]:
    """(bytes, operations) that one mode's MTTKRP must move and do."""
    n = len(dims)
    nbytes = WORD * (nnz * (1 + n) + rank * sum(dims))
    return nbytes, n * nnz * rank


def sweep_census(dims, nnz: int, rank: int) -> tuple[int, int]:
    """(bytes, operations) of the sweep's ``N`` MTTKRPs together."""
    per = [mode_census(dims, nnz, rank, m) for m in range(len(dims))]
    return sum(b for b, _ in per), sum(f for _, f in per)


def roofline_time(nbytes: float, ops: float, peak: dict) -> tuple[float, str]:
    """The least time the chip needs for the work, and which bound sets it."""
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    t_ops = ops / peak["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "compute")
