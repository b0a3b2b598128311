"""Which device operations of a trace are the MTTKRP kernel's.

In the TPU trace (``XLA Ops`` line of ``/device:TPU:<n>``) each event is
named by its HLO instruction text, ``%<name> = <shape> <opcode>(...)``.
The Mosaic kernel of ``repro.kernels.mttkrp.kernel`` (a ``pallas_call``
with no ``name=``) shows as ``%mttkrp_pallas_call.<n> = ... custom-call(...),
custom_call_target="tpu_custom_call"`` (read off a trace by hand).  A
Mosaic kernel is told apart by its custom-call target, which no rename
in the program changes; in the single-job cell the MTTKRP is the only
Mosaic kernel.  The factor-row gather and lane pad that stage its
operands are fusions with generated names (``select_maximum_fusion``,
``fusion.<n>``) and are not told apart from the rest of the sweep.
"""

from __future__ import annotations

KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def is_kernel(name: str) -> bool:
    return KERNEL_MARK in name


def split_ns(ops) -> tuple[float, float]:
    """(kernel ns, other ns) summed over the operations given."""
    kern = sum(e.dur for e in ops if is_kernel(e.name))
    return kern, sum(e.dur for e in ops) - kern
