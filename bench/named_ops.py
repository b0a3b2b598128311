"""Device ops of the newest profiler trace, each with every part of its
``op_name``.

``program_trace`` files each op under the innermost of its ``SCOPES``
alone.  The scopes read here are not among them, so no bucket of
``program_trace`` moves: ``mttkrp_table``, nested in ``mttkrp_gather``
around the lane pad and concatenation of the gather's factor table, and
``als_mode<m>``, around mode ``m``'s MTTKRP and update in
``FusedCPALS.sweep_fn``.  An op with no ``op_name`` has no parts.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from pathlib import Path

from bench import harness, program_trace, trace

MODE_SCOPE = re.compile(r"als_mode(\d+)")


@dataclasses.dataclass(frozen=True)
class NamedOp:
    parts: frozenset[str]  # the ``/``- and ``;``-separated parts of its op_name
    start: float
    dur: float
    device: int = 0

    @property
    def end(self) -> float:
        return self.start + self.dur

    @property
    def mode(self) -> int | None:
        """``m`` of the op's ``als_mode<m>`` scope, else ``None``."""
        found = [int(m.group(1)) for p in self.parts if (m := MODE_SCOPE.fullmatch(p))]
        return found[0] if found else None


def parse(xspace: bytes) -> list[NamedOp]:
    """Every device op of a serialized XSpace, with its op_name's parts."""
    from jax.profiler import ProfileData

    names = program_trace._op_names(xspace)
    ops = []
    for plane in ProfileData.from_serialized_xspace(xspace).planes:
        if not plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        dev = int(plane.name[len(trace.DEVICE_PLANE_PREFIX):].split()[0] or 0)
        table = names.get(plane.name, {})
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                ops.extend(NamedOp(frozenset(re.split(r"[/;]", table.get(e.name) or "")) - {""},
                                   e.start_ns, e.duration_ns, dev) for e in line.events)
    return ops


@functools.lru_cache(maxsize=1)
def _load(path: str) -> list[NamedOp]:
    return parse(Path(path).read_bytes())


def in_window(record) -> list[NamedOp]:
    """The ops of the newest trace under ``harness.TRACE_DIR`` that
    overlap the run's window, read once per file."""
    files = sorted(Path(harness.TRACE_DIR).glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {harness.TRACE_DIR}")
    return program_trace.in_window(_load(str(files[-1])), record["trace"].window)


def ms_per_sweep(record, key) -> dict | None:
    """Device ms per sweep of the window's ops, summed by ``key(op)``;
    ``None`` where the run counted no sweeps."""
    sweeps = record["window"].get("sweeps")
    if not sweeps:
        return None
    acc: dict = {}
    for op in in_window(record):
        k = key(op)
        acc[k] = acc.get(k, 0.0) + op.dur * 1e-6 / sweeps
    return acc
