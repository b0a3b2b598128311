"""The program's own scopes and spans in the newest profiler trace.

The program names the parts of a CP-ALS sweep with ``jax.named_scope``
(``SCOPES``) and opens host spans named ``cp_als.*`` with
``jax.profiler.TraceAnnotation`` in ``FusedCPALS.run``.  This module
reads both back from the ``*.xplane.pb`` that a ``--trace 1`` run wrote
under ``harness.TRACE_DIR``, once per file.

Where the trace holds an op's scope (read off a TPU v5e trace by hand):
a device op's event (``XLA Ops`` line of ``/device:TPU:<n>``) is named
by its HLO text, ``%<instruction> = <shape> <opcode>(...)``, with no
metadata, and ``ProfileData`` gives it timing stats only.  The event's
metadata in the plane carries a ``tf_op`` stat, ``<op_name>:<op_type>``
(the type is empty here), which ``ProfileData`` does not expose; so the
device planes' event metadata is read with a small protobuf wire reader
(``_fields``).  (The ``/host:metadata`` plane's HLO protos hold the same
``op_name``s; on the first chip trace both gave the same buckets.)

An op's scope is the innermost of ``SCOPES`` among the ``/``- and
``;``-separated parts of its ``op_name`` (``scope_of``); an op with no
``op_name`` (compiler-inserted copies, bitcast concatenations) or with
none of the scopes in it is ``UNSCOPED``.  A trace of a program that opens no scope
or span reads as empty, and the readers then return ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
import re
from pathlib import Path

from bench import harness, trace

SCOPES = ("mttkrp_gather", "mttkrp_kernel", "mttkrp", "als_update", "als_fit")
UNSCOPED = "unscoped"
BUCKETS = SCOPES + (UNSCOPED,)
SPAN_PREFIX = "cp_als."
RUN_SPAN = "cp_als.run"
TF_OP_STAT = "tf_op"


@dataclasses.dataclass(frozen=True)
class Op:
    scope: str
    start: float
    dur: float
    device: int = 0

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    dur: float
    stats: dict = dataclasses.field(default_factory=dict)

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class ProgramTrace:
    ops: list[Op]        # device ops, each with its program scope
    spans: list[Span]    # the program's cp_als.* host spans


def scope_of(op_name: str | None) -> str:
    """The innermost program scope in ``op_name``, else ``UNSCOPED``: the
    scope opened last.  A scope's name met again deeper down does not
    open it again (the kernel's own name, ``mttkrp``, sits under
    ``mttkrp/mttkrp_kernel``)."""
    opened = [part for part in re.split(r"[/;]", op_name or "") if part in SCOPES]
    return max(opened, key=opened.index, default=UNSCOPED)


# -- protobuf wire format (only what the metadata plane needs) --------------


def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of each field of one message; a
    length-delimited value is a memoryview, any other an int or bytes."""
    b = memoryview(b)
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(b[i:i + n]), i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _first(b, field: int):
    return next((v for f, v in _fields(b) if f == field), None)


def _op_names(xspace: bytes) -> dict[str, dict[str, str]]:
    """Device plane name -> {event name: ``op_name``}, from the ``tf_op``
    stat of each event metadata (XSpace ``planes`` 1; plane ``name`` 2,
    ``event_metadata`` 4 and ``stat_metadata`` 5, map entries with the
    value in 2; metadata ``id`` 1, ``name`` 2, ``stats`` 5; stat
    ``metadata_id`` 1, ``str_value`` 5)."""
    out = {}
    for f, plane in _fields(xspace):
        name = bytes(_first(plane, 2) or b"").decode() if f == 1 else ""
        if not name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        entries = [(g, _first(entry, 2)) for g, entry in _fields(plane) if g in (4, 5)]
        tf_op = {_first(md, 1) for g, md in entries
                 if g == 5 and bytes(_first(md, 2) or b"") == TF_OP_STAT.encode()}
        names = out[name] = {}
        for g, md in entries:
            if g != 4:
                continue
            for h, stat in _fields(md):
                if h == 5 and _first(stat, 1) in tf_op:
                    op = bytes(_first(stat, 5) or b"").decode().rpartition(":")[0]
                    names[bytes(_first(md, 2) or b"").decode()] = op
    return out


def parse(xspace: bytes) -> ProgramTrace:
    """The program's scoped device ops and ``cp_als.*`` spans in a
    serialized XSpace."""
    from jax.profiler import ProfileData

    names = _op_names(xspace)
    data = ProfileData.from_serialized_xspace(xspace)
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            dev = int(plane.name[len(trace.DEVICE_PLANE_PREFIX):].split()[0] or 0)
            table = names.get(plane.name, {})
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.extend(Op(scope_of(table.get(e.name)), e.start_ns, e.duration_ns, dev)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Span(e.name, e.start_ns, e.duration_ns, dict(e.stats))
                             for e in line.events if e.name.startswith(SPAN_PREFIX))
    return ProgramTrace(ops=ops, spans=spans)


@functools.lru_cache(maxsize=1)
def _load(path: str) -> ProgramTrace:
    return parse(Path(path).read_bytes())


def newest(log_dir: Path | None = None) -> ProgramTrace:
    """The parsed newest trace under ``log_dir`` (default
    ``harness.TRACE_DIR``), read once per file."""
    log_dir = Path(log_dir or harness.TRACE_DIR)
    files = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return _load(str(files[-1]))


# -- reductions used by the metric readers ------------------------------------


def in_window(items, window):
    lo, hi = window
    return [x for x in items if x.end > lo and x.start < hi]


def bucket_ns(ops: list[Op]) -> dict[str, float]:
    """Device time of each bucket (``BUCKETS``), summed over ``ops``."""
    acc = dict.fromkeys(BUCKETS, 0.0)
    for op in ops:
        acc[op.scope] += op.dur
    return acc


def scope_ms(record, scopes, label: str | None = None):
    """Device ms per sweep of the ops in ``scopes`` inside the window;
    ``None`` where the program named none of its ops.  With ``label``,
    prints every bucket, their sum with the kernel's events
    (``kernel_events``) beside it, and device busy time, per sweep."""
    from bench import kernel_events

    sweeps = record["window"].get("sweeps")
    t = record["trace"]
    ops = in_window(newest().ops, t.window)
    if not sweeps or not any(op.scope != UNSCOPED for op in ops):
        return None
    acc = bucket_ns(ops)
    if label:
        per = {k: v * 1e-6 / sweeps for k, v in acc.items()}
        kern, _ = kernel_events.split_ns(record["ops"])
        busy = trace.busy_ns(record["ops"], t.window, t.devices) * 1e-6 / sweeps
        shown = " ".join(f"{k}={v!r}" for k, v in per.items())
        print(f"[{label}] ms per sweep: {shown} sum={sum(per.values())!r} "
              f"mttkrp_kernel_ms={kern * 1e-6 / sweeps!r} busy={busy!r}")
    return sum(acc[s] for s in scopes) * 1e-6 / sweeps


def _intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_by_span(ops, spans: list[Span], window, device: int = 0) -> dict[str, float]:
    """Device-idle ns inside ``cp_als.run`` spans, split by the inner
    ``cp_als.*`` span that covers it (``init``, ``block``, ``fit_sync``;
    ``none`` where no inner span does).  The inner spans are siblings
    inside ``cp_als.run`` and do not overlap."""
    lo, hi = window
    busy = trace.union(trace.clip([(o.start, o.end) for o in ops if o.device == device],
                                  window))
    free = _intersect([(a, b) for a, b in zip([lo] + [e for _, e in busy],
                                               [s for s, _ in busy] + [hi]) if b > a],
                      trace.union(trace.clip([(s.start, s.end) for s in spans
                                              if s.name == RUN_SPAN], window)))
    out = {}
    for name in sorted({s.name for s in spans} - {RUN_SPAN}):
        cover = trace.union([(s.start, s.end) for s in spans if s.name == name])
        out[name[len(SPAN_PREFIX):]] = sum(b - a for a, b in _intersect(free, cover))
    out["none"] = sum(b - a for a, b in free) - sum(out.values())
    return out


def programs_built(spans: list[Span]) -> int:
    """Programs built by the ``cp_als.block`` spans given: the sum of
    their ``new_program`` stats."""
    return sum(int(s.stats.get("new_program", 0)) for s in spans
               if s.name == "cp_als.block")
