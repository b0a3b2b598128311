"""The per-mode and gather-table scopes read back from a small synthetic
trace (no chip needed): ``largest_mode_ms`` and ``mttkrp_table_ms`` sum
the right ops and pick the mode with the most rows, return ``None`` for
a trace without those scopes, and leave ``program_trace``'s buckets as
they were."""

import re

import pytest

from bench import harness, named_ops, program_trace, trace

SWEEP = "jit(sweep)/while/body/closed_call"
GATHER = "mttkrp/mttkrp_gather"
# (op_name or None, start ns, duration ns) of one sweep's ops over dims (10, 20, 50)
OPS = [
    (f"{SWEEP}/als_mode0/{GATHER}/mttkrp_table/jit(_pad)/pad", 0, 4),
    (f"{SWEEP}/als_mode0/{GATHER}/jit(_take)/gather", 4, 6),
    (f"{SWEEP}/als_mode0/mttkrp/mttkrp_kernel/jit(mttkrp_pallas_call)/mttkrp/pallas_call",
     10, 10),
    (f"{SWEEP}/als_mode0/als_update/jit(solve)/lu", 20, 2),
    (f"{SWEEP}/als_mode1/{GATHER}/mttkrp_table/concatenate", 22, 3),
    (f"{SWEEP}/als_mode1/mttkrp/mttkrp_kernel/jit(mttkrp_pallas_call)/mttkrp/pallas_call",
     25, 20),
    (f"{SWEEP}/als_mode2/{GATHER}/mttkrp_table/jit(_pad)/pad", 45, 1),
    (f"{SWEEP}/als_mode2/{GATHER}/jit(_take)/gather", 46, 9),
    (f"{SWEEP}/als_mode2/mttkrp/mttkrp_kernel/jit(mttkrp_pallas_call)/mttkrp/pallas_call",
     55, 30),
    (f"{SWEEP}/als_mode2/als_update/jit(solve)/lu", 85, 1),
    (f"{SWEEP}/als_fit/jit(_take)/gather", 86, 10),
    (None, 96, 2),
]
DIMS = (10, 20, 50)
NEW_SCOPES = re.compile(r"als_mode\d+|mttkrp_table")


def _xspace(scoped: bool) -> bytes:
    """A one-chip trace of ``OPS`` and the benchmark's window span;
    ``scoped=False`` takes the new scopes out of every ``op_name``, as the
    program compiled before it opened them."""
    from jax.profiler import ProfileData

    md, events = [], []
    for k, (op_name, start, dur) in enumerate(OPS, start=2):
        if op_name and not scoped:
            op_name = "/".join(p for p in op_name.split("/") if not NEW_SCOPES.fullmatch(p))
        stat = f'stats {{ metadata_id: 9 str_value: "{op_name}:" }}' if op_name else ""
        md.append(f'event_metadata {{ key: {k} value {{ id: {k} name: "%op.{k} = f32[8] '
                  f'fusion()" {stat} }} }}')
        events.append(f"events {{ metadata_id: {k} offset_ps: {start * 1000} "
                      f"duration_ps: {dur * 1000} }}")
    device = (f'planes {{ id: 1 name: "/device:TPU:0" '
              f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000 {" ".join(events)} }} '
              f'{" ".join(md)} stat_metadata {{ key: 9 value {{ id: 9 name: "tf_op" }} }} }}')
    host = ('planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" timestamp_ns: 1000 '
            'events { metadata_id: 1 offset_ps: 0 duration_ps: 100000 } } '
            'event_metadata { key: 1 value { id: 1 name: "window" } } }')
    return ProfileData.text_proto_to_serialized_xspace(device + " " + host)


def _record(tmp_path, monkeypatch, scoped=True):
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_xspace(scoped))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    t = trace.read_trace(tmp_path)
    return {"trace": t, "ops": t.ops_in_window(), "device_kind": "TPU v5 lite",
            "window": {"sweeps": 1, "dims": DIMS, "nnz": 1000, "rank": 16}}


def _reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read


def test_parse_keeps_every_part_of_each_op_name():
    ops = named_ops.parse(_xspace(scoped=True))
    assert [op.mode for op in ops] == [0, 0, 0, 0, 1, 1, 2, 2, 2, 2, None, None]
    assert ["mttkrp_table" in op.parts for op in ops] == [
        True, False, False, False, True, False, True] + [False] * 5
    assert ops[-1].parts == frozenset()


def test_largest_mode_ms_sums_the_mode_with_the_most_rows(tmp_path, monkeypatch, capsys):
    record = _record(tmp_path, monkeypatch)
    assert _reader("largest_mode_ms")(record) == pytest.approx((1 + 9 + 30 + 1) * 1e-6)
    out = capsys.readouterr().out
    shown = dict(re.findall(r"(\w+)=([\d.e-]+)", out))
    assert float(shown["als_mode0"]) == pytest.approx(22e-6)
    assert float(shown["als_mode1"]) == pytest.approx(23e-6)
    assert float(shown["none"]) == pytest.approx(12e-6)  # the fit and the op with no name
    assert "largest=als_mode2" in out


def test_mttkrp_table_ms_sums_the_table_ops(tmp_path, monkeypatch):
    record = _record(tmp_path, monkeypatch)
    assert _reader("mttkrp_table_ms")(record) == pytest.approx((4 + 3 + 1) * 1e-6)


@pytest.mark.parametrize("name", ["largest_mode_ms", "mttkrp_table_ms"])
def test_reader_returns_none_without_the_new_scopes(tmp_path, monkeypatch, name):
    record = _record(tmp_path, monkeypatch, scoped=False)
    assert record["ops"]  # the device ran: only the new scopes are missing
    assert _reader(name)(record) is None


def test_new_scopes_leave_the_program_buckets_as_they_were():
    with_new = program_trace.bucket_ns(program_trace.parse(_xspace(scoped=True)).ops)
    without = program_trace.bucket_ns(program_trace.parse(_xspace(scoped=False)).ops)
    assert with_new == without
    assert with_new["mttkrp_gather"] == 4 + 6 + 3 + 1 + 9
