"""The apart-gather scope read back from a small synthetic trace (no chip
needed): ``mttkrp_gather_apart_ms`` sums the ops under
``mttkrp_gather_apart`` and counts its slot updates, returns ``None``
for a trace without that scope, and the scope leaves ``program_trace``'s
buckets as they were."""

import re

import pytest

from bench import harness, named_ops, program_trace, trace

SWEEP = "jit(sweep)/while/body/closed_call"
GATHER = "mttkrp/mttkrp_gather"
APART = f"{GATHER}/mttkrp_gather_apart"
# (op_name or None, start ns, duration ns) of one sweep's ops over dims (10, 20, 500):
# modes 0 and 1 gather the tall mode-2 factor apart, mode 2 takes the one table
OPS = [
    (f"{SWEEP}/als_mode0/{GATHER}/mttkrp_table/jit(_pad)/pad", 0, 4),
    (f"{SWEEP}/als_mode0/{GATHER}/jit(_take)/gather", 4, 6),
    (f"{SWEEP}/als_mode0/{APART}/jit(_take)/gather", 10, 5),
    (f"{SWEEP}/als_mode0/{APART}/dynamic_update_slice", 15, 2),
    (f"{SWEEP}/als_mode0/mttkrp/mttkrp_kernel/jit(mttkrp_pallas_call)/mttkrp/pallas_call",
     17, 10),
    (f"{SWEEP}/als_mode1/{GATHER}/jit(_take)/gather", 27, 3),
    (f"{SWEEP}/als_mode1/{APART}/jit(_take)/gather", 30, 7),
    (f"{SWEEP}/als_mode1/{APART}/dynamic_update_slice", 37, 1),
    (f"{SWEEP}/als_mode1/mttkrp/mttkrp_kernel/jit(mttkrp_pallas_call)/mttkrp/pallas_call",
     38, 20),
    (f"{SWEEP}/als_mode2/{GATHER}/jit(_take)/gather", 58, 9),
    (f"{SWEEP}/als_mode2/als_update/jit(solve)/lu", 67, 1),
    (None, 68, 2),
]
DIMS = (10, 20, 500)
SCOPE = re.compile(r"mttkrp_gather_apart")


def _xspace(scoped: bool) -> bytes:
    """A one-chip trace of ``OPS`` and the benchmark's window span;
    ``scoped=False`` takes ``mttkrp_gather_apart`` out of every
    ``op_name``, as a program without that scope compiles."""
    from jax.profiler import ProfileData

    md, events = [], []
    for k, (op_name, start, dur) in enumerate(OPS, start=2):
        if op_name and not scoped:
            op_name = "/".join(p for p in op_name.split("/") if not SCOPE.fullmatch(p))
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


def _record(tmp_path, monkeypatch, scoped=True, sweeps=1):
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_xspace(scoped))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    t = trace.read_trace(tmp_path)
    return {"trace": t, "ops": t.ops_in_window(), "device_kind": "TPU v5 lite",
            "window": {"sweeps": sweeps, "dims": DIMS, "nnz": 1000, "rank": 16}}


def _read(record):
    return harness.load_module(harness.BENCH / "metrics" / "mttkrp_gather_apart_ms.py").read(record)


def test_parse_puts_only_the_apart_ops_under_the_scope():
    ops = named_ops.parse(_xspace(scoped=True))
    assert ["mttkrp_gather_apart" in op.parts for op in ops] == [
        False, False, True, True, False, False, True, True] + [False] * 4
    assert all("mttkrp_gather" in op.parts for op in ops if "mttkrp_gather_apart" in op.parts)


@pytest.mark.parametrize("sweeps", [1, 2])
def test_mttkrp_gather_apart_ms_sums_the_apart_ops(tmp_path, monkeypatch, capsys, sweeps):
    record = _record(tmp_path, monkeypatch, sweeps=sweeps)
    assert _read(record) == pytest.approx((5 + 2 + 7 + 1) * 1e-6 / sweeps)
    assert f"apart factor gathers per sweep: {2 / sweeps!r}" in capsys.readouterr().out


def test_mttkrp_gather_apart_ms_is_none_without_the_scope(tmp_path, monkeypatch, capsys):
    record = _record(tmp_path, monkeypatch, scoped=False)
    assert record["ops"]  # the device ran: only the apart scope is missing
    assert _read(record) is None
    assert "apart factor gathers" not in capsys.readouterr().out


def test_apart_scope_leaves_the_program_buckets_as_they_were():
    with_apart = program_trace.bucket_ns(program_trace.parse(_xspace(scoped=True)).ops)
    without = program_trace.bucket_ns(program_trace.parse(_xspace(scoped=False)).ops)
    assert with_apart == without
    assert with_apart["mttkrp_gather"] == 4 + 6 + 5 + 2 + 3 + 7 + 1 + 9
