"""The program's scopes and spans read back from a small synthetic trace
(no chip needed): the innermost-scope rule, ops without a scope, idle
time under the program's host spans, and readers with nothing to read."""

import pytest

from bench import harness, program_trace, trace

SWEEP = "jit(sweep)/while/body/closed_call"
# (instruction, op_name or None, start ns, duration ns) of one module's ops
OPS = [
    ("select_maximum_fusion", f"{SWEEP}/mttkrp/mttkrp_gather/concatenate", 0, 10),
    ("mttkrp.6", f"{SWEEP}/mttkrp/mttkrp_kernel/jit(mttkrp_pallas_call)/mttkrp/pallas_call",
     10, 20),
    ("slice.1", f"{SWEEP}/mttkrp/slice", 30, 2),
    ("custom-call.39", f"{SWEEP}/als_update/jit(solve)/lu", 32, 3),
    ("fusion.12", f"{SWEEP}/als_fit/jit(_take)/gather", 40, 10),
    ("custom-call.77", None, 50, 1),
    ("copy.46", "factors[2]", 51, 1),
]
# host spans: (name, start ns, duration ns, stats)
SPANS = [
    ("window", 0, 130, {}),
    ("cp_als.run", 0, 120, {"n_iters": 1, "fit_every": 1, "restarts": 1}),
    ("cp_als.init", 0, 5, {}),
    ("cp_als.block", 30, 8, {"sweeps": 1, "new_program": 1}),
    ("cp_als.fit_sync", 38, 62, {}),
]
BASE_NS = 1000
KERNEL = ('custom-call(f32[2,8,128] %select_maximum_fusion), '
          'custom_call_target=\\"tpu_custom_call\\"')  # quotes escaped for the text proto


def _xspace(scoped: bool = True, spans: bool = True) -> bytes:
    """A one-chip trace: the sweep's ops, each with its ``tf_op``
    (``<op_name>:``), the benchmark's window span and, optionally, the
    program's spans; ``scoped=False`` takes the scopes out of every
    ``op_name``, as a program without them compiles."""
    from jax.profiler import ProfileData

    md, ops = [], []
    for k, (name, op_name, start, dur) in enumerate(OPS, start=2):
        text = f"%{name} = f32[8] " + (KERNEL if name.startswith("mttkrp") else "fusion()")
        if op_name and not scoped:
            op_name = "/".join(p for p in op_name.split("/") if p not in program_trace.SCOPES)
        stat = f'stats {{ metadata_id: 9 str_value: "{op_name}:" }}' if op_name else ""
        md.append(f'event_metadata {{ key: {k} value {{ id: {k} name: "{text}" {stat} }} }}')
        ops.append(f"events {{ metadata_id: {k} offset_ps: {start * 1000} "
                   f"duration_ps: {dur * 1000} }}")
    device = (f'planes {{ id: 1 name: "/device:TPU:0" '
              f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: {BASE_NS} {" ".join(ops)} }} '
              f'{" ".join(md)} '
              f'stat_metadata {{ key: 9 value {{ id: 9 name: "tf_op" }} }} }}')
    stat_ids = {"n_iters": 1, "fit_every": 2, "restarts": 3, "sweeps": 4, "new_program": 5}
    events, emd = [], []
    for k, (name, start, dur, stats) in enumerate(SPANS, start=1):
        if name.startswith("cp_als.") and not spans:
            continue
        st = " ".join(f"stats {{ metadata_id: {stat_ids[s]} int64_value: {v} }}"
                      for s, v in stats.items())
        emd.append(f'event_metadata {{ key: {k} value {{ id: {k} name: "{name}" }} }}')
        events.append(f"events {{ metadata_id: {k} offset_ps: {start * 1000} "
                      f"duration_ps: {dur * 1000} {st} }}")
    smd = " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{s}" }} }}'
                   for s, i in stat_ids.items())
    host = (f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 name: "python" '
            f'timestamp_ns: {BASE_NS} {" ".join(events)} }} {" ".join(emd)} {smd} }}')
    return ProfileData.text_proto_to_serialized_xspace(device + " " + host)


def _record(tmp_path, monkeypatch, **kw):
    """A traced run's record over the synthetic trace, as the harness
    builds it, with the trace written where the readers look."""
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(_xspace(**kw))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    t = trace.read_trace(tmp_path)
    return {"trace": t, "ops": t.ops_in_window(), "device_kind": "TPU v5 lite",
            "window": {"sweeps": 1, "dims": (12_100, 9_200, 28_800), "nnz": 6_783_976,
                       "rank": 16}}


@pytest.mark.parametrize("op_name,scope", [
    (f"{SWEEP}/mttkrp/mttkrp_gather/jit(_take)/gather", "mttkrp_gather"),
    (f"{SWEEP}/als_fit/jit(_take)/gather", "als_fit"),
    (f"{SWEEP}/mttkrp/mttkrp_kernel/jit(mttkrp_pallas_call)/mttkrp/pallas_call",
     "mttkrp_kernel"),
    (f"{SWEEP}/mttkrp/slice", "mttkrp"),
    (f"{SWEEP}/als_update/jit(solve)/vmap()/jit(_lu_solve)/triangular_solve", "als_update"),
    (f"{SWEEP}/als_fit/mul;while/body/closed_call", "als_fit"),
    ("jit(sweep)/while/body/gather", "unscoped"),
    ("factors[2]", "unscoped"),
    ("", "unscoped"),
    (None, "unscoped"),
])
def test_scope_is_the_innermost_program_scope(op_name, scope):
    assert program_trace.scope_of(op_name) == scope


def test_parse_takes_scopes_from_the_ops_metadata_and_spans_from_the_host():
    pt = program_trace.parse(_xspace())
    assert [op.scope for op in pt.ops] == ["mttkrp_gather", "mttkrp_kernel", "mttkrp",
                                           "als_update", "als_fit", "unscoped", "unscoped"]
    assert [op.dur for op in pt.ops] == [d for *_, d in OPS]
    spans = {s.name: s.stats for s in pt.spans}
    assert set(spans) == {"cp_als.run", "cp_als.init", "cp_als.block", "cp_als.fit_sync"}
    assert spans["cp_als.block"] == {"sweeps": 1, "new_program": 1}
    assert program_trace.programs_built(pt.spans) == 1


def test_ops_with_no_op_name_are_unscoped_buckets_not_dropped():
    buckets = program_trace.bucket_ns(program_trace.parse(_xspace()).ops)
    assert buckets["unscoped"] == 2
    assert sum(buckets.values()) == sum(d for *_, d in OPS)


def test_idle_time_goes_to_the_innermost_program_span():
    pt = program_trace.parse(_xspace())
    window = (BASE_NS, BASE_NS + 130)
    # busy 0..35 and 40..52 inside cp_als.run (0..120): idle 35..40 and 52..120
    got = program_trace.idle_by_span(pt.ops, pt.spans, window)
    assert got == {"init": 0, "block": 3, "fit_sync": 2 + 48, "none": 20}


def test_readers_read_the_program_scopes_and_spans(tmp_path, monkeypatch, capsys):
    record = _record(tmp_path, monkeypatch)
    read = {name: harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read
            for name in ("mttkrp_gather_ms", "als_update_ms", "als_fit_ms",
                         "unscoped_device_ms", "executor_idle_ms",
                         "mttkrp_staged_roofline")}
    assert read["mttkrp_gather_ms"](record) == pytest.approx(10e-6)
    assert read["als_update_ms"](record) == pytest.approx(3e-6)
    assert read["als_fit_ms"](record) == pytest.approx(10e-6)
    assert read["unscoped_device_ms"](record) == pytest.approx(2e-6)
    assert read["executor_idle_ms"](record) == pytest.approx(73e-6)
    assert read["mttkrp_staged_roofline"](record) > 0
    out = capsys.readouterr().out
    assert "sum=" in out and "busy=" in out
    assert "programs built inside the window: 1" in out


NEW_READERS = ["mttkrp_gather_ms", "als_update_ms", "als_fit_ms", "unscoped_device_ms",
               "mttkrp_staged_roofline", "executor_idle_ms"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_returns_none_on_a_program_without_scopes_or_spans(tmp_path, monkeypatch,
                                                                    name):
    record = _record(tmp_path, monkeypatch, scoped=False, spans=False)
    assert record["ops"]  # the device ran: only the program's names are missing
    read = harness.load_module(harness.BENCH / "metrics" / f"{name}.py").read
    assert read(record) is None
