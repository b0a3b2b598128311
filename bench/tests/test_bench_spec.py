"""BENCHMARK.json against the benchmark's contract, and cells found by name."""

import json
import re
import shutil

import pytest

from bench import harness

SPEC = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n\r]{1,200}$")


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    assert len((harness.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert all(LINE.match(w) for w in SPEC["command"]) and len(SPEC["command"]) <= 32
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and not p.startswith("/")
        assert ".." not in p.split("/") and (harness.ROOT / p).is_dir()


def test_names_units_and_entry_keys():
    names = []
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["source"]) and LINE.match(c["why"])
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert (harness.ROOT / c["file"]).is_file()
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        names.append(c["name"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and LINE.match(w["why"])
        assert w["config"] in names and w["chips"] in (1, 4)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    all_names = ([c["name"] for c in SPEC["configs"]] + [w["name"] for w in SPEC["workloads"]]
                 + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]])
    assert len(set(all_names)) == len(all_names)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_metrics_bounds_sources_and_moves():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert LINE.match(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells and cell in moved.get("workloads", cells)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for cell in cells:  # setup_s, another end-to-end metric and a per-layer one
        assert sum(cell in m.get("workloads", cells) for m in SPEC["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", cells) for m in SPEC["per_layer"])


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(name):
    info = harness.cell(name)
    assert (harness.BENCH / "traffic" / f"{info['spec']['kind']}.py").is_file()
    for m in info["per_layer"]:
        assert hasattr(harness.load_module(harness.BENCH / "metrics" / f"{m['name']}.py"),
                       "read")
    assert info["spec"]["limits"]


def test_a_new_cell_is_picked_up_from_its_own_file(tmp_path):
    """A later change adds a cell by adding a workload file and an entry;
    no file that exists is edited."""
    import jax

    bench_dir = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench_dir, ignore=shutil.ignore_patterns("traces", "tests"))
    spec = json.loads((harness.BENCH / "workloads" / "nell2.sweep.json").read_text())
    spec["params"] = {**spec["params"], "n_iters": 3}
    (bench_dir / "workloads" / "nell2.short.json").write_text(json.dumps(spec))
    benchmark = json.loads(json.dumps(SPEC))
    benchmark["workloads"].append({"name": "nell2.short", "config": "nell2", "traffic": "short",
                                   "chips": 1, "why": "test"})
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if "nell2.sweep" in m.get("workloads", []):
            m["workloads"].append("nell2.short")
    result = harness.run_cell("nell2.short", 11, 0.5, False, 0.0, devices=jax.devices(),
                              overrides={"dims": [64, 48, 80], "nnz": 3000},
                              benchmark=benchmark, bench_dir=bench_dir)
    assert result["correct"] and result["attempted"] >= 1
    assert set(result["metrics"]) == {"setup_s", "sweep_ms"}
