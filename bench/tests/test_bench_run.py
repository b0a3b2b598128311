"""bench/run.py refuses to produce a result off the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ARGS = ["--workload", "nell2.sweep", "--seed", "1", "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "REPRO_PALLAS_INTERPRET"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    if lines:
        with pytest.raises(ValueError):
            json.loads(lines[-1])


def test_no_tpu_exits_nonzero_without_a_result():
    proc = _run(harness.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    _no_result(proc)


def test_interpret_mode_is_refused():
    proc = _run(harness.ROOT, {"REPRO_PALLAS_INTERPRET": "1"})
    assert proc.returncode != 0 and "REPRO_PALLAS_INTERPRET" in proc.stderr
    _no_result(proc)


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("traces", "__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    _no_result(proc)
