"""Run one cell of the benchmark by name and print its result.

Everything that belongs to one cell lives in files of its own, found by
name: ``BENCHMARK.json`` names the cell's configuration, read from
``bench/configs/<config>.json``, and the end-to-end and per-layer metrics
it reports; the cell's own ``bench/workloads/<cell>.json`` holds its
traffic ``kind``, the traffic's ``params`` and the correctness
``limits``; the traffic driver is ``bench/traffic/<kind>.py`` and each
per-layer metric's reader ``bench/metrics/<metric>.py``.

A traffic driver module defines

* ``setup(ctx) -> state``: build inputs from ``ctx.seed`` and the
  program's objects, and warm every shape the window will use;
* ``window(ctx, state) -> Window``: drive the program for ``ctx.seconds``;
* ``check(ctx, state, win, contract="exact") -> {name: value}``: after the
  window, the numbers compared against the cell's ``limits`` (with another
  ``contract``, the same numbers for a lower-precision reference put in
  the program's place; see ``reference.py``).

A metric reader module defines ``read(record) -> float | None``; ``None``
means that the run held nothing to read, and the metric is left out.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = BENCH / "traces"


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, bad file)."""


@dataclasses.dataclass
class Window:
    attempted: int
    failed: int
    metrics: dict[str, float]          # end-to-end values, by name
    record: dict = dataclasses.field(default_factory=dict)  # for metric readers


@dataclasses.dataclass
class Context:
    name: str
    seed: int
    seconds: float
    trace: bool
    config: dict
    params: dict
    limits: dict

    def span(self, name: str):
        """A host span in the profiler's trace (only when tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def say(self, line: str) -> None:
        print(f"[{self.name}] {line}", flush=True)


def load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise BenchError(f"missing {path}") from None


def load_module(path: Path):
    """Import a file under ``bench/`` by path (names may hold dots)."""
    if not path.is_file():
        raise BenchError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, benchmark: dict | None = None, bench_dir: Path = BENCH) -> dict:
    """The cell's entry in BENCHMARK.json, joined with its own files
    under ``bench_dir``."""
    benchmark = benchmark or load_json(ROOT / "BENCHMARK.json")
    entries = [w for w in benchmark["workloads"] if w["name"] == name]
    if not entries:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[0]
    spec = load_json(bench_dir / "workloads" / f"{name}.json")
    config = load_json(bench_dir / "configs" / f"{entry['config']}.json")

    def reported(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {
        "entry": entry,
        "spec": spec,
        "config": config,
        "end_to_end": reported(benchmark["end_to_end"]),
        "per_layer": reported(benchmark["per_layer"]),
    }


def require_tpu(chips: int):
    """The cell's devices; no accelerator, or too few chips, is an error."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX reports platform {devices[0].platform!r}; "
                         "the benchmark does not fall back to the CPU")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX reports {len(devices)}")
    return devices[:chips]


def peak_bytes(devices) -> int:
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class CompileCounter:
    """Counts programs compiled or loaded from the cache inside a ``with``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def _on_event(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def enable_cache() -> None:
    """JAX's persistent cache at the program's fixed path in the checkout,
    keeping every program, however fast it compiled."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def precision(config: dict):
    """The configuration's ``matmul_precision``, for every float32 matrix
    product that states none (on a TPU the default is one bfloat16 pass),
    as a context around everything that traces the program.

    The program's CP-ALS Grams, solve and fit state no precision, so
    without this they would not run as the configuration states; once
    they do, this context has nothing left to set and can go.
    """
    import jax

    return jax.default_matmul_precision(config["matmul_precision"])


def _measure(ctx, driver, devices, t_start):
    """Set-up, then the window (traced if asked); the peak memory is read
    before anything else runs."""
    import jax

    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    ctx.say(f"setup_s={setup_s!r}")
    if ctx.trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    try:
        with CompileCounter() as counter, ctx.span("window"):
            win = driver.window(ctx, state)
    finally:
        if ctx.trace:
            jax.profiler.stop_trace()
    ctx.say(f"programs built or loaded inside the window: {counter.count}")
    memory = peak_bytes(devices)
    ctx.say(f"peak_bytes_in_use={memory}")
    return state, win, setup_s, memory


def run_cell(name: str, seed: int, seconds: float, trace: bool, t_start: float, *,
             devices=None, overrides: dict | None = None, params: dict | None = None,
             benchmark: dict | None = None,
             bench_dir: Path = BENCH) -> dict:
    """Set up, measure and check one cell; returns the result object.

    ``devices`` skips the look for a chip (tests); ``overrides`` and
    ``params`` replace keys of the configuration and of the cell's traffic
    parameters (tests at a small size); ``benchmark`` and
    ``bench_dir`` replace ``BENCHMARK.json`` and the ``bench/`` tree.
    """
    info = cell(name, benchmark, bench_dir)
    entry, spec = info["entry"], info["spec"]
    config = {**info["config"], **(overrides or {})}
    if devices is None:
        devices = require_tpu(entry["chips"])
    driver = load_module(bench_dir / "traffic" / f"{spec['kind']}.py")
    readers = {m["name"]: load_module(bench_dir / "metrics" / f"{m['name']}.py").read
               for m in info["per_layer"]} if trace else {}
    ctx = Context(name=name, seed=seed, seconds=seconds, trace=trace, config=config,
                  params={**spec["params"], **(params or {})}, limits=spec["limits"])
    with precision(config):
        state, win, setup_s, memory = _measure(ctx, driver, devices, t_start)
        readings = driver.check(ctx, state, win)
    del state
    checks = {k: {"value": readings[k], "limit": v} for k, v in ctx.limits.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": memory}
    result = {"correct": correct, "attempted": win.attempted, "failed": win.failed}
    if trace:
        from bench import trace as tr

        t = tr.read_trace(TRACE_DIR)
        ops = t.ops_in_window()
        busy = tr.busy_ns(ops, t.window, t.devices) * 1e-9
        device.update(busy_s=busy, window_s=t.window_s)
        record = {"trace": t, "ops": ops, "window": win.record, "device_kind": dev.device_kind}
        metrics = {}
        for m in info["per_layer"]:
            value = readers[m["name"]](record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": tr.top_ops(ops),
                               "idle_gaps": tr.idle_gaps(ops, t.spans, t.window)}
    else:
        values = {**win.metrics, "setup_s": setup_s}
        result["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                             for m in info["end_to_end"]}
    result["device"] = device
    result["checks"] = checks
    return result


def main(argv: list[str], t_start: float) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if os.environ.get("REPRO_PALLAS_INTERPRET") is not None:
            raise BenchError("REPRO_PALLAS_INTERPRET is set; the kernel must run compiled")
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError(f"the program is not under {ROOT / 'src'}")
        enable_cache()
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
