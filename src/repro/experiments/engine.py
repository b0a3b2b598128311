"""End-to-end experiment engine: measured CP-ALS reconciled with the model.

The missing link between the repo's two reproduction paths (DESIGN.md §1):
the analytic side prices full-size FROSTT tensors it can never run, while
the executable side runs scaled tensors it never prices.  This engine does
both on the SAME workload and reconciles them (DESIGN.md §7):

  1. materialize every requested FROSTT spec at a configurable scale
     (``repro.data.synthetic_tensors``);
  2. execute full CP-ALS sweeps through each impl — ``ref`` and ``pallas``
     in-process, ``sharded`` on CPU in a subprocess with
     ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (XLA pins the
     device count at first init) and on an accelerator in-process over
     ``jax.devices()`` — collecting per-mode wall time, HLO
     ``cost_analysis`` FLOPs/bytes, and exact LRU hit rates over the
     impl's executed nonzero order (``repro.experiments.measure``);
  3. price the same runs on all four memory stacks — E-SRAM, O-SRAM,
     TPU-v5e, photonic IMC — twice through the DSE evaluator: once with
     the measured executed-order hit rates (``ExecutedTraceHitRates``)
     and once with the Che model, yielding speedup/energy tables plus
     per-mode measured-vs-modeled residuals and a trace-vs-Che hit-rate
     reconciliation at the documented 0.10 tolerance
     (``tests/test_dse.py::CHE_VS_TRACE_TOL``, DESIGN.md §7).

``scripts/run_experiments.py`` (``make experiments``) drives this and
writes the ``BENCH_experiments.json`` artifact.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path
from typing import Sequence

from repro.core.hierarchy import PHOTONIC_IMC, split_capacity_hit_rates
from repro.core.memory_tech import E_SRAM, O_SRAM, TPU_V5E
from repro.data.frostt import PAPER_RANK, FrosttTensor
from repro.data.synthetic_tensors import (
    EXPERIMENT_SCALES,
    make_frostt_like,
    scaled_characteristics,
)
from repro.dse import evaluate_sweep, tech_comparison
from repro.experiments.measure import (
    ExecutedTraceHitRates,
    MeasuredRun,
    measure_cp_als,
)
from repro.reorder import prepare_execution

__all__ = [
    "ALL_TECHS",
    "CHE_VS_TRACE_TOL",
    "ExperimentSpec",
    "TechReconciliation",
    "HitRateReconciliation",
    "RunResult",
    "ExperimentResult",
    "run_experiments",
]

# The four memory stacks of DESIGN.md §9, priced through the one engine.
ALL_TECHS = (E_SRAM, O_SRAM, TPU_V5E, PHOTONIC_IMC)

# The documented Che-vs-exact-LRU tolerance (DESIGN.md §7); the golden
# value lives in tests/test_dse.py::CHE_VS_TRACE_TOL and must stay equal.
CHE_VS_TRACE_TOL = 0.10

# The pure-Python Pallas EMULATOR is quadratically slow in blocks × tiles
# (it replays every output block's read-modify-write per grid step), so a
# huge output mode (LBNL's ~400K-row mode 4) makes interpret-mode wall
# time meaningless; the engine skips pallas for such tensors ONLY when
# the resolved backend is "interpret" and records why.  Compiled backends
# (mosaic / triton / the XLA fallback — the default everywhere since the
# DESIGN.md §13 dispatch) execute these cells directly.
PALLAS_MAX_OUTPUT_ROWS = 20_000


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """One experiment-engine invocation (tensors × impls × technologies)."""

    tensors: tuple[tuple[str, float], ...] = tuple(EXPERIMENT_SCALES.items())
    impls: tuple[str, ...] = ("ref", "pallas", "sharded")
    rank: int = PAPER_RANK
    n_iters: int = 3
    seed: int = 0
    n_shards: int = 8
    scheme: str = "mode_ordered"  # sharded partitioning scheme
    # Nonzero execution-order strategies to measure + price per run
    # (repro.reorder, DESIGN.md §10).  ``None`` is the impl-native order
    # (raw COO for ref, lex plan for pallas, mode-sorted shards) — the
    # historical single-run behavior.  The degree strategy relabels the
    # executed tensor engine-side (factors are re-initialized to the
    # relabeled shapes; the fit metric is label-invariant).
    orderings: tuple[str | None, ...] = (None,)
    cost_analysis: bool = True
    # Also time the fused executor (repro.core.cp_als_fused, DESIGN.md §11)
    # on every (tensor, impl, ordering) cell, attaching the ``fused_*``
    # wall-time fields to each MeasuredRun and the fused-vs-eager table to
    # the artifact.
    fused: bool = True
    fit_every: int = 1
    # Pallas-path execution backend (repro.kernels.mttkrp.ops.BACKENDS);
    # None resolves to the platform's compiled path — the XLA fallback on
    # CPU — so measured cells are real kernel wall times (DESIGN.md §13).
    backend: str | None = None
    # Tune (tile_nnz, rows_per_block) per tensor through the closed-loop
    # DSE autotuner before measuring the pallas cells (DESIGN.md §13).
    autotune: bool = False

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class TechReconciliation:
    """Measured vs modeled, one (tensor, impl, technology) cell.

    ``priced_mode_s`` injects the measured executed-order hit rates into
    the technology's hierarchy; ``modeled_mode_s`` uses the Che model.
    Residuals compare per-mode SHARES (fraction of the sweep spent in a
    mode): wall clocks of a CPU-executed kernel and an FPGA model live on
    different absolute scales, but the model's claim about WHERE the time
    goes is testable against the measured run.
    """

    tech: str
    measured_mode_s: tuple[float, ...]
    priced_mode_s: tuple[float, ...]
    modeled_mode_s: tuple[float, ...]
    priced_energy_j: float | None
    modeled_energy_j: float | None
    share_residuals: tuple[float, ...]  # measured share − priced share
    max_share_residual: float

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class HitRateReconciliation:
    """Exact executed-trace vs Che, one (geometry, mode) scenario.

    The measured side is the RAW exact-LRU hit rate over the executed
    nonzero order; the modeled side is the Che approximation solved in
    its finite-trace form at the per-cache-unit trace length
    (``che_hit_rate(trace_length=...)``) — a measured run is a transient,
    and comparing it against steady-state Che would conflate the model
    error with the cold start.  ``within_tol`` applies the documented
    0.10 tolerance to |trace − che_transient| per input factor; the
    steady-state Che values (what the full-size analytic tables use) and
    the warm rates are kept for reference.
    """

    capacity_bytes: int
    line_bytes: int | None
    associativity: int | None
    mode: int
    trace_length: float  # accesses per cache unit
    trace: tuple[float, ...]
    trace_warm: tuple[float, ...]
    che_transient: tuple[float, ...]
    che_steady: tuple[float, ...]
    max_abs_err: float
    within_tol: bool

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class RunResult:
    """Everything measured + reconciled for one (tensor, impl)."""

    frostt: str
    scale: float
    tensor: str  # scaled-characteristics name, e.g. "NELL-2@0.0002"
    dims: tuple[int, ...]
    nnz: int
    impl: str
    measured: MeasuredRun
    techs: tuple[TechReconciliation, ...]
    hit_rates: tuple[HitRateReconciliation, ...]
    # Execution-order strategy of this run (repro.reorder, DESIGN.md §10);
    # None = the impl-native order (the historical behavior).
    ordering: str | None = None

    @property
    def key(self) -> str:
        base = f"{self.tensor}/{self.impl}"
        return base if self.ordering is None else f"{base}/{self.ordering}"

    @property
    def all_within_tol(self) -> bool:
        return all(h.within_tol for h in self.hit_rates)

    def tech(self, name: str) -> TechReconciliation:
        for t in self.techs:
            if t.tech == name:
                return t
        raise KeyError(name)

    def to_dict(self) -> dict:
        return {
            "frostt": self.frostt,
            "scale": self.scale,
            "tensor": self.tensor,
            "dims": list(self.dims),
            "nnz": self.nnz,
            "impl": self.impl,
            "ordering": self.ordering,
            "measured": self.measured.to_dict(),
            "technologies": [t.to_dict() for t in self.techs],
            "hit_rates": [h.to_dict() for h in self.hit_rates],
            "all_within_tol": self.all_within_tol,
        }


@dataclasses.dataclass
class ExperimentResult:
    spec: ExperimentSpec
    runs: list[RunResult]
    skipped: list[dict]  # {"tensor", "impl", "reason"}

    @property
    def all_within_tol(self) -> bool:
        return all(r.all_within_tol for r in self.runs)

    def speedup_table(self) -> dict[str, dict[str, float]]:
        """Per run (tensor/impl[/ordering]): E-SRAM→O-SRAM speedup, trace-
        and Che-priced."""
        out: dict[str, dict[str, float]] = {}
        for r in self.runs:
            e, o = r.tech("E-SRAM"), r.tech("O-SRAM")
            out[r.key] = {
                "priced": sum(e.priced_mode_s) / sum(o.priced_mode_s),
                "modeled": sum(e.modeled_mode_s) / sum(o.modeled_mode_s),
            }
        return out

    def energy_table(self) -> dict[str, dict[str, float]]:
        """Per run (tensor/impl[/ordering]): E-SRAM→O-SRAM energy savings,
        both pricings."""
        out: dict[str, dict[str, float]] = {}
        for r in self.runs:
            e, o = r.tech("E-SRAM"), r.tech("O-SRAM")
            out[r.key] = {
                "priced": e.priced_energy_j / o.priced_energy_j,
                "modeled": e.modeled_energy_j / o.modeled_energy_j,
            }
        return out

    def fused_table(self) -> dict[str, dict[str, float]]:
        """Per run (tensor/impl[/ordering]): eager vs fused executor wall
        time (DESIGN.md §11).  Empty when the spec ran without ``fused``.

        Like-for-like only: ``speedup_cold`` compares two cold runs (the
        eager wall includes per-mode first-call compiles, the fused wall
        its plan build + trace/compile); ``speedup_warm_est`` compares
        the warm fused run against ``MeasuredRun.eager_warm_est_s`` (the
        eager wall with the measured per-mode compile surplus removed —
        the dedicated ``make cp-als`` bench measures warm-vs-warm
        directly and is the gated comparison)."""
        out: dict[str, dict[str, float]] = {}
        for r in self.runs:
            m = r.measured
            if m.fused_warm_wall_s is None:
                continue
            out[r.key] = {
                "eager_wall_s": m.wall_s,
                "eager_warm_est_s": m.eager_warm_est_s,
                "fused_wall_s": m.fused_wall_s,
                "fused_warm_wall_s": m.fused_warm_wall_s,
                "speedup_cold": m.wall_s / m.fused_wall_s,
                "speedup_warm_est": m.eager_warm_est_s / m.fused_warm_wall_s,
                "max_fit_delta": m.fused_max_fit_delta,
            }
        return out

    def to_json_dict(self) -> dict:
        return {
            "benchmark": "experiments",
            "spec": self.spec.to_dict(),
            "technologies": [t.name for t in ALL_TECHS],
            "che_tolerance": CHE_VS_TRACE_TOL,
            "all_within_tol": self.all_within_tol,
            "speedup_table": self.speedup_table(),
            "energy_table": self.energy_table(),
            "fused_table": self.fused_table(),
            "runs": [r.to_dict() for r in self.runs],
            "skipped": self.skipped,
        }


def _shares(values: Sequence[float]) -> tuple[float, ...]:
    total = sum(values)
    if total <= 0:
        return tuple(0.0 for _ in values)
    return tuple(v / total for v in values)


def _measure(
    spec: ExperimentSpec,
    name: str,
    scale: float,
    impl: str,
    tensor,
    ft,
    ordering: str | None,
    tile_nnz: int = 256,
    rows_per_block: int = 256,
):
    if impl == "sharded":
        import jax

        if jax.default_backend() == "cpu":
            return _measure_sharded_subprocess(spec, name, scale, ft.name, ordering)
        # An accelerator belongs to the process that initialized JAX (this
        # one): a child would fail or hang on it, so measure here over the
        # real devices.
        if jax.device_count() != spec.n_shards:
            raise ValueError(
                f"sharded leg needs n_shards={spec.n_shards} devices; this "
                f"{jax.default_backend()} host has {jax.device_count()}"
            )
    return measure_cp_als(
        tensor,
        name=ft.name,
        rank=spec.rank,
        n_iters=spec.n_iters,
        impl=impl,
        seed=spec.seed,
        tile_nnz=tile_nnz,
        rows_per_block=rows_per_block,
        ordering=ordering,
        backend=spec.backend,
        # The sharded shard_map path has no single compiled HLO to analyze
        # (as in repro.experiments.worker).
        cost_analysis=spec.cost_analysis and impl != "sharded",
        fused=spec.fused,
        fit_every=spec.fit_every,
        scheme=spec.scheme,
    )


def _measure_sharded_subprocess(
    spec: ExperimentSpec,
    name: str,
    scale: float,
    tensor_name: str,
    ordering: str | None,
) -> MeasuredRun:
    """Run the sharded measurement under 8 forced host devices.

    XLA fixes the platform device count at first initialization, so the
    parent process (single-device, hosting ref/pallas) cannot flip it;
    the worker re-materializes the tensor deterministically from
    (name, scale, seed) — re-applying the degree relabeling when the
    ordering asks for it — and reports the measured run as JSON.
    """
    src_dir = Path(__file__).resolve().parents[2]
    payload = json.dumps(
        {
            "name": name,
            "scale": scale,
            "tensor_name": tensor_name,
            "rank": spec.rank,
            "n_iters": spec.n_iters,
            "seed": spec.seed,
            "scheme": spec.scheme,
            "ordering": ordering,
            "devices": spec.n_shards,
            "fused": spec.fused,
            "fit_every": spec.fit_every,
            "backend": spec.backend,
        }
    )
    env = os.environ.copy()
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={spec.n_shards}"
    env["PYTHONPATH"] = str(src_dir) + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run(
        [sys.executable, "-m", "repro.experiments.worker"],
        input=payload,
        capture_output=True,
        text=True,
        env=env,
        timeout=1800,
    )
    if res.returncode != 0:
        raise RuntimeError(
            f"sharded worker failed for {tensor_name}:\n{res.stderr[-4000:]}"
        )
    last = [ln for ln in res.stdout.splitlines() if ln.strip()][-1]
    return MeasuredRun.from_dict(json.loads(last))


def _reconcile_hit_rates(
    trace_cache: ExecutedTraceHitRates, ft: FrosttTensor, rank: int
) -> tuple[HitRateReconciliation, ...]:
    n_units = trace_cache.n_shards if trace_cache.impl == "sharded" else 1
    out = []
    for key, stats in sorted(trace_cache.stats.items()):
        geometry, mode = trace_cache.geometries[key]
        # Every input factor sees the same access count (one gather per
        # real nonzero), so one per-unit trace length covers the scenario.
        trace_length = stats[0].accesses / n_units
        che_transient = split_capacity_hit_rates(
            ft,
            mode,
            capacity_bytes=geometry.capacity_bytes,
            rank=rank,
            trace_length=trace_length,
        )
        che_steady = split_capacity_hit_rates(
            ft, mode, capacity_bytes=geometry.capacity_bytes, rank=rank
        )
        warm = tuple(s.warm_hit_rate for s in stats)
        raw = tuple(s.hit_rate for s in stats)
        max_err = max(abs(r - c) for r, c in zip(raw, che_transient))
        out.append(
            HitRateReconciliation(
                capacity_bytes=geometry.capacity_bytes,
                line_bytes=geometry.line_bytes,
                associativity=geometry.associativity,
                mode=mode,
                trace_length=trace_length,
                trace=raw,
                trace_warm=warm,
                che_transient=che_transient,
                che_steady=che_steady,
                max_abs_err=max_err,
                within_tol=max_err <= CHE_VS_TRACE_TOL,
            )
        )
    return tuple(out)


def run_experiments(spec: ExperimentSpec = ExperimentSpec()) -> ExperimentResult:
    """Execute the full measured↔modeled reconciliation (module docstring)."""
    from repro.kernels.mttkrp.ops import resolve_backend

    runs: list[RunResult] = []
    skipped: list[dict] = []
    points = tech_comparison(list(ALL_TECHS), rank=spec.rank)
    pallas_backend = resolve_backend(spec.backend)
    tuner = None
    if spec.autotune:
        from repro.dse.autotune import Autotuner

        tuner = Autotuner(backend=spec.backend)
    for name, scale in spec.tensors:
        tensor = make_frostt_like(name, scale=scale, seed=spec.seed)
        ft = scaled_characteristics(name, tensor, scale=scale)
        tensors = {ft.name: ft}
        modeled = evaluate_sweep(points, tensors, hit_rate_method="che")
        for impl in spec.impls:
            # The emulator-only size guard (PALLAS_MAX_OUTPUT_ROWS comment
            # above): compiled backends run every cell.
            if (
                impl == "pallas"
                and pallas_backend == "interpret"
                and max(tensor.shape) > PALLAS_MAX_OUTPUT_ROWS
            ):
                skipped.append(
                    {
                        "tensor": ft.name,
                        "impl": impl,
                        "reason": (
                            f"output mode of {max(tensor.shape)} rows exceeds "
                            f"PALLAS_MAX_OUTPUT_ROWS={PALLAS_MAX_OUTPUT_ROWS} "
                            "on the interpret backend (emulator-only guard; "
                            "compiled backends run this cell)"
                        ),
                    }
                )
                continue
            tile_nnz = rows_per_block = 256
            if tuner is not None and impl == "pallas":
                cfg = tuner.tune(tensor, spec.rank).best
                tile_nnz, rows_per_block = cfg.tile_nnz, cfg.rows_per_block
            for ordering in spec.orderings:
                # The degree strategy relabels the executed tensor once,
                # globally (DESIGN.md §10).  The dims/nnz characteristics
                # — everything the analytic model reads — are
                # label-invariant.
                exec_tensor, _perms = prepare_execution(tensor, ordering)
                measured = _measure(
                    spec, name, scale, impl, exec_tensor, ft, ordering,
                    tile_nnz=tile_nnz, rows_per_block=rows_per_block,
                )
                trace_cache = ExecutedTraceHitRates(
                    exec_tensor,
                    impl,
                    scheme=spec.scheme,
                    n_shards=spec.n_shards,
                    tile_nnz=tile_nnz,
                    rows_per_block=rows_per_block,
                    ordering=ordering,
                )
                priced = evaluate_sweep(points, tensors, cache=trace_cache)
                techs = []
                for tech in ALL_TECHS:
                    p_cell = priced.cell(tech.name, ft.name)
                    m_cell = modeled.cell(tech.name, ft.name)
                    meas_share = _shares(measured.steady_mode_s)
                    priced_share = _shares(p_cell.mode_seconds)
                    residuals = tuple(
                        ms - ps for ms, ps in zip(meas_share, priced_share)
                    )
                    techs.append(
                        TechReconciliation(
                            tech=tech.name,
                            measured_mode_s=measured.steady_mode_s,
                            priced_mode_s=p_cell.mode_seconds,
                            modeled_mode_s=m_cell.mode_seconds,
                            priced_energy_j=p_cell.energy_j,
                            modeled_energy_j=m_cell.energy_j,
                            share_residuals=residuals,
                            max_share_residual=max(abs(r) for r in residuals),
                        )
                    )
                runs.append(
                    RunResult(
                        frostt=name,
                        scale=scale,
                        tensor=ft.name,
                        dims=tensor.shape,
                        nnz=tensor.nnz,
                        impl=impl,
                        measured=measured,
                        techs=tuple(techs),
                        hit_rates=_reconcile_hit_rates(trace_cache, ft, spec.rank),
                        ordering=ordering,
                    )
                )
    return ExperimentResult(spec=spec, runs=runs, skipped=skipped)
