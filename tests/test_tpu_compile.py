"""Ahead-of-time compiles of the main path for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler compiles for a topology that
is described, not attached, and raises what the chip's compiler would
raise (a block shape whose layout Mosaic refuses, VMEM or HBM overuse).

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU compiler
library, and every pytest-xdist worker imports every test file.  Keep
these tests in this one file, so one worker owns the library.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cp_als_fused import FusedCPALS
from repro.core.sparse_tensor import random_sparse_tensor
from repro.data.frostt import FROSTT_TENSORS, PAPER_RANK
from repro.dse.autotune import TuneSpace
from repro.kernels.mttkrp.kernel import LANE, mttkrp_pallas_call
from repro.kernels.mttkrp.ops import PlanBuffers, get_plan, mttkrp_from_plan

# The chip smoke test's single job (chip_smoke.py): NELL-2 cut to 3.9 M nnz.
NELL2 = FROSTT_TENSORS["NELL-2"]
SMOKE_NNZ = 3_906_850
SMOKE_TILES = 15_360  # the smoke run's plans have 15,280-15,318 tiles per mode
RANK = PAPER_RANK
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described ``v5e:2x2``, with the persistent
    compilation cache off: an entry written for a described chip cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler library in this environment
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


@pytest.mark.parametrize(
    "nmodes,tile_nnz,num_tiles",
    [(3, t, 64) for t in TuneSpace().tile_nnz]  # every tile size the autotuner tries
    + [(5, 256, 64), (3, 256, SMOKE_TILES)],
)
def test_mosaic_kernel_compiles_for_v5e(one_chip, nmodes, tile_nnz, num_tiles):
    nnz_pad = num_tiles * tile_nnz
    r_pad = -(-RANK // LANE) * LANE
    compiled = mttkrp_pallas_call.lower(
        _spec((num_tiles,), jnp.int32, one_chip),
        _spec((nnz_pad,), jnp.float32, one_chip),
        _spec((nnz_pad,), jnp.int32, one_chip),
        _spec((nmodes - 1, nnz_pad, r_pad), jnp.float32, one_chip),
        tile_nnz=tile_nnz,
        rows_per_block=256,
        num_blocks=48,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def smoke_sweep(one_chip):
    """The chip smoke test's fused sweep, lowered and compiled with
    full-size operands as arguments: ``(lowered, compiled)``."""
    tensor = random_sparse_tensor(NELL2.dims, nnz=20_000, seed=0, zipf_a=NELL2.zipf_alpha)
    executor = FusedCPALS(tensor, RANK, impl="pallas", backend="mosaic")
    mode_specs = []
    for plan in (get_plan(tensor, m) for m in range(tensor.nmodes)):
        # a block pads its nonzeros by less than one tile
        nnz_pad = -(-(SMOKE_NNZ + plan.num_blocks * plan.tile_nnz) // plan.tile_nnz)
        nnz_pad *= plan.tile_nnz
        mode_specs.append(
            PlanBuffers(
                indices=_spec((nnz_pad, 3), jnp.int32, one_chip),
                values=_spec((nnz_pad,), jnp.float32, one_chip),
                local_row=_spec((nnz_pad,), jnp.int32, one_chip),
                tile_block=_spec((nnz_pad // plan.tile_nnz,), jnp.int32, one_chip),
            )
        )
    fit_specs = (
        _spec((), jnp.float32, one_chip),
        _spec((SMOKE_NNZ, 3), jnp.int32, one_chip),
        _spec((SMOKE_NNZ,), jnp.float32, one_chip),
    )
    factors = tuple(_spec((d, RANK), jnp.float32, one_chip) for d in NELL2.dims)
    weights = _spec((RANK,), jnp.float32, one_chip)
    lowered = executor.sweep_fn(1, False).lower(
        (tuple(mode_specs), fit_specs), factors, weights
    )
    return lowered, lowered.compile()


def test_fused_sweep_compiles_for_v5e_at_smoke_size(smoke_sweep):
    """A Mosaic kernel is in the sweep, no tensor data is embedded in
    the program, and it fits one chip's HBM."""
    lowered, compiled = smoke_sweep
    assert len(lowered.as_text()) < 1 << 20
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


# The program's scopes, as each may nest: the MTTKRP dispatch holds the
# gather and the kernel; the update and the fit stand alone.
SCOPE_CHAINS = [
    {"mttkrp"}, {"mttkrp", "mttkrp_gather"}, {"mttkrp", "mttkrp_kernel"},
    {"als_update"}, {"als_fit"},
]
SCOPES = set().union(*SCOPE_CHAINS)


NO_DEVICE_OP = {"parameter", "constant", "bitcast", "tuple", "get-tuple-element"}


def _entry_lines(hlo_text: str) -> dict[str, tuple[str, str]]:
    """Instruction name -> (its line, its opcode) for every instruction
    of the entry computation of compiled HLO text."""
    entry = hlo_text[hlo_text.index("\nENTRY "):]
    entry = entry[: entry.index("\n}\n")]
    lines = {}
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][\w\-]*)\(", line)
        if m:
            lines[m.group(1)] = (line, m.group(2))
    return lines


def _entry_ops(hlo_text: str) -> dict[str, tuple[str, str | None]]:
    """Instruction name -> (its line, its ``op_name`` or None) for the
    entry computation of compiled HLO text: the ops that run on the
    device (``NO_DEVICE_OP`` instructions are left out)."""
    ops = {}
    for name, (line, opcode) in _entry_lines(hlo_text).items():
        if opcode not in NO_DEVICE_OP:
            op_name = re.search(r'op_name="([^"]*)"', line)
            ops[name] = (line, op_name.group(1) if op_name else None)
    return ops


def _kernel_factor_operand(hlo_text: str, kernel_line: str) -> str:
    """The instruction that computes a kernel call's last operand (the
    gathered factor rows), looked up through bitcasts."""
    lines = _entry_lines(hlo_text)
    name = re.search(r"custom-call\((.*?)\)", kernel_line).group(1).split(", ")[-1]
    name = name.lstrip("%")
    while lines[name][1] == "bitcast":
        name = re.search(r"bitcast\(%([\w.\-]+)\)", lines[name][0]).group(1)
    return name


def _scopes(op_name: str) -> set[str]:
    return SCOPES & set(re.split(r"[/;]", op_name))


def test_fused_sweep_ops_carry_the_program_scopes_on_v5e(smoke_sweep):
    """Each part of the compiled sweep sits under its scope (the names a
    profiler trace reads back), and no op of the program's own code
    falls outside the scopes or between two of them."""
    _, compiled = smoke_sweep
    hlo = compiled.as_text()
    ops = _entry_ops(hlo)
    kernels = [(line, name) for line, name in ops.values() if "tpu_custom_call" in line]
    assert len(kernels) == 3  # one MTTKRP per mode
    for line, op_name in kernels:
        assert _scopes(op_name) == {"mttkrp", "mttkrp_kernel"}
        # the kernel's last operand comes from the gather
        gathered = _kernel_factor_operand(hlo, line)
        assert _scopes(ops[gathered][1]) == {"mttkrp", "mttkrp_gather"}
    solve = [name for _, name in ops.values() if name and "jit(solve)" in name]
    assert solve and all(_scopes(name) == {"als_update"} for name in solve)
    assert any(name and _scopes(name) == {"als_fit"} for _, name in ops.values())
    # Ops of the traced program; the others are compiler-inserted (no
    # op_name) or name an argument they copy (``factors[2]``).
    program = [name for _, name in ops.values() if name and name.startswith("jit(sweep)/")]
    assert program
    for name in program:
        assert _scopes(name) in SCOPE_CHAINS, name


def test_mttkrp_factor_operand_is_one_gather_on_v5e(one_chip):
    """At the smoke size, the kernel's ``(K, nnz_pad, R_pad)`` factor
    operand is written by one gather fusion, seen through a bitcast: no
    select masks the gathered rows, no stack or pad copies them, and the
    compiled temporaries hold that operand about once (a gather per
    factor, masked, then stacked and padded, takes twice)."""
    tensor = random_sparse_tensor(NELL2.dims, nnz=20_000, seed=0, zipf_a=NELL2.zipf_alpha)
    plan = get_plan(tensor, 0)
    nnz_pad = -(-(SMOKE_NNZ + plan.num_blocks * plan.tile_nnz) // plan.tile_nnz)
    nnz_pad *= plan.tile_nnz
    bufs = PlanBuffers(
        indices=_spec((nnz_pad, 3), jnp.int32, one_chip),
        values=_spec((nnz_pad,), jnp.float32, one_chip),
        local_row=_spec((nnz_pad,), jnp.int32, one_chip),
        tile_block=_spec((nnz_pad // plan.tile_nnz,), jnp.int32, one_chip),
    )
    factors = tuple(_spec((d, RANK), jnp.float32, one_chip) for d in NELL2.dims)
    compiled = jax.jit(
        lambda b, fs: mttkrp_from_plan(plan, fs, backend="mosaic", bufs=b)
    ).lower(bufs, factors).compile()

    r_pad = -(-RANK // LANE) * LANE
    operand_bytes = (len(NELL2.dims) - 1) * nnz_pad * r_pad * 4
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.1 * operand_bytes

    hlo = compiled.as_text()
    lines = _entry_lines(hlo)
    (kernel,) = [line for line, _ in lines.values() if "tpu_custom_call" in line]
    line, opcode = lines[_kernel_factor_operand(hlo, kernel)]
    assert opcode == "fusion"
    body = re.search(r"calls=%([\w.\-]+)", line).group(1)
    body = hlo[hlo.index(f"\n%{body} "):]
    assert " gather(" in body[: body.index("\n}\n")]

    def shape(name):  # an instruction's result shape, as printed
        line, opcode = lines[name]
        return line.split(" = ", 1)[1].split(f" {opcode}(", 1)[0]

    for name, (line, opcode) in lines.items():
        if "select" in name:
            operands = re.search(rf" {opcode}\((.*?)\)", line).group(1)
            for read in [name] + re.findall(r"%([\w.\-]+)", operands):
                assert str(nnz_pad) not in shape(read), line


# LBNL-network at its published size (the ``lbnl.sweep`` cell): 1.7 M
# nonzeros after coalescing, and each mode's nnz_pad from the cell's
# seed-0 plans at tile (256, 256); mode 4 holds 3,392 blocks in 8,155 tiles.
LBNL = FROSTT_TENSORS["LBNL"]
LBNL_NNZ = 1_700_000
LBNL_NNZ_PAD = (1_701_120, 1_702_400, 1_700_608, 1_701_888, 2_087_680)
MODE_SCOPE = re.compile(r"als_mode(\d+)")


@pytest.fixture(scope="module")
def lbnl_sweep(one_chip):
    """The LBNL fused sweep, compiled with its full-size operands as
    arguments: ``(compiled, entry ops)``."""
    tensor = random_sparse_tensor(LBNL.dims, nnz=20_000, seed=0, zipf_a=LBNL.zipf_alpha)
    executor = FusedCPALS(tensor, RANK, impl="pallas", backend="mosaic")
    nmodes = len(LBNL.dims)
    mode_specs = tuple(
        PlanBuffers(
            indices=_spec((n, nmodes), jnp.int32, one_chip),
            values=_spec((n,), jnp.float32, one_chip),
            local_row=_spec((n,), jnp.int32, one_chip),
            tile_block=_spec((n // 256,), jnp.int32, one_chip),
        )
        for n in LBNL_NNZ_PAD
    )
    fit_specs = (
        _spec((), jnp.float32, one_chip),
        _spec((LBNL_NNZ, nmodes), jnp.int32, one_chip),
        _spec((LBNL_NNZ,), jnp.float32, one_chip),
    )
    factors = tuple(_spec((d, RANK), jnp.float32, one_chip) for d in LBNL.dims)
    weights = _spec((RANK,), jnp.float32, one_chip)
    compiled = executor.sweep_fn(1, False).lower(
        (mode_specs, fit_specs), factors, weights
    ).compile()
    return compiled, _entry_ops(compiled.as_text())


def _modes(op_name: str) -> list[int]:
    return [int(m.group(1)) for part in re.split(r"[/;]", op_name)
            if (m := MODE_SCOPE.fullmatch(part))]


def test_lbnl_sweep_fits_one_v5e_at_its_published_size(lbnl_sweep):
    compiled, _ = lbnl_sweep
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_lbnl_sweep_names_each_mode_and_the_gather_table_on_v5e(lbnl_sweep):
    """Five Mosaic kernels, one per mode, each under the MTTKRP kernel's
    scopes and exactly one ``als_mode<m>``; the gather table's ops under
    ``mttkrp_table`` inside each mode's gather."""
    _, ops = lbnl_sweep
    kernels = [name for line, name in ops.values() if "tpu_custom_call" in line]
    assert len(kernels) == 5
    for op_name in kernels:
        assert _scopes(op_name) == {"mttkrp", "mttkrp_kernel"}
        assert len(_modes(op_name)) == 1
    assert sorted(_modes(name)[0] for name in kernels) == list(range(5))
    table = [name for _, name in ops.values() if name and "/mttkrp_table/" in name]
    for op_name in table:
        assert _scopes(op_name) == {"mttkrp", "mttkrp_gather"}
        assert len(_modes(op_name)) == 1
    assert {_modes(name)[0] for name in table} == set(range(5))
    # the factors' lane pads all sit in the table
    pads = [name for line, name in ops.values()
            if name and " pad(" in line and "/mttkrp/" in name]
    assert pads and all("/mttkrp_table/" in name for name in pads)


# A gather's table that XLA:TPU places in on-chip VMEM prints memory space S(1).
VMEM = "S(1)"


def _operand_names(line: str, opcode: str) -> list[str]:
    return re.findall(r"%([\w.\-]+)", re.search(rf" {opcode}\((.*?)\)", line).group(1))


def _through(lines: dict[str, tuple[str, str]], name: str, opcodes=("bitcast",)) -> str:
    """The instruction that ``name`` passes on, looked up through
    ``opcodes`` of one operand."""
    while lines[name][1] in opcodes:
        name = _operand_names(*lines[name])[0]
    return name


def _fusion_body(hlo_text: str, line: str) -> str:
    """The computation a fusion calls, else ``""``."""
    called = re.search(r"calls=%([\w.\-]+)", line)
    if not called:
        return ""
    body = hlo_text[hlo_text.index(f"\n%{called.group(1)} "):]
    return body[: body.index("\n}\n")]


def _shape(lines: dict[str, tuple[str, str]], name: str) -> str:
    line, opcode = lines[name]
    return line.split(" = ", 1)[1].split(f" {opcode}(", 1)[0]


def _elements(shape: str) -> list[int]:
    """The element count of each array in a shape (a tuple has several)."""
    return [math.prod(int(d) for d in dims.split(",") if d)
            for dims in re.findall(r"\w+\[([\d,]*)\]", shape)]


def _factor_operand_writes(hlo_text: str, kernel_line: str) -> tuple[str, list[str]]:
    """(the table gather, the slot updates in the order they run) that
    write a kernel call's factor operand, seen through bitcasts."""
    lines = _entry_lines(hlo_text)
    name = _kernel_factor_operand(hlo_text, kernel_line)
    updates = []
    while " dynamic-update-slice(" in _fusion_body(hlo_text, lines[name][0]):
        updates.insert(0, name)
        name = _through(lines, _operand_names(*lines[name])[0])
    assert " gather(" in _fusion_body(hlo_text, lines[name][0])
    return name, updates


def _gather_table(lines: dict[str, tuple[str, str]], gather: str) -> str:
    """The shape of the table a gather fusion reads (its first operand)."""
    return _shape(lines, _operand_names(*lines[gather])[0])


def test_lbnl_time_factor_is_gathered_apart_on_v5e(one_chip):
    """At LBNL's sizes, mode 0's factor operand: one gather from a table
    of the three small factors, placed in on-chip memory, fills every
    slot, and the 868,100-row time factor's rows come from a gather of
    their own, written over its slot in place.  Temporaries hold the
    operand and one apart gather's rows about once, and no op copies the
    whole operand."""
    mode, nnz_pad = 0, LBNL_NNZ_PAD[0]
    tensor = random_sparse_tensor(LBNL.dims, nnz=20_000, seed=0, zipf_a=LBNL.zipf_alpha)
    plan = get_plan(tensor, mode)
    nmodes = len(LBNL.dims)
    bufs = PlanBuffers(
        indices=_spec((nnz_pad, nmodes), jnp.int32, one_chip),
        values=_spec((nnz_pad,), jnp.float32, one_chip),
        local_row=_spec((nnz_pad,), jnp.int32, one_chip),
        tile_block=_spec((nnz_pad // plan.tile_nnz,), jnp.int32, one_chip),
    )
    factors = tuple(_spec((d, RANK), jnp.float32, one_chip) for d in LBNL.dims)
    compiled = jax.jit(
        lambda b, fs: mttkrp_from_plan(plan, fs, backend="mosaic", bufs=b)
    ).lower(bufs, factors).compile()

    r_pad = -(-RANK // LANE) * LANE
    slot_bytes = nnz_pad * r_pad * 4
    operand_bytes = (nmodes - 1) * slot_bytes
    assert compiled.memory_analysis().temp_size_in_bytes <= 1.1 * (operand_bytes + slot_bytes)

    hlo = compiled.as_text()
    lines = _entry_lines(hlo)
    (kernel,) = [line for line, _ in lines.values() if "tpu_custom_call" in line]
    gather, (update,) = _factor_operand_writes(hlo, kernel)
    table = _gather_table(lines, gather)
    assert table.startswith(f"f32[{sum(LBNL.dims[1:4])},{r_pad}]") and VMEM in table
    apart = _through(lines, _operand_names(*lines[update])[1], ("bitcast", "copy"))
    assert " gather(" in _fusion_body(hlo, lines[apart][0])
    assert _gather_table(lines, apart).startswith(f"f32[{LBNL.dims[4]},")
    for name, (line, opcode) in lines.items():
        if operand_bytes // 4 in _elements(_shape(lines, name)):
            assert name in (gather, update) or opcode == "bitcast", line


@pytest.mark.parametrize("sweep", ["smoke_sweep", "lbnl_sweep"])
def test_only_a_tall_factor_is_gathered_apart_in_the_sweep_on_v5e(request, sweep):
    """In the compiled sweeps every MTTKRP's gather table sits in on-chip
    memory: LBNL's modes 0-3 gather the time factor apart, one slot
    update each, while its mode 4 and every NELL-2 mode keep the one
    gather.  No op copies a whole factor operand, and the LBNL sweep's
    temporaries stay within a third of a chip."""
    compiled = {"smoke_sweep": lambda f: f[1], "lbnl_sweep": lambda f: f[0]}[sweep](
        request.getfixturevalue(sweep)
    )
    hlo = compiled.as_text()
    lines = _entry_lines(hlo)
    updates, writes, sizes = {}, set(), set()
    for line, opcode in lines.values():
        if "tpu_custom_call" in line:
            (mode,) = _modes(re.search(r'op_name="([^"]*)"', line).group(1))
            gather, updates[mode] = _factor_operand_writes(hlo, line)
            assert VMEM in _gather_table(lines, gather)
            writes |= {gather, *updates[mode]}
            sizes |= set(_elements(_shape(lines, gather)))
    for name, (line, opcode) in lines.items():  # no op copies a whole operand
        if sizes & set(_elements(_shape(lines, name))):
            assert name in writes or opcode == "bitcast", line
    if sweep == "lbnl_sweep":
        assert {m: len(u) for m, u in updates.items()} == {0: 1, 1: 1, 2: 1, 3: 1, 4: 0}
        assert compiled.memory_analysis().temp_size_in_bytes <= 5.3 * 2**30
    else:
        assert updates == {0: [], 1: [], 2: []}
