"""Symbolic memory-traffic interpreter over the MTTKRP kernel ASTs.

The tentpole of DESIGN.md §15's PR-10 extension: an abstract interpreter
that walks the Pallas streaming-accumulation kernel
(``kernels/mttkrp/kernel.py``) and the XLA scatter-accumulate fallback
(``kernels/mttkrp/compiled.py``) at the AST level and evaluates every
``*_ref`` / streamed-operand load and store site under the Laurent
polynomial domain of :mod:`repro.analysis.poly`.  The result is a
per-kernel **traffic census**: closed-form element counts per access
site, tagged with

  * the grid-weighted execution count — top-level statements run once
    per grid step (``num_tiles``), ``pl.when(first)`` bodies run once
    per output block (``num_blocks``), ``pl.when(not first)`` bodies run
    ``num_tiles - num_blocks`` times, factor loops multiply by
    ``n_inputs``;
  * the predicate class — the ``t == 0``-wrapped block-first test and
    the clamped look-ahead block-last test are recognized structurally
    (through the shared reaching-definition layer in
    ``repro.analysis.core``), so predicated accesses are priced by how
    often the predicate is true, not how often it is evaluated;
  * placement — HBM-pipelined operands, scalar-prefetch SMEM metadata,
    VMEM scratch, and the XLA scan carry are distinct spaces.

Two censuses exist per kernel: the **padded** census is polynomial in
the plan geometry (``nnz_pad``, ``num_tiles``, ``num_blocks``) and is
evaluated exactly against concrete plans; the **semantic** census
substitutes the padding-free identities (``num_tiles·tile_nnz =
nnz_pad → nnz``, ``num_blocks·rows_per_block → I_mode``,
``num_chunks·nnz_chunk → nnz``) and is what the ``traffic-model-drift``
checker compares term-for-term against ``repro.core.hierarchy``'s
per-nonzero counts and ``repro.model.controller.request_streams``.

The interpreter never imports the scanned kernels — it is pure AST
inspection, so it proves the TPU kernel's traffic on a CPU-only box.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Iterable, Sequence

from repro.analysis.core import (
    AnalysisContext,
    FunctionIndex,
    FunctionInfo,
    SourceFile,
    call_name,
    reaching_def,
    straightline_defs,
)
from repro.analysis.poly import Poly, poly_sum

__all__ = [
    "AccessSite",
    "KernelTrafficCensus",
    "Pred",
    "SEMANTIC_SUBS",
    "find_traffic_censuses",
    "semantic",
]

#: Local-name -> canonical symbol conventions for the kernel family
#: (matches the shipped wrappers' parameter/unpack spelling; unknown
#: names become symbols of their own name).
NAME_TO_SYM = {
    "tile_nnz": "tile_nnz",
    "rows_per_block": "rows_per_block",
    "rank": "rank",
    "r_pad": "rank",  # lane padding excluded: the census counts logical rank
    "nfac": "n_inputs",
    "num_blocks": "num_blocks",
    "num_tiles": "num_tiles",
    "nnz_pad": "nnz_pad",
    "nnz_chunk": "nnz_chunk",
    "nchunks": "num_chunks",
    "i_out": "I_mode",
}

#: Shapes of the plan device-buffer attributes consumed by the gather
#: wrappers (the ``PlanBuffers`` contract in ``kernels.mttkrp.ops``).
#: ``None`` axes are dropped by the ``[:, k]`` slice before counting.
PLAN_BUFFER_SHAPES: dict[str, tuple[str | None, ...]] = {
    "indices": ("nnz_pad", None),
    "values": ("nnz_pad",),
    "local_row": ("nnz_pad",),
    "tile_block": ("num_tiles",),
}

#: Padding-free normalization, applied iteratively by :func:`semantic`:
#: tiles×tile size collapses to the padded stream, block count × block
#: height to the output height, then plan/chunk padding to the real nnz
#: (padding rows carry value 0 pointing at the block's first row — an
#: exact IEEE +0.0, so the padding-free census is the semantic traffic).
SEMANTIC_SUBS: tuple[tuple[str, Poly], ...] = (
    ("num_tiles", Poly.var("nnz_pad") / Poly.var("tile_nnz")),
    ("num_chunks", Poly.var("nnz_pad") / Poly.var("nnz_chunk")),
    ("num_blocks", Poly.var("I_mode") / Poly.var("rows_per_block")),
    ("nnz_pad", Poly.var("nnz")),
)


def semantic(p: Poly) -> Poly:
    """The padding-free concretization of a padded-census polynomial."""
    for var, repl in SEMANTIC_SUBS:
        p = p.subs({var: repl})
    return p


def _sym(name: str) -> Poly:
    return Poly.var(NAME_TO_SYM.get(name, name))


class Pred:
    """Predicate classes of ``pl.when`` guards, with per-grid counts."""

    EVERY = "every-step"
    FIRST = "block-first"  # t==0 ∪ block boundary (wrap-guarded)
    NOT_FIRST = "block-interior"
    LAST = "block-last"  # t==N-1 ∪ clamped look-ahead boundary
    NOT_LAST = "not-block-last"
    FIRST_NO_WRAP = "block-first-unwrapped"  # boundary test missing t==0
    NOT_FIRST_NO_WRAP = "block-interior-unwrapped"
    UNKNOWN = "unknown"

    _NEG = {
        EVERY: UNKNOWN,
        FIRST: NOT_FIRST,
        NOT_FIRST: FIRST,
        LAST: NOT_LAST,
        NOT_LAST: LAST,
        FIRST_NO_WRAP: NOT_FIRST_NO_WRAP,
        NOT_FIRST_NO_WRAP: FIRST_NO_WRAP,
        UNKNOWN: UNKNOWN,
    }

    @classmethod
    def negate(cls, pred: str) -> str:
        return cls._NEG.get(pred, cls.UNKNOWN)

    @classmethod
    def count(cls, pred: str, grid: Poly, num_blocks: Poly | None) -> Poly:
        """How many grid steps satisfy the predicate.  Block-first and
        block-last each fire exactly once per output block (the plan's
        tile_block array is non-decreasing and covers every block)."""
        blocks = num_blocks if num_blocks is not None else Poly.var("num_blocks")
        if pred == cls.EVERY or pred == cls.UNKNOWN:
            return grid
        if pred in (cls.FIRST, cls.LAST, cls.FIRST_NO_WRAP):
            return blocks
        return grid - blocks  # the complements


@dataclasses.dataclass(frozen=True)
class AccessSite:
    """One load/store site with its grid-weighted symbolic traffic."""

    file: str
    line: int
    fn: str  # qualname of the function containing the site
    ref: str  # operand accessed (kernel ref or streamed name)
    op: str  # "load" | "store" | "rmw"
    space: str  # "hbm" | "vmem" | "smem" | "carry"
    role: str  # value|index|meta_index|factor_gather|factor_stream|output|psum
    pred: str  # Pred.* class of the guarding predicate
    count: Poly  # executions over the whole grid
    elements: Poly  # elements touched per execution
    note: str = ""

    @property
    def total(self) -> Poly:
        return self.count * self.elements

    def loads(self) -> Poly:
        return self.total if self.op in ("load", "rmw") else Poly()

    def stores(self) -> Poly:
        return self.total if self.op in ("store", "rmw") else Poly()

    def to_dict(self) -> dict:
        return {
            "file": self.file,
            "line": self.line,
            "fn": self.fn,
            "ref": self.ref,
            "op": self.op,
            "space": self.space,
            "role": self.role,
            "pred": self.pred,
            "count": str(self.count),
            "elements": str(self.elements),
            "total": str(self.total),
            "note": self.note,
        }


@dataclasses.dataclass
class KernelTrafficCensus:
    """The closed-form traffic census of one kernel program."""

    program: str  # wrapper function name, e.g. mttkrp_pallas_call
    kind: str  # "pallas" | "xla"
    file: str
    kernel_fn: str
    grid: Poly
    num_blocks: Poly | None
    sites: list[AccessSite]
    scratch_refs: tuple[str, ...]
    notes: list[str]

    def total(
        self,
        *,
        op: str | None = None,  # "load" / "store" (rmw counts in both)
        role: str | None = None,
        space: str | None = None,
    ) -> Poly:
        picked: list[Poly] = []
        for s in self.sites:
            if role is not None and s.role != role:
                continue
            if space is not None and s.space != space:
                continue
            if op == "load":
                picked.append(s.loads())
            elif op == "store":
                picked.append(s.stores())
            else:
                picked.append(s.total)
        return poly_sum(picked)

    def semantic_total(
        self,
        *,
        op: str | None = None,
        role: str | None = None,
        space: str | None = None,
    ) -> Poly:
        return semantic(self.total(op=op, role=role, space=space))

    def to_dict(self) -> dict:
        roles = sorted({s.role for s in self.sites})
        return {
            "program": self.program,
            "kind": self.kind,
            "file": self.file,
            "kernel_fn": self.kernel_fn,
            "grid": str(self.grid),
            "num_blocks": str(self.num_blocks) if self.num_blocks else None,
            "scratch_refs": list(self.scratch_refs),
            "sites": [s.to_dict() for s in self.sites],
            "totals": {
                role: {
                    "loads": str(self.total(op="load", role=role)),
                    "stores": str(self.total(op="store", role=role)),
                    "semantic_loads": str(self.semantic_total(op="load", role=role)),
                    "semantic_stores": str(
                        self.semantic_total(op="store", role=role)
                    ),
                }
                for role in roles
            },
            "notes": self.notes,
        }


# --------------------------------------------------------------------------
# Expression evaluation into the polynomial domain
# --------------------------------------------------------------------------


class _EvalError(Exception):
    pass


def _eval_poly(node: ast.expr, env: dict[str, Poly]) -> Poly:
    """Evaluate an integer-geometry expression to a Poly; raises
    :class:`_EvalError` on anything outside the exact fragment."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return Poly.const(node.value)
    if isinstance(node, ast.Name):
        if node.id in env:
            return env[node.id]
        raise _EvalError(f"unbound name {node.id}")
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return -_eval_poly(node.operand, env)
    if isinstance(node, ast.BinOp):
        left = _eval_poly(node.left, env)
        right = _eval_poly(node.right, env)
        if isinstance(node.op, ast.Add):
            return left + right
        if isinstance(node.op, ast.Sub):
            return left - right
        if isinstance(node.op, ast.Mult):
            return left * right
        if isinstance(node.op, (ast.FloorDiv, ast.Div)):
            # exact by the plan's divisibility guarantees (the wrappers
            # raise on non-multiples before this division runs)
            return left / right
        if isinstance(node.op, ast.Pow):
            exp = _eval_poly(node.right, env).as_constant()
            if exp is not None and exp.denominator == 1:
                return left ** int(exp)
    raise _EvalError(f"non-polynomial expression {ast.dump(node)[:60]}")


def _bind(env: dict[str, Poly], name: str, value: Poly | None) -> None:
    env[name] = value if value is not None else _sym(name)


def _build_env(
    fn: ast.FunctionDef | ast.AsyncFunctionDef,
    shape_env: dict[str, tuple[Poly, ...]],
    origin_env: dict[str, str],
) -> dict[str, Poly]:
    """Wrapper-level symbol environment: parameters bind by name
    convention, assignments evaluate where polynomial (``num_tiles =
    nnz_pad // tile_nnz``), shape unpacks bind both the names and the
    unpacked operand's symbolic shape."""
    env: dict[str, Poly] = {}
    for a in list(fn.args.args) + list(fn.args.kwonlyargs):
        env[a.arg] = _sym(a.arg)
        origin_env[a.arg] = a.arg
    for node in ast.walk(fn):
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target, value = node.targets[0], node.value
        # a, b, c = X.shape — bind names AND X's symbolic shape
        if isinstance(target, ast.Tuple) and isinstance(value, ast.Attribute) \
                and value.attr == "shape" and isinstance(value.value, ast.Name) \
                and all(isinstance(e, ast.Name) for e in target.elts):
            dims = tuple(_sym(e.id) for e in target.elts)  # type: ignore[union-attr]
            shape_env[value.value.id] = dims
            for e, d in zip(target.elts, dims):
                env[e.id] = d  # type: ignore[union-attr]
        elif isinstance(target, ast.Name):
            try:
                env[target.id] = _eval_poly(value, env)
            except _EvalError:
                env.setdefault(target.id, _sym(target.id))
            # array-shape tracking through reshape/moveaxis/zeros chains
            shp = _shape_of(value, env, shape_env)
            if shp is not None:
                shape_env[target.id] = shp
            # origin tracking: reshape/moveaxis/pad chains keep the root
            root = _origin_of(value, origin_env)
            if root is not None:
                origin_env[target.id] = root
    # shape guards like `if rows.shape != (nnz_pad,)` reveal param shapes
    for node in ast.walk(fn):
        if isinstance(node, ast.Compare) and len(node.comparators) == 1:
            lhs, rhs = node.left, node.comparators[0]
            if isinstance(lhs, ast.Attribute) and lhs.attr == "shape" and \
                    isinstance(lhs.value, ast.Name) and \
                    isinstance(rhs, ast.Tuple) and \
                    lhs.value.id not in shape_env:
                try:
                    shape_env[lhs.value.id] = tuple(
                        _eval_poly(e, env) for e in rhs.elts
                    )
                except _EvalError:
                    pass
    return env


def _origin_of(node: ast.expr, origin_env: dict[str, str]) -> str | None:
    """The root operand a value derives from, through reshape/moveaxis/
    pad/astype chains (load-bearing for role assignment: ``rows_c``
    derives from ``rows``, so its scan slices count as index loads)."""
    while True:
        if isinstance(node, ast.Name):
            return origin_env.get(node.id, node.id)
        if isinstance(node, ast.Attribute):
            node = node.value
            continue
        if isinstance(node, ast.Call):
            fname = call_name(node) or ""
            if fname.endswith((".reshape", ".astype")):
                node = node.func.value  # type: ignore[attr-defined]
                continue
            if fname.split(".")[-1] in ("moveaxis", "pad", "asarray"):
                if node.args:
                    node = node.args[0]
                    continue
            return None
        if isinstance(node, ast.Subscript):
            node = node.value
            continue
        return None


def _shape_of(
    node: ast.expr,
    env: dict[str, Poly],
    shape_env: dict[str, tuple[Poly, ...]],
) -> tuple[Poly, ...] | None:
    """Symbolic shape of a geometry expression where derivable:
    explicit ``reshape``/``zeros`` dims, ``moveaxis`` permutes, plan
    buffer attributes, scalar subscripts drop axes, ``[:, k]`` slices."""
    if isinstance(node, ast.Name):
        return shape_env.get(node.id)
    if isinstance(node, ast.Attribute):
        # bufs.indices / bufs.values / … — the PlanBuffers contract
        tmpl = PLAN_BUFFER_SHAPES.get(node.attr)
        if tmpl is not None:
            return tuple(
                Poly.var(t) if t is not None else Poly.var("_dropped")
                for t in tmpl
            )
        return None
    if isinstance(node, ast.Call):
        fname = call_name(node) or ""
        if fname.endswith(".reshape"):
            try:
                return tuple(_eval_poly(a, env) for a in node.args)
            except _EvalError:
                return None
        if fname.split(".")[-1] in ("zeros", "ones", "full", "empty") and node.args:
            shp = node.args[0]
            if isinstance(shp, ast.Tuple):
                try:
                    return tuple(_eval_poly(e, env) for e in shp.elts)
                except _EvalError:
                    return None
        if fname.split(".")[-1] == "moveaxis" and len(node.args) >= 3:
            inner = _shape_of(node.args[0], env, shape_env)
            try:
                src = int(_eval_poly(node.args[1], env).as_constant() or 0)
                dst = int(_eval_poly(node.args[2], env).as_constant() or 0)
            except _EvalError:
                return None
            if inner is None:
                return None
            dims = list(inner)
            dims.insert(dst, dims.pop(src))
            return tuple(dims)
        if fname.endswith((".astype",)):
            return _shape_of(node.func.value, env, shape_env)  # type: ignore[attr-defined]
        if fname.split(".")[-1] == "pad" and node.args:
            return _shape_of(node.args[0], env, shape_env)
    if isinstance(node, ast.Subscript):
        inner = _shape_of(node.value, env, shape_env)
        if inner is None:
            return None
        return _sliced_shape(inner, node.slice, env)
    return None


def _sliced_shape(
    shape: tuple[Poly, ...], sl: ast.expr, env: dict[str, Poly]
) -> tuple[Poly, ...]:
    """Shape after subscripting: scalar indices drop their axis, slices
    and Ellipsis keep theirs."""
    items = list(sl.elts) if isinstance(sl, ast.Tuple) else [sl]
    out: list[Poly] = []
    axis = 0
    for item in items:
        if axis >= len(shape):
            break
        if isinstance(item, ast.Slice):
            out.append(shape[axis])
            axis += 1
        elif isinstance(item, ast.Constant) and item.value is Ellipsis:
            # Ellipsis keeps all remaining axes not consumed by later items
            keep = len(shape) - axis - (len(items) - items.index(item) - 1)
            out.extend(shape[axis:axis + keep])
            axis += keep
        elif isinstance(item, ast.Constant) and item.value is None:
            out.append(Poly.const(1))  # newaxis
        else:
            axis += 1  # scalar index drops the axis
    out.extend(shape[axis:])
    return tuple(out)


def _elements(shape: Sequence[Poly]) -> Poly:
    out = Poly.const(1)
    for d in shape:
        out = out * d
    return out


def _role_for(name: str) -> str:
    """Role conventions for kernel refs and plan-derived operands."""
    lowered = name.lower()
    if "tile_block" in lowered or lowered in ("tb", "tb_ref"):
        return "meta_index"
    if "local" in lowered or lowered.startswith("rows") or lowered == "rr":
        return "index"
    if "val" in lowered or lowered == "vv":
        return "value"
    if "fac" in lowered or "gather" in lowered or lowered == "gg":
        return "factor_stream"
    if "out" in lowered:
        return "output"
    if "acc" in lowered or "scratch" in lowered:
        return "psum"
    return "data"


# --------------------------------------------------------------------------
# Pallas program extraction
# --------------------------------------------------------------------------


@dataclasses.dataclass
class _RefInfo:
    name: str
    shape: tuple[Poly, ...]
    space: str
    role: str


@dataclasses.dataclass
class PallasProgram:
    sf: SourceFile
    wrapper: FunctionInfo
    kernel: FunctionInfo
    grid: tuple[Poly, ...]
    refs: dict[str, _RefInfo]
    scratch_refs: tuple[str, ...]
    scalar_prefetch_refs: tuple[str, ...]
    num_blocks: Poly | None
    env: dict[str, Poly]
    notes: list[str]


def _blockspec_dims(call: ast.Call, env: dict[str, Poly]) -> tuple[Poly, ...]:
    if not call.args:
        raise _EvalError("BlockSpec without a block shape")
    shp = call.args[0]
    elts = shp.elts if isinstance(shp, ast.Tuple) else [shp]
    return tuple(_eval_poly(e, env) for e in elts)


def _extract_pallas_program(
    sf: SourceFile, index: FunctionIndex, wrapper: FunctionInfo
) -> PallasProgram | None:
    """Parse the grid spec + pallas_call out of a wrapper function.
    Returns None (with no side effects) when the function is not a
    scalar-prefetch streaming program of the MTTKRP shape."""
    grid_call: ast.Call | None = None
    for node in ast.walk(wrapper.node):
        if isinstance(node, ast.Call) and \
                (call_name(node) or "").endswith("PrefetchScalarGridSpec"):
            grid_call = node
            break
    if grid_call is None:
        return None

    shape_env: dict[str, tuple[Poly, ...]] = {}
    origin_env: dict[str, str] = {}
    env = _build_env(wrapper.node, shape_env, origin_env)
    kw = {k.arg: k.value for k in grid_call.keywords if k.arg}

    notes: list[str] = []
    nsp = 0
    if isinstance(kw.get("num_scalar_prefetch"), ast.Constant):
        nsp = int(kw["num_scalar_prefetch"].value)  # type: ignore[attr-defined]
    grid_node = kw.get("grid")
    if not isinstance(grid_node, ast.Tuple):
        return None
    try:
        grid = tuple(_eval_poly(e, env) for e in grid_node.elts)
        in_dims = [
            _blockspec_dims(c, env)
            for c in getattr(kw.get("in_specs"), "elts", [])
            if isinstance(c, ast.Call)
        ]
        out_node = kw.get("out_specs")
        out_calls = (
            [c for c in out_node.elts if isinstance(c, ast.Call)]
            if isinstance(out_node, ast.List)
            else [out_node] if isinstance(out_node, ast.Call) else []
        )
        out_dims = [_blockspec_dims(c, env) for c in out_calls]
        scratch_dims = []
        for c in getattr(kw.get("scratch_shapes"), "elts", []):
            if isinstance(c, ast.Call) and c.args and \
                    isinstance(c.args[0], ast.Tuple):
                scratch_dims.append(
                    tuple(_eval_poly(e, env) for e in c.args[0].elts)
                )
    except _EvalError as exc:
        notes.append(f"grid spec not fully symbolic: {exc}")
        return None

    # Resolve the kernel function through the pallas_call argument.
    kernel_info: FunctionInfo | None = None
    for node in ast.walk(wrapper.node):
        if isinstance(node, ast.Call) and \
                (call_name(node) or "").endswith("pallas_call") and node.args:
            first = node.args[0]
            if isinstance(first, ast.Name):
                kernel_info = index.resolve(first.id)
    if kernel_info is None:
        return None

    params = [a.arg for a in kernel_info.node.args.args]
    expected = nsp + len(in_dims) + len(out_dims) + len(scratch_dims)
    if len(params) != expected:
        notes.append(
            f"kernel has {len(params)} refs, grid spec implies {expected}"
        )
        return None

    refs: dict[str, _RefInfo] = {}
    i = 0
    for _ in range(nsp):
        refs[params[i]] = _RefInfo(
            params[i], (grid[0],), "smem", _role_for(params[i])
        )
        i += 1
    for dims in in_dims:
        refs[params[i]] = _RefInfo(params[i], dims, "hbm", _role_for(params[i]))
        i += 1
    for dims in out_dims:
        refs[params[i]] = _RefInfo(params[i], dims, "hbm", "output")
        i += 1
    scratch = []
    for dims in scratch_dims:
        refs[params[i]] = _RefInfo(params[i], dims, "vmem", "psum")
        scratch.append(params[i])
        i += 1

    return PallasProgram(
        sf=sf,
        wrapper=wrapper,
        kernel=kernel_info,
        grid=grid,
        refs=refs,
        scratch_refs=tuple(scratch),
        scalar_prefetch_refs=tuple(params[:nsp]),
        num_blocks=env.get("num_blocks"),
        env=env,
        notes=notes,
    )


# --------------------------------------------------------------------------
# Pallas kernel-body interpretation
# --------------------------------------------------------------------------


def _is_pid_zero_test(node: ast.expr, pid_vars: set[str]) -> bool:
    return (
        isinstance(node, ast.Compare)
        and len(node.ops) == 1
        and isinstance(node.ops[0], ast.Eq)
        and (
            (isinstance(node.left, ast.Name) and node.left.id in pid_vars
             and isinstance(node.comparators[0], ast.Constant)
             and node.comparators[0].value == 0)
            or (isinstance(node.comparators[0], ast.Name)
                and node.comparators[0].id in pid_vars
                and isinstance(node.left, ast.Constant)
                and node.left.value == 0)
        )
    )


def _is_grid_end_test(
    node: ast.expr, pid_vars: set[str], nprog_vars: set[str]
) -> bool:
    """``t == num_tiles - 1`` in either operand order."""
    if not (isinstance(node, ast.Compare) and len(node.ops) == 1
            and isinstance(node.ops[0], ast.Eq)):
        return False
    operands = [node.left, node.comparators[0]]
    has_pid = any(isinstance(o, ast.Name) and o.id in pid_vars for o in operands)
    has_end = any(
        isinstance(o, ast.BinOp) and isinstance(o.op, ast.Sub)
        and isinstance(o.left, ast.Name) and o.left.id in nprog_vars
        and isinstance(o.right, ast.Constant) and o.right.value == 1
        for o in operands
    )
    return has_pid and has_end


def _boundary_kind(
    node: ast.expr,
    pid_vars: set[str],
    prefetch_refs: tuple[str, ...],
    resolve: "dict[str, ast.expr]",
) -> str | None:
    """Classify a ``!=`` comparison as a prev/next block-boundary test:
    one side (after one reaching-definition hop) subscripts a
    scalar-prefetch ref at ``t-1`` (prev) or a clamped/advanced ``t+1``
    (next)."""
    if not (isinstance(node, ast.Compare) and len(node.ops) == 1
            and isinstance(node.ops[0], ast.NotEq)):
        return None
    for side in (node.left, node.comparators[0]):
        expr = side
        if isinstance(expr, ast.Name) and expr.id in resolve:
            expr = resolve[expr.id]
        if not (isinstance(expr, ast.Subscript)
                and isinstance(expr.value, ast.Name)
                and expr.value.id in prefetch_refs):
            continue
        for n in ast.walk(expr.slice):
            if isinstance(n, ast.BinOp) and isinstance(n.left, ast.Name) \
                    and n.left.id in pid_vars:
                if isinstance(n.op, ast.Sub):
                    return "prev"
                if isinstance(n.op, ast.Add):
                    return "next"
    return None


def _classify_predicates(
    kernel: ast.FunctionDef | ast.AsyncFunctionDef,
    pid_vars: set[str],
    nprog_vars: set[str],
    prefetch_refs: tuple[str, ...],
) -> dict[str, str]:
    """Predicate-name -> Pred class for the kernel's guard assignments."""
    defs = straightline_defs(kernel)
    resolve = {n: es[0] for n, es in defs.items() if len(es) == 1}
    preds: dict[str, str] = {}

    def classify(expr: ast.expr) -> str:
        name = call_name(expr) if isinstance(expr, ast.Call) else None
        if name and name.split(".")[-1] == "logical_or" and \
                len(expr.args) == 2:  # type: ignore[union-attr]
            parts = expr.args  # type: ignore[union-attr]
            kinds = []
            for p in parts:
                if _is_pid_zero_test(p, pid_vars):
                    kinds.append("zero")
                elif _is_grid_end_test(p, pid_vars, nprog_vars):
                    kinds.append("end")
                else:
                    kinds.append(_boundary_kind(p, pid_vars, prefetch_refs,
                                                resolve) or "?")
            ks = set(kinds)
            if ks == {"zero", "prev"}:
                return Pred.FIRST
            if ks == {"end", "next"}:
                return Pred.LAST
            return Pred.UNKNOWN
        if name and name.split(".")[-1] == "logical_not" and \
                len(expr.args) == 1:  # type: ignore[union-attr]
            inner = expr.args[0]  # type: ignore[union-attr]
            if isinstance(inner, ast.Name) and inner.id in preds:
                return Pred.negate(preds[inner.id])
            return Pred.negate(classify(inner))
        kind = _boundary_kind(expr, pid_vars, prefetch_refs, resolve)
        if kind == "prev":
            return Pred.FIRST_NO_WRAP
        if kind == "next":
            return Pred.LAST  # clamped look-ahead alone still fires per block
        return Pred.UNKNOWN

    for stmt in ast.walk(kernel):
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                isinstance(stmt.targets[0], ast.Name):
            cls = classify(stmt.value)
            if cls != Pred.UNKNOWN:
                preds[stmt.targets[0].id] = cls
    return preds


def interpret_pallas_kernel(program: PallasProgram) -> list[AccessSite]:
    """Walk the kernel body in textual (= execution) order, emitting one
    :class:`AccessSite` per ref subscript, grid-weighted and
    predicate-priced.  ``pl.when``-decorated defs execute at their
    definition point, so textual order is execution order."""
    sf, kernel = program.sf, program.kernel.node
    grid_total = _elements(program.grid)
    refs = program.refs
    env: dict[str, Poly] = {}
    for a in list(kernel.args.args) + list(kernel.args.kwonlyargs):
        if a.arg not in refs:
            env[a.arg] = _sym(a.arg)

    pid_vars: set[str] = set()
    nprog_vars: set[str] = set()
    for node in ast.walk(kernel):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                isinstance(node.value, ast.Call):
            fname = (call_name(node.value) or "").split(".")[-1]
            if fname == "program_id":
                pid_vars.add(node.targets[0].id)
            elif fname == "num_programs":
                nprog_vars.add(node.targets[0].id)
                env[node.targets[0].id] = program.grid[0]

    preds = _classify_predicates(
        kernel, pid_vars, nprog_vars, program.scalar_prefetch_refs
    )
    sites: list[AccessSite] = []

    def emit(node: ast.Subscript, op: str, count: Poly, pred: str) -> None:
        assert isinstance(node.value, ast.Name)
        info = refs[node.value.id]
        shape = _sliced_shape(info.shape, node.slice, env)
        note = ""
        if pred == Pred.FIRST_NO_WRAP:
            note = "predicate lacks the t==0 wrap guard"
        sites.append(
            AccessSite(
                file=sf.path,
                line=node.lineno,
                fn=program.kernel.qualname,
                ref=info.name,
                op=op,
                space=info.space,
                role=info.role,
                pred=pred,
                count=count,
                elements=_elements(shape),
                note=note,
            )
        )

    def ref_subscripts(expr: ast.expr) -> list[ast.Subscript]:
        return [
            n for n in ast.walk(expr)
            if isinstance(n, ast.Subscript)
            and isinstance(n.value, ast.Name) and n.value.id in refs
        ]

    def walk(body: Iterable[ast.stmt], count: Poly, pred: str) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inner_pred = pred
                inner_count = count
                for dec in stmt.decorator_list:
                    if isinstance(dec, ast.Call) and \
                            (call_name(dec) or "").split(".")[-1] == "when" \
                            and dec.args:
                        guard = dec.args[0]
                        if isinstance(guard, ast.Name):
                            inner_pred = preds.get(guard.id, Pred.UNKNOWN)
                        elif isinstance(guard, ast.Call) and \
                                (call_name(guard) or "").split(".")[-1] == \
                                "logical_not" and guard.args and \
                                isinstance(guard.args[0], ast.Name):
                            inner_pred = Pred.negate(
                                preds.get(guard.args[0].id, Pred.UNKNOWN)
                            )
                        inner_count = count * Pred.count(
                            inner_pred, grid_total, program.num_blocks
                        ) / grid_total
                walk(stmt.body, inner_count, inner_pred)
                continue
            if isinstance(stmt, ast.For):
                trips: Poly | None = None
                it = stmt.iter
                if isinstance(it, ast.Call) and \
                        (call_name(it) or "").split(".")[-1] == "range":
                    try:
                        if len(it.args) == 1:
                            trips = _eval_poly(it.args[0], env)
                        elif len(it.args) >= 2:
                            trips = _eval_poly(it.args[1], env) - \
                                _eval_poly(it.args[0], env)
                    except _EvalError:
                        trips = None
                walk(stmt.body, count * (trips if trips is not None
                                         else Poly.var("_loop")), pred)
                continue
            # loads/stores in this statement
            store_nodes: list[ast.Subscript] = []
            if isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    if isinstance(t, ast.Subscript) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id in refs:
                        store_nodes.append(t)
                for sub in ref_subscripts(stmt.value):
                    emit(sub, "load", count, pred)
                for t in store_nodes:
                    emit(t, "store", count, pred)
            elif isinstance(stmt, ast.AugAssign):
                if isinstance(stmt.target, ast.Subscript) and \
                        isinstance(stmt.target.value, ast.Name) and \
                        stmt.target.value.id in refs:
                    emit(stmt.target, "rmw", count, pred)
                for sub in ref_subscripts(stmt.value):
                    emit(sub, "load", count, pred)
            else:
                for sub in ref_subscripts(stmt):
                    emit(sub, "load", count, pred)

    walk(kernel.body, grid_total, Pred.EVERY)
    return sites


# --------------------------------------------------------------------------
# Gather-wrapper interpretation (the dispatch layer's jnp.take sites)
# --------------------------------------------------------------------------


def _is_modes_minus_one(expr: ast.expr) -> bool:
    """``[k for k in range(len(factors)) if k != mode]`` — the all-but-
    the-output-mode iteration of the gather wrappers."""
    if not isinstance(expr, ast.ListComp) or len(expr.generators) != 1:
        return False
    gen = expr.generators[0]
    it = gen.iter
    if not (isinstance(it, ast.Call)
            and (call_name(it) or "").split(".")[-1] == "range"):
        return False
    return any(
        isinstance(test, ast.Compare) and len(test.ops) == 1
        and isinstance(test.ops[0], ast.NotEq)
        for test in gen.ifs
    )


def find_gather_sites(
    sf: SourceFile, fn: FunctionInfo, program_names: set[str]
) -> list[AccessSite]:
    """``jnp.take(factor, idx, axis=0)`` sites in a wrapper that calls
    one of the kernel programs: each take is one factor-row gather (the
    cache-subsystem request the hierarchy prices) plus one read of the
    index column driving it.  Two forms are recognized:

      * a take inside the modes-minus-one comprehension, one per input
        factor: the comprehension multiplies by ``n_inputs``;
      * a single take from a table of all input factors, whose index is
        ``concatenate([col_k + offset_k for k in other])`` over that
        comprehension: one column per input factor, so again
        ``n_inputs`` columns' worth of rows and indices.  The element may
        be ``col_k + offset_k if k in table else zeros``: a factor left
        out of the table is read by a take of its own, outside any
        comprehension, in place of its column here, so the count holds.
    """
    calls_program = any(
        isinstance(n, ast.Call)
        and (call_name(n) or "").split(".")[-1] in program_names
        for n in ast.walk(fn.node)
    )
    if not calls_program:
        return []

    defs = straightline_defs(fn.node)
    shape_env: dict[str, tuple[Poly, ...]] = {}
    origin_env: dict[str, str] = {}
    env = _build_env(fn.node, shape_env, origin_env)
    sites: list[AccessSite] = []

    def comp_mult(comp: ast.ListComp) -> Poly:
        """``n_inputs`` for a modes-minus-one comprehension (written out
        or iterating a name bound to one), else 1."""
        gen = comp.generators[0] if comp.generators else None
        if gen is not None and isinstance(gen.iter, ast.Name):
            target = defs.get(gen.iter.id, [None])[0]
            if target is not None and _is_modes_minus_one(target):
                return Poly.var("n_inputs")
        elif gen is not None and _is_modes_minus_one(comp):
            return Poly.var("n_inputs")
        return Poly.const(1)

    def stacked_columns(idx: ast.expr) -> tuple[Poly, Poly] | None:
        """(multiplier, column length) of an index stacked from one
        offset column per input factor, else None."""
        built = reaching_def(fn.node, idx.id, defs) if isinstance(idx, ast.Name) else idx
        if not (isinstance(built, ast.Call)
                and (call_name(built) or "").split(".")[-1] == "concatenate"
                and built.args):
            return None
        comp = built.args[0]
        if not isinstance(comp, ast.ListComp):
            return None
        mult = comp_mult(comp)
        col = comp.elt
        if isinstance(col, ast.IfExp):  # col_k + offset_k if k in table else zeros
            col = col.body
        while isinstance(col, ast.BinOp):  # col_k + offset_k
            col = col.left
        shape = _shape_of(col, env, shape_env)
        if mult == Poly.const(1) or shape is None or len(shape) != 1:
            return None
        return mult, shape[0]

    def emit(take: ast.Call, mult: Poly, length: Poly) -> None:
        sites.append(
            AccessSite(
                file=sf.path, line=take.lineno, fn=fn.qualname,
                ref=ast.unparse(take.args[0])[:40],
                op="load", space="hbm", role="factor_gather",
                pred=Pred.EVERY, count=mult,
                elements=length * Poly.var("rank"),
                note="factor-row gather (one row per nonzero)",
            )
        )
        sites.append(
            AccessSite(
                file=sf.path, line=take.lineno, fn=fn.qualname,
                ref=ast.unparse(take.args[1])[:40],
                op="load", space="hbm", role="index",
                pred=Pred.EVERY, count=mult,
                elements=length,
                note="gather index column",
            )
        )

    def takes(tree: ast.AST) -> list[ast.Call]:
        return [
            n for n in ast.walk(tree)
            if isinstance(n, ast.Call) and len(n.args) >= 2
            and (call_name(n) or "").split(".")[-1] == "take"
        ]

    seen: set[int] = set()
    for node in ast.walk(fn.node):
        if not isinstance(node, ast.ListComp):
            continue
        mult = comp_mult(node)
        for take in takes(node):
            if id(take) in seen:
                continue
            seen.add(id(take))
            idx_shape = _shape_of(take.args[1], env, shape_env)
            if idx_shape is not None and len(idx_shape) == 1:
                emit(take, mult, idx_shape[0])
    for take in takes(fn.node):
        if id(take) not in seen:
            stacked = stacked_columns(take.args[1])
            if stacked is not None:
                emit(take, *stacked)
    return sites


# --------------------------------------------------------------------------
# XLA scatter-accumulate program interpretation
# --------------------------------------------------------------------------


@dataclasses.dataclass
class XlaProgram:
    sf: SourceFile
    wrapper: FunctionInfo
    scan_body: FunctionInfo
    env: dict[str, Poly]
    shape_env: dict[str, tuple[Poly, ...]]
    origin_env: dict[str, str]
    notes: list[str]


def _find_at_add(node: ast.expr) -> tuple[ast.Name, ast.expr] | None:
    """Match ``carry.at[idx].add(x)`` -> (carry name node, idx expr)."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
            and node.func.attr == "add":
        sub = node.func.value
        if isinstance(sub, ast.Subscript) and \
                isinstance(sub.value, ast.Attribute) and \
                sub.value.attr == "at" and \
                isinstance(sub.value.value, ast.Name):
            return sub.value.value, sub.slice
    return None


def interpret_xla_program(program: XlaProgram) -> list[AccessSite]:
    """Interpret the chunked ``lax.scan`` scatter-accumulate: the scan
    multiplies body sites by ``num_chunks``, ``acc.at[rows].add`` is a
    read-modify-write of one accumulator row per nonzero, the zero init
    and the returned accumulator are the output-sized stores."""
    sf = program.sf
    wrapper = program.wrapper
    env, shape_env = program.env, program.shape_env
    origin_env = program.origin_env
    sites: list[AccessSite] = []

    # locate the scan call
    scan_call: ast.Call | None = None
    carry_names: set[str] = set()
    for node in ast.walk(wrapper.node):
        if isinstance(node, ast.Call) and \
                (call_name(node) or "").split(".")[-1] == "scan" and \
                len(node.args) >= 3:
            scan_call = node
    if scan_call is None:
        return sites

    init_node, xs_node = scan_call.args[1], scan_call.args[2]
    carry_shape = _shape_of(init_node, env, shape_env)
    xs_elts = list(xs_node.elts) if isinstance(xs_node, ast.Tuple) else [xs_node]
    xs_shapes = [_shape_of(e, env, shape_env) for e in xs_elts]
    xs_origins = [_origin_of(e, origin_env) for e in xs_elts]
    steps: Poly | None = None
    for shp in xs_shapes:
        if shp:
            steps = shp[0]
            break
    if steps is None or carry_shape is None:
        program.notes.append("scan operand shapes not derivable")
        return sites

    # the scan result is the carry; wrapper-level returns of it are the
    # output store
    for node in ast.walk(wrapper.node):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and node.value is scan_call:
            for t in node.targets:
                elts = t.elts if isinstance(t, ast.Tuple) else [t]
                if elts and isinstance(elts[0], ast.Name):
                    carry_names.add(elts[0].id)

    # accumulator init (jnp.zeros((i_out, rank)))
    sites.append(
        AccessSite(
            file=sf.path, line=init_node.lineno, fn=wrapper.qualname,
            ref=ast.unparse(init_node)[:40], op="store", space="carry",
            role="psum", pred=Pred.EVERY, count=Poly.const(1),
            elements=_elements(carry_shape), note="accumulator zero-init",
        )
    )

    # body interpretation
    body_fn = program.scan_body.node
    body_params = [a.arg for a in body_fn.args.args]
    operand_names: dict[str, tuple[tuple[Poly, ...], str]] = {}
    carry_param = body_params[0] if body_params else None
    if len(body_params) >= 2:
        xs_param = body_params[1]
        # `rr, vv, gg = xs` unpack inside the body
        for node in ast.walk(body_fn):
            if isinstance(node, ast.Assign) and \
                    isinstance(node.value, ast.Name) and \
                    node.value.id == xs_param and \
                    isinstance(node.targets[0], ast.Tuple):
                for e, shp, origin in zip(
                    node.targets[0].elts, xs_shapes, xs_origins
                ):
                    if isinstance(e, ast.Name) and shp is not None:
                        operand_names[e.id] = (
                            tuple(shp[1:]), _role_for(origin or e.id)
                        )

    benv = dict(env)
    for a in list(body_fn.args.args) + list(body_fn.args.kwonlyargs):
        benv.setdefault(a.arg, _sym(a.arg))

    # per-iteration loop multipliers inside the body (factor loop)
    def body_walk(body: Iterable[ast.stmt], count: Poly) -> None:
        for stmt in body:
            if isinstance(stmt, ast.For):
                trips: Poly | None = None
                it = stmt.iter
                if isinstance(it, ast.Call) and \
                        (call_name(it) or "").split(".")[-1] == "range":
                    try:
                        if len(it.args) == 1:
                            trips = _eval_poly(it.args[0], benv)
                        elif len(it.args) >= 2:
                            trips = _eval_poly(it.args[1], benv) - \
                                _eval_poly(it.args[0], benv)
                    except _EvalError:
                        trips = None
                body_walk(stmt.body, count * (trips if trips is not None
                                              else Poly.var("_loop")))
                continue
            excluded: set[int] = set()
            # carry.at[idx].add(x) — RMW of the addressed rows
            for node in ast.walk(stmt):
                hit = _find_at_add(node) if isinstance(node, ast.expr) else None
                if hit is None:
                    continue
                carry_node, idx = hit
                excluded.add(id(carry_node))
                idx_shape = (
                    operand_names.get(idx.id, ((), ""))[0]
                    if isinstance(idx, ast.Name) else None
                )
                rows = idx_shape[0] if idx_shape else Poly.var("_rows")
                sites.append(
                    AccessSite(
                        file=sf.path, line=node.lineno,
                        fn=program.scan_body.qualname,
                        ref=carry_node.id, op="rmw", space="carry",
                        role="psum", pred=Pred.EVERY, count=count,
                        elements=rows * _elements(carry_shape[1:]),
                        note="scatter-accumulate rows (2·rank per nonzero)",
                    )
                )
            # subscripted operand slices (gg[0], gg[k])
            for node in ast.walk(stmt):
                if isinstance(node, ast.Subscript) and \
                        isinstance(node.value, ast.Name) and \
                        node.value.id in operand_names:
                    shp, role = operand_names[node.value.id]
                    excluded.add(id(node.value))
                    sites.append(
                        AccessSite(
                            file=sf.path, line=node.lineno,
                            fn=program.scan_body.qualname,
                            ref=node.value.id, op="load", space="hbm",
                            role=role, pred=Pred.EVERY, count=count,
                            elements=_elements(
                                _sliced_shape(shp, node.slice, benv)
                            ),
                        )
                    )
            # whole-operand reads (vv, rr)
            for node in ast.walk(stmt):
                if isinstance(node, ast.Name) and \
                        isinstance(node.ctx, ast.Load) and \
                        node.id in operand_names and \
                        id(node) not in excluded:
                    shp, role = operand_names[node.id]
                    sites.append(
                        AccessSite(
                            file=sf.path, line=node.lineno,
                            fn=program.scan_body.qualname,
                            ref=node.id, op="load", space="hbm",
                            role=role, pred=Pred.EVERY, count=count,
                            elements=_elements(shp),
                        )
                    )

    body_walk(body_fn.body, steps)

    for node in ast.walk(wrapper.node):
        if isinstance(node, ast.Return) and isinstance(node.value, ast.Name) \
                and node.value.id in carry_names:
            sites.append(
                AccessSite(
                    file=sf.path, line=node.lineno, fn=wrapper.qualname,
                    ref=node.value.id, op="store", space="hbm",
                    role="output", pred=Pred.EVERY, count=Poly.const(1),
                    elements=_elements(carry_shape),
                    note="exact (I_mode, rank) output — no block padding",
                )
            )
    _ = carry_param
    return sites


# --------------------------------------------------------------------------
# Program discovery + census assembly
# --------------------------------------------------------------------------


def find_traffic_censuses(
    files: Sequence[SourceFile],
) -> tuple[list[KernelTrafficCensus], list[dict]]:
    """Discover every kernel program in ``files`` and interpret it.

    Returns (censuses, skipped): Pallas scalar-prefetch streaming
    programs and XLA scan/scatter-accumulate programs get a census;
    other ``pallas_call`` users (e.g. the flash-attention kernel, which
    has no scalar-prefetch grid) are recorded as skipped with a reason.
    """
    censuses: list[KernelTrafficCensus] = []
    skipped: list[dict] = []
    programs: list[tuple[SourceFile, FunctionInfo, str]] = []

    indexes: dict[str, FunctionIndex] = {}
    for sf in files:
        index = indexes.setdefault(sf.path, FunctionIndex(sf))
        for info in index.infos.values():
            has_pallas_call = any(
                isinstance(n, ast.Call)
                and (call_name(n) or "").split(".")[-1] == "pallas_call"
                for n in ast.walk(info.node)
            )
            if has_pallas_call:
                prog = _extract_pallas_program(sf, index, info)
                if prog is None:
                    skipped.append(
                        {
                            "file": sf.path,
                            "fn": info.qualname,
                            "reason": "no scalar-prefetch streaming grid "
                                      "spec (not an MTTKRP-shaped program)",
                        }
                    )
                    continue
                sites = interpret_pallas_kernel(prog)
                censuses.append(
                    KernelTrafficCensus(
                        program=info.node.name,
                        kind="pallas",
                        file=sf.path,
                        kernel_fn=prog.kernel.qualname,
                        grid=_elements(prog.grid),
                        num_blocks=prog.num_blocks,
                        sites=sites,
                        scratch_refs=prog.scratch_refs,
                        notes=prog.notes + [
                            "scalar-prefetch metadata (tile_block) is "
                            "sub-linear plan traffic, excluded from the "
                            "§IV-A stream term",
                        ],
                    )
                )
                continue
            # XLA scatter-accumulate: lax.scan whose local body does
            # carry.at[...].add(...)
            for node in ast.walk(info.node):
                if not (isinstance(node, ast.Call)
                        and (call_name(node) or "").split(".")[-1] == "scan"
                        and node.args):
                    continue
                body_name = node.args[0]
                if not isinstance(body_name, ast.Name):
                    continue
                body_info = index.resolve(body_name.id)
                if body_info is None or not any(
                    isinstance(n, ast.expr) and _find_at_add(n)
                    for n in ast.walk(body_info.node)
                ):
                    continue
                shape_env: dict[str, tuple[Poly, ...]] = {}
                origin_env: dict[str, str] = {}
                env = _build_env(info.node, shape_env, origin_env)
                prog_x = XlaProgram(
                    sf=sf, wrapper=info, scan_body=body_info, env=env,
                    shape_env=shape_env, origin_env=origin_env, notes=[],
                )
                sites = interpret_xla_program(prog_x)
                if sites:
                    censuses.append(
                        KernelTrafficCensus(
                            program=info.node.name,
                            kind="xla",
                            file=sf.path,
                            kernel_fn=body_info.qualname,
                            grid=Poly.var("num_chunks"),
                            num_blocks=None,
                            sites=sites,
                            scratch_refs=(),
                            notes=prog_x.notes,
                        )
                    )
                break
        programs.extend(
            (sf, info, info.node.name) for info in index.infos.values()
        )

    # attach gather-wrapper sites to the programs they call; a test's own
    # wrapper (under tests/) restages rows to check the program against,
    # and is no part of the program's traffic
    program_by_name = {c.program: c for c in censuses}
    for sf in files:
        if sf.path.split("/")[0] == "tests":
            continue
        index = indexes[sf.path]
        for info in index.infos.values():
            if info.node.name in program_by_name:
                continue
            gsites = find_gather_sites(sf, info, set(program_by_name))
            if not gsites:
                continue
            # attribute to the (unique) program this wrapper calls
            called = {
                (call_name(n) or "").split(".")[-1]
                for n in ast.walk(info.node) if isinstance(n, ast.Call)
            } & set(program_by_name)
            for name in sorted(called):
                program_by_name[name].sites.extend(gsites)

    _ = programs
    return censuses, skipped
