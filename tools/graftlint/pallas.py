"""The GL020-series: Pallas/Mosaic kernel soundness rules (ISSUE 16).

The only defect class that has ever broken this repo ON HARDWARE —
Mosaic's "failed to prove that a tile index ... is divisible by the
tiling (8)" alignment proof (ops/pallas_blend.py round-1 failure) —
plus VMEM overspill, scratch read-before-write and async-copy protocol
bugs are all invisible on the CPU box: they surface only at Mosaic
compile/run time on the chip. These rules move the
statically-provable share of that class to lint time; the runtime half
is the kernelcheck interpret-mode sanitizer
(chunkflow_tpu/testing/kernelcheck.py).

The rules rest on a per-file Pallas kernel model (:class:`PallasModel`):
every ``pl.pallas_call`` site with its kernel function, grid spec
(``PrefetchScalarGridSpec``/``GridSpec``), BlockSpecs (memory space,
block shape, index-map constancy), scratch shapes, scalar-prefetch
count, ``input_output_aliases`` and ``interpret`` kwarg — plus the
positional mapping from kernel parameters to those roles (scalar
prefetch args, then inputs, then outputs, then scratch: the Pallas
calling convention).

Like every graftlint analysis this is module-local, name-based and
fold-what-you-can: symbolic shapes (the shipping kernels' ``py``/``px``
arguments) make a quantity unfoldable and the affected check SKIPS
rather than guesses — a lint that cries wolf on the kernels it exists
to protect would be deleted within a week. Deliberate exceptions get
``# graftlint: disable=GL02x`` with a justification.
"""
from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.graftlint.context import (
    FileContext,
    FuncNode,
    enclosing_function,
    walk_local,
)
from tools.graftlint.model import Finding, make_finding
from tools.graftlint.rules import Rule

#: Mosaic sublane tilings of the second-minor dim by dtype width
#: (f32 8, 16-bit 16, 8-bit 32); the minor dim is always 128 lanes
SUBLANE_TILINGS = (8, 16, 32)
LANE_TILING = 128

#: analytic VMEM budgets by device kind, bytes. ~16 MiB/core holds for
#: every generation this repo targets; the table exists so a future
#: part with a different budget is one entry, and CHUNKFLOW_VMEM_BUDGET
#: overrides outright (CI boxes lint for a specific target).
VMEM_BUDGETS: Dict[str, int] = {
    "tpu v3": 16 * 2**20,
    "tpu v4": 16 * 2**20,
    "tpu v5e": 16 * 2**20,
    "tpu v5p": 16 * 2**20,
    "tpu v6": 32 * 2**20,
    "default": 16 * 2**20,
}

#: jnp/np dtype name -> itemsize, for scratch-shape byte accounting
DTYPE_SIZES = {
    "float64": 8, "int64": 8, "uint64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool_": 1, "bool": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1,
}


def vmem_budget_bytes() -> int:
    """The device VMEM budget GL021 lints against:
    ``CHUNKFLOW_VMEM_BUDGET`` (bytes) wins outright; otherwise
    ``CHUNKFLOW_VMEM_DEVICE`` picks a :data:`VMEM_BUDGETS` row by
    substring (default row when unset/unmatched)."""
    raw = os.environ.get("CHUNKFLOW_VMEM_BUDGET", "").strip()
    if raw:
        try:
            return max(1, int(float(raw)))
        except ValueError:
            pass
    kind = os.environ.get("CHUNKFLOW_VMEM_DEVICE", "").lower()
    for needle, budget in VMEM_BUDGETS.items():
        if needle != "default" and needle in kind:
            return budget
    return VMEM_BUDGETS["default"]


# ---------------------------------------------------------------------------
# constant folding over module + function-local int bindings
# ---------------------------------------------------------------------------
def _const_env(ctx: FileContext, func: Optional[FuncNode]) -> Dict[str, int]:
    """Name -> int for simple constant assignments visible at ``func``:
    module-level ``_SUBLANE = 8`` style bindings plus the function's own
    locals. Reassigned names are dropped (ambiguous)."""
    env: Dict[str, int] = {}
    ambiguous: Set[str] = set()

    def note(target: ast.AST, value: ast.AST) -> None:
        if not isinstance(target, ast.Name):
            return
        folded = _fold_int(value, env)
        if folded is None or target.id in ambiguous:
            env.pop(target.id, None)
            ambiguous.add(target.id)
        elif target.id in env and env[target.id] != folded:
            env.pop(target.id)
            ambiguous.add(target.id)
        else:
            env[target.id] = folded

    for node in ctx.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            note(node.targets[0], node.value)
    scope = func
    while scope is not None:
        if not isinstance(scope, ast.Lambda):
            for node in walk_local(scope):
                if isinstance(node, ast.Assign) and len(node.targets) == 1:
                    note(node.targets[0], node.value)
        scope = enclosing_function(scope)
    return env


def _fold_int(node: Optional[ast.AST],
              env: Dict[str, int]) -> Optional[int]:
    """Fold an expression to an int using ``env``; None when symbolic."""
    if node is None:
        return None
    if isinstance(node, ast.Constant):
        return node.value if isinstance(node.value, int) \
            and not isinstance(node.value, bool) else None
    if isinstance(node, ast.Name):
        return env.get(node.id)
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        inner = _fold_int(node.operand, env)
        return -inner if inner is not None else None
    if isinstance(node, ast.BinOp):
        left = _fold_int(node.left, env)
        right = _fold_int(node.right, env)
        if left is None or right is None:
            return None
        if isinstance(node.op, ast.Add):
            return left + right
        if isinstance(node.op, ast.Sub):
            return left - right
        if isinstance(node.op, ast.Mult):
            return left * right
        if isinstance(node.op, ast.FloorDiv) and right != 0:
            return left // right
        if isinstance(node.op, ast.Mod) and right != 0:
            return left % right
        if isinstance(node.op, ast.Pow) and right >= 0:
            return left ** right
    return None


def _fold_shape(node: Optional[ast.AST],
                env: Dict[str, int]) -> Optional[Tuple[int, ...]]:
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    dims = [_fold_int(elt, env) for elt in node.elts]
    if any(d is None for d in dims):
        return None
    return tuple(dims)  # type: ignore[arg-type]


def _dtype_size(ctx: FileContext, node: Optional[ast.AST]) -> Optional[int]:
    """Itemsize of a dtype reference like ``jnp.float32``; None when the
    dtype is a runtime value (``chunk.dtype``)."""
    if node is None:
        return None
    resolved = ctx.imports.resolve(node)
    name = resolved.rsplit(".", 1)[-1] if resolved else (
        node.attr if isinstance(node, ast.Attribute) else (
            node.id if isinstance(node, ast.Name) else None))
    return DTYPE_SIZES.get(name) if name else None


def _resolve_tail(ctx: FileContext, node: ast.AST) -> str:
    """The resolved dotted path of a call target, or its syntactic tail
    when the root is not an import alias ('' when neither applies)."""
    resolved = ctx.imports.resolve(node)
    if resolved:
        return resolved
    parts: List[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
    return ".".join(reversed(parts))


def _is_call_to(ctx: FileContext, node: ast.AST, suffix: str) -> bool:
    return isinstance(node, ast.Call) and \
        _resolve_tail(ctx, node.func).endswith(suffix)


def _local_value(ctx: FileContext, name: str,
                 at: ast.AST) -> Optional[ast.AST]:
    """The value last assigned to ``name`` in the scope chain of ``at``
    (lexical, source order — good enough for the build-then-call shape
    every pallas_call site in this repo has)."""
    scope = enclosing_function(at)
    while True:
        body = walk_local(scope) if scope is not None else \
            ast.walk(ctx.tree)
        hit: Optional[ast.AST] = None
        for node in body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and node.targets[0].id == name:
                if hit is None or node.lineno <= getattr(at, "lineno", 1):
                    hit = node.value
        if hit is not None:
            return hit
        if scope is None:
            return None
        scope = enclosing_function(scope)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
@dataclass
class BlockSpecInfo:
    """One parsed ``pl.BlockSpec`` (or an unparseable stand-in)."""

    node: Optional[ast.AST] = None
    any_space: bool = False        # memory_space=pl.ANY / pltpu.HBM
    shape: Optional[Tuple[int, ...]] = None  # folded block shape
    has_block_shape: bool = False
    constant_index: bool = False   # index_map returns only constants


@dataclass
class ScratchInfo:
    """One parsed scratch_shapes entry."""

    node: Optional[ast.AST] = None
    kind: str = "other"            # 'vmem' | 'smem' | 'sem' | 'other'
    nbytes: Optional[int] = None   # folded shape x dtype size


@dataclass
class PallasCallSite:
    """One ``pl.pallas_call`` site with everything the rules inspect."""

    call: ast.Call
    builder: Optional[FuncNode]            # enclosing function
    kernel: Optional[FuncNode] = None
    num_scalar_prefetch: int = 0
    grid: Optional[ast.AST] = None
    in_specs: List[BlockSpecInfo] = field(default_factory=list)
    out_specs: List[BlockSpecInfo] = field(default_factory=list)
    scratch: List[ScratchInfo] = field(default_factory=list)
    #: folded input_output_aliases; None = kwarg absent;
    #: "unknown" = present but not a literal dict
    aliases: object = None
    interpret: Optional[ast.AST] = None    # the kwarg's value node
    #: kernel param name -> (kind, index within kind); kinds:
    #: 'scalar' | 'in' | 'out' | 'scratch'. Empty when the param count
    #: does not reconcile with the spec counts (model incomplete).
    params: Dict[str, Tuple[str, int]] = field(default_factory=dict)
    env: Dict[str, int] = field(default_factory=dict)


class PallasModel:
    """Every pallas_call site in one file, parsed once per file."""

    def __init__(self, ctx: FileContext):
        self.ctx = ctx
        self.sites: List[PallasCallSite] = []
        #: module defines/imports a ``*_mode`` selector (GL024)
        self.has_mode_selector = self._find_mode_selector(ctx)
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and \
                    _resolve_tail(ctx, node.func).endswith("pallas_call"):
                self.sites.append(self._parse_site(node))

    @staticmethod
    def _find_mode_selector(ctx: FileContext) -> bool:
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name.endswith("_mode"):
                return True
            if isinstance(node, ast.ImportFrom):
                for alias in node.names:
                    if (alias.asname or alias.name).endswith("_mode"):
                        return True
        return False

    # -- parsing -------------------------------------------------------
    def _parse_site(self, call: ast.Call) -> PallasCallSite:
        ctx = self.ctx
        builder = enclosing_function(call)
        site = PallasCallSite(call=call, builder=builder)
        site.env = _const_env(ctx, builder)

        # the kernel function: first positional arg
        if call.args:
            arg = call.args[0]
            if isinstance(arg, ast.Lambda):
                site.kernel = arg
            elif isinstance(arg, ast.Name):
                site.kernel = ctx.resolve_local(arg.id, call)

        kwargs = {kw.arg: kw.value for kw in call.keywords if kw.arg}
        site.interpret = kwargs.get("interpret")

        # grid spec: inline kwargs or a grid_spec object
        spec_kwargs = dict(kwargs)
        grid_spec = kwargs.get("grid_spec")
        if isinstance(grid_spec, ast.Name):
            grid_spec = _local_value(ctx, grid_spec.id, call)
        if isinstance(grid_spec, ast.Call):
            for kw in grid_spec.keywords:
                if kw.arg:
                    spec_kwargs.setdefault(kw.arg, kw.value)

        nsp = _fold_int(spec_kwargs.get("num_scalar_prefetch"), site.env)
        site.num_scalar_prefetch = nsp or 0
        site.grid = spec_kwargs.get("grid")
        site.in_specs = self._parse_spec_list(
            spec_kwargs.get("in_specs"), call)
        site.out_specs = self._parse_spec_list(
            spec_kwargs.get("out_specs"), call)
        site.scratch = self._parse_scratch(
            spec_kwargs.get("scratch_shapes"), call, site.env)
        site.aliases = self._parse_aliases(
            kwargs.get("input_output_aliases"), call, site.env)

        # out_specs may be implicit: one output per out_shape entry
        if not site.out_specs:
            out_shape = kwargs.get("out_shape")
            n_out = len(out_shape.elts) if isinstance(
                out_shape, (ast.List, ast.Tuple)) else 1
            site.out_specs = [BlockSpecInfo() for _ in range(n_out)]

        self._map_params(site)
        return site

    def _parse_spec_list(self, node: Optional[ast.AST],
                         at: ast.AST) -> List[BlockSpecInfo]:
        if isinstance(node, ast.Name):
            node = _local_value(self.ctx, node.id, at)
        if node is None:
            return []
        if isinstance(node, (ast.List, ast.Tuple)):
            return [self._parse_spec(elt, at) for elt in node.elts]
        return [self._parse_spec(node, at)]

    def _parse_spec(self, node: ast.AST, at: ast.AST) -> BlockSpecInfo:
        ctx = self.ctx
        if isinstance(node, ast.Name):
            resolved = _local_value(ctx, node.id, at)
            if resolved is not None:
                node = resolved
        info = BlockSpecInfo(node=node)
        if not _is_call_to(ctx, node, "BlockSpec"):
            return info
        assert isinstance(node, ast.Call)
        kwargs = {kw.arg: kw.value for kw in node.keywords if kw.arg}
        space = kwargs.get("memory_space")
        if space is not None:
            tail = _resolve_tail(ctx, space)
            info.any_space = tail.endswith(".ANY") or tail.endswith(".HBM")
        shape_node = node.args[0] if node.args else kwargs.get(
            "block_shape")
        if isinstance(shape_node, (ast.Tuple, ast.List)):
            info.has_block_shape = True
            env = _const_env(ctx, enclosing_function(at))
            info.shape = _fold_shape(shape_node, env)
        index_map = (node.args[1] if len(node.args) > 1
                     else kwargs.get("index_map"))
        if isinstance(index_map, ast.Lambda):
            body = index_map.body
            elts = body.elts if isinstance(body, ast.Tuple) else [body]
            info.constant_index = all(
                isinstance(e, ast.Constant) for e in elts)
        return info

    def _parse_scratch(self, node: Optional[ast.AST], at: ast.AST,
                       env: Dict[str, int]) -> List[ScratchInfo]:
        if isinstance(node, ast.Name):
            node = _local_value(self.ctx, node.id, at)
        if not isinstance(node, (ast.List, ast.Tuple)):
            return []
        out: List[ScratchInfo] = []
        for elt in node.elts:
            info = ScratchInfo(node=elt)
            tail = _resolve_tail(self.ctx, elt.func) if isinstance(
                elt, ast.Call) else ""
            if "SemaphoreType" in tail:
                info.kind = "sem"
            elif tail.endswith(".VMEM") or tail.endswith(".SMEM"):
                info.kind = "vmem" if tail.endswith(".VMEM") else "smem"
                assert isinstance(elt, ast.Call)
                shape = _fold_shape(
                    elt.args[0] if elt.args else None, env)
                size = _dtype_size(
                    self.ctx, elt.args[1] if len(elt.args) > 1 else None)
                if shape is not None and size is not None:
                    nbytes = size
                    for d in shape:
                        nbytes *= d
                    info.nbytes = nbytes
            out.append(info)
        return out

    @staticmethod
    def _parse_aliases(node: Optional[ast.AST], at: ast.AST,
                       env: Dict[str, int]) -> object:
        if node is None:
            return None
        if isinstance(node, ast.Dict):
            folded: Dict[int, int] = {}
            for k, v in zip(node.keys, node.values):
                ki, vi = _fold_int(k, env), _fold_int(v, env)
                if ki is None or vi is None:
                    return "unknown"
                folded[ki] = vi
            return folded
        return "unknown"

    @staticmethod
    def _map_params(site: PallasCallSite) -> None:
        if site.kernel is None:
            return
        args = site.kernel.args
        names = [a.arg for a in args.posonlyargs + args.args]
        counts = (site.num_scalar_prefetch, len(site.in_specs),
                  len(site.out_specs), len(site.scratch))
        if len(names) != sum(counts):
            return  # model incomplete: rules needing the mapping skip
        kinds = ("scalar", "in", "out", "scratch")
        i = 0
        for kind, count in zip(kinds, counts):
            for j in range(count):
                site.params[names[i]] = (kind, j)
                i += 1


def get_pallas_model(ctx: FileContext) -> PallasModel:
    model = getattr(ctx, "_pallas_model", None)
    if model is None:
        model = PallasModel(ctx)
        ctx._pallas_model = model  # type: ignore[attr-defined]
    return model


# ---------------------------------------------------------------------------
# kernel-body helpers shared by the rules
# ---------------------------------------------------------------------------
def _ref_of_subscript(node: ast.Subscript) -> Optional[str]:
    """The base ref name of ``ref[...]`` / ``ref.at[...]``."""
    value = node.value
    if isinstance(value, ast.Attribute) and value.attr == "at":
        value = value.value
    if isinstance(value, ast.Name):
        return value.id
    return None


def _index_elts(node: ast.Subscript) -> List[ast.AST]:
    idx = node.slice
    return list(idx.elts) if isinstance(idx, ast.Tuple) else [idx]


def _multiple_of_hints(ctx: FileContext,
                       kernel: FuncNode) -> Dict[str, ast.AST]:
    """name -> divisor expression for ``x = pl.multiple_of(expr, N)``
    bindings in the kernel body."""
    hints: Dict[str, ast.AST] = {}
    for node in walk_local(kernel):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and _is_call_to(ctx, node.value, ".multiple_of") \
                and len(node.value.args) > 1:
            hints[node.targets[0].id] = node.value.args[1]
    return hints


def _start_aligned(ctx: FileContext, expr: ast.AST, required: int,
                   hints: Dict[str, ast.AST],
                   env: Dict[str, int]) -> bool:
    """Whether a slice-start expression is provably aligned to the
    tiling: a divisible constant, a ``pl.multiple_of`` hint (inline or
    via a hinted local) whose divisor is a multiple of ``required`` (an
    unfoldable divisor gets the benefit of the doubt — the hint's
    PRESENCE is what this rule enforces; a wrong divisor still fails at
    Mosaic compile), or arithmetic that preserves alignment."""
    if isinstance(expr, ast.Constant):
        return isinstance(expr.value, int) and expr.value % required == 0
    if isinstance(expr, ast.Name):
        folded = _fold_int(expr, env)
        if folded is not None:
            return folded % required == 0
        divisor = hints.get(expr.id)
        if divisor is None:
            return False
        return _divisor_ok(divisor, required, env)
    if _is_call_to(ctx, expr, ".multiple_of") and len(expr.args) > 1:
        return _divisor_ok(expr.args[1], required, env)
    if isinstance(expr, ast.BinOp):
        if isinstance(expr.op, ast.Mult):
            for side in (expr.left, expr.right):
                folded = _fold_int(side, env)
                if folded is not None and folded % required == 0:
                    return True
            return False
        if isinstance(expr.op, (ast.Add, ast.Sub)):
            return all(
                _start_aligned(ctx, side, required, hints, env)
                for side in (expr.left, expr.right)
            )
    return False


def _divisor_ok(divisor: ast.AST, required: int,
                env: Dict[str, int]) -> bool:
    folded = _fold_int(divisor, env)
    if folded is None:
        return True  # hint present, divisor symbolic: benefit of doubt
    return folded % required == 0


# ---------------------------------------------------------------------------
# GL020: unaligned DMA slice corner
# ---------------------------------------------------------------------------
class UnalignedDmaSlice(Rule):
    """Dynamic slice corner into the minor dims of an ANY-space ref
    without a ``pl.multiple_of`` tiling hint.

    Mosaic requires DMA slice offsets into the two minor dims of a
    tiled HBM/ANY memref *provably* divisible by the dtype tiling —
    (sublane, 128) with sublane 8 for f32, 16 for 16-bit, 32 for 8-bit
    dtypes. A runtime index (a prefetched starts-table entry) carries no
    such proof, and the kernel dies at Mosaic compile time with
    "failed to prove that a tile index ... is divisible by the tiling"
    — the round-1 hardware failure of ops/pallas_blend.py, visible only
    on the chip. Round the corner down to the
    tiling host-side and hint it (``pl.multiple_of(start, 8)`` /
    ``(start, 128)``), then address the patch at its (dy, dx) offset
    inside the aligned VMEM window (the shipping kernels' pattern).
    """

    code = "GL020"
    name = "unaligned-dma-slice"

    def run(self, ctx: FileContext, config) -> Iterator[Finding]:
        model = get_pallas_model(ctx)
        for site in model.sites:
            if site.kernel is None or not site.params:
                continue
            any_refs = {
                name for name, (kind, j) in site.params.items()
                if kind == "in" and site.in_specs[j].any_space
                or kind == "out" and site.out_specs[j].any_space
            }
            if not any_refs:
                continue
            hints = _multiple_of_hints(ctx, site.kernel)
            for node in walk_local(site.kernel):
                if not isinstance(node, ast.Subscript):
                    continue
                ref = _ref_of_subscript(node)
                if ref not in any_refs:
                    continue
                elts = _index_elts(node)
                if len(elts) < 2:
                    continue
                checks = (
                    (elts[-2], "second-minor", min(SUBLANE_TILINGS),
                     "8/16/32"),
                    (elts[-1], "minor", LANE_TILING, "128"),
                )
                for elt, dim, required, tiling in checks:
                    start = elt.args[0] if _is_call_to(ctx, elt, ".ds") \
                        and elt.args else elt
                    if isinstance(start, ast.Slice):
                        start = start.lower or ast.Constant(value=0)
                    if not _start_aligned(ctx, start, required,
                                          hints, site.env):
                        yield make_finding(
                            ctx, node, self.code,
                            f"dynamic {dim}-dim slice corner into "
                            f"ANY-space ref `{ref}` without a "
                            f"`pl.multiple_of` hint matching the dtype "
                            f"tiling ({tiling}) — Mosaic cannot prove "
                            f"divisibility and fails at compile time "
                            f"on hardware; round the corner down and "
                            f"add the hint",
                        )


# ---------------------------------------------------------------------------
# GL021: analytic VMEM budget overflow
# ---------------------------------------------------------------------------
class VmemBudgetOverflow(Rule):
    """Analytic VMEM footprint exceeds the device budget.

    Per grid step a pallas_call holds: every blocked (non-ANY) in/out
    window — DOUBLED for non-constant-index blocks, which the pipeline
    double-buffers — plus every VMEM/SMEM scratch allocation. When that
    sum (folding what is constant-foldable; symbolic dims make a block
    unaccountable and it contributes nothing — this rule under-counts
    rather than guesses) exceeds the device VMEM budget
    (:func:`vmem_budget_bytes`; ``CHUNKFLOW_VMEM_BUDGET`` overrides,
    ``CHUNKFLOW_VMEM_DEVICE`` picks the table row), the kernel cannot
    compile on hardware — another failure class invisible on the CPU
    box. Block dtypes are unknown statically and assumed float32
    (4 bytes); scratch entries carry their dtype and are counted
    exactly. ``tools/kernel_report.py`` prints the same arithmetic with
    runtime shapes filled in.
    """

    code = "GL021"
    name = "vmem-budget-overflow"

    def run(self, ctx: FileContext, config) -> Iterator[Finding]:
        model = get_pallas_model(ctx)
        budget = vmem_budget_bytes()
        for site in model.sites:
            total = 0
            accounted = []
            for spec in site.in_specs + site.out_specs:
                if spec.any_space or spec.shape is None:
                    continue
                elems = 1
                for d in spec.shape:
                    elems *= d
                nbytes = elems * 4  # dtype unknown statically: assume f32
                if not spec.constant_index:
                    nbytes *= 2  # double-buffered by the pipeline
                total += nbytes
                accounted.append(nbytes)
            for scratch in site.scratch:
                if scratch.nbytes:
                    total += scratch.nbytes
                    accounted.append(scratch.nbytes)
            if total > budget:
                yield make_finding(
                    ctx, site.call, self.code,
                    f"analytic VMEM footprint {total} bytes "
                    f"({len(accounted)} accounted windows/scratch, "
                    f"double-buffered blocks x2) exceeds the device "
                    f"budget {budget} — the kernel cannot compile on "
                    f"hardware; shrink the block windows or override "
                    f"CHUNKFLOW_VMEM_BUDGET if the target differs",
                )


# ---------------------------------------------------------------------------
# GL022: in-place RMW output not aliased
# ---------------------------------------------------------------------------
class RmwOutputNotAliased(Rule):
    """A kernel output that is READ in the kernel body without an
    ``input_output_aliases`` entry.

    Reading an output ref (as an async-copy source or a subscript load)
    makes the kernel a read-modify-write over that buffer — its initial
    contents matter. Without ``input_output_aliases`` tying an input to
    that output, XLA materializes the output as a FRESH buffer: on the
    CPU interpreter the read sees zeros and the accumulate silently
    drops prior contributions; under donation the behavior differs
    between backends. Pass the buffer as an input and alias it
    (``input_output_aliases={in_idx: out_idx}`` — the fused blend
    kernel's pattern), or don't read the output.
    """

    code = "GL022"
    name = "rmw-output-not-aliased"

    def run(self, ctx: FileContext, config) -> Iterator[Finding]:
        model = get_pallas_model(ctx)
        for site in model.sites:
            if site.kernel is None or not site.params:
                continue
            if site.aliases == "unknown":
                continue  # present but unfoldable: benefit of the doubt
            aliased_outputs = set(
                site.aliases.values()) if isinstance(
                site.aliases, dict) else set()
            out_refs = {
                name: j for name, (kind, j) in site.params.items()
                if kind == "out"
            }
            read = self._read_outputs(ctx, site, out_refs)
            for name, node in read.items():
                j = out_refs[name]
                if j not in aliased_outputs:
                    yield make_finding(
                        ctx, node, self.code,
                        f"output ref `{name}` (output {j}) is read in "
                        f"the kernel body but no input_output_aliases "
                        f"entry aliases an input to it — the RMW reads "
                        f"an undefined fresh buffer; alias the operand "
                        f"(input_output_aliases={{in_idx: {j}}})",
                    )

    @staticmethod
    def _read_outputs(ctx: FileContext, site: PallasCallSite,
                      out_refs: Dict[str, int]) -> Dict[str, ast.AST]:
        """output param name -> first node where it is READ. A read is a
        Load-context subscript on the ref, or the ref (directly or via a
        ``x = ref.at[...]`` binding) used as an async-copy SOURCE."""
        reads: Dict[str, ast.AST] = {}
        at_bindings: Dict[str, str] = {}
        for node in walk_local(site.kernel):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Subscript):
                base = _ref_of_subscript(node.value)
                if base in out_refs:
                    at_bindings[node.targets[0].id] = base
        for node in walk_local(site.kernel):
            if isinstance(node, ast.Subscript) and \
                    isinstance(node.ctx, ast.Load) and \
                    not (isinstance(node.value, ast.Attribute)
                         and node.value.attr == "at"):
                base = _ref_of_subscript(node)
                if base in out_refs:
                    reads.setdefault(base, node)
            if _is_call_to(ctx, node, "make_async_copy") and node.args:
                src = node.args[0]
                base = None
                if isinstance(src, ast.Name):
                    base = at_bindings.get(src.id)
                    if src.id in out_refs:
                        base = src.id
                elif isinstance(src, ast.Subscript):
                    base = _ref_of_subscript(src)
                if base in out_refs:
                    reads.setdefault(base, node)
        return reads


# ---------------------------------------------------------------------------
# GL023: async-copy protocol
# ---------------------------------------------------------------------------
class AsyncCopyProtocol(Rule):
    """Started-but-unwaited ``make_async_copy``, or a DMA semaphore
    reused by overlapping copies.

    A DMA that is ``.start()``ed but never ``.wait()``ed races the
    compute that reads its destination (or the next grid step reusing
    the scratch); a second copy started on the SAME semaphore while the
    first is still in flight makes the waits ambiguous — either copy's
    completion satisfies either wait, including across ``pl.when`` arms
    where only one copy actually ran. Every started copy needs its wait
    on every path, and concurrent copies need distinct semaphores.
    Statements are scanned in source order with ``@pl.when`` arms
    inlined at their definition point (that is their execution point).
    """

    code = "GL023"
    name = "async-copy-protocol"

    def run(self, ctx: FileContext, config) -> Iterator[Finding]:
        model = get_pallas_model(ctx)
        for site in model.sites:
            if site.kernel is None or isinstance(site.kernel, ast.Lambda):
                continue
            yield from self._scan(ctx, site.kernel)

    def _scan(self, ctx: FileContext,
              kernel: FuncNode) -> Iterator[Finding]:
        copies: Dict[str, dict] = {}     # name -> {sem, started, waited}
        outstanding: Dict[str, dict] = {}  # sem name -> copy rec
        findings: List[Finding] = []

        def sem_of(call: ast.Call) -> Optional[str]:
            kwargs = {kw.arg: kw.value for kw in call.keywords if kw.arg}
            sem = call.args[2] if len(call.args) > 2 else \
                kwargs.get("sem")
            return sem.id if isinstance(sem, ast.Name) else None

        def start(rec: dict, node: ast.AST) -> None:
            rec["started"] = node
            sem = rec.get("sem")
            if sem is None:
                return
            other = outstanding.get(sem)
            if other is not None and other is not rec:
                findings.append(make_finding(
                    ctx, node, self.code,
                    f"DMA semaphore `{sem}` is reused by overlapping "
                    f"copies: a copy started on it has not been waited "
                    f"— either wait first or use a distinct semaphore",
                ))
            outstanding[sem] = rec

        def wait(rec: dict) -> None:
            rec["waited"] = True
            sem = rec.get("sem")
            if sem is not None and outstanding.get(sem) is rec:
                del outstanding[sem]

        def visit(stmts: List[ast.stmt]) -> None:
            for stmt in stmts:
                if isinstance(stmt, ast.FunctionDef):
                    # @pl.when arms execute where they are defined
                    visit(stmt.body)
                    continue
                if isinstance(stmt, ast.Assign) and \
                        len(stmt.targets) == 1 and \
                        isinstance(stmt.targets[0], ast.Name) and \
                        _is_call_to(ctx, stmt.value, "make_async_copy"):
                    copies[stmt.targets[0].id] = {
                        "sem": sem_of(stmt.value), "node": stmt.value,
                        "started": None, "waited": False,
                    }
                    continue
                for node in ast.walk(stmt):
                    if not isinstance(node, ast.Call) or \
                            not isinstance(node.func, ast.Attribute):
                        continue
                    owner = node.func.value
                    if node.func.attr in ("start", "wait") and \
                            isinstance(owner, ast.Name) and \
                            owner.id in copies:
                        rec = copies[owner.id]
                        if node.func.attr == "start":
                            start(rec, node)
                        else:
                            wait(rec)
                    elif node.func.attr == "start" and \
                            _is_call_to(ctx, owner, "make_async_copy"):
                        # inline chain: can never be waited
                        rec = {"sem": sem_of(owner), "node": node,
                               "started": node, "waited": False}
                        copies[f"<inline:{node.lineno}>"] = rec
                        start(rec, node)
                if isinstance(stmt, (ast.If, ast.For, ast.While,
                                     ast.With)):
                    visit(stmt.body)
                    visit(getattr(stmt, "orelse", []))

        visit(kernel.body)
        for name, rec in copies.items():
            if rec["started"] is not None and not rec["waited"]:
                findings.append(make_finding(
                    ctx, rec["started"], self.code,
                    f"async copy `{name}` is started but never waited "
                    f"— the DMA races every read of its destination; "
                    f"call .wait() before the data is used",
                ))
        yield from findings


# ---------------------------------------------------------------------------
# GL024: unguarded pallas_call site
# ---------------------------------------------------------------------------
class UnguardedPallasCall(Rule):
    """A ``pl.pallas_call`` site with no mode selector and no dynamic
    ``interpret=`` seam.

    A compiled Mosaic kernel hard-fails on a CPU box (and on any box
    whose platform string the code did not anticipate). Every kernel in
    this repo sits behind a ``pallas_mode()``/``gather_mode()``-style
    env selector (core/envmode.py) so the XLA fallback runs by default
    and CPU tests run the kernel in interpret mode. A bare pallas_call
    — module defines/imports no ``*_mode`` selector AND the call's
    ``interpret`` kwarg is absent or a literal — has no off-ramp. Add a
    selector (and fold it into the program cache key so env flips
    rebuild), or thread ``interpret=`` through from one.
    """

    code = "GL024"
    name = "unguarded-pallas-call"

    def run(self, ctx: FileContext, config) -> Iterator[Finding]:
        model = get_pallas_model(ctx)
        if model.has_mode_selector:
            return
        for site in model.sites:
            if site.interpret is not None and \
                    not isinstance(site.interpret, ast.Constant):
                continue  # interpret= threaded from a caller: guarded
            yield make_finding(
                ctx, site.call, self.code,
                "pallas_call has no selection seam: the module defines/"
                "imports no `*_mode` selector and `interpret=` is not "
                "threaded from a caller — a CPU box hard-fails instead "
                "of falling back; guard it behind an env-mode selector "
                "(core/envmode.py) like pallas_mode/gather_mode",
            )


PALLAS_RULES = [
    UnalignedDmaSlice(),
    VmemBudgetOverflow(),
    RmwOutputNotAliased(),
    AsyncCopyProtocol(),
    UnguardedPallasCall(),
]
