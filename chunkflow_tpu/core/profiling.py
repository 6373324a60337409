"""Device-performance plane: program cost ledger, roofline accounting,
and bounded profiler capture.

The host-side observability stack (core/telemetry.py, PR 3/6) attributes
*wall-clock*; this module attributes the *device*. Three instruments,
all riding the telemetry registry and its kill switch
(``CHUNKFLOW_TELEMETRY=0`` ⇒ no ledger, no files, no capture threads,
no ``/profile`` route — nothing):

1. **Program cost ledger.** Every :class:`~chunkflow_tpu.core.
   compile_cache.ProgramCache` build passes through
   :func:`instrument_program`: the jit program is wrapped so its FIRST
   invocation (the one that pays trace + XLA compile) is timed as
   ``compile_s``, and the lowered computation's XLA
   ``cost_analysis()`` — FLOPs and bytes accessed — is captured
   best-effort *without compiling twice* (``Lowered.cost_analysis``
   runs on the unoptimized HLO). Results land in ``program/*``
   counters, one ``compile``-kind telemetry event per program, and a
   per-run ``programs.json`` catalog written at flush time
   (:func:`catalog` lists an entry's keys, with and without a sink).
   With a sink configured the entry also says where in the program each
   compiled op lies, so that a device trace (whose events keep only the
   op's name) can be split by the program's own names: ``op_scopes``
   (which of the six :data:`DEVICE_SCOPES` steps), ``op_parts`` (below a
   scope, which flax module or model scope: ``enc0``, ``pool1``; a
   fusion by its widest convolution, not by its root) and
   ``op_convolutions`` (the convolutions an op holds).

2. **Roofline accounting.** At catalog time each program's cost is
   scored against a small peak-FLOPs/HBM-bandwidth table keyed on
   ``jax.devices()[0].device_kind`` (a kind with no row is an error;
   the labelled ``cpu`` row keeps the math defined on the test mesh):
   ``roofline_s = max(flops/peak_flops, bytes/peak_bw)`` and
   ``roofline_util = roofline_s / exec_s``. ``exec_s`` is the mean
   post-compile *dispatch wall* — under async dispatch that is a lower
   bound on device time, so the utilisation figure is an upper bound;
   it answers "which program family is worth a kernel" (the Pallas
   blend / multi-chip question), not "publishable MXU utilisation"
   (not measured).

3. **Bounded profiler capture.** The whole-run ``--profile-dir`` trace
   is replaced by a task window (:func:`start_task_window`: first N
   tasks, ``CHUNKFLOW_PROFILE_TASKS`` default 4), and two *automatic*
   triggers capture one bounded ``jax.profiler`` window each — the
   retrace watchdog firing (:func:`note_retrace`) and a dominant stall
   share holding above ``CHUNKFLOW_PROFILE_STALL_SHARE`` for
   ``CHUNKFLOW_PROFILE_STALL_TICKS`` controller intervals
   (:func:`note_stall`) — with a cooldown
   (``CHUNKFLOW_PROFILE_COOLDOWN``, default 300 s) so an anomaly storm
   cannot fill the disk with traces. A fleet operator can also demand a
   window from a live worker: ``POST /profile?seconds=N``
   (parallel/restapi.py). Captures land under the metrics dir
   (``profile-<reason>-<n>/``) and are summarised offline by
   ``tools/analyze_trace.py`` through ``log-summary``. An automatic
   capture yields to any profiler session already running in the
   process, whoever started it (``profile/capture_skipped``).

Design rules inherited from core/telemetry.py: never inside jit
(GL007 — every clock here wraps the program from the host side), zero
when off, zero dependencies beyond jax itself (imported lazily, only
on paths that already run jax programs).

See docs/observability.md "Device program view".
"""
from __future__ import annotations

import json
import math
import os
import re
import threading
import time
from typing import NamedTuple, Optional, Tuple

from chunkflow_tpu.core import telemetry

__all__ = [
    "instrument_program", "stamp_cost", "catalog", "write_catalog",
    "device_peaks", "DEVICE_SCOPES", "OP_MAPS", "op_scopes", "op_parts",
    "trace_gauge", "note_h2d",
    "h2d_by_family",
    "note_hbm_intermediate", "hbm_intermediate_by_family",
    "note_collective", "collective_by_family",
    "capture", "maybe_capture", "note_retrace", "note_stall",
    "note_slo_page", "start_task_window", "note_task_done",
    "wait_for_captures", "capture_base_dir",
]


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name, "")
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "")
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


# ---------------------------------------------------------------------------
# roofline peak table
# ---------------------------------------------------------------------------
#: (device_kind substring, (peak FLOP/s, peak HBM bytes/s)) — matched
#: case-insensitively, first hit wins, most specific first. Values are
#: published bf16 peaks per chip (the inference dtype of record). The
#: ``cpu`` row is a labelled stand-in, not a measurement: it keeps the
#: roofline arithmetic defined for the CPU test suite.
DEVICE_PEAKS = (
    ("tpu v6", (918e12, 1640e9)),   # Trillium
    ("tpu v5p", (459e12, 2765e9)),
    ("tpu v5 lite", (197e12, 819e9)),
    ("tpu v5e", (197e12, 819e9)),
    ("tpu v4", (275e12, 1228e9)),
    ("tpu v3", (123e12, 900e9)),
    ("cpu", (1e11, 5e10)),
)


def device_peaks(device_kind: str) -> dict:
    """Peak FLOP/s + bytes/s for a device kind from :data:`DEVICE_PEAKS`.
    A kind with no row raises: a roofline scored against another
    device's peaks is a wrong number, not an estimate."""
    kind = (device_kind or "").lower()
    for needle, (flops, bw) in DEVICE_PEAKS:
        if needle in kind:
            return {"flops_per_s": flops, "bytes_per_s": bw,
                    "source": f"table:{needle}"}
    raise KeyError(
        f"device_kind {device_kind!r} has no row in "
        f"chunkflow_tpu.core.profiling.DEVICE_PEAKS; add its published "
        f"peaks there"
    )


# ---------------------------------------------------------------------------
# device scopes and parts: the program's own names, readable from a device trace
# ---------------------------------------------------------------------------
#: The ``jax.named_scope`` names every patch program traces its steps
#: under: patch gather, model forward, bump-weighted accumulation, weight
#: normalization, the mesh engine's cross-chip exchanges and, in the
#: programs the chunk operators build for a device-resident chunk,
#: ``mask`` (ops/mask.py), ``normalize_contrast`` (ops/contrast.py: the
#: sections through their lookup tables) and ``thumbnail`` (the grey
#: quantization of chunk/affinity_map.py and the average pooling of
#: ops/downsample.py)
#: (``grep -rn named_scope chunkflow_tpu`` lists the files). Below a scope
#: the path goes on with the names of whoever emitted the op: a flax
#: module's (``forward/RSUNet/enc0/conv2/conv_general_dilated``) or a
#: scope the model opens around what its own ``__call__`` emits
#: (models/rsunet.py: ``in``, ``pool{i}``, ``crop{i}``, ``skip{i}``,
#: ``post``). The first name below the scope's root module is the op's
#: *part* (:func:`op_parts`). Scopes and parts are metadata: the compiled
#: code is the same with and without them.
DEVICE_SCOPES = ("gather", "forward", "accumulate", "normalize",
                 "collective", "mask", "normalize_contrast", "thumbnail")

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_HLO_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLED = re.compile(
    r"(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=(%?[\w.\-]+)|branch_computations=\{([^}]*)\}")
_HLO_OPERAND = re.compile(r"%([\w.\-]+)")
_HLO_WINDOW = re.compile(r"window=\{size=([0-9x]+)")
# an op's path in a lowered module's locations, which name it as
# loc("jit(program)/while/body/forward/RSUNet/enc0/conv1/conv_general_dilated"(#loc7))
# and a source file as loc("/a/path/forward/model.py":12:3): not a path
_LOWERED_PATH = re.compile(r'loc\("([^"]+)"(?=[()])')
# never a device-trace event of their own
_HLO_FREE = frozenset(("parameter", "get-tuple-element", "tuple", "bitcast",
                       "constant"))


def _split(op_name: str) -> Tuple[Optional[str], list]:
    """``(scope, names below the scope's root module)`` of a
    ``/``-separated ``op_name`` path, the primitive at its end left off:
    ``jit(program)/while/body/forward/RSUNet/enc0/conv2/
    conv_general_dilated`` -> ``("forward", ["enc0", "conv2"])``. The
    scope is the outermost :data:`DEVICE_SCOPES` name on the path; the
    last component is the primitive and never a scope (a ``lax.gather``
    outside every scope ends in ``/gather`` and is under none). With no
    scope on it: ``(None, the whole path but for the primitive)``."""
    path = op_name.split("/")[:-1]
    for i, name in enumerate(path):
        if name in DEVICE_SCOPES:
            return name, path[i + 2:]
    return None, path


def _place(op_name: str) -> Tuple[Optional[str], str]:
    """``(scope, part)`` of an ``op_name`` path, the part being the first
    name below the scope's root module: ``("forward", "enc0")`` for the
    path above, ``("forward", "")`` for ``forward/RSUNet/add``, and
    ``(None, "")`` under no scope."""
    scope, below = _split(op_name)
    return scope, (below[0] if scope and below else "")


class _HloOp(NamedTuple):
    """One instruction of a compiled module's text."""
    name: str
    opcode: str
    op_name: Optional[str]   # the metadata's, None where it has none
    called: tuple            # computations it calls; a fusion's are in fused
    fused: tuple             # the computations a fusion's ``calls=`` names
    operands: tuple          # instruction names, where the text has them
    window: Optional[str]    # a convolution's window size, "3x3x3"


def _parse_hlo(hlo_text: str) -> Tuple[dict, Optional[str]]:
    """``({computation: [_HloOp, ...]}, entry computation)`` of one
    compiled module's text (``Compiled.as_text()``)."""
    computations: dict = {}
    current = None
    entry = None
    for line in hlo_text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head and not line.startswith(" "):
            current = computations.setdefault(head.group(1), [])
            if line.startswith("ENTRY"):
                entry = head.group(1)
            continue
        match = _HLO_INSTRUCTION.match(line)
        if match is None or current is None:
            continue
        name, rest = match.group(1), match.group(2)
        opcode = _HLO_OPCODE.search(" " + rest)
        op_name = _HLO_OP_NAME.search(rest)
        called = []
        for one, several in _HLO_CALLED.findall(rest):
            called += [c.strip().lstrip("%")
                       for c in (one or several).split(",")]
        # the operand list ends at the parenthesis that closes the opcode's
        depth, stop = 0, len(rest)
        for at in range(opcode.end() - 2 if opcode else stop, len(rest)):
            depth += (rest[at] == "(") - (rest[at] == ")")
            if depth == 0:
                stop = at
                break
        opcode = opcode.group(1) if opcode else ""
        window = _HLO_WINDOW.search(rest) if opcode == "convolution" else None
        fusion = opcode == "fusion"
        current.append(_HloOp(
            name, opcode, op_name.group(1) if op_name else None,
            () if fusion else tuple(called), tuple(called) if fusion else (),
            tuple(_HLO_OPERAND.findall(rest[:stop])),
            window.group(1) if window else None))
    return computations, entry


def _walk(computations: dict, entry: Optional[str], own):
    """Every op that runs as an event of its own, with what it inherits:
    yields ``(computation, op, value)`` from the entry computation down
    through ``while``, ``call`` and ``conditional``, ``value`` being
    ``own(op)`` or, where that is None, the value of the instruction that
    calls the op's computation."""
    seen = set()
    stack = [(entry, None)]
    while stack:
        computation, inherited = stack.pop()
        if computation in seen or computation not in computations:
            continue
        seen.add(computation)
        for op in computations[computation]:
            value = own(op)
            value = inherited if value is None else value
            yield computation, op, value
            stack += [(c, value) for c in op.called]


def op_scopes(hlo_text: str) -> dict:
    """``{scope: [op names]}`` of one compiled module's text
    (``Compiled.as_text()``), the ops under none of
    :data:`DEVICE_SCOPES` listed under ``""``. An op's scope is the
    outermost scope name in its own ``op_name`` metadata; an op that has
    none takes the scope of the instruction that calls its computation
    (XLA expands a ``scatter-add`` into a ``while`` whose body ops carry
    no metadata, the ``while`` does). Ops inside fusions and ops that
    never run as an event of their own are left out."""
    return _op_scopes(*_parse_hlo(hlo_text))


def _op_scopes(computations: dict, entry: Optional[str]) -> dict:
    out: dict = {}
    for _, op, scope in _walk(
            computations, entry,
            lambda op: _place(op.op_name)[0] if op.op_name else None):
        if op.opcode not in _HLO_FREE:
            out.setdefault(scope or "", []).append(op.name)
    return out


KERNEL_MARKER = "kernel_convolution_"
# the marker scope on a kernel's path: /kernel_convolution_3x3x3/, with
# behind two dots the name of a module below the scope's root for one
# more convolution the kernel holds
_KERNEL_SCOPE = re.compile(
    "/" + KERNEL_MARKER + r"([0-9]+(?:x[0-9]+)*)(?:\.\.(\w+))?(?=/)")


def kernel_convolution(window, module: str = ""):
    """The scope a kernel's wrapper opens around its ``pallas_call``,
    inside the module's own, to say that the custom call is a convolution
    of that window (``kernel_convolution((3, 3, 3))`` ->
    ``kernel_convolution_3x3x3``): :func:`op_parts` then lists the call in
    ``op_convolutions`` under the module's path, and the benchmark files
    its time as a convolution's (``benchmarks/cfbench/trace.py``
    ``file_by_contents``). A kernel that holds one more convolution opens
    one more scope, with the name of that convolution's ``module`` below
    the scope's root module (``kernel_convolution((1, 1, 1), "out")`` ->
    ``kernel_convolution_1x1x1..out``: ``RSUNet/out``, from inside
    ``RSUNet/dec0/conv3``). Metadata only."""
    import jax

    mark = KERNEL_MARKER + "x".join(str(int(k)) for k in window)
    return jax.named_scope(f"{mark}..{module}" if module else mark)


def _kernel_convolutions(op_name: str) -> list:
    """``[(op_name, window)]`` that the marker scopes on a custom call's
    path name (:func:`kernel_convolution`): the path down to the
    innermost marker without the markers, then the primitive (what lies
    between them is the wrapper's own: ``jit(folded_conv)``); none where
    the path has no marker."""
    marks = list(_KERNEL_SCOPE.finditer(op_name))
    if not marks:
        return []
    primitive = op_name.rpartition("/")[2]
    above = _KERNEL_SCOPE.sub("", op_name[:marks[-1].end()] + "/")[:-1]
    path = above.split("/")
    root = next((i + 2 for i, name in enumerate(path)
                 if name in DEVICE_SCOPES), 0)
    return [("/".join(path[:root] + [mark[2], primitive]) if mark[2]
             else f"{above}/{primitive}", mark[1]) for mark in marks]


def _taps(window: str) -> int:
    return math.prod(map(int, window.split("x")))


def _convolutions(computations: dict, op: _HloOp) -> list:
    """``[(op_name, window)]`` of the convolutions ``op`` holds: its own,
    or those of a fusion's computations, nested fusions included, or
    those a kernel's ``custom-call`` names by its marker scopes
    (:func:`kernel_convolution`; one without a marker holds none); the one
    with the most taps first (of equals, the first in program order)."""
    found = []
    if op.opcode == "custom-call" and op.op_name:
        found = _kernel_convolutions(op.op_name)
    pending, seen = [op], set()
    for one in pending:   # grows while it is walked
        if one.opcode == "convolution" and one.window:
            found.append((one.op_name or "", one.window))
        for computation in one.fused:
            if computation not in seen:
                seen.add(computation)
                pending += computations.get(computation, [])
    return sorted(found, key=lambda conv: -_taps(conv[1]))


def op_parts(hlo_text: str) -> Tuple[dict, dict]:
    """``(op_parts, op_convolutions)`` of one compiled module's text.

    ``op_parts`` is ``{scope: {part: [op names]}}`` over the ops
    :func:`op_scopes` lists: the scope as there, the part the first name
    on the op's path below the scope's root module (:func:`_place`), ``""``
    where there is none. XLA names, shapes and annotates a fusion after
    its *root*, which is the consumer fused in last and not where the
    time goes (``fusion.1066 bf16[20,256,32,9,12]`` is ``dec0/conv3``
    with the 1x1x1 head as its root: PERF.md, PR 38), so, in this order:

    (a) a fusion that holds convolutions (its computations searched,
        nested fusions too) takes the path of the one with the largest
        ``window``;
    (b) any other op takes its own ``op_name``'s;
    (c) an op with no metadata takes what the instruction that calls its
        computation has, as in :func:`op_scopes`;
    (d) an op that is still under no scope and has no metadata (XLA's own
        ``copy``, a weight's ``copy-start``) takes the part of the ops
        that read it, where they all have the same. Its scope stays
        ``""``: every scope holds the ops :func:`op_scopes` lists there.

    ``op_convolutions`` is ``{op name: [[module path, window], ...]}``
    for every listed op that holds a convolution, the one that names the
    part first: ``{"fusion.1066": [["dec0/conv3", "3x3x3"], ["out",
    "1x1x1"]]}``. A name in a trace or in the ledger's ``device_ops`` is
    looked up here."""
    return _op_parts(*_parse_hlo(hlo_text))


def _op_parts(computations: dict, entry: Optional[str]) -> Tuple[dict, dict]:
    convolutions: dict = {}

    def own(op):
        held = _convolutions(computations, op)
        if held:
            convolutions[op.name] = held
        path = held[0][0] if held and held[0][0] else op.op_name
        place = _place(path) if path else (None, "")
        return place if place[0] else None

    placed, walked = {}, set()   # op name -> (scope or None, part)
    for computation, op, place in _walk(computations, entry, own):
        walked.add(computation)
        if op.opcode not in _HLO_FREE:
            placed[op.name] = place or (None, "")
    # (d): from the readers back, so that copy-start -> copy-done -> a
    # fusion resolves in one pass (a computation lists an op before its
    # readers); an op that is no event hands its readers' parts through
    for computation in walked:
        readers: dict = {}   # op name -> the parts of the ops that read it
        for op in reversed(computations[computation]):
            parts = readers.get(op.name, set())
            if op.name in placed:
                scope, part = placed[op.name]
                if scope is None and op.op_name is None \
                        and len(parts) == 1 and "" not in parts:
                    part = next(iter(parts))
                    placed[op.name] = (None, part)
                parts = {part}
            for operand in op.operands:
                readers.setdefault(operand, set()).update(parts)
    by_scope: dict = {}
    for name, (scope, part) in placed.items():
        by_scope.setdefault(scope or "", {}).setdefault(part, []).append(name)
    return by_scope, {
        name: [["/".join(_split(op_name)[1]), window]
               for op_name, window in held]
        for name, held in convolutions.items() if name in placed}


# primitives that only move data: XLA folds such an op into a neighbour and
# the neighbour's metadata stays, so a name that only they carry in the
# lowered module (``crop1``: one slice) may be missing from a fresh executable
_MOVES_DATA = frozenset((
    "slice", "dynamic_slice", "reshape", "squeeze", "expand_dims",
    "transpose", "broadcast_in_dim", "convert_element_type", "copy",
    "concatenate", "pad"))


def _names_of(paths, computing: bool = False) -> set:
    """The scopes and ``scope/part`` pairs that ``op_name`` paths name;
    with ``computing``, those of paths that end in a primitive which
    computes something (not one of :data:`_MOVES_DATA`)."""
    names = set()
    for path in paths:
        if computing and path.rsplit("/", 1)[-1] in _MOVES_DATA:
            continue
        scope, part = _place(path)
        if scope:
            names.add(scope)
            if part:
                names.add(f"{scope}/{part}")
    return names


# ---------------------------------------------------------------------------
# program cost ledger
# ---------------------------------------------------------------------------
class _ProgramRecord:
    """One ProgramCache build's cost story. ``compile_s`` is None until
    the program's first invocation pays trace + XLA compile."""

    __slots__ = (
        "family", "key", "label", "build_s", "compile_s", "flops",
        "bytes_accessed", "vmem_bytes", "hbm_intermediate", "optimal_s",
        "calls", "dispatch_s", "platform", "device_kind", "op_maps",
        "traced", "lock",
    )

    def __init__(self, family: str, key: str, label: str, build_s: float):
        self.family = family
        self.key = key
        self.label = label
        self.build_s = build_s
        self.compile_s: Optional[float] = None
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.vmem_bytes: Optional[float] = None
        self.hbm_intermediate: Optional[float] = None
        self.optimal_s: Optional[float] = None
        self.calls = 0
        self.dispatch_s = 0.0  # post-compile dispatch wall, cumulative
        self.platform = ""
        self.device_kind = ""
        self.op_maps: Optional[dict] = None  # _op_maps(), runs with a sink
        self.traced: dict = {}  # trace_gauge() values of the program's trace
        self.lock = threading.Lock()


_LEDGER_LOCK = threading.Lock()
_LEDGER: dict = {}  # (family, key) -> _ProgramRecord

# ``inference/<name>`` gauges the patch program sets while it is traced
# (Inferencer._trace_geometry_gauges), each a key of its programs.json entry
GEOMETRY_GAUGES = ("output_patch_share", "patches_per_task",
                   "accumulator_bytes", "chunk_bytes")

_TRACING = threading.local()  # .gauges: dict while a first call traces


def trace_gauge(name: str, value: float) -> None:
    """A gauge set by code that runs while a program is traced (a model
    saying which lowering it chose from the shapes it was given): a
    telemetry gauge, and a value on the entry of the program being built
    in ``programs.json`` (there ``forward/<name>`` and
    ``inference/<name>`` are ``<name>``)."""
    telemetry.gauge(name, value)
    gauges = getattr(_TRACING, "gauges", None)
    if gauges is not None:
        gauges[name] = value


def _device_identity() -> Tuple[str, str]:
    import jax

    dev = jax.devices()[0]
    return dev.platform, dev.device_kind


def _lower(program, args, kwargs):
    """The program lowered at these argument shapes, or None."""
    try:
        return program.lower(*args, **kwargs)
    except Exception:
        return None


def _cost_analysis(lowered) -> dict:
    """Best-effort XLA cost analysis via ``Lowered.cost_analysis()`` (no
    second compile). Returns {} when the backend / program doesn't
    expose it."""
    try:
        cost = lowered.cost_analysis()
    except Exception:
        return {}
    return cost if isinstance(cost, dict) else {}


def _arg_specs(args, kwargs):
    """``(args, kwargs)`` with every array replaced by its shape, dtype
    and sharding: enough to lower the program again after the call has
    consumed a donated buffer."""
    import jax

    def spec(leaf):
        if isinstance(leaf, jax.Array):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                        sharding=leaf.sharding)
        return leaf

    return jax.tree_util.tree_map(spec, (args, kwargs))


def _compile_past_the_cache(lowered):
    """``lowered.compile()`` with the persistent compile cache off for
    the duration (the switch is process-wide and remembered: it takes a
    ``reset_cache()`` on either side to be read again). ``lowered`` is
    fresh: a ``Lowered`` keeps the executable of its first compile."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


#: The keys of a ``programs.json`` entry that :func:`_op_maps` fills
OP_MAPS = ("op_scopes", "op_parts", "op_convolutions")


def _op_maps(hlo_text: str) -> dict:
    """The three maps a ``programs.json`` entry carries when the run has
    a sink, from one pass over the compiled module's text."""
    parsed = _parse_hlo(hlo_text)
    parts, convolutions = _op_parts(*parsed)
    return {"op_scopes": _op_scopes(*parsed), "op_parts": parts,
            "op_convolutions": convolutions}


def _compiled_op_maps(lowered, lower_again) -> Optional[dict]:
    """:func:`_op_maps` of the compiled module. ``Lowered.compile()``
    after the program's first call finds the executable in the
    persistent compile cache where that is on; otherwise it compiles a
    second time, which is why only runs with a sink pay for it.

    JAX leaves metadata out of the cache key, so the cache may hand back
    an executable that was compiled from the same program before it had
    today's names (another checkout's, an older release's): its module
    has ``forward`` and lacks ``pool0``, or names no scope at all,
    although the lowered one does. So what the lowered module's locations
    name (scopes, and parts below them; of ops that compute something,
    :data:`_MOVES_DATA`) is held against what the executable's
    ``op_name``s name, fused instructions included, and if the
    executable lacks one the maps are read from one compile past the
    cache, of the program lowered again (``lower_again()``): the op names
    are the same, the optimized code being the same but for metadata.
    Once per program and process as long as that entry lives
    (``program/stale_cache_entries``)."""
    t0 = time.perf_counter()
    try:
        text = lowered.compile().as_text()
        wanted = _names_of(_LOWERED_PATH.findall(
            lowered.as_text(debug_info=True)), computing=True)
        if wanted - _names_of(_HLO_OP_NAME.findall(text)):
            telemetry.inc("program/stale_cache_entries")
            text = _compile_past_the_cache(lower_again()).as_text()
        return _op_maps(text)
    except Exception:
        return None
    finally:
        telemetry.inc("program/op_map_seconds", time.perf_counter() - t0)


class _InstrumentedProgram:
    """Transparent wrapper around one cached jit program: first call
    timed as compile, later calls accumulate dispatch wall; attribute
    access (``lower``, ``_cache_size``, ...) forwards to the program."""

    __slots__ = ("_fn", "_rec")

    def __init__(self, fn, rec: _ProgramRecord):
        self._fn = fn
        self._rec = rec

    def __call__(self, *args, **kwargs):
        rec = self._rec
        if rec.compile_s is None:
            return self._first_call(args, kwargs)
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        with rec.lock:
            rec.calls += 1
            rec.dispatch_s += dt
        return out

    def _first_call(self, args, kwargs):
        # the program's Python body runs, on this thread, inside the
        # first of the lowering and the call: trace_gauge() lands here
        _TRACING.gauges = traced = {}
        try:
            return self._traced_first_call(args, kwargs, traced)
        finally:
            _TRACING.gauges = None

    def _traced_first_call(self, args, kwargs, traced):
        rec = self._rec
        # an analytic cost stamp (stamp_cost) wins over XLA's
        # cost_analysis: programs whose HLO hides traffic behind custom
        # calls (the fused Pallas kernel) or loop bodies are opaque or
        # miscounted by the unoptimized-HLO analysis
        cost = getattr(self._fn, "_chunkflow_cost", None)
        want_scopes = telemetry.configured_path() is not None
        lowered = None
        if not isinstance(cost, dict) or want_scopes:
            # lower BEFORE dispatch: afterwards a donated input buffer
            # is dead, and lowering only needs shapes anyway
            lowered = _lower(self._fn, args, kwargs)
        if not isinstance(cost, dict):
            cost = _cost_analysis(lowered) if lowered is not None else {}
        specs = _arg_specs(args, kwargs) if want_scopes else None
        t0 = time.perf_counter()
        out = self._fn(*args, **kwargs)
        dt = time.perf_counter() - t0
        maps = None
        if want_scopes and lowered is not None:
            maps = _compiled_op_maps(
                lowered, lambda: self._fn.lower(*specs[0], **specs[1]))
        first = False
        with rec.lock:
            if rec.compile_s is None:
                first = True
                rec.compile_s = dt
                rec.platform, rec.device_kind = _device_identity()
                flops = cost.get("flops")
                nbytes = cost.get("bytes accessed")
                vmem = cost.get("vmem_bytes")
                hbm_i = cost.get("hbm_intermediate_bytes")
                optimal = cost.get("optimal_seconds")
                rec.flops = float(flops) if flops is not None else None
                rec.bytes_accessed = (
                    float(nbytes) if nbytes is not None else None
                )
                rec.vmem_bytes = float(vmem) if vmem is not None else None
                rec.hbm_intermediate = (
                    float(hbm_i) if hbm_i is not None else None
                )
                rec.optimal_s = (
                    float(optimal) if optimal is not None else None
                )
                rec.op_maps = maps
                rec.traced = traced
            else:  # raced: the other thread's call was the compile
                rec.calls += 1
                rec.dispatch_s += dt
        if first:
            telemetry.inc("program/builds")
            telemetry.inc("program/compile_seconds", dt)
            if rec.flops:
                telemetry.inc("program/flops_total", rec.flops)
            if rec.bytes_accessed:
                telemetry.inc("program/bytes_total", rec.bytes_accessed)
            telemetry.event(
                "compile", f"program/{rec.family}",
                family=rec.family, key=rec.key, label=rec.label,
                build_s=round(rec.build_s, 4),
                compile_s=round(dt, 4),
                flops=rec.flops, bytes_accessed=rec.bytes_accessed,
                device=rec.device_kind, platform=rec.platform,
            )
        return out

    def __getattr__(self, name):
        return getattr(self._fn, name)


class _CostStamped:
    """A jit program carrying an analytic cost model. Transparent:
    ``__call__`` and attribute access (``lower``, ...) forward to the
    program; :func:`instrument_program`'s wrapper reads the stamp."""

    __slots__ = ("_fn", "_chunkflow_cost")

    def __init__(self, fn, cost: dict):
        self._fn = fn
        self._chunkflow_cost = cost

    def __call__(self, *args, **kwargs):
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def stamp_cost(program, flops: Optional[float] = None,
               bytes_accessed: Optional[float] = None,
               vmem_bytes: Optional[float] = None,
               hbm_intermediate_bytes: Optional[float] = None):
    """Attach an ANALYTIC cost model to a program before it enters a
    ProgramCache: the ledger then scores its roofline against these
    numbers instead of XLA's ``cost_analysis()``. Use for programs the
    unoptimized-HLO analysis cannot see into (Pallas custom calls) or
    systematically miscounts (loop-body traffic) — the stamp is the
    builder's arithmetic, so it must state what the program actually
    moves/computes, not what would look good. ``vmem_bytes`` is the
    kernel's analytic on-chip footprint (block windows, double-buffered
    where the pipeline does, plus scratch — the GL021 arithmetic; see
    ``ops/pallas_blend.fused_kernel_cost`` /
    ``ops/pallas_gather.gather_kernel_cost``), surfaced as the catalog's
    ``vmem_bytes`` column so a budget regression shows up in the DEVICE
    PROGRAMS table before it shows up as a Mosaic OOM.
    ``hbm_intermediate_bytes`` is the inter-stage stack traffic this
    program's composition materializes in HBM between pipeline stages
    per call (ISSUE 17): the separate gather/forward/blend legs stamp
    the stacks they write+re-read, the fused pipeline stamps ~0 — the
    fusion's prize, surfaced as the catalog's
    ``hbm_intermediate_bytes`` / log-summary ``hbm_i`` column."""
    cost: dict = {}
    if flops is not None:
        cost["flops"] = float(flops)
    if bytes_accessed is not None:
        cost["bytes accessed"] = float(bytes_accessed)
    if vmem_bytes is not None:
        cost["vmem_bytes"] = float(vmem_bytes)
    if hbm_intermediate_bytes is not None:
        cost["hbm_intermediate_bytes"] = float(hbm_intermediate_bytes)
    return _CostStamped(program, cost)


_H2D_LOCK = threading.Lock()
_H2D: dict = {}  # program family -> staged H2D bytes


def note_h2d(nbytes, key=None, label: str = "") -> None:
    """Count one host->device staging transfer at the staging seam
    (ISSUE 15): the ``transfer/h2d_bytes`` / ``transfer/h2d_chunks``
    counters make the front-half win visible in byte terms, and ``key``
    (a ProgramCache key) attributes the bytes to the program family that
    consumes them — the ``h2d_bytes`` column of the programs.json
    catalog / log-summary DEVICE PROGRAMS table. No-op under the
    telemetry kill switch."""
    if not telemetry.enabled():
        return
    telemetry.inc("transfer/h2d_bytes", float(nbytes))
    telemetry.inc("transfer/h2d_chunks")
    if key is not None:
        family, _ = _family_of(key, label)
        with _H2D_LOCK:
            _H2D[family] = _H2D.get(family, 0.0) + float(nbytes)


def h2d_by_family() -> dict:
    """Staged H2D bytes per program family (a copy)."""
    with _H2D_LOCK:
        return dict(_H2D)


_HBM_I_LOCK = threading.Lock()
_HBM_I: dict = {}  # program family -> inter-stage stack bytes


def note_hbm_intermediate(nbytes, key=None, label: str = "") -> None:
    """Count inter-stage stack traffic the SEPARATE-programs composition
    pays between pipeline stages (ISSUE 17): the gathered-patch /
    weighted-prediction stacks one program materializes and the next
    re-reads (including the serving packer's D2H+H2D round trip of the
    weighted stack). The fused pipeline leg notes ~nothing here — the
    ``transfer/hbm_intermediate_bytes`` counter and the per-family
    bucket (the catalog's ``hbm_intermediate_bytes`` fallback when no
    stamp carries it) make the fusion win visible in byte terms, the
    same shape as :func:`note_h2d`. No-op under the telemetry kill
    switch."""
    if not telemetry.enabled():
        return
    telemetry.inc("transfer/hbm_intermediate_bytes", float(nbytes))
    if key is not None:
        family, _ = _family_of(key, label)
        with _HBM_I_LOCK:
            _HBM_I[family] = _HBM_I.get(family, 0.0) + float(nbytes)


def hbm_intermediate_by_family() -> dict:
    """Inter-stage stack bytes per program family (a copy)."""
    with _HBM_I_LOCK:
        return dict(_HBM_I)


_COLLECTIVE_LOCK = threading.Lock()
_COLLECTIVE: dict = {}  # program family -> analytic collective bytes


def note_collective(nbytes, key=None, label: str = "") -> None:
    """Count ANALYTIC cross-chip collective traffic for one sharded
    dispatch (ISSUE 18): halo ``ppermute`` exchanges, the weighted-
    stack ``all_gather`` (replicated-replay legs only), the fringe
    replay-strip ``ppermute`` exchanges of the sharded blend replay,
    and the per-tick activation handoffs of the ``pipeline=N`` ring
    (ISSUE 19) — each computed by the engine from halo/fringe widths,
    shard shapes and dtypes — the same stamped-arithmetic discipline as
    :func:`stamp_cost`, because XLA's cost analysis does not price
    inter-chip links. Feeds the ``shard/collective_bytes`` counter and
    a per-family bucket (the catalog's ``collective_bytes`` column), so
    the MESH block can show collective-vs-compute per mesh shape; the
    engine additionally splits the total into ``shard/halo_bytes``,
    ``shard/gather_bytes``, ``shard/replay_strip_bytes`` and
    ``shard/handoff_bytes`` counters. No-op under the telemetry kill
    switch."""
    if not telemetry.enabled():
        return
    telemetry.inc("shard/collective_bytes", float(nbytes))
    if key is not None:
        family, _ = _family_of(key, label)
        with _COLLECTIVE_LOCK:
            _COLLECTIVE[family] = _COLLECTIVE.get(family, 0.0) \
                + float(nbytes)


def collective_by_family() -> dict:
    """Analytic collective bytes per program family (a copy)."""
    with _COLLECTIVE_LOCK:
        return dict(_COLLECTIVE)


def _family_of(key, label: str) -> Tuple[str, str]:
    """(family, shape-ish remainder) from a ProgramCache key. Keys are
    tuples like ``("scatter",)`` / ``("fold", (8, 32, 32))``; anything
    else falls back to the cache label."""
    if isinstance(key, tuple) and key:
        family = str(key[0])
        rest = ",".join(str(part) for part in key[1:])
    else:
        family = label or str(key)
        rest = "" if isinstance(key, tuple) else str(key)
    return family, rest


def instrument_program(program, key, label: str = "",
                       build_s: float = 0.0):
    """Wrap a freshly built cached program into the cost ledger; returns
    the program untouched when telemetry is off (kill switch: the plane
    does not exist) or when the object is not a lowerable jit program
    (tests cache plain sentinels)."""
    if not telemetry.enabled():
        return program
    if not callable(program) or not hasattr(program, "lower"):
        return program
    family, rest = _family_of(key, label)
    rec = _ProgramRecord(family=family, key=rest, label=label,
                         build_s=build_s)
    with _LEDGER_LOCK:
        _LEDGER[(family, rest, id(rec))] = rec
    return _InstrumentedProgram(program, rec)


def catalog() -> list:
    """The cost ledger with roofline derivations, one dict per program:
    compile seconds, FLOPs / bytes accessed (when XLA exposed them),
    post-compile dispatch stats, and — against :func:`device_peaks` —
    ``roofline_s`` (the cost-model floor per call) and
    ``roofline_util`` (floor / mean dispatch wall; an *upper bound*
    under async dispatch, see module docstring).

    The keys of an entry, which is also one of ``programs.json``'s
    ``programs``. Always: ``family``, ``key``, ``label``, ``build_s``,
    ``compile_s``, ``flops``, ``bytes_accessed``, ``vmem_bytes``,
    ``optimal_s``, ``calls``, ``dispatch_total_s``, ``platform``,
    ``device_kind``, ``peak_flops_per_s``, ``peak_bytes_per_s``,
    ``peak_source``, ``roofline_s``, ``exec_mean_s``, ``roofline_util``,
    ``lost_s``, ``achieved_flops_per_s``, ``h2d_bytes``,
    ``hbm_intermediate_bytes``, ``collective_bytes``; ``x_fold`` and the
    :data:`GEOMETRY_GAUGES` (None where the program's trace set none) and
    whatever else its trace said under ``forward/<name>``. With a sink
    configured when the program first ran, the three :data:`OP_MAPS`
    (``op_scopes``, ``op_parts``, ``op_convolutions``: :func:`op_scopes`,
    :func:`op_parts`); without one they are None, and nothing was
    compiled or parsed to fill them."""
    with _LEDGER_LOCK:
        records = list(_LEDGER.values())
    h2d = h2d_by_family()
    hbm_i = hbm_intermediate_by_family()
    coll = collective_by_family()
    out = []
    for rec in records:
        with rec.lock:
            entry = {
                "family": rec.family,
                "key": rec.key,
                "label": rec.label,
                "build_s": round(rec.build_s, 4),
                "compile_s": (
                    round(rec.compile_s, 4)
                    if rec.compile_s is not None else None
                ),
                "flops": rec.flops,
                "bytes_accessed": rec.bytes_accessed,
                "vmem_bytes": rec.vmem_bytes,
                "optimal_s": rec.optimal_s,
                "calls": rec.calls + (1 if rec.compile_s is not None else 0),
                "dispatch_total_s": round(rec.dispatch_s, 4),
                "platform": rec.platform,
                "device_kind": rec.device_kind,
                **{name: (rec.op_maps or {}).get(name) for name in OP_MAPS},
                "x_fold": rec.traced.get("forward/x_fold"),
                # what else the model's trace said of its lowering
                # (models/rsunet.py: dec{i}_voxel_share, flops_share)
                **{name.split("/", 1)[1]: value
                   for name, value in rec.traced.items()
                   if name.startswith("forward/")},
                **{name: rec.traced.get(f"inference/{name}")
                   for name in GEOMETRY_GAUGES},
            }
            calls, dispatch_s = rec.calls, rec.dispatch_s
            flops, nbytes = rec.flops, rec.bytes_accessed
            kind = rec.device_kind
        # a program that never ran has met no device: no peaks, no roofline
        peaks = device_peaks(kind) if kind else dict.fromkeys(
            ("flops_per_s", "bytes_per_s", "source"))
        entry["peak_flops_per_s"] = peaks["flops_per_s"]
        entry["peak_bytes_per_s"] = peaks["bytes_per_s"]
        entry["peak_source"] = peaks["source"]
        roofline_s = None
        if kind and (flops is not None or nbytes is not None):
            roofline_s = max(
                (flops or 0.0) / peaks["flops_per_s"],
                (nbytes or 0.0) / peaks["bytes_per_s"],
            )
        entry["roofline_s"] = roofline_s
        exec_s = dispatch_s / calls if calls else None
        entry["exec_mean_s"] = round(exec_s, 6) if exec_s else None
        entry["roofline_util"] = (
            round(roofline_s / exec_s, 4)
            if roofline_s and exec_s else None
        )
        # lost seconds: (dispatch_wall − roofline_s) × calls — the total
        # wall this program spent ABOVE its cost-model floor, i.e. the
        # prize for fusing/optimizing it. The "what do I fuse next"
        # ranking key (log-summary DEVICE PROGRAMS); clamped at zero
        # because async dispatch can put measured wall under the floor.
        entry["lost_s"] = (
            round(max(0.0, exec_s - roofline_s) * calls, 6)
            if roofline_s is not None and exec_s else None
        )
        entry["achieved_flops_per_s"] = (
            round(flops / exec_s, 2) if flops and exec_s else None
        )
        # staged H2D bytes attributed to this family (note_h2d): the
        # front-half "what does this program cost the PCIe link" column
        entry["h2d_bytes"] = h2d.get(rec.family)
        # inter-stage stack traffic (ISSUE 17): a stamp on the program
        # wins (the builder's analytic per-call figure); otherwise the
        # note_hbm_intermediate family bucket (measured counters, e.g.
        # the serving round trip) — ~0 / absent on the fused pipeline
        entry["hbm_intermediate_bytes"] = (
            rec.hbm_intermediate
            if rec.hbm_intermediate is not None
            else hbm_i.get(rec.family)
        )
        # analytic cross-chip traffic attributed to this family
        # (note_collective): the "what does this program cost the
        # interconnect" column — absent on single-device programs
        entry["collective_bytes"] = coll.get(rec.family)
        out.append(entry)
    out.sort(key=lambda e: -(e["compile_s"] or 0.0))
    return out


def write_catalog(metrics_dir: Optional[str] = None) -> Optional[str]:
    """Write the per-run ``programs.json`` catalog (and emit a
    ``programs``-kind event carrying the same entries) under
    ``metrics_dir`` — default: the telemetry sink's directory. No-op
    (returns None) with telemetry off, an empty ledger, or nowhere to
    write. Registered as a telemetry flush hook, so every run that
    flushes a sink gets its catalog for free."""
    if not telemetry.enabled():
        return None
    entries = catalog()
    if not entries:
        return None
    if metrics_dir is None:
        path = telemetry.configured_path()
        metrics_dir = os.path.dirname(path) if path else None
    if metrics_dir is None:
        return None
    # the JSONL stream gets the ledger without the per-op maps, which
    # only a trace reducer needs and which programs.json keeps
    telemetry.event("programs", "program/catalog", programs=[
        {k: v for k, v in e.items() if k not in OP_MAPS} for e in entries])
    payload = {
        "worker": telemetry.worker_id(),
        "t": time.time(),
        "programs": entries,
    }
    target = os.path.join(metrics_dir, "programs.json")
    try:
        os.makedirs(metrics_dir, exist_ok=True)
        tmp = target + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f, indent=2)
        os.replace(tmp, target)
    except OSError:
        return None
    return target


# ---------------------------------------------------------------------------
# bounded profiler capture (anomaly-triggered + operator-requested)
# ---------------------------------------------------------------------------
_STATE_LOCK = threading.Lock()
_TRACE_ACTIVE = False  # one jax profiler session at a time, window or capture
_LAST_CAPTURE_T: Optional[float] = None  # monotonic, automatic cooldown clock
_CAPTURE_SEQ = 0
_CAPTURE_THREADS: list = []
_STALL_PHASE: Optional[str] = None
_STALL_TICKS = 0
_WINDOW = None


def capture_base_dir() -> Optional[str]:
    """Where captures land: the telemetry sink's directory, else
    ``CHUNKFLOW_PROFILE_DIR``, else None (captures disabled)."""
    path = telemetry.configured_path()
    if path:
        return os.path.dirname(path)
    return os.environ.get("CHUNKFLOW_PROFILE_DIR") or None


def _anomaly_capture_enabled() -> bool:
    return os.environ.get(
        "CHUNKFLOW_PROFILE_ON_ANOMALY", "1"
    ).lower() not in ("0", "off", "false", "no")


_SESSION_ACTIVE = "a profiler session is already active"


def _foreign_session() -> bool:
    """Whether a ``jax.profiler`` session that this module did not start
    is tracing now: a harness's ``start_trace``, ``jax.profiler.trace``
    in user code, a profiler server's client."""
    try:
        return telemetry.profiler_session_active()
    except Exception:
        return False


def _acquire_trace() -> bool:
    global _TRACE_ACTIVE
    with _STATE_LOCK:
        if _TRACE_ACTIVE or _foreign_session():
            return False
        _TRACE_ACTIVE = True
        return True


def _release_trace() -> None:
    global _TRACE_ACTIVE
    with _STATE_LOCK:
        _TRACE_ACTIVE = False


def _safe_name(reason: str) -> str:
    return "".join(
        ch if ch.isalnum() or ch in "._-" else "-" for ch in reason
    )[:48]


def _run_capture(target: str, seconds: float, reason: str) -> bool:
    """One bounded profiler window into ``target``; the caller holds the
    trace flag. Never raises — a failed capture is an event, not a
    pipeline death."""
    try:
        import jax

        os.makedirs(target, exist_ok=True)
        jax.profiler.start_trace(target)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    except Exception as exc:
        if "already been started" in str(exc):
            # someone else's session began between the check in
            # capture() and here: yielding to it is not a failure
            telemetry.event("profile", "profile/capture_skipped",
                            reason=reason, why=_SESSION_ACTIVE)
            return False
        telemetry.inc("profile/capture_errors")
        telemetry.event("profile", "profile/capture_error",
                        reason=reason, error=str(exc)[:300])
        return False
    finally:
        _release_trace()
    telemetry.inc("profile/captures")
    telemetry.event("profile", "profile/capture", dir=target,
                    seconds=seconds, reason=reason)
    return True


def capture(seconds: float, reason: str, force: bool = False,
            background: bool = False) -> Tuple[Optional[str], Optional[str]]:
    """One bounded profiler window; returns ``(trace_dir, error)``.

    ``force=True`` (operator request, the ``/profile`` route) bypasses
    the automatic-capture cooldown but never the one-session-at-a-time
    exclusion, which holds against sessions this module did not start
    too (:func:`_foreign_session`). ``background=True`` runs the window in its own thread
    (anomaly triggers must not stall the pipeline for the window's
    duration; process exit waits for it). Disabled telemetry or no capture dir ⇒ ``(None, why)``.
    """
    global _TRACE_ACTIVE, _LAST_CAPTURE_T, _CAPTURE_SEQ
    if not telemetry.enabled():
        return None, "telemetry disabled (CHUNKFLOW_TELEMETRY=0)"
    base = capture_base_dir()
    if base is None:
        return None, ("no capture dir: run with --metrics-dir or set "
                      "CHUNKFLOW_PROFILE_DIR")
    seconds = min(max(float(seconds), 0.05),
                  _env_float("CHUNKFLOW_PROFILE_MAX_SECONDS", 60.0))
    cooldown = _env_float("CHUNKFLOW_PROFILE_COOLDOWN", 300.0)
    with _STATE_LOCK:
        if _TRACE_ACTIVE or _foreign_session():
            return None, _SESSION_ACTIVE
        if not force and _LAST_CAPTURE_T is not None \
                and time.monotonic() - _LAST_CAPTURE_T < cooldown:
            return None, "capture cooldown in effect"
        _TRACE_ACTIVE = True
        _LAST_CAPTURE_T = time.monotonic()
        _CAPTURE_SEQ += 1
        seq = _CAPTURE_SEQ
    target = os.path.join(base, f"profile-{_safe_name(reason)}-{seq}")
    if background:
        # NOT a daemon: the window is bounded, and a process that exits
        # with the profiler session open aborts in interpreter shutdown
        # ("FATAL: exception not rethrown", exit 134, on a v5e) — so
        # exit waits for the window to close
        thread = threading.Thread(
            target=_run_capture, args=(target, seconds, reason),
            name=f"chunkflow-profile-{seq}",
        )
        with _STATE_LOCK:  # wait_for_captures never sees it unstarted
            thread.start()
            _CAPTURE_THREADS.append(thread)
        return target, None
    ok = _run_capture(target, seconds, reason)
    return (target, None) if ok else (None, "capture failed (see events)")


def maybe_capture(reason: str) -> bool:
    """Automatic (anomaly) capture: bounded window in a background
    thread, honoring the cooldown and the anomaly kill switch
    (``CHUNKFLOW_PROFILE_ON_ANOMALY=0``). Returns True when a capture
    was started."""
    if not telemetry.enabled() or not _anomaly_capture_enabled():
        return False
    seconds = _env_float("CHUNKFLOW_PROFILE_SECONDS", 3.0)
    target, err = capture(seconds, reason, force=False, background=True)
    if target is None:
        if err not in ("capture cooldown in effect",):
            telemetry.event("profile", "profile/capture_skipped",
                            reason=reason, why=err)
        return False
    return True


def note_retrace(label: str) -> None:
    """The retrace watchdog fired (core/compile_cache.py): the pipeline
    is paying an unplanned XLA compile per chunk — exactly the moment a
    bounded trace is worth its cost."""
    maybe_capture(f"retrace-{_safe_name(label)}")


def note_slo_page(objective: str) -> None:
    """A page-severity SLO burn-rate alert fired (core/slo.py): the
    serving plane is burning error budget fast enough to page a human —
    grab one bounded trace while the regression is still live, so the
    evidence is on disk before anyone is awake. Rides the same cooldown
    and kill switches as every other anomaly capture: an alert storm
    cannot fill the disk, and a second alert inside the cooldown
    captures nothing."""
    maybe_capture(f"slo-{_safe_name(objective)}")


#: Phases in which the host waits for the device. Their dominating a
#: worker's stall time is the healthy state of a device-bound pipeline
#: (with async dispatch the wait lands in ``pipeline/dispatch`` once the
#: device queue is full), so it is never an anomaly worth a capture.
DEVICE_PACED_PHASES = ("pipeline/dispatch", "pipeline/compute")


def note_stall(phase: str, share: float) -> None:
    """One depth-controller tick's dominant stall sample
    (flow/scheduler.py). A share at or above
    ``CHUNKFLOW_PROFILE_STALL_SHARE`` (default 0.8) for
    ``CHUNKFLOW_PROFILE_STALL_TICKS`` (default 3) *consecutive* ticks
    on the SAME phase triggers one bounded capture — a persistent
    bottleneck the depth controller could not widen away. A host that
    waits on the device (:data:`DEVICE_PACED_PHASES`) is no such
    bottleneck: it breaks the streak like a low share does."""
    global _STALL_PHASE, _STALL_TICKS
    threshold = _env_float("CHUNKFLOW_PROFILE_STALL_SHARE", 0.8)
    need = _env_int("CHUNKFLOW_PROFILE_STALL_TICKS", 3)
    with _STATE_LOCK:
        if share < threshold or phase in DEVICE_PACED_PHASES:
            _STALL_PHASE, _STALL_TICKS = None, 0
            return
        if phase != _STALL_PHASE:
            _STALL_PHASE, _STALL_TICKS = phase, 1
        else:
            _STALL_TICKS += 1
        if _STALL_TICKS < need:
            return
        _STALL_PHASE, _STALL_TICKS = None, 0
    maybe_capture(f"stall-{_safe_name(phase)}")


def wait_for_captures(timeout: float = 10.0) -> None:
    """Join outstanding background capture threads (tests, teardown)."""
    deadline = time.monotonic() + timeout
    with _STATE_LOCK:
        threads = list(_CAPTURE_THREADS)
    for thread in threads:
        thread.join(timeout=max(0.0, deadline - time.monotonic()))
    with _STATE_LOCK:
        _CAPTURE_THREADS[:] = [
            t for t in _CAPTURE_THREADS if t.is_alive()
        ]


# ---------------------------------------------------------------------------
# windowed --profile-dir capture (first N tasks)
# ---------------------------------------------------------------------------
class _TaskWindow:
    """A profiler session covering the first N pipeline tasks (N<=0:
    the whole run — the historical behavior, now opt-in)."""

    def __init__(self, trace_dir: str, tasks: int):
        self.trace_dir = trace_dir
        self.remaining = tasks
        self.active = False
        self._lock = threading.Lock()

    def _start(self) -> bool:
        if not _acquire_trace():
            return False
        try:
            import jax

            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
        except Exception as exc:
            _release_trace()
            telemetry.event("profile", "profile/window_error",
                            error=str(exc)[:300])
            return False
        self.active = True
        telemetry.event("profile", "profile/window_start",
                        dir=self.trace_dir, tasks=self.remaining)
        return True

    def note_task(self) -> None:
        with self._lock:
            if not self.active or self.remaining <= 0:
                return  # whole-run window: only close() stops it
            self.remaining -= 1
            if self.remaining > 0:
                return
            self._stop()

    def close(self) -> None:
        with self._lock:
            if self.active:
                self._stop()

    def _stop(self) -> None:
        """Caller holds self._lock (or is single-threaded teardown)."""
        self.active = False
        try:
            import jax

            jax.profiler.stop_trace()
        except Exception as exc:
            telemetry.event("profile", "profile/window_error",
                            error=str(exc)[:300])
        finally:
            _release_trace()
        telemetry.inc("profile/windows")
        telemetry.event("profile", "profile/window_stop",
                        dir=self.trace_dir)


def start_task_window(trace_dir: str,
                      tasks: Optional[int] = None) -> Optional[_TaskWindow]:
    """Start the windowed ``--profile-dir`` trace: the profiler runs
    from now until ``tasks`` pipeline tasks complete
    (``CHUNKFLOW_PROFILE_TASKS`` default 4; <=0 traces the whole run).
    Returns None — creating nothing — when telemetry is off or another
    profiler session is active."""
    global _WINDOW
    if not telemetry.enabled():
        return None
    if tasks is None:
        tasks = _env_int("CHUNKFLOW_PROFILE_TASKS", 4)
    window = _TaskWindow(trace_dir, tasks)
    if not window._start():
        return None
    _WINDOW = window
    return window


def note_task_done() -> None:
    """One pipeline task finished (flow/runtime.process_stream). Cheap
    flag check when no window is open."""
    window = _WINDOW
    if window is not None:
        window.note_task()


# ---------------------------------------------------------------------------
# per-run lifecycle: ride telemetry's flush/reset
# ---------------------------------------------------------------------------
def _on_reset() -> None:
    global _LAST_CAPTURE_T, _STALL_PHASE, _STALL_TICKS, _WINDOW
    window = _WINDOW
    if window is not None:
        window.close()
    _WINDOW = None
    with _LEDGER_LOCK:
        _LEDGER.clear()
    with _H2D_LOCK:
        _H2D.clear()
    with _HBM_I_LOCK:
        _HBM_I.clear()
    with _COLLECTIVE_LOCK:
        _COLLECTIVE.clear()
    with _STATE_LOCK:
        _LAST_CAPTURE_T = None
        _STALL_PHASE, _STALL_TICKS = None, 0


telemetry.add_flush_hook(write_catalog)
telemetry.add_reset_hook(_on_reset)
