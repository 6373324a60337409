"""Unified multi-chip sharded inference engine: ONE shard_map program
family for streaming AND serving across a pod slice.

This module subsumes the four divergent parallel variants that grew up
around the fused inference program — ``distributed.py`` (patch-parallel
psum), ``spatial.py`` (1D y-slab ring), ``spatial2d.py`` (2D mesh with
two-phase halo/spill) — into
a single :class:`ShardedEngine` driven by a mesh spec:

    CHUNKFLOW_MESH=1           kill switch: the single-device reference
                               path, bit-identically (no engine is built)
    CHUNKFLOW_MESH=auto        one 'data' axis over every local device
    CHUNKFLOW_MESH=data=8      patch-parallel over 8 chips
    CHUNKFLOW_MESH=y=4         chunk sharded in y slabs over 4 chips
    CHUNKFLOW_MESH=y=4,x=2     chunk sharded over a (4, 2) (y, x) mesh
    CHUNKFLOW_MESH=pipeline=4  the convnet's layer stack staged over 4
                               chips, patch micro-batches streamed
                               through a ppermute ring (ISSUE 19; needs
                               an engine declaring the stage protocol,
                               parallel/pipeline.py)

**Bit-identity contract.** Every mesh shape produces bitwise-identical
output to the single-device fused program. The legacy variants merged
*partial blend buffers* across chips (psum / spill ``ppermute``), which
regroups the float accumulation and drifts by ulps; this engine instead
shards the roofline-dominant stage — the convnet forward — and replays
the *reference accumulation verbatim*:

1. each chip gathers and forwards its share of patch batches at the SAME
   per-batch shape ``[B, ci, *pin]`` the single-device program scans
   (per-patch forward math is row-independent, so results are bitwise
   equal no matter which rows share a batch — the same property the
   serving packer's parity contract rests on, serve/packer.py);
2. the weighted prediction stacks ``all_gather`` over the mesh (pure
   data movement, exact);
3. every chip replays the single-device scan-over-batches scatter
   accumulation — same :func:`ops.blend.make_accumulate` step, same
   batch grouping, same order — and the same ``normalize_blend``.

For the spatial kinds the *input chunk itself* is sharded (each chip
holds one slab plus ``ppermute``-exchanged halos — the HBM-scaling win of
the old spatial variants, kept), patches are bucketed to the slab that
owns their output start, and a host-precomputed index restores global
patch order before the replay.

**Sharded blend replay (ISSUE 19, the default).** Step 3 no longer runs
replicated into a full-chunk buffer: each chip replays ONLY the windows
that touch its output slab, into a slab+margin buffer, and the output
stays sharded over the mesh. The bitwise contract survives because the
per-voxel scatter accumulation is a sequential in-order fold — XLA
applies overlapping updates per voxel in update order, so regrouping
the window list into per-slab batches (same relative order, verified by
the parity matrix) leaves every voxel's fold identical to the
single-device program's. Windows whose footprint crosses a slab
boundary (their output start lives on the neighbour) ride a forward
``ppermute`` fringe exchange — y phase then x phase, corner windows
two-hopping through the x neighbour, the same no-diagonal pattern as
the input halos — and each chip's host-precomputed replay index merges
own + received windows back into global order. Crucially the exchange
ships *whole weighted windows*, never partially-accumulated buffers
(which is what made the legacy spill paths drift by ulps). Per-chip
blend HBM drops from full-chunk to slab+margin — the path to chunks
bigger than one chip's HBM. ``CHUNKFLOW_SHARD_REPLAY=replicated``
(ops/blend.shard_replay_mode) restores the historical PR 13 full-chunk
replicated replay as the bisection leg; the tag joins the program key.

**Pipeline mesh (ISSUE 19).** ``pipeline=N`` stages the engine's layer
stack over N chips (the stage protocol, parallel/pipeline.py) and
streams patch micro-batches through a double-buffered forward
``ppermute`` ring, PipeFusion-style: at tick ``t`` stage 0 gathers
micro-batch ``t`` while stage ``s`` runs micro-batch ``t-s``, so the
inter-stage handoff hides behind compute and the pipeline drains in
``T + N - 1`` ticks. Stages are contiguous groups of the engine's
declared bodies, whose composition IS the engine's apply (bitwise), so
the pipelined forward computes the same per-row expression; the blend
then replays exactly as above (slab-sharded over the ring, or
replicated under the kill switch). The serving packer's
``serve_forward_program`` gets the same treatment so packed batches
fill the pipeline bubbles.

Programs build through the PR 2 :class:`~chunkflow_tpu.core.
compile_cache.ProgramCache`, so sharded programs get chunk-buffer
donation (GL005), compile-cache shape bucketing, and the PR 8 roofline
ledger (``programs.json``) exactly like the single-device family — none
of the four legacy variants did.

Telemetry (host-side only, GL007): ``shard/mesh_devices`` /
``shard/mesh_y`` / ``shard/mesh_x`` / ``shard/per_chip_voxels`` gauges,
``shard/chunks`` counter, and a ``shard/dispatch`` span labelled with the
mesh around every sharded dispatch (the collective span — under async
dispatch it measures enqueue, not device wall; docs/multichip.md).

Per-chip attribution (ISSUE 18, docs/observability.md "Timeline view"):

* ``shard/chip/<i>/voxels`` — output voxels each chip actually computed
  this dispatch (its share of valid patches × output-patch voxels), the
  load-balance gauge for a mesh shape;
* a sampled readiness probe (first dispatch, then every
  ``CHUNKFLOW_CHIP_PROBE_EVERY``-th, default 8) blocks on each output
  shard in device order and records ``shard/chip/<i>/ready_s`` plus the
  headline ``shard/chip_skew_s`` (last ready − first ready). Per-chip
  ready stamps are probe-ordered lower bounds — chip ``i+1``'s wait
  overlaps chip ``i``'s — but the skew survives that caveat: it is
  exactly the straggler wall the probe observed;
* analytic collective byte counters, stamped from halo widths / shard
  shapes / dtypes the way ``profiling.stamp_cost`` stamps HBM bytes
  (XLA's cost analysis does not price inter-chip links):
  ``shard/halo_bytes`` (``ppermute`` halo exchange, spatial kinds),
  ``shard/gather_bytes`` (the weighted-stack / slab-output
  ``all_gather``), ``shard/replay_strip_bytes`` (the sharded replay's
  fringe-window ``ppermute`` strips) and ``shard/handoff_bytes`` (the
  pipeline ring's stage handoffs) — all folded per program family via
  ``profiling.note_collective`` (what the exchanges cost in time is
  the device trace's to say: every exchange traces under the
  ``collective`` named scope, core/profiling.py ``DEVICE_SCOPES``); and
  the analytic
  ``shard/replay_buffer_bytes`` (+ per-chip
  ``shard/chip/<i>/replay_buffer_bytes``) blend-buffer footprint — the
  slab+margin vs full-chunk HBM claim, asserted in-suite next to the
  ``device/chip/<i>/*`` watermark plane.

Everything above is gated on the telemetry kill switch: under
``CHUNKFLOW_TELEMETRY=0`` no gauge, counter, or readiness probe exists
(the probe would otherwise cost a sampled device sync).

Multi-process runtimes: the ``data`` kind keeps the cross-host global-
array recipe (``multihost.run_global``: psum program + consistency
guard) on backends whose collectives span processes; on backends that
cannot run multiprocess computations (the CPU backend — podsim/tier-1)
the engine verifies input consistency through the coordination-service
digest exchange and computes over the process-local mesh instead
(``multihost.ensure_consistent``; docs/multichip.md "Simulation vs a
real slice").
"""
from __future__ import annotations

import os
import re
import time
from functools import partial
from typing import NamedTuple, Optional, Tuple

import numpy as np

from chunkflow_tpu.core import profiling, telemetry
from chunkflow_tpu.core.compile_cache import ProgramCache
from chunkflow_tpu.inference.patching import (
    PatchGrid,
    enumerate_patches,
    pad_to_batch,
)

__all__ = [
    "MeshSpec", "parse_mesh_spec", "mesh_env_spec", "ShardedEngine",
    "sharded_inference",
]

Triple = Tuple[int, int, int]

_OFF_VALUES = ("", "1", "none", "off", "single", "0")


class MeshSpec(NamedTuple):
    """A parsed mesh request: ``kind`` is ``single`` (no engine),
    ``data`` (patch-parallel, chunk replicated), ``spatial`` (chunk
    sharded over a ``(ny, nx)`` mesh; ``nx == 1`` is the 1D y-slab
    layout) or ``pipeline`` (layer stack staged over N chips, patch
    micro-batches streamed — the stage protocol,
    parallel/pipeline.py)."""

    kind: str           # "single" | "data" | "spatial" | "pipeline"
    shape: Tuple[int, ...]  # ("data"/"pipeline": (n,); "spatial": (ny, nx))

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n

    def describe(self) -> str:
        if self.kind == "single":
            return "1"
        if self.kind == "data":
            return f"data={self.shape[0]}"
        if self.kind == "pipeline":
            return f"pipeline={self.shape[0]}"
        ny, nx = self.shape
        return f"y={ny},x={nx}" if nx > 1 else f"y={ny}"


def parse_mesh_spec(value: Optional[str],
                    n_devices: Optional[int] = None) -> MeshSpec:
    """Parse a mesh spec string (the ``CHUNKFLOW_MESH`` grammar).

    ``n_devices`` bounds ``auto`` and validates explicit sizes; ``None``
    defers the device-count check to mesh construction (spec parsing must
    not force a jax import)."""
    raw = (value or "").strip().lower()
    if raw in _OFF_VALUES:
        return MeshSpec("single", (1,))
    if raw == "auto":
        n = n_devices if n_devices is not None else 0
        if n <= 1:
            return MeshSpec("single", (1,))
        return MeshSpec("data", (n,))
    if re.fullmatch(r"\d+", raw):
        n = int(raw)
        spec = MeshSpec("single", (1,)) if n <= 1 else MeshSpec("data", (n,))
        _check_devices(spec, n_devices, value)
        return spec
    axes = {}
    for part in raw.split(","):
        m = re.fullmatch(r"\s*(data|y|x|pipeline)\s*=\s*(\d+)\s*", part)
        if not m:
            raise ValueError(
                f"bad mesh spec {value!r}: expected '1', 'auto', 'N', "
                f"'data=N', 'y=A', 'y=A,x=B' or 'pipeline=N' "
                f"(docs/multichip.md)"
            )
        axis, n = m.group(1), int(m.group(2))
        if axis in axes:
            raise ValueError(f"bad mesh spec {value!r}: duplicate '{axis}='")
        if n < 1:
            raise ValueError(f"bad mesh spec {value!r}: {axis}={n}")
        axes[axis] = n
    if "pipeline" in axes:
        if len(axes) > 1:
            raise ValueError(
                f"bad mesh spec {value!r}: 'pipeline' does not compose "
                f"with other axes"
            )
        n = axes["pipeline"]
        spec = MeshSpec("single", (1,)) if n <= 1 \
            else MeshSpec("pipeline", (n,))
        _check_devices(spec, n_devices, value)
        return spec
    if "data" in axes:
        if len(axes) > 1:
            raise ValueError(
                f"bad mesh spec {value!r}: 'data' does not compose with "
                f"spatial axes"
            )
        n = axes["data"]
        spec = MeshSpec("single", (1,)) if n <= 1 else MeshSpec("data", (n,))
    else:
        ny = axes.get("y", 1)
        nx = axes.get("x", 1)
        if ny * nx <= 1:
            spec = MeshSpec("single", (1,))
        else:
            spec = MeshSpec("spatial", (ny, nx))
    _check_devices(spec, n_devices, value)
    return spec


def _check_devices(spec: MeshSpec, n_devices: Optional[int], value) -> None:
    if n_devices is not None and spec.n_devices > n_devices:
        raise ValueError(
            f"mesh spec {value!r} needs {spec.n_devices} devices, only "
            f"{n_devices} available"
        )


def mesh_env_spec(n_devices: Optional[int] = None) -> MeshSpec:
    """The ``CHUNKFLOW_MESH`` environment spec (default: the single-
    device kill switch). Re-read per call so tests and long-lived
    workers can flip it."""
    return parse_mesh_spec(os.environ.get("CHUNKFLOW_MESH", "1"), n_devices)


# ---------------------------------------------------------------------------
# geometry
# ---------------------------------------------------------------------------

def axis_geometry(extent: int, n_dev: int, pin: int, pout: int):
    """(slab, halo_left, halo_right, padded) for sharding one spatial
    axis over ``n_dev`` chips. ``n_dev == 1`` means no exchange: the
    whole extent is one slab with zero halos. For ``n_dev > 1`` this is
    the proven 1D slab math (parallel/spatial.spatial_geometry) minus
    the output-spill floor, which the replay design no longer needs —
    but the slab keeps the spill floor so legacy callers share one
    geometry."""
    if n_dev <= 1:
        return extent, 0, 0, extent
    margin = (pin - pout) // 2
    halo_left = margin
    halo_right = pin - margin
    slab = max(-(-extent // n_dev), halo_left, halo_right, pout)
    return slab, halo_left, halo_right, slab * n_dev


def _pad_chunk(arr, padded_y: int, padded_x: int):
    """Zero-pad [C, Z, y, x] on the high side of y/x (device-side for jax
    arrays)."""
    pad = [(0, 0)] * arr.ndim
    pad[-2] = (0, padded_y - arr.shape[-2])
    pad[-1] = (0, padded_x - arr.shape[-1])
    if not any(p != (0, 0) for p in pad):
        return arr
    if isinstance(arr, np.ndarray):
        return np.pad(arr, pad)
    import jax.numpy as jnp

    return jnp.pad(arr, pad)


class _Partition(NamedTuple):
    """Host-side patch partition for one (grid, mesh) pair."""

    dev_in: np.ndarray      # [ny, nx, P, 3] int32, slab-localized gathers
    dev_valid: np.ndarray   # [ny, nx, P] float32
    src_index: np.ndarray   # [n_ref] int32: global padded row -> gathered row
    out_starts: np.ndarray  # [n_ref, 3] int32, GLOBAL replay coords
    valid: np.ndarray       # [n_ref] float32, the reference validity
    per_dev: int            # P
    global_index: np.ndarray  # [ny, nx, P] int32 global row per local row
                              # (-1 for filler slots)
    counts: np.ndarray        # [ny, nx] int32 real rows per chip


def partition_for_mesh(
    grid: PatchGrid,
    shape: Tuple[int, int],
    batch_size: int,
    yslab: int,
    xslab: int,
    halo_left_y: int,
    halo_left_x: int,
) -> _Partition:
    """Bucket the REFERENCE padded patch list (``pad_to_batch(grid, B)``,
    global padding rows included) by output-start slab and localize the
    gather coordinates to each device's extended-slab frame.

    Keeping the global padding rows inside the buckets matters for the
    bit-identity contract: their forwarded values (``preds * bump * 0``,
    a signed-zero pattern) flow through the replay exactly as the
    single-device program computes them, instead of being approximated
    by fresh ``+0.0`` rows."""
    ny, nx = shape
    in_starts, out_starts, valid = pad_to_batch(grid, batch_size)
    n_ref = len(valid)
    by = np.clip(out_starts[:, 1] // yslab, 0, ny - 1)
    bx = np.clip(out_starts[:, 2] // xslab, 0, nx - 1)
    flat = by * nx + bx
    max_count = max(int((flat == d).sum()) for d in range(ny * nx))
    per_dev = max(-(-max_count // batch_size) * batch_size, batch_size)

    dev_in = np.zeros((ny, nx, per_dev, 3), dtype=np.int32)
    dev_valid = np.zeros((ny, nx, per_dev), dtype=np.float32)
    src_index = np.zeros(n_ref, dtype=np.int32)
    global_index = np.full((ny, nx, per_dev), -1, dtype=np.int32)
    counts = np.zeros((ny, nx), dtype=np.int32)
    for dy in range(ny):
        for dx in range(nx):
            idx = np.nonzero(flat == dy * nx + dx)[0]
            k = idx.size
            local = in_starts[idx].copy()
            # both extended slabs start at global (dy*yslab - hl_y,
            # dx*xslab - hl_x); z is never sharded
            local[:, 1] -= dy * yslab - halo_left_y
            local[:, 2] -= dx * xslab - halo_left_x
            dev_in[dy, dx, :k] = local
            dev_valid[dy, dx, :k] = valid[idx]
            global_index[dy, dx, :k] = idx.astype(np.int32)
            counts[dy, dx] = k
            src_index[idx] = (dy * nx + dx) * per_dev + np.arange(
                k, dtype=np.int32
            )
    return _Partition(dev_in, dev_valid, src_index, out_starts, valid,
                      per_dev, global_index, counts)


# ---------------------------------------------------------------------------
# sharded-replay plans (ISSUE 19)
# ---------------------------------------------------------------------------

class _ReplayPlan(NamedTuple):
    """Host-side plan for the spatial kinds' sharded blend replay: which
    weighted windows each chip forwards to its +y / +x neighbour (the
    fringe — windows whose footprint crosses the slab boundary; since
    ``slab >= pout`` a window spans at most two slabs per axis, so one
    forward hop per phase suffices, corners two-hopping y-then-x exactly
    like the input halos) and, per chip, the global-order replay index
    over the pool ``own ++ recv_y ++ recv_x ++ zeros-row``. Sorting by
    global row restores the reference accumulation order restricted to
    this slab's covering windows — the bitwise argument in the module
    docstring. Filler slots select the zeros row and a start inside the
    cropped top margin, so they add nothing (not even a signed zero) to
    any live voxel."""

    fringe_y: np.ndarray   # [ny, nx, Fy] int32 into own rows (fwd in y)
    fringe_x: np.ndarray   # [ny, nx, Fx] int32 into own++recv_y (fwd in x)
    index: np.ndarray      # [ny, nx, R] int32 into own++recv_y++recv_x++zero
    starts: np.ndarray     # [ny, nx, R, 3] int32, slab-frame coords
    valid: np.ndarray      # [ny, nx, R] float32
    margin_y: int
    margin_x: int
    fy: int
    fx: int
    r: int


def replay_plan_spatial(
    part: _Partition,
    pout: Triple,
    shape: Tuple[int, int],
    yslab: int,
    xslab: int,
    batch_size: int,
) -> _ReplayPlan:
    """Build the sharded-replay plan for a spatial partition. All pool
    bookkeeping is host-side numpy over the same bucket metadata
    ``partition_for_mesh`` produced, so the device program is pure
    ``take`` + ``ppermute`` + the shared accumulation step."""
    ny, nx = shape
    py, px = pout[1], pout[2]
    m_y = py if ny > 1 else 0
    m_x = px if nx > 1 else 0
    out_starts = part.out_starts
    ref_valid = part.valid
    per_dev = part.per_dev

    # (global_row, pool_index) per chip, in global (ascending) order
    own = [[[(int(g), j) for j, g in enumerate(
        part.global_index[dy, dx, : int(part.counts[dy, dx])])]
        for dx in range(nx)] for dy in range(ny)]

    # y-phase fringe: own rows whose window crosses the +y slab boundary
    fringe_y_meta = [[[
        (g, j) for g, j in own[dy][dx]
        if out_starts[g, 1] + py > (dy + 1) * yslab
    ] for dx in range(nx)] for dy in range(ny)]
    fy = max(
        (len(fringe_y_meta[dy][dx])
         for dy in range(ny - 1) for dx in range(nx)),
        default=0,
    ) if ny > 1 else 0

    # pool after the y phase: own ++ recv_y (recv slot k holds the
    # sender's k-th fringe row)
    pool_y = [[list(own[dy][dx]) for dx in range(nx)] for dy in range(ny)]
    if fy:
        for dy in range(1, ny):
            for dx in range(nx):
                pool_y[dy][dx] += [
                    (g, per_dev + k)
                    for k, (g, _) in enumerate(fringe_y_meta[dy - 1][dx])
                ]

    # x-phase fringe: pool rows (own AND y-received corners) crossing +x
    fringe_x_meta = [[[
        (g, p) for g, p in pool_y[dy][dx]
        if out_starts[g, 2] + px > (dx + 1) * xslab
    ] for dx in range(nx)] for dy in range(ny)]
    fx = max(
        (len(fringe_x_meta[dy][dx])
         for dy in range(ny) for dx in range(nx - 1)),
        default=0,
    ) if nx > 1 else 0

    pool = [[list(pool_y[dy][dx]) for dx in range(nx)] for dy in range(ny)]
    if fx:
        for dy in range(ny):
            for dx in range(1, nx):
                pool[dy][dx] += [
                    (g, per_dev + fy + k)
                    for k, (g, _) in enumerate(fringe_x_meta[dy][dx - 1])
                ]

    r_need = max(len(pool[dy][dx]) for dy in range(ny) for dx in range(nx))
    r = max(-(-max(r_need, 1) // batch_size) * batch_size, batch_size)
    zero_row = per_dev + fy + fx
    filler_start = (
        (0, m_y + yslab, 0) if ny > 1 else (0, 0, m_x + xslab)
    )

    fringe_y = np.zeros((ny, nx, fy), dtype=np.int32)
    fringe_x = np.zeros((ny, nx, fx), dtype=np.int32)
    index = np.full((ny, nx, r), zero_row, dtype=np.int32)
    starts = np.tile(
        np.asarray(filler_start, dtype=np.int32), (ny, nx, r, 1)
    )
    valid = np.zeros((ny, nx, r), dtype=np.float32)
    for dy in range(ny):
        for dx in range(nx):
            for k, (_, j) in enumerate(fringe_y_meta[dy][dx][:fy]):
                fringe_y[dy, dx, k] = j
            for k, (_, p) in enumerate(fringe_x_meta[dy][dx][:fx]):
                fringe_x[dy, dx, k] = p
            rows = sorted(pool[dy][dx])  # by global row: reference order
            for i, (g, p) in enumerate(rows):
                index[dy, dx, i] = p
                starts[dy, dx, i] = (
                    out_starts[g, 0],
                    out_starts[g, 1] - dy * yslab + m_y,
                    out_starts[g, 2] - dx * xslab + m_x,
                )
                valid[dy, dx, i] = ref_valid[g]
    return _ReplayPlan(fringe_y, fringe_x, index, starts, valid,
                       m_y, m_x, fy, fx, r)


class _ReplayPlan1D(NamedTuple):
    """Sharded-replay plan for the kinds that hold the FULL global
    weighted stack on every chip after reassembly (``data``'s tiled
    all_gather, ``pipeline``'s drain collect): no fringe exchange is
    needed — each chip simply takes, in global order, the rows whose
    window intersects its y output slab and replays them into a
    slab+margin buffer. A window may intersect several slabs (the 1D
    slab can be thinner than the output patch) and is replayed on each;
    every slab voxel still folds exactly its covering windows in
    reference order."""

    index: np.ndarray   # [n_dev, R] int32 into stack ++ zeros-row
    starts: np.ndarray  # [n_dev, R, 3] int32, slab-frame coords
    valid: np.ndarray   # [n_dev, R] float32
    margin: int
    r: int


def replay_plan_1d(
    out_starts: np.ndarray,
    ref_valid: np.ndarray,
    n_ref: int,
    pool_rows: int,
    pout: Triple,
    n_dev: int,
    slab: int,
    batch_size: int,
) -> _ReplayPlan1D:
    py = pout[1]
    margin = py
    rows = [[] for _ in range(n_dev)]
    for g in range(n_ref):
        y = int(out_starts[g, 1])
        # the window [y, y+py) intersects slabs y//slab .. (y+py-1)//slab
        d_lo = min(n_dev - 1, y // slab)
        d_hi = min(n_dev - 1, (y + py - 1) // slab)
        for d in range(d_lo, d_hi + 1):
            if y + py > d * slab and y < (d + 1) * slab:
                rows[d].append(g)
    r_need = max(len(rs) for rs in rows)
    r = max(-(-max(r_need, 1) // batch_size) * batch_size, batch_size)
    index = np.full((n_dev, r), pool_rows, dtype=np.int32)
    starts = np.tile(
        np.asarray((0, margin + slab, 0), dtype=np.int32), (n_dev, r, 1)
    )
    valid = np.zeros((n_dev, r), dtype=np.float32)
    for d in range(n_dev):
        for i, g in enumerate(rows[d]):
            index[d, i] = g
            starts[d, i] = (
                out_starts[g, 0],
                out_starts[g, 1] - d * slab + margin,
                out_starts[g, 2],
            )
            valid[d, i] = ref_valid[g]
    return _ReplayPlan1D(index, starts, valid, margin, r)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

class ShardedEngine:
    """One mesh-aware sharded inference engine for every mesh kind.

    Construct via :meth:`for_inferencer` (the production seam: shares the
    Inferencer's :class:`ProgramCache`, forward — including TTA — and
    result dtype) or directly from a raw ``engines.Engine`` for
    standalone use (bench, legacy wrappers)."""

    def __init__(
        self,
        forward,
        num_input_channels: int,
        num_output_channels: int,
        input_patch_size: Triple,
        output_patch_size: Triple,
        batch_size: int,
        spec: MeshSpec,
        programs: Optional[ProgramCache] = None,
        out_dtype: str = "float32",
        devices=None,
        stage_entry=None,
        stage_bodies=None,
        stage_tail=None,
        precision_tag: str = "",
    ):
        if spec.kind == "single":
            raise ValueError("single spec needs no ShardedEngine "
                             "(the kill switch path)")
        self.forward = forward
        self.num_input_channels = num_input_channels
        self.num_output_channels = num_output_channels
        self.input_patch_size = tuple(input_patch_size)
        self.output_patch_size = tuple(output_patch_size)
        self.batch_size = int(batch_size)
        self.spec = spec
        self.out_dtype = out_dtype
        self.programs = programs if programs is not None else ProgramCache(
            label="sharded"
        )
        self._devices = devices
        self._mesh = None
        self._dispatches = 0  # readiness-probe sampling clock
        # the stage protocol (parallel/pipeline.py): precision-wrapped
        # entry cast + bodies + tail for the pipeline kind; None means
        # the forward is opaque and pipeline meshes fail loudly
        self.stage_entry = stage_entry
        self.stage_bodies = stage_bodies
        self.stage_tail = stage_tail
        # the resolved forward precision as a key component (ISSUE 19:
        # precision composes with the pipeline/gather/kernel tags in
        # every shard program key); "" is the float32 default
        self.precision_tag = precision_tag

    # ------------------------------------------------------------------
    @classmethod
    def for_inferencer(cls, inferencer, spec: MeshSpec,
                       devices=None) -> "ShardedEngine":
        from chunkflow_tpu.inference.precision import (
            precision_tag,
            wrap_stages,
        )

        # TTA wraps the forward in an 8-variant scan the stage protocol
        # cannot split; a staged engine under augment simply reports no
        # stages (the pipeline kind then refuses loudly)
        if getattr(inferencer, "augment", False):
            entry = bodies = tail = None
        else:
            entry, bodies, tail = wrap_stages(
                getattr(inferencer.engine, "stage_bodies", None),
                getattr(inferencer.engine, "stage_tail", None),
                inferencer.precision,
            )
        return cls(
            inferencer._forward,
            inferencer.num_input_channels,
            inferencer.num_output_channels,
            tuple(inferencer.input_patch_size),
            tuple(inferencer.output_patch_size),
            inferencer.batch_size,
            spec,
            programs=inferencer._programs,
            out_dtype=inferencer.output_dtype,
            devices=devices,
            stage_entry=entry,
            stage_bodies=bodies,
            stage_tail=tail,
            precision_tag=precision_tag(inferencer.precision),
        )

    # ------------------------------------------------------------------
    def mesh(self):
        """The jax Mesh for this spec over the (local) devices. The data
        kind uses one ``('data',)`` axis; spatial kinds a ``('y', 'x')``
        grid (``nx == 1`` keeps the axis — exchange phases skip it
        statically)."""
        if self._mesh is not None:
            return self._mesh
        import jax
        from jax.sharding import Mesh

        devices = self._devices
        if devices is None:
            devices = jax.local_devices()
        devices = np.asarray(devices).reshape(-1)
        need = self.spec.n_devices
        if devices.size < need:
            raise ValueError(
                f"mesh spec {self.spec.describe()!r} needs {need} devices, "
                f"only {devices.size} available"
            )
        devices = devices[:need]
        if self.spec.kind == "data":
            self._mesh = Mesh(devices, ("data",))
        elif self.spec.kind == "pipeline":
            self._mesh = Mesh(devices, ("pipe",))
        else:
            ny, nx = self.spec.shape
            # axis-order: devices laid out row-major (y outer, x inner)
            self._mesh = Mesh(devices.reshape(ny, nx), ("y", "x"))
        return self._mesh

    # ------------------------------------------------------------------
    def _make_blend_parts(self):
        """The pieces shared with the single-device program: bump map,
        the per-batch accumulation step (same kernel selection —
        XLA scatter or the fused Pallas kernel — same dnums, same
        grouping: ops.blend.make_accumulate, the weighted flavor since
        the all_gathered stacks already carry bump*valid) and
        normalize."""
        from chunkflow_tpu.inference.bump import bump_const
        from chunkflow_tpu.ops.blend import make_accumulate, normalize_blend

        pout = self.output_patch_size
        bump = bump_const(pout)
        _, accumulate_weighted, pad_y, pad_x = make_accumulate(pout, bump)
        return bump, accumulate_weighted, pad_y, pad_x, normalize_blend

    def _make_front(self):
        """The device-resident front half shared with the single-device
        program (ops/pallas_gather.make_gather, ISSUE 15): ``prepare``
        converts the RAW chip-local chunk (or slab) to float32 on the
        XLA legs / alignment-pads it for the Pallas kernel, ``gather``
        slices one batch of patch windows. Resolved at build time —
        callers fold ``gather_key()`` into the program key so a
        ``CHUNKFLOW_GATHER`` flip rebuilds."""
        from chunkflow_tpu.ops.pallas_gather import make_gather

        return make_gather(self.num_input_channels, self.input_patch_size)

    def _forward_scan(self, bump, prepare, gather):
        """Per-device gather+forward over local patch batches. Returns
        ``scan_stack(chunk_like, in_starts, valid, params) -> [P, co,
        *pout]`` computing ``forward * bump * valid`` in batches of B —
        the identical per-row math (and per-batch shape) of the
        single-device program's ``forward_batch``. ``chunk_like`` is the
        RAW chip-local chunk: ``prepare`` runs here, AFTER any halo
        exchange, so exchanges ship the narrow dtype."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        B = self.batch_size
        co = self.num_output_channels
        pout = self.output_patch_size
        forward = self.forward

        def scan_stack(chunk_raw, in_starts, valid, params):
            n_local = in_starts.shape[0]
            chunk_like = prepare(chunk_raw)

            def fwd_batch(b):
                i0 = b * B
                s_in = lax.dynamic_slice(in_starts, (i0, 0), (B, 3))
                v = lax.dynamic_slice(valid, (i0,), (B,))
                patches = gather(chunk_like, s_in)
                with jax.named_scope("forward"):
                    preds = forward(params, patches)
                with jax.named_scope("accumulate"):
                    return (preds * bump[None, None]
                            * v[:, None, None, None, None])

            _, stack = lax.scan(
                lambda c, b: (c, fwd_batch(b)), None,
                jnp.arange(n_local // B),
            )
            # [n_batches, B, co, *pout(zyx)] -> [n_local, co, *pout(zyx)]:
            # flattens the scan axis into the batch axis, patch order
            # preserved; spatial axes untouched
            return stack.reshape((n_local, co) + pout)

        return scan_stack

    def _replay(self, accumulate, bump, zyx, pad_y, pad_x, n_ref,
                normalize_blend):
        """The reference accumulation, replayed verbatim: scan batches of
        B over the global-order weighted stack and accumulate with the
        shared (weighted-flavor) step — XLA scatter-add or the fused
        Pallas kernel, whichever ``make_accumulate`` selected — then
        normalize. Runs replicated on every chip (outputs are identical
        by construction)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        B = self.batch_size
        co = self.num_output_channels
        pout = self.output_patch_size
        zyx_buf = (zyx[0], zyx[1] + pad_y, zyx[2] + pad_x)
        num_batches = n_ref // B
        out_dtype = self.out_dtype

        def replay(weighted, valid, out_starts):
            with jax.named_scope("accumulate"):
                out0 = jnp.zeros((co,) + zyx_buf, dtype=jnp.float32)
                w0 = jnp.zeros(zyx_buf, dtype=jnp.float32)

            def step(carry, b):
                out, weight = carry
                i0 = b * B
                w = lax.dynamic_slice(
                    weighted, (i0, 0, 0, 0, 0), (B, co) + pout)
                v = lax.dynamic_slice(valid, (i0,), (B,))
                s_out = lax.dynamic_slice(out_starts, (i0, 0), (B, 3))
                out, weight = accumulate(out, weight, w, v, s_out)
                return (out, weight), None

            (out, weight), _ = lax.scan(
                step, (out0, w0), jnp.arange(num_batches)
            )
            if pad_y or pad_x:
                with jax.named_scope("accumulate"):
                    out = out[:, :, : zyx[1], : zyx[2]]
                    weight = weight[:, : zyx[1], : zyx[2]]
            return normalize_blend(out, weight, out_dtype)

        return replay

    def _slab_replay(self, accumulate, z, slab_y, slab_x, m_y, m_x,
                     pad_y, pad_x, n_rows, normalize):
        """The sharded-replay flavor of :meth:`_replay` (ISSUE 19): the
        same scan-over-batches accumulation step, into a slab+margin
        buffer instead of the full chunk. ``m_y``/``m_x`` margins hold
        the in-slab part of boundary-crossing windows on the low side
        and keep every replayed window in bounds on the high side (XLA
        clamps out-of-bounds scatter starts, which would corrupt live
        voxels — the margin makes clamping unreachable, including for
        the filler rows parked at ``(0, m_y + slab_y, 0)``). The crop
        back to the bare slab drops the margins and the Pallas
        alignment pad together, then normalizes per slab (elementwise —
        exact)."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        B = self.batch_size
        co = self.num_output_channels
        pout = self.output_patch_size
        buf = (z, slab_y + 2 * m_y + pad_y, slab_x + 2 * m_x + pad_x)
        num_batches = n_rows // B
        out_dtype = self.out_dtype

        def replay(weighted, valid, starts):
            with jax.named_scope("accumulate"):
                out0 = jnp.zeros((co,) + buf, dtype=jnp.float32)
                w0 = jnp.zeros(buf, dtype=jnp.float32)

            def step(carry, b):
                out, weight = carry
                i0 = b * B
                w = lax.dynamic_slice(
                    weighted, (i0, 0, 0, 0, 0), (B, co) + pout)
                v = lax.dynamic_slice(valid, (i0,), (B,))
                s_out = lax.dynamic_slice(starts, (i0, 0), (B, 3))
                out, weight = accumulate(out, weight, w, v, s_out)
                return (out, weight), None

            (out, weight), _ = lax.scan(
                step, (out0, w0), jnp.arange(num_batches)
            )
            with jax.named_scope("accumulate"):
                out = out[:, :, m_y:m_y + slab_y, m_x:m_x + slab_x]
                weight = weight[:, m_y:m_y + slab_y, m_x:m_x + slab_x]
            return normalize(out, weight, out_dtype)

        return replay

    @staticmethod
    def _append_zero_row(pool):
        """Pool ++ one all-zeros row — the row every filler replay slot
        selects. Filler windows land entirely inside the cropped margin,
        so they touch no live voxel (not even with a signed zero)."""
        import jax.numpy as jnp

        return jnp.concatenate(
            [pool, jnp.zeros((1,) + pool.shape[1:], pool.dtype)], axis=0
        )

    # ------------------------------------------------------------------
    def _build_data_program(self, chunk_shape, n_pad_g, n_ref,
                            plan: Optional[_ReplayPlan1D], slab: int):
        """Patch-parallel program: chunk replicated, the padded global
        patch list contiguously sharded over 'data', forward stacks
        all_gathered back into global order (contiguous shards ⇒ no
        permutation). ``plan`` selects the replay: the slab-sharded
        default (each chip takes, in global order, the gathered rows
        whose window intersects its y output slab and accumulates into
        a slab+margin buffer; output stays sharded over 'data') or the
        historical replicated full-chunk replay (``plan=None``,
        CHUNKFLOW_SHARD_REPLAY=replicated)."""
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = self.mesh()
        n_dev = mesh.devices.size
        bump, accumulate, pad_y, pad_x, normalize = self._make_blend_parts()
        prepare, gather = self._make_front()
        scan_stack = self._forward_scan(bump, prepare, gather)
        assert n_pad_g % n_dev == 0

        n_local = n_pad_g // n_dev
        z, x = chunk_shape[1], chunk_shape[3]

        if plan is None:
            replay = self._replay(accumulate, bump, chunk_shape[1:],
                                  pad_y, pad_x, n_ref, normalize)
        else:
            replay = self._slab_replay(accumulate, z, slab, x,
                                       plan.margin, 0, pad_y, pad_x,
                                       plan.r, normalize)

        def stack_global(chunk, in_starts, valid, params):
            # in_starts arrives as this chip's contiguous shard
            # [n_local, 3]; chunk/valid replicated — the replay needs
            # the GLOBAL validity, so each chip slices its own
            # contiguous rows by mesh position instead
            idx = lax.axis_index("data")
            local_valid = lax.dynamic_slice(
                valid, (idx * n_local,), (n_local,)
            )
            stack = scan_stack(chunk, in_starts, local_valid, params)
            # exact data movement: tiled all_gather reassembles the
            # stacks in mesh-axis order == global patch order
            with jax.named_scope("collective"):
                return lax.all_gather(stack, "data", axis=0, tiled=True)

        if plan is None:
            def device_fn(chunk, in_starts, out_starts, valid, params):
                gathered = stack_global(chunk, in_starts, valid, params)
                return replay(gathered[:n_ref], valid[:n_ref],
                              out_starts[:n_ref])

            in_specs = (P(), P("data"), P(), P(), P())
            out_specs = P()
        else:
            def device_fn(chunk, in_starts, valid,
                          rp_index, rp_starts, rp_valid, params):
                import jax.numpy as jnp

                gathered = stack_global(chunk, in_starts, valid, params)
                with jax.named_scope("collective"):
                    pool = self._append_zero_row(gathered)
                    weighted = jnp.take(pool, rp_index[0], axis=0)
                return replay(weighted, rp_valid[0], rp_starts[0])

            in_specs = (P(), P("data"), P(),
                        P("data"), P("data"), P("data"), P())
            out_specs = P(None, None, "data")

        sharded = shard_map(
            device_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )

        # chunk is donated (GL005): dead after the call, may be aliased
        # into the blend buffers — callers hand over a buffer they own
        @partial(jax.jit, donate_argnums=(0,))
        def program(chunk, *rest):
            return sharded(chunk, *rest)

        return program

    def _build_spatial_program(self, chunk_shape, geometry, per_dev,
                               n_ref, plan: Optional[_ReplayPlan]):
        """Spatially-sharded program: the chunk lives sharded over the
        (y, x) mesh, input halos ride ppermute (y phase then x phase, so
        corner strips arrive without diagonal sends), each chip forwards
        the patches whose output start falls in its slab. The replay is
        where the two modes diverge:

        - ``plan`` set (the sharded default): NO full-stack all_gather.
          Each chip ppermutes only its fringe — the whole weighted
          windows that cross the +y / +x slab boundary (y phase then x
          phase; corner windows two-hop exactly like the input halos) —
          then replays ``own ∪ received`` in global order into a
          slab+margin buffer and normalizes its slab. The output stays
          sharded over (y, x).
        - ``plan=None`` (CHUNKFLOW_SHARD_REPLAY=replicated): stacks
          all_gather + take back into global order, reference replay
          replicated on every chip."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from jax import shard_map

        mesh = self.mesh()
        ny, nx = self.spec.shape
        (yslab, hl_y, hr_y, _), (xslab, hl_x, hr_x, _) = geometry
        bump, accumulate, pad_y, pad_x, normalize = self._make_blend_parts()
        prepare, gather = self._make_front()
        scan_stack = self._forward_scan(bump, prepare, gather)
        if plan is None:
            replay = self._replay(accumulate, bump, chunk_shape[1:],
                                  pad_y, pad_x, n_ref, normalize)
        else:
            replay = self._slab_replay(
                accumulate, chunk_shape[1], yslab, xslab,
                plan.margin_y, plan.margin_x, pad_y, pad_x, plan.r,
                normalize,
            )
        fwd_y = [(i, i + 1) for i in range(ny - 1)]
        bwd_y = [(i + 1, i) for i in range(ny - 1)]
        fwd_x = [(i, i + 1) for i in range(nx - 1)]
        bwd_x = [(i + 1, i) for i in range(nx - 1)]

        def halo_exchange(ext):
            # ---- 1a. y halo exchange (skipped statically at ny=1) ----
            if ny > 1:
                pieces = []
                if hl_y:
                    pieces.append(lax.ppermute(
                        ext[:, :, yslab - hl_y:, :], "y", fwd_y))
                pieces.append(ext)
                if hr_y:
                    pieces.append(lax.ppermute(
                        ext[:, :, :hr_y, :], "y", bwd_y))
                ext = lax.concatenate(pieces, dimension=2)
            # ---- 1b. x halo exchange of the y-extended block ----
            if nx > 1:
                pieces = []
                if hl_x:
                    pieces.append(lax.ppermute(
                        ext[:, :, :, xslab - hl_x:], "x", fwd_x))
                pieces.append(ext)
                if hr_x:
                    pieces.append(lax.ppermute(
                        ext[:, :, :, :hr_x], "x", bwd_x))
                ext = lax.concatenate(pieces, dimension=3)
            return ext

        def local_stack(chunk_slab, in_starts, local_valid, params):
            with jax.named_scope("collective"):
                ext = halo_exchange(chunk_slab)

            # ---- 2. local gather + forward over the extended slab ----
            return scan_stack(ext, in_starts, local_valid, params)

        if plan is None:
            def device_fn(chunk_slab, dev_in, dev_valid, src_index,
                          out_starts, valid, params):
                # chunk_slab: [C, Z, yslab, xslab]; dev_in/dev_valid
                # carry two leading sharded axes of size 1 each
                stack = local_stack(chunk_slab, dev_in[0, 0],
                                    dev_valid[0, 0], params)

                # ---- 3. global reassembly: x-major then y-major gather
                # matches the row-major device layout; take() restores
                # global patch order (exact data movement) ----
                with jax.named_scope("collective"):
                    gathered = stack
                    if nx > 1:
                        gathered = lax.all_gather(gathered, "x", axis=0,
                                                  tiled=True)
                    if ny > 1:
                        gathered = lax.all_gather(gathered, "y", axis=0,
                                                  tiled=True)
                    weighted = jnp.take(gathered, src_index, axis=0)
                return replay(weighted, valid, out_starts)

            in_specs = (
                P(None, None, "y", "x"),
                P("y", "x"),
                P("y", "x"),
                P(),
                P(),
                P(),
                P(),
            )
            out_specs = P()
        else:
            fy, fx = plan.fy, plan.fx

            def device_fn(chunk_slab, dev_in, dev_valid, fr_y, fr_x,
                          rp_index, rp_starts, rp_valid, params):
                stack = local_stack(chunk_slab, dev_in[0, 0],
                                    dev_valid[0, 0], params)

                # ---- 3. fringe exchange: whole weighted windows that
                # cross the +y (then +x) slab boundary ride ppermute;
                # the pool order own ++ recv_y ++ recv_x ++ zeros-row
                # matches the host plan's index space exactly ----
                with jax.named_scope("collective"):
                    pool = stack
                    if ny > 1 and fy:
                        recv_y = lax.ppermute(
                            jnp.take(stack, fr_y[0, 0], axis=0), "y",
                            fwd_y)
                        pool = jnp.concatenate([pool, recv_y], axis=0)
                    if nx > 1 and fx:
                        recv_x = lax.ppermute(
                            jnp.take(pool, fr_x[0, 0], axis=0), "x",
                            fwd_x)
                        pool = jnp.concatenate([pool, recv_x], axis=0)
                    pool = self._append_zero_row(pool)

                    # ---- 4. slab replay in global order ----
                    weighted = jnp.take(pool, rp_index[0, 0], axis=0)
                return replay(weighted, rp_valid[0, 0], rp_starts[0, 0])

            in_specs = (
                P(None, None, "y", "x"),
                P("y", "x"),
                P("y", "x"),
                P("y", "x"),
                P("y", "x"),
                P("y", "x"),
                P("y", "x"),
                P("y", "x"),
                P(),
            )
            out_specs = P(None, None, "y", "x")

        sharded = shard_map(
            device_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )

        # chunk is donated (GL005): dead after the call, may be aliased
        # into the blend buffers — callers hand over a buffer they own
        @partial(jax.jit, donate_argnums=(0,))
        def program(chunk, *rest):
            return sharded(chunk, *rest)

        return program

    # ------------------------------------------------------------------
    def _build_pipeline_program(self, chunk_shape, n_ref,
                                plan: Optional[_ReplayPlan1D], slab: int):
        """Pipeline-parallel program (ISSUE 19): the convnet's stage
        groups live one per chip of the ``pipeline=S`` mesh; patch
        micro-batches of B stream through a ``ppermute`` activation ring
        for ``T + S - 1`` ticks (T micro-batches, S-1 drain ticks). Each
        tick, stage 0 gathers + entry-casts the next micro-batch while
        every other chip consumes the activation its predecessor sent —
        the double-buffered handoff: compute on tick t overlaps the
        transfer produced on tick t-1. The last stage's tail output
        (masked to the ticks where a real micro-batch completes, i.e.
        ``t >= S-1``) accumulates into the weighted output stack, which
        the drain collect (all_gather over 'pipe', last stage's copy)
        reassembles in global patch order — bitwise the non-pipelined
        stack because ``apply == tail ∘ bodies`` holds bitwise (the
        stage protocol, parallel/pipeline.py). Replay then runs
        slab-sharded over 'pipe' (``plan``) or replicated."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from chunkflow_tpu.parallel import pipeline as pipe_mod
        from jax import shard_map

        pipe_mod.require_stages(self.stage_bodies, self.stage_tail,
                                "CHUNKFLOW_MESH=" + self.spec.describe())
        mesh = self.mesh()
        S = self.spec.shape[0]
        B = self.batch_size
        ci = self.num_input_channels
        co = self.num_output_channels
        pin = self.input_patch_size
        pout = self.output_patch_size
        T = n_ref // B
        entry = self.stage_entry
        tail = self.stage_tail
        stage_fns = pipe_mod.stage_groups(self.stage_bodies, S)
        bump, accumulate, pad_y, pad_x, normalize = self._make_blend_parts()
        prepare, gather = self._make_front()
        fwd = [(i, i + 1) for i in range(S - 1)]
        # the ring carries ONE uniform activation buffer; its dtype is
        # whatever the entry cast produces (the precision boundary —
        # inference/precision.wrap_stages)
        act_sd = jax.eval_shape(
            entry, jax.ShapeDtypeStruct((B, ci) + pin, jnp.float32)
        )
        if plan is None:
            replay = self._replay(accumulate, bump, chunk_shape[1:],
                                  pad_y, pad_x, n_ref, normalize)
        else:
            replay = self._slab_replay(
                accumulate, chunk_shape[1], slab, chunk_shape[3],
                plan.margin, 0, pad_y, pad_x, plan.r, normalize,
            )

        def weighted_stack(chunk, in_starts, valid, params):
            s = lax.axis_index("pipe")
            chunk_like = prepare(chunk)
            act0 = jnp.zeros(act_sd.shape, act_sd.dtype)
            outstack0 = jnp.zeros((n_ref, co) + pout, jnp.float32)

            def tick(carry, t):
                act, outstack = carry
                # predecessor's activation from the PREVIOUS tick — the
                # recv overlaps this tick's stage compute
                with jax.named_scope("collective"):
                    recv = lax.ppermute(act, "pipe", fwd)
                # stage 0 feeds the next micro-batch (clamped during
                # drain: the repeats are masked out below)
                i0 = jnp.clip(t, 0, T - 1) * B
                s_in = lax.dynamic_slice(in_starts, (i0, 0), (B, 3))
                patches = gather(chunk_like, s_in)
                with jax.named_scope("forward"):
                    x0 = entry(patches)
                    x = jnp.where(s == 0, x0, recv)
                    new_act = lax.switch(s, stage_fns, params, x)
                    # every chip runs the tail SPMD-uniformly; only the
                    # last stage's (post-warmup) result is kept
                    out = tail(params, new_act)
                mb_out = jnp.clip(t - (S - 1), 0, T - 1)
                o0 = mb_out * B
                with jax.named_scope("accumulate"):
                    v = lax.dynamic_slice(valid, (o0,), (B,))
                    weighted = (out * bump[None, None]
                                * v[:, None, None, None, None])
                    cur = lax.dynamic_slice(
                        outstack, (o0, 0, 0, 0, 0), (B, co) + pout)
                    keep = jnp.logical_and(s == S - 1, t >= S - 1)
                    outstack = lax.dynamic_update_slice(
                        outstack, jnp.where(keep, weighted, cur),
                        (o0, 0, 0, 0, 0))
                return (new_act, outstack), None

            (_, outstack), _ = lax.scan(
                tick, (act0, outstack0), jnp.arange(T + S - 1)
            )
            # drain collect: the last stage holds the only real stack
            with jax.named_scope("collective"):
                gathered = lax.all_gather(outstack, "pipe", axis=0)
                return gathered[S - 1]

        if plan is None:
            def device_fn(chunk, in_starts, out_starts, valid, params):
                stack = weighted_stack(chunk, in_starts, valid, params)
                return replay(stack, valid, out_starts)

            in_specs = (P(), P(), P(), P(), P())
            out_specs = P()
        else:
            def device_fn(chunk, in_starts, valid,
                          rp_index, rp_starts, rp_valid, params):
                stack = weighted_stack(chunk, in_starts, valid, params)
                with jax.named_scope("collective"):
                    pool = self._append_zero_row(stack)
                    weighted = jnp.take(pool, rp_index[0], axis=0)
                return replay(weighted, rp_valid[0], rp_starts[0])

            in_specs = (P(), P(), P(),
                        P("pipe"), P("pipe"), P("pipe"), P())
            out_specs = P(None, None, "pipe")

        sharded = shard_map(
            device_fn,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )

        # chunk is donated (GL005): dead after the call, may be aliased
        # into the blend buffers — callers hand over a buffer they own
        @partial(jax.jit, donate_argnums=(0,))
        def program(chunk, *rest):
            return sharded(chunk, *rest)

        return program

    # ------------------------------------------------------------------
    def serve_forward_program(self):
        """The serving packer's forward program over the chips of this
        mesh. Data/spatial kinds: a packed ``[B * n_chips, ci, *pin]``
        batch splits into per-chip ``[B, ...]`` rows over a 1D
        ('data',) layout (the packed batch has no spatial structure to
        shard), each chip computes ``forward * bump * valid`` for its
        rows — the same per-batch shape as the fused program, so
        per-row bitwise equality holds as everywhere else. The
        ``pipeline`` kind instead streams the packed batch through the
        staged ring (ISSUE 19): n_chips micro-batches of B cross the
        n_chips stages in ``2·n_chips - 1`` ticks, the same row
        grouping — and ``apply == tail ∘ bodies`` bitwise — so the
        serving results are bit-identical across kinds too."""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P

        from jax import shard_map

        n_chips = self.spec.n_devices
        forward = self.forward
        pipelined = self.spec.kind == "pipeline"

        def serve_devices():
            devices = self._devices
            if devices is None:
                devices = jax.local_devices()
            return np.asarray(devices).reshape(-1)[:n_chips]

        def build():
            from chunkflow_tpu.inference.bump import bump_const

            mesh = Mesh(serve_devices(), ("data",))
            bump = bump_const(self.output_patch_size)

            def device_fn(patches, valid, params):
                # the same weighting expression, in the same order, as
                # the fused program's forward_batch (ops/blend.py)
                with jax.named_scope("forward"):
                    preds = forward(params, patches)
                with jax.named_scope("accumulate"):
                    return (preds * bump[None, None]
                            * valid[:, None, None, None, None])

            sharded = shard_map(
                device_fn,
                mesh=mesh,
                in_specs=(P("data"), P("data"), P()),
                out_specs=P("data"),
                check_vma=False,
            )

            # the packed batch buffer is packer-owned and dead after the
            # call (GL005): donate it into the program
            return jax.jit(sharded, donate_argnums=(0,))

        def build_pipelined():
            import jax.numpy as jnp
            from jax import lax

            from chunkflow_tpu.inference.bump import bump_const
            from chunkflow_tpu.parallel import pipeline as pipe_mod

            pipe_mod.require_stages(
                self.stage_bodies, self.stage_tail,
                "serving over CHUNKFLOW_MESH=" + self.spec.describe())
            mesh = Mesh(serve_devices(), ("pipe",))
            bump = bump_const(self.output_patch_size)
            S = n_chips
            B = self.batch_size
            ci = self.num_input_channels
            co = self.num_output_channels
            pin = self.input_patch_size
            pout = self.output_patch_size
            entry = self.stage_entry
            tail = self.stage_tail
            stage_fns = pipe_mod.stage_groups(self.stage_bodies, S)
            fwd = [(i, i + 1) for i in range(S - 1)]
            act_sd = jax.eval_shape(
                entry, jax.ShapeDtypeStruct((B, ci) + pin, jnp.float32)
            )

            def device_fn(patches, valid, params):
                # normally T == n_chips (one B-row micro-batch per
                # chip), but a kill-switch race can widen the packed
                # batch — jit retraces per shape, so derive T here
                T = patches.shape[0] // B
                s = lax.axis_index("pipe")
                act0 = jnp.zeros(act_sd.shape, act_sd.dtype)
                outstack0 = jnp.zeros((T * B, co) + pout, jnp.float32)

                def tick(carry, t):
                    act, outstack = carry
                    with jax.named_scope("collective"):
                        recv = lax.ppermute(act, "pipe", fwd)
                    i0 = jnp.clip(t, 0, T - 1) * B
                    with jax.named_scope("forward"):
                        x0 = entry(lax.dynamic_slice(
                            patches, (i0, 0, 0, 0, 0), (B, ci) + pin))
                        x = jnp.where(s == 0, x0, recv)
                        new_act = lax.switch(s, stage_fns, params, x)
                        out = tail(params, new_act)
                    o0 = jnp.clip(t - (S - 1), 0, T - 1) * B
                    with jax.named_scope("accumulate"):
                        v = lax.dynamic_slice(valid, (o0,), (B,))
                        weighted = (out * bump[None, None]
                                    * v[:, None, None, None, None])
                        cur = lax.dynamic_slice(
                            outstack, (o0, 0, 0, 0, 0), (B, co) + pout)
                        keep = jnp.logical_and(s == S - 1, t >= S - 1)
                        outstack = lax.dynamic_update_slice(
                            outstack, jnp.where(keep, weighted, cur),
                            (o0, 0, 0, 0, 0))
                    return (new_act, outstack), None

                (_, outstack), _ = lax.scan(
                    tick, (act0, outstack0), jnp.arange(T + S - 1)
                )
                with jax.named_scope("collective"):
                    return lax.all_gather(outstack, "pipe",
                                          axis=0)[S - 1]

            sharded = shard_map(
                device_fn,
                mesh=mesh,
                in_specs=(P(), P(), P()),
                out_specs=P(),
                check_vma=False,
            )

            # no donation here: the replicated input cannot alias the
            # replicated (differently-shaped) output
            return jax.jit(sharded)

        from chunkflow_tpu.ops.blend import pipeline_key

        # pipeline-independent math, but the tags join anyway (the
        # every-serving-key convention — see serve/packer.py); the
        # precision tag rides along since a shared ProgramCache may
        # serve engines wrapped at different precisions
        key = (
            ("serve_forward", n_chips)
            + (("pipeline",) if pipelined else ())
            + pipeline_key()
            + ((self.precision_tag,) if self.precision_tag else ())
        )
        return self.programs.get(
            key, build_pipelined if pipelined else build)

    # ------------------------------------------------------------------
    def _spatial_geometry(self, y: int, x: int):
        ny, nx = self.spec.shape
        pin = self.input_patch_size
        pout = self.output_patch_size
        gy = axis_geometry(y, ny, pin[1], pout[1])
        gx = axis_geometry(x, nx, pin[2], pout[2])
        return gy, gx

    def _gauges(self, arr_shape, per_chip_voxels: int,
                chip_patches=None) -> None:
        spec = self.spec
        telemetry.gauge("shard/mesh_devices", float(spec.n_devices))
        if spec.kind in ("data", "pipeline"):
            telemetry.gauge("shard/mesh_y", 1.0)
            telemetry.gauge("shard/mesh_x", 1.0)
        else:
            telemetry.gauge("shard/mesh_y", float(spec.shape[0]))
            telemetry.gauge("shard/mesh_x", float(spec.shape[1]))
        # stage count of a pipeline mesh (0 otherwise) so the MESH block
        # can label the shape honestly instead of folding it into data=N
        telemetry.gauge(
            "shard/mesh_pipeline",
            float(spec.shape[0]) if spec.kind == "pipeline" else 0.0,
        )
        telemetry.gauge("shard/per_chip_voxels", float(per_chip_voxels))
        if chip_patches is not None:
            # per-chip OUTPUT voxels actually computed this dispatch:
            # that chip's share of valid patches × output-patch voxels —
            # the load-balance signal per mesh shape (padding rows carry
            # valid 0 and so contribute nothing)
            pvox = float(np.prod(self.output_patch_size))
            for i, npatches in enumerate(chip_patches):
                telemetry.chip_gauge("shard", i, "voxels",
                                     float(npatches) * pvox)
        telemetry.inc("shard/chunks")

    def _note_collectives(self, key, halo_bytes: float,
                          gather_bytes: float,
                          replay_strip_bytes: float = 0.0,
                          handoff_bytes: float = 0.0) -> None:
        """Stamp this dispatch's analytic cross-chip traffic (see module
        docstring): counters + per-family ledger bucket. Four analytic
        planes (ISSUE 19 extends the original two): input halos,
        weighted-stack gathers, sharded-replay fringe strips
        (``ppermute`` of the boundary-crossing windows) and pipeline
        stage handoffs (the activation ring). Bytes only: what the
        exchanges cost in time is read from a device trace (the ops
        under the ``collective`` scope), not reckoned from bytes."""
        if not telemetry.enabled():
            return
        if halo_bytes > 0:
            telemetry.inc("shard/halo_bytes", float(halo_bytes))
            telemetry.gauge("shard/halo_bytes_per_chunk",
                            float(halo_bytes))
        if gather_bytes > 0:
            telemetry.inc("shard/gather_bytes", float(gather_bytes))
            telemetry.gauge("shard/gather_bytes_per_chunk",
                            float(gather_bytes))
        if replay_strip_bytes > 0:
            telemetry.inc("shard/replay_strip_bytes",
                          float(replay_strip_bytes))
            telemetry.gauge("shard/replay_strip_bytes_per_chunk",
                            float(replay_strip_bytes))
        if handoff_bytes > 0:
            telemetry.inc("shard/handoff_bytes", float(handoff_bytes))
            telemetry.gauge("shard/handoff_bytes_per_chunk",
                            float(handoff_bytes))
        total = (float(halo_bytes) + float(gather_bytes)
                 + float(replay_strip_bytes) + float(handoff_bytes))
        if total > 0:
            profiling.note_collective(total, key=key, label="sharded")

    def _replay_buffer_gauges(self, z: int, buf_y: int, buf_x: int,
                              n_chips: int) -> None:
        """Analytic per-chip blend-buffer footprint (out + weight planes,
        float32; kernel alignment pad excluded): the HBM figure the
        sharded replay shrinks from full-chunk to slab+margin. One
        global gauge plus the per-chip plane (uniform by construction —
        slabs are equal-sized) so the PR 18 watermark tooling can set it
        against measured per-chip peaks."""
        if not telemetry.enabled():
            return
        nbytes = float(
            (self.num_output_channels + 1) * z * buf_y * buf_x * 4
        )
        telemetry.gauge("shard/replay_buffer_bytes", nbytes)
        for i in range(n_chips):
            telemetry.chip_gauge("shard", i, "replay_buffer_bytes",
                                 nbytes)

    def _chip_probe_every(self) -> int:
        raw = os.environ.get("CHUNKFLOW_CHIP_PROBE_EVERY", "")
        try:
            return max(1, int(raw)) if raw else 8
        except ValueError:
            return 8

    def _probe_chip_readiness(self, result) -> None:
        """Sampled per-chip readiness probe: block on each output shard
        in device order, recording cumulative wall until that chip's
        buffer is ready. Runs on the first dispatch and then every
        ``CHUNKFLOW_CHIP_PROBE_EVERY``-th (default 8) — the probe syncs
        the device, so sampling keeps it off the steady-state dispatch
        path. Never under the telemetry kill switch."""
        n = self._dispatches
        self._dispatches = n + 1
        if not telemetry.enabled() or n % self._chip_probe_every():
            return
        try:
            shards = sorted(result.addressable_shards,
                            key=lambda s: getattr(s.device, "id", 0))
        except Exception:
            return
        if not shards:
            return
        t0 = time.perf_counter()
        readies = []
        for shard in shards:
            try:
                shard.data.block_until_ready()
            except Exception:
                return
            readies.append(time.perf_counter() - t0)
        for i, ready_s in enumerate(readies):
            telemetry.chip_gauge("shard", i, "ready_s", ready_s)
        telemetry.gauge("shard/chip_skew_s", readies[-1] - readies[0])

    # ------------------------------------------------------------------
    def run(self, arr, grid: PatchGrid, params, host_params=None):
        """Dispatch the sharded program for one device-resident float32
        chunk ``[C, Z, y, x]`` (ownership transfers: the program donates
        the buffer). Returns the normalized output array — dispatch is
        async; callers block when they materialize. ``host_params`` is
        the host-side parameter tree used for the cross-process
        consistency digest (defaults to ``params``)."""
        import jax

        if jax.process_count() > 1:
            return self._run_multiprocess(
                arr, grid, params,
                params if host_params is None else host_params,
            )
        return self._run_local(arr, grid, params)

    def _run_local(self, arr, grid: PatchGrid, params):
        import jax.numpy as jnp

        from chunkflow_tpu.ops.blend import (
            kernel_tag,
            pipeline_key,
            replay_key,
            shard_replay_mode,
        )
        from chunkflow_tpu.ops.pallas_gather import gather_key

        # the accumulation-kernel, gather-front, fused-pipeline,
        # replay-sharding AND forward-precision selections are part of
        # the program key (the CHUNKFLOW_PALLAS / CHUNKFLOW_GATHER /
        # CHUNKFLOW_FUSED_PIPELINE / CHUNKFLOW_SHARD_REPLAY flip
        # convention; no suffix for the defaults keeps the historical
        # key strings)
        tag = kernel_tag()
        kernel_key = (
            (() if tag == "scatter" else (tag,)) + gather_key()
            + pipeline_key() + replay_key()
            + ((self.precision_tag,) if self.precision_tag else ())
        )
        B = self.batch_size
        chunk_shape = tuple(arr.shape)
        pvox = int(np.prod(self.output_patch_size))
        py = self.output_patch_size[1]
        sharded_replay = shard_replay_mode() == "sharded"
        if self.spec.kind == "data":
            n_dev = self.spec.n_devices
            in_starts, out_starts, valid = pad_to_batch(grid, B * n_dev)
            n_pad_g = len(valid)
            n_ref = grid.num_patches + (-grid.num_patches % B)
            plan = None
            slab = 0
            if sharded_replay:
                slab = -(-chunk_shape[2] // n_dev)
                plan = replay_plan_1d(
                    np.asarray(out_starts), np.asarray(valid), n_ref,
                    n_pad_g, self.output_patch_size, n_dev, slab, B,
                )
            # plan.r is a program SHAPE (the padded per-chip replay
            # roster), not just data — it joins the key
            program_key = (("shard", "data", n_dev, chunk_shape, n_pad_g)
                           + kernel_key
                           + ((plan.r,) if plan is not None else ()))
            program = self.programs.get(
                program_key,
                lambda: self._build_data_program(chunk_shape, n_pad_g,
                                                 n_ref, plan, slab),
            )
            self._gauges(
                chunk_shape, int(np.prod(chunk_shape[1:])),
                chip_patches=np.asarray(valid).reshape(n_dev, -1)
                .sum(axis=1),
            )
            with telemetry.span("shard/dispatch",
                                mesh=self.spec.describe()):
                if plan is None:
                    result = program(
                        arr,
                        jnp.asarray(in_starts),
                        jnp.asarray(out_starts),
                        jnp.asarray(valid),
                        params,
                    )
                else:
                    result = program(
                        arr,
                        jnp.asarray(in_starts),
                        jnp.asarray(valid),
                        jnp.asarray(plan.index),
                        jnp.asarray(plan.starts),
                        jnp.asarray(plan.valid),
                        params,
                    )
            # weighted-prediction stack all_gather: each chip's
            # [rows, co, *pout] float32 shard reaches the n-1 others
            rows = n_pad_g // n_dev
            shard_bytes = rows * self.num_output_channels * pvox * 4
            self._note_collectives(
                program_key, 0.0, float(n_dev * (n_dev - 1) * shard_bytes),
            )
            if plan is not None:
                self._replay_buffer_gauges(
                    chunk_shape[1], slab + 2 * py, chunk_shape[3], n_dev)
            self._probe_chip_readiness(result)
            if plan is not None:
                # sharded output is [co, z, slab * n_dev, x]
                return result[:, :, : chunk_shape[2], :]
            return result

        if self.spec.kind == "pipeline":
            S = self.spec.n_devices
            in_starts, out_starts, valid = pad_to_batch(grid, B)
            n_ref = len(valid)
            plan = None
            slab = 0
            if sharded_replay:
                slab = -(-chunk_shape[2] // S)
                plan = replay_plan_1d(
                    np.asarray(out_starts), np.asarray(valid), n_ref,
                    n_ref, self.output_patch_size, S, slab, B,
                )
            program_key = (("shard", "pipeline", S, chunk_shape, n_ref)
                           + kernel_key
                           + ((plan.r,) if plan is not None else ()))
            program = self.programs.get(
                program_key,
                lambda: self._build_pipeline_program(chunk_shape, n_ref,
                                                     plan, slab),
            )
            # pipeline chips are stage-parallel: every chip touches
            # every patch, so there is no per-chip patch share to plot
            self._gauges(chunk_shape, int(np.prod(chunk_shape[1:])))
            with telemetry.span("shard/dispatch",
                                mesh=self.spec.describe()):
                if plan is None:
                    result = program(
                        arr,
                        jnp.asarray(in_starts),
                        jnp.asarray(out_starts),
                        jnp.asarray(valid),
                        params,
                    )
                else:
                    result = program(
                        arr,
                        jnp.asarray(in_starts),
                        jnp.asarray(valid),
                        jnp.asarray(plan.index),
                        jnp.asarray(plan.starts),
                        jnp.asarray(plan.valid),
                        params,
                    )
            # stage handoffs: one activation micro-batch rides each of
            # the S-1 ring edges every tick (T + S - 1 ticks); the drain
            # collect all_gathers each chip's weighted stack
            T = n_ref // B
            act_itemsize = 2 if self.precision_tag == "prec-bfloat16" \
                else 4
            act_bytes = (B * self.num_input_channels
                         * int(np.prod(self.input_patch_size))
                         * act_itemsize)
            handoff_bytes = float((T + S - 1) * (S - 1) * act_bytes)
            stack_bytes = n_ref * self.num_output_channels * pvox * 4
            self._note_collectives(
                program_key, 0.0, float(S * (S - 1) * stack_bytes),
                handoff_bytes=handoff_bytes,
            )
            if plan is not None:
                self._replay_buffer_gauges(
                    chunk_shape[1], slab + 2 * py, chunk_shape[3], S)
            self._probe_chip_readiness(result)
            if plan is not None:
                return result[:, :, : chunk_shape[2], :]
            return result

        # spatial kinds: shard the chunk itself
        ny, nx = self.spec.shape
        c, z, y, x = chunk_shape
        geometry = self._spatial_geometry(y, x)
        (yslab, hl_y, _, padded_y), (xslab, hl_x, _, padded_x) = geometry
        part = partition_for_mesh(
            grid, (ny, nx), B, yslab, xslab, hl_y, hl_x
        )
        plan = replay_plan_spatial(
            part, self.output_patch_size, (ny, nx), yslab, xslab, B,
        ) if sharded_replay else None
        arr = _pad_chunk(arr, padded_y, padded_x)
        padded_shape = tuple(arr.shape)
        # fringe widths and the replay roster are program SHAPES
        program_key = (("shard", "spatial", (ny, nx), padded_shape,
                        part.per_dev, len(part.valid)) + kernel_key
                       + ((plan.fy, plan.fx, plan.r)
                          if plan is not None else ()))
        program = self.programs.get(
            program_key,
            lambda: self._build_spatial_program(
                padded_shape, geometry, part.per_dev, len(part.valid),
                plan,
            ),
        )
        self._gauges(
            chunk_shape, int(c * z * yslab * xslab),
            chip_patches=np.asarray(part.dev_valid).sum(axis=2)
            .reshape(-1),
        )
        with telemetry.span("shard/dispatch", mesh=self.spec.describe()):
            if plan is None:
                result = program(
                    arr,
                    jnp.asarray(part.dev_in),
                    jnp.asarray(part.dev_valid),
                    jnp.asarray(part.src_index),
                    jnp.asarray(part.out_starts),
                    jnp.asarray(part.valid),
                    params,
                )
            else:
                result = program(
                    arr,
                    jnp.asarray(part.dev_in),
                    jnp.asarray(part.dev_valid),
                    jnp.asarray(plan.fringe_y),
                    jnp.asarray(plan.fringe_x),
                    jnp.asarray(plan.index),
                    jnp.asarray(plan.starts),
                    jnp.asarray(plan.valid),
                    params,
                )
        # halo ppermute traffic: every chip exchanges its float32 halo
        # rows/columns with neighbours (y at slab width, x at the
        # y-extended height); plus either the weighted-stack all_gather
        # (replicated replay) or the fringe-window strips (sharded)
        n_chips = ny * nx
        (_, hl_y2, hr_y2, _), (_, hl_x2, hr_x2, _) = geometry
        halo_bytes = 0.0
        if ny > 1:
            halo_bytes += n_chips * c * z * (hl_y2 + hr_y2) * xslab * 4
        if nx > 1:
            halo_bytes += (n_chips * c * z * (yslab + hl_y2 + hr_y2)
                           * (hl_x2 + hr_x2) * 4)
        row_bytes = self.num_output_channels * pvox * 4
        if plan is None:
            shard_bytes = part.per_dev * row_bytes
            gather_bytes = float(n_chips * (n_chips - 1) * shard_bytes)
            strip_bytes = 0.0
        else:
            gather_bytes = 0.0
            strip_bytes = float(
                ((ny - 1) * nx * plan.fy + ny * (nx - 1) * plan.fx)
                * row_bytes
            )
        self._note_collectives(
            program_key, halo_bytes, gather_bytes,
            replay_strip_bytes=strip_bytes,
        )
        if plan is not None:
            self._replay_buffer_gauges(
                z, yslab + 2 * plan.margin_y, xslab + 2 * plan.margin_x,
                n_chips)
        self._probe_chip_readiness(result)
        return result[:, :, :y, :x]

    # ------------------------------------------------------------------
    def _run_multiprocess(self, arr, grid: PatchGrid, params, host_params):
        """A jax runtime spanning processes. Collective-capable backends
        run the proven cross-host recipe for the data kind (global psum
        program + run_global's guard, ulp-level parity); backends that
        cannot run multiprocess computations (CPU — podsim) verify input
        consistency host-side and compute over the process-local mesh
        (bitwise-deterministic, so every process holds the same copy)."""
        from chunkflow_tpu.parallel import multihost

        if multihost.backend_supports_collectives() \
                and self.spec.kind == "data":
            import jax.numpy as jnp

            from chunkflow_tpu.ops.pallas_gather import convert_chunk
            from chunkflow_tpu.parallel.distributed import (
                build_sharded_program,
            )

            # the cross-host recipe keeps its float32 global-array
            # contract: a raw chunk converts host-side with the same
            # IEEE expression the device front applies (bitwise equal)
            if np.dtype(arr.dtype) != np.float32:
                arr = np.asarray(convert_chunk(np.asarray(arr)))

            mesh = multihost.global_mesh()
            B = self.batch_size
            in_starts, out_starts, valid = pad_to_batch(
                grid, B * mesh.devices.size
            )
            program = self.programs.get(
                ("shard", "global", tuple(d.id for d in mesh.devices.flat),
                 tuple(arr.shape), len(valid)),
                lambda: build_sharded_program(
                    self.forward,
                    self.num_input_channels,
                    self.num_output_channels,
                    self.input_patch_size,
                    self.output_patch_size,
                    B,
                    mesh,
                    _bump_array(self.output_patch_size),
                    out_dtype=self.out_dtype,
                ),
            )
            n_glob = mesh.devices.size
            self._gauges(
                tuple(arr.shape), int(np.prod(tuple(arr.shape)[1:])),
                chip_patches=np.asarray(valid).reshape(n_glob, -1)
                .sum(axis=1),
            )
            # the cross-host recipe psums partial float32 blend buffers:
            # a ring all-reduce moves ~2(n−1) output-buffer copies
            out_bytes = (self.num_output_channels
                         * int(np.prod(tuple(arr.shape)[1:])) * 4)
            self._note_collectives(
                ("shard", "global"), 0.0,
                float(2 * (n_glob - 1) * out_bytes),
            )
            with telemetry.span("shard/dispatch", mesh="global"):
                out = multihost.run_global(
                    program, np.asarray(arr), in_starts, out_starts,
                    valid, host_params, mesh,
                )
            return jnp.asarray(out)

        # no multiprocess collectives: guard, then compute locally
        multihost.ensure_consistent(np.asarray(arr), host_params)
        local = ShardedEngine(
            self.forward,
            self.num_input_channels,
            self.num_output_channels,
            self.input_patch_size,
            self.output_patch_size,
            self.batch_size,
            self._local_spec(),
            programs=self.programs,
            out_dtype=self.out_dtype,
        )
        return local._run_local(arr, grid, params)

    def _local_spec(self) -> MeshSpec:
        """This spec clamped to the process-local device count (the
        no-collectives fallback)."""
        import jax

        n_local = len(jax.local_devices())
        if self.spec.kind == "data":
            n = min(self.spec.shape[0], n_local)
            return (MeshSpec("data", (n,)) if n > 1
                    else MeshSpec("data", (max(n_local, 1),)))
        if self.spec.kind == "pipeline":
            # fewer chips just means coarser stage groups — the stage
            # protocol keeps the composition (and the bits) identical
            n = min(self.spec.shape[0], n_local)
            return (MeshSpec("pipeline", (n,)) if n > 1
                    else MeshSpec("data", (max(n_local, 1),)))
        ny, nx = self.spec.shape
        if ny * nx <= n_local:
            return self.spec
        # shrink y first (the outer axis) until the mesh fits
        while ny * nx > n_local and ny > 1:
            ny -= 1
        while ny * nx > n_local and nx > 1:
            nx -= 1
        return MeshSpec("spatial", (max(ny, 1), max(nx, 1))) \
            if ny * nx > 1 else MeshSpec("data", (max(n_local, 1),))


def _bump_array(pout: Triple) -> np.ndarray:
    from chunkflow_tpu.inference.bump import bump_map

    return bump_map(tuple(pout))


# ---------------------------------------------------------------------------
# standalone wrapper (bench / legacy module shims)
# ---------------------------------------------------------------------------

def sharded_inference(
    chunk_array,
    engine,
    input_patch_size: Triple,
    output_patch_size: Optional[Triple] = None,
    output_patch_overlap: Triple = (0, 0, 0),
    batch_size: int = 1,
    spec: Optional[MeshSpec] = None,
    mesh_spec: Optional[str] = None,
    out_dtype: str = "float32",
    programs: Optional[ProgramCache] = None,
):
    """Run unified sharded inference on a raw array with a raw
    ``engines.Engine`` — the standalone entry the legacy
    ``distributed.sharded_inference`` / ``spatial*_sharded_inference``
    wrappers now delegate to. Returns the (async) device result."""
    import jax.numpy as jnp

    if spec is None:
        import jax

        n_local = len(jax.local_devices())
        spec = (parse_mesh_spec(mesh_spec, n_local) if mesh_spec
                else MeshSpec("data", (n_local,)))
    pin = tuple(input_patch_size)
    pout = tuple(output_patch_size) if output_patch_size else pin
    arr = jnp.asarray(chunk_array, dtype=jnp.float32)
    if arr.ndim == 3:
        arr = arr[None]
    if arr is chunk_array:
        # the program donates its chunk argument; never hand it the
        # caller's own (already float32, already device) buffer
        arr = arr.copy()
    grid = enumerate_patches(
        tuple(arr.shape), pin, pout, tuple(output_patch_overlap)
    )
    sharded = ShardedEngine(
        engine.apply,
        engine.num_input_channels,
        engine.num_output_channels,
        pin,
        tuple(grid.output_patch_size),
        batch_size,
        spec,
        programs=programs,
        out_dtype=out_dtype,
    )
    return sharded.run(arr, grid, engine.params)
