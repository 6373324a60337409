"""The fused patch-inference engine: one XLA program per chunk.

Parity target: reference flow/divid_conquer/inferencer.py — chunk -> patch
decomposition, batched convnet forward, bump-weighted overlap-add, chunk
weight-mask normalization. The reference runs this as a Python loop with a
host<->GPU round trip per batch (its acknowledged hot spot, SURVEY §3.2);
here the whole thing — patch gather (dynamic_slice), forward pass, bump
multiply, scatter-add blend, reciprocal normalization — is a single
jit-compiled program over an HBM-resident chunk:

    lax.scan over patch batches
      -> vmap(dynamic_slice) gather         [B, Ci, *Pi]
      -> engine.apply (MXU matmuls/convs)   [B, Co, *Po]
      -> (optional 8x TTA average, scanned)
      -> bump multiply + validity mask
      -> single scatter-add / pallas DMA accumulation (ops/blend.py)
    -> out / weight  (exact everywhere, including chunk edges)

Design deltas from the reference, on purpose:
- no separate "aligned" vs "mask_output_chunk" modes: the weight mask is
  always accumulated on device and reciprocal-applied, which is exact for
  arbitrary chunk sizes (the reference's aligned mode is the special case
  where the mask is uniform in the interior);
- patch grids pad to a batch multiple with zero-validity entries instead of
  a dynamic trailing batch, keeping shapes static for XLA.
"""
from __future__ import annotations

import itertools
import sys
import time
import weakref
from collections import deque
from typing import Optional, Tuple

import numpy as np

from chunkflow_tpu.chunk.base import Chunk, LayerType
from chunkflow_tpu.core.cartesian import Cartesian, to_cartesian
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.core.compile_cache import (
    ProgramCache,
    enable_persistent_cache,
)
from chunkflow_tpu.core.contracts import Spec, contract
from chunkflow_tpu.inference import engines
from chunkflow_tpu.inference.bump import bump_map
from chunkflow_tpu.inference.patching import enumerate_patches, pad_to_batch


class Inferencer:
    def __init__(
        self,
        input_patch_size,
        output_patch_size=None,
        output_patch_overlap=(0, 0, 0),
        num_output_channels: int = 1,
        num_input_channels: int = 1,
        framework: str = "identity",
        model_path: str = "",
        weight_path: Optional[str] = None,
        batch_size: int = 1,
        augment: bool = False,
        bump: str = "wu",
        crop_output_margin: bool = True,
        mask_myelin_threshold: Optional[float] = None,
        dtype: str = "float32",
        output_dtype: str = "float32",
        model_variant: str = "parity",
        engine=None,
        sharding: str = "none",
        mesh: Optional[str] = None,
        precision: Optional[str] = None,
        shape_bucket=None,
        blend: str = "auto",
        dry_run: bool = False,
    ):
        self.input_patch_size = Cartesian.from_collection(input_patch_size)
        self.output_patch_size = (
            Cartesian.from_collection(output_patch_size)
            if output_patch_size is not None
            else self.input_patch_size
        )
        self.output_patch_overlap = Cartesian.from_collection(output_patch_overlap)
        self.crop_margin = (self.input_patch_size - self.output_patch_size) // 2
        self.num_output_channels = num_output_channels
        self.num_input_channels = num_input_channels
        self.batch_size = batch_size
        self.augment = augment
        self.crop_output_margin = crop_output_margin
        self.mask_myelin_threshold = mask_myelin_threshold
        self.dry_run = dry_run
        self.framework = framework
        # Accumulation/normalization stay float32 (blend exactness); this
        # only narrows the RESULT before it leaves the device. bfloat16
        # halves D2H bytes, and uint8 quantizes on device exactly like
        # the reference's save-time float->uint8 conversion
        # (save_precomputed.py:90-92), quartering the bytes.
        if output_dtype not in ("float32", "bfloat16", "uint8"):
            raise ValueError(
                f"output_dtype must be float32, bfloat16 or uint8, got "
                f"{output_dtype!r}"
            )
        if output_dtype == "uint8" and mask_myelin_threshold is not None:
            raise ValueError(
                "mask_myelin_threshold compares [0,1] probabilities; "
                "combine it with float output_dtype, not uint8"
            )
        self.output_dtype = output_dtype
        if sharding not in ("none", "patch", "spatial", "spatial2d"):
            raise ValueError(f"unknown sharding mode {sharding!r}")
        self.sharding = sharding
        # Multi-chip mesh spec (docs/multichip.md): an explicit ``mesh``
        # argument ("data=8" / "y=4,x=2" / "auto") wins over the
        # CHUNKFLOW_MESH env var, which is re-read per chunk so the
        # ``CHUNKFLOW_MESH=1`` kill switch restores the single-device
        # path bit-identically at any moment. The legacy ``sharding``
        # names map onto the same unified engine (parallel/engine.py).
        if mesh is not None and sharding != "none":
            raise ValueError(
                f"mesh={mesh!r} does not compose with the legacy "
                f"sharding={sharding!r}; pick one"
            )
        self.mesh_spec = mesh
        self._shard_engines: dict = {}
        self._fold_mesh_noted = False
        # Optional shape bucketing (SURVEY §7 hard parts): pad every chunk
        # up to multiples of this zyx quantum so ragged edge chunks reuse
        # the same compiled program instead of recompiling per shape.
        # Trade-off: the convnet sees edge-replicated padding past the
        # true edge instead of the reference's edge-snapped real context,
        # so predictions within one patch of a padded face can differ —
        # hence opt-in.
        self.shape_bucket = (
            Cartesian.from_collection(shape_bucket)
            if shape_bucket is not None and any(shape_bucket)
            else None
        )
        if self.shape_bucket is not None and not self.shape_bucket.all_positive():
            raise ValueError(
                f"shape_bucket must be all-positive (or all-zero to "
                f"disable), got {tuple(self.shape_bucket)}"
            )
        # Blend strategy: "scatter" (runtime-coordinate scatter-add /
        # pallas, ops/blend.py), "fold" (static parity-class dense
        # overlap-add, ops/fold_blend.py; pads the chunk to a uniform
        # grid), "auto" (env CHUNKFLOW_BLEND or scatter). Fold applies to
        # the single-device path; sharded paths keep scatter.
        import os as _os

        if blend == "auto":
            blend = _os.environ.get("CHUNKFLOW_BLEND", "scatter").lower()
        if blend not in ("scatter", "fold"):
            raise ValueError(f"unknown blend mode {blend!r}")
        if blend == "fold" and sharding != "none":
            # loud, not silent: sharded programs use the scatter blend;
            # quietly running scatter would misattribute numbers to fold
            raise ValueError(
                f"blend='fold' applies to the single-device path only "
                f"(got sharding={sharding!r}); use blend='scatter' or "
                f"sharding='none'"
            )
        self.blend_mode = blend
        # optional explicit device set for the mesh engine (tests /
        # multihost bring-up inject a mesh here; its devices are used)
        self._mesh = None
        # one keyed cache for every program family this inferencer builds
        # (scatter/fold/patch/spatial/spatial2d); keys derive from the
        # BUCKETED run shape, so ragged edge chunks that pad into the
        # same bucket share one compiled program and never retrace. The
        # retrace watchdog warns past CHUNKFLOW_EXPECTED_PROGRAMS builds
        # (default 8: one per family plus a few fold/spatial geometries)
        # — the signature of a silent retrace per chunk.
        self._programs = ProgramCache(
            label="inferencer",
            expected_builds=int(
                _os.environ.get("CHUNKFLOW_EXPECTED_PROGRAMS", "8")
            ),
        )
        # persistent on-disk XLA cache: a worker restart skips the
        # UNet compile (JAX_COMPILATION_CACHE_DIR places it)
        enable_persistent_cache()
        if bump != "wu":
            raise ValueError(f"only the 'wu' bump is implemented, got {bump!r}")
        if augment and (
            self.input_patch_size.y != self.input_patch_size.x
            or self.output_patch_size.y != self.output_patch_size.x
        ):
            raise ValueError(
                "test-time augmentation needs square yx input AND output patches"
            )

        self.engine = engines.create_engine(
            framework,
            engine=engine,
            input_patch_size=tuple(self.input_patch_size),
            output_patch_size=tuple(self.output_patch_size),
            num_output_channels=num_output_channels,
            num_input_channels=num_input_channels,
            model_path=model_path,
            weight_path=weight_path,
            dtype=dtype,
            model_variant=model_variant,
        )
        # Forward precision (inference/precision.py): an explicit
        # ``precision`` argument is strict; otherwise CHUNKFLOW_PRECISION
        # resolves once here (a per-chunk re-read would retrace every
        # program on a flip). float32 keeps engine.apply ITSELF — the
        # default path stays bitwise untouched; bf16/int8 wrap the
        # forward only, while blend accumulation stays float32. The
        # serving packer and the sharded engine both build on
        # ``_forward``, so every execution path shares one precision.
        from chunkflow_tpu.inference.precision import (
            resolve_precision,
            wrap_apply,
        )

        self.precision = resolve_precision(precision)
        self._apply = wrap_apply(self.engine.apply, self.precision)
        self._device_params = None
        # results of earlier infer_async calls that were not ready when
        # last looked at, oldest first and held weakly (a result the
        # caller dropped is freed): what the byte bound waits for
        self._ahead: deque = deque()

    # ------------------------------------------------------------------
    def _scatter_key(self) -> tuple:
        """ProgramCache key for the single-device blend program. The
        accumulation-kernel selection (XLA scatter vs the fused Pallas
        kernel, ops/blend.kernel_tag) AND the gather-front selection
        (``CHUNKFLOW_GATHER``, ops/pallas_gather.gather_key — empty for
        the default device leg) are part of the key, so flipping either
        env mid-stream builds the right program instead of reusing a
        stale one — the same re-read-per-chunk convention as
        ``CHUNKFLOW_MESH``. ``CHUNKFLOW_FUSED_PIPELINE`` joins too
        (ops/blend.pipeline_key): the pipeline forces both kernel legs,
        so a user already running PALLAS=interpret + GATHER=interpret
        would otherwise flip the pipeline without changing the key."""
        from chunkflow_tpu.ops.blend import kernel_tag, pipeline_key
        from chunkflow_tpu.ops.pallas_gather import gather_key

        tag = kernel_tag()
        base = ("scatter",) if tag == "scatter" else ("scatter_fused", tag)
        return base + gather_key() + pipeline_key()

    @property
    def _program(self):
        """The compiled single-device blend program, if built (tests) —
        whichever accumulation kernel and gather front it selected."""
        prog = self._programs.peek(("scatter",))
        if prog is not None:
            return prog
        for key, cached in self._programs.items():
            if key and key[0] in ("scatter", "scatter_fused"):
                return cached
        return None

    @property
    def _fold_programs(self) -> dict:
        """padded-shape -> program view of the fold family (tests)."""
        return {
            key[1]: prog
            for key, prog in self._programs.items()
            if key[0] == "fold"
        }

    # ------------------------------------------------------------------
    def _bucketed_shape(self, zyx) -> Cartesian:
        """Round a zyx shape up to the bucket quantum (and at least one
        input patch)."""
        return (
            Cartesian.from_collection(zyx).ceildiv(self.shape_bucket)
            * self.shape_bucket
        ).maximum(self.input_patch_size)

    def _run_shape(self, zyx) -> tuple:
        """The shape actually executed for an incoming chunk shape:
        bucketing, then (fold mode) a min-pad to one input patch so thin
        chunks work in BOTH the fold path and its scatter budget
        fallback. Shared by _infer and patch_grid_shape so the asserted
        grid can never drift from the executed one."""
        run = tuple(zyx)[-3:]
        if self.shape_bucket is not None:
            run = tuple(self._bucketed_shape(run))
        if self.blend_mode == "fold":
            run = tuple(
                max(length, p)
                for length, p in zip(run, tuple(self.input_patch_size))
            )
        return run

    def patch_grid_shape(self, chunk_shape) -> Tuple[int, int, int]:
        """Patches per axis for a chunk shape (reference --patch-num
        contract: the caller may assert the grid it planned for). Derived
        from the same enumerate_patches call the engine runs — including
        shape bucketing — so the asserted grid can never drift from the
        executed one."""
        shape = self._run_shape(chunk_shape)
        if self._use_fold(shape):
            _, grid_shape = self._fold_geometry(shape)
            return grid_shape
        grid = enumerate_patches(
            shape,
            self.input_patch_size,
            self.output_patch_size,
            self.output_patch_overlap,
        )
        return tuple(
            int(np.unique(grid.input_starts[:, i]).size) for i in range(3)
        )

    # ------------------------------------------------------------------
    @property
    def compute_device(self) -> str:
        import jax

        dev = jax.devices()[0]
        return f"{dev.platform}:{dev.device_kind}"

    # ------------------------------------------------------------------
    def _forward(self, params, patches):
        """Engine forward with optional 8-fold test-time augmentation.

        TTA variants are the product of {yx-transpose, y-flip, x-flip}
        (reference transform.py:114-156). The eight forwards run as a
        ``lax.scan`` over the stacked pre-transformed variants so XLA
        compiles the engine once (instead of unrolling eight compiled
        UNet copies into the program); the per-variant inverse transforms
        are static ops applied to the stacked scan output.
        """
        import jax.numpy as jnp
        from jax import lax

        if not self.augment:
            return self._apply(params, patches)

        combos = list(itertools.product((False, True), repeat=3))
        variants = []
        for transpose, flip_y, flip_x in combos:
            x = patches
            if flip_y:
                x = jnp.flip(x, axis=-2)
            if flip_x:
                x = jnp.flip(x, axis=-1)
            if transpose:
                x = jnp.swapaxes(x, -1, -2)
            variants.append(x)
        xs = jnp.stack(variants)  # [8, B, ci, *pin]

        _, ys = lax.scan(
            lambda c, x: (c, self._apply(params, x)), None, xs
        )

        acc = None
        for i, (transpose, flip_y, flip_x) in enumerate(combos):
            y = ys[i]
            if transpose:
                y = jnp.swapaxes(y, -1, -2)
            if flip_x:
                y = jnp.flip(y, axis=-1)
            if flip_y:
                y = jnp.flip(y, axis=-2)
            acc = y if acc is None else acc + y
        return acc / 8.0

    # ------------------------------------------------------------------
    def _trace_geometry_gauges(self, chunk) -> None:
        """Runs while the patch program is traced for a chunk shape: what
        the program believes it holds, as gauges and on its entry in
        ``programs.json`` (docs/observability.md), to lay against the
        device's own ``peak_bytes_in_use``."""
        from chunkflow_tpu.core import profiling

        grid = enumerate_patches(
            chunk.shape, self.input_patch_size, self.output_patch_size,
            self.output_patch_overlap,
        )
        profiling.trace_gauge(
            "inference/output_patch_share",
            float(np.prod(tuple(self.output_patch_size)))
            / float(np.prod(tuple(self.input_patch_size))),
        )
        profiling.trace_gauge("inference/patches_per_task", grid.num_patches)
        profiling.trace_gauge(
            "inference/accumulator_bytes", self._accumulator_bytes(chunk))
        profiling.trace_gauge(
            "inference/chunk_bytes", self._chunk_bytes(chunk))

    def _accumulator_bytes(self, chunk) -> int:
        """The float32 sums and the weight volume, chunk-sized (without
        the Pallas leg's aligned-window padding)."""
        voxels = int(np.prod(chunk.shape[-3:]))
        return 4 * (self.num_output_channels + 1) * voxels

    @staticmethod
    def _chunk_bytes(chunk) -> int:
        """The chunk as it arrives; the XLA gather leg adds a float32
        copy."""
        return int(chunk.size) * np.dtype(chunk.dtype).itemsize

    def _build_program(self):
        import jax

        from chunkflow_tpu.ops.blend import build_local_blend, normalize_blend

        local_blend = build_local_blend(
            self._forward,
            self.num_input_channels,
            self.num_output_channels,
            tuple(self.input_patch_size),
            tuple(self.output_patch_size),
            self.batch_size,
            bump_map(tuple(self.output_patch_size)),
        )

        out_dtype = self.output_dtype

        def program(chunk, in_starts, out_starts, valid, params):
            self._trace_geometry_gauges(chunk)
            out, weight = local_blend(chunk, in_starts, out_starts, valid, params)
            return normalize_blend(out, weight, out_dtype)

        # the chunk buffer is dead after the call (GL005): XLA may alias
        # it into the blend accumulator/output instead of allocating per
        # chunk — _infer guarantees the buffer is program-owned
        return jax.jit(program, donate_argnums=(0,))

    # ------------------------------------------------------------------
    def _fold_geometry(self, zyx):
        """(padded_shape, grid_shape) for the fold path — the ONE place
        fold geometry is derived, shared by patch_grid_shape, the fit
        check, and execution so the asserted grid never drifts from the
        executed one."""
        from chunkflow_tpu.ops.fold_blend import fold_grid, fold_pad_shape

        pin = tuple(self.input_patch_size)
        stride = tuple(self.output_patch_size - self.output_patch_overlap)
        padded = fold_pad_shape(tuple(zyx), pin, stride)
        return padded, fold_grid(padded, pin, stride)

    def _use_fold(self, zyx) -> bool:
        """Fold applies when selected AND the patch stacks fit the same
        byte budget that gates the stacked scatter path — jumbo chunks
        (e.g. 108x2048x2048 production tasks) fall back to the scan
        accumulate instead of OOMing HBM."""
        if self.blend_mode != "fold" or self.sharding != "none":
            return False
        from chunkflow_tpu.ops.blend import stack_budget_bytes

        budget = stack_budget_bytes()
        padded, grid = self._fold_geometry(zyx)
        n = int(np.prod(grid))
        pin = tuple(self.input_patch_size)
        pout = tuple(self.output_patch_size)
        co = self.num_output_channels
        # per patch: the input-patch stack, the prediction stack, its
        # bump-weighted float32 copy (fold materializes both), and the
        # weight-patch stack
        per_patch = 4 * (
            self.num_input_channels * int(np.prod(pin))
            + (2 * co + 1) * int(np.prod(pout))
        )
        # fixed: the parity-class accumulation buffers — two (co+1)-channel
        # float32 volumes at the padded shape (out+weight, double-buffered
        # across the dense adds)
        fixed = 8 * (co + 1) * int(np.prod(padded))
        return n * per_patch + fixed <= budget

    @contract(arr=Spec(None, "z", "y", "x", dtype="float32"))
    def _run_fold(self, arr):
        """Static-geometry scatter-free path (ops/fold_blend.py): pad to
        a uniform patch grid, run the cached per-shape fold program, crop
        back. Edge predictions within one patch of a padded face see
        EDGE-REPLICATED context (the closest uniform-grid analog of the
        reference's edge-snapped real context) rather than true snapped
        data — still a face-adjacent approximation, which is why fold is
        opt-in."""
        import jax.numpy as jnp

        from chunkflow_tpu.ops.fold_blend import build_fold_program

        pin = tuple(self.input_patch_size)
        pout = tuple(self.output_patch_size)
        stride = tuple(self.output_patch_size - self.output_patch_overlap)
        zyx = tuple(arr.shape[-3:])
        padded, _ = self._fold_geometry(zyx)
        if padded != zyx:
            pad = [(0, 0)] + [(0, p - s) for p, s in zip(padded, zyx)]
            # edge-replicate, not zeros: grid-edge patches then see real
            # boundary context (the closest uniform-grid analog of the
            # reference's edge-snapped patch starts,
            # inferencer.py:404-455); padded voxels are cropped below
            arr = jnp.pad(arr, pad, mode="edge")
        program = self._programs.get(
            ("fold", padded),
            lambda: build_fold_program(
                self._forward,
                self.num_input_channels,
                self.num_output_channels,
                pin,
                pout,
                stride,
                self.batch_size,
                bump_map(pout),
                padded,
                out_dtype=self.output_dtype,
            ),
        )
        result = program(arr, self._device_params)
        return result[:, : zyx[0], : zyx[1], : zyx[2]]

    # ------------------------------------------------------------------
    def _resolve_shard_spec(self):
        """The effective mesh spec for this call: legacy ``sharding``
        names map to fixed layouts over the local devices; otherwise the
        explicit ``mesh`` argument wins over ``CHUNKFLOW_MESH`` (env is
        re-read per chunk — the kill switch works mid-stream)."""
        from chunkflow_tpu.parallel.engine import MeshSpec, parse_mesh_spec

        if self.sharding != "none":
            import jax

            n = (self._mesh.devices.size if self._mesh is not None
                 else len(jax.local_devices()))
            if self.sharding == "patch":
                return (MeshSpec("data", (n,)) if n > 1
                        else MeshSpec("single", (1,)))
            if self.sharding == "spatial":
                return (MeshSpec("spatial", (n, 1)) if n > 1
                        else MeshSpec("single", (1,)))
            # spatial2d: near-square (y, x) factorization, y outer
            from chunkflow_tpu.parallel.spatial2d import near_square_shape

            return (MeshSpec("spatial", near_square_shape(n)) if n > 1
                    else MeshSpec("single", (1,)))
        if self.mesh_spec is not None:
            return parse_mesh_spec(self.mesh_spec)
        import os as _os

        return parse_mesh_spec(_os.environ.get("CHUNKFLOW_MESH", "1"))

    def shard_engine(self):
        """The unified sharded engine for the resolved mesh spec, or
        None for the single-device path (the ``CHUNKFLOW_MESH=1`` kill
        switch). Engines are cached per spec; their programs live in the
        shared :class:`ProgramCache`, so they get donation, shape-bucket
        keying and the roofline ledger like every other family."""
        from chunkflow_tpu.parallel.engine import ShardedEngine

        spec = self._resolve_shard_spec()
        if spec.kind == "single":
            return None
        engine = self._shard_engines.get(spec)
        if engine is None:
            devices = (
                self._mesh.devices.reshape(-1)
                if self._mesh is not None else None
            )
            engine = ShardedEngine.for_inferencer(
                self, spec, devices=devices
            )
            self._shard_engines[spec] = engine
        return engine

    def _run_sharded(self, arr, grid, shard_engine=None):
        """Multi-chip execution through the unified engine
        (parallel/engine.py): every mesh kind — patch-parallel 'data',
        1D y slabs, 2D (y, x) — produces output bitwise identical to the
        single-device program (forward sharded, reference accumulation
        replayed; see the engine docstring for the argument)."""
        engine = shard_engine if shard_engine is not None \
            else self.shard_engine()
        return engine.run(arr, grid, self._device_params,
                          host_params=self.engine.params)

    # ------------------------------------------------------------------
    def __call__(self, chunk: Chunk) -> Chunk:
        # host-side span around the whole dispatch+wait (never inside
        # the compiled program, GL007); blend mode labels the event so
        # fold-vs-scatter time is separable offline
        with telemetry.span("inference/infer", blend=self.blend_mode):
            result = self._infer(chunk, block=True)
        # achieved-Mvox/s numerator (host-side, GL007): the pipelined
        # paths count in flow/pipeline._drain_host instead
        shape = getattr(getattr(result, "array", None), "shape", None)
        if shape:
            voxels = 1
            for length in shape[-3:]:
                voxels *= int(length)
            telemetry.inc("inference/voxels", float(voxels))
        return result

    def stream(self, chunks, postprocess=None, post_depth: int = 2,
               ring: int = 2, prefetch_depth: int = 2, adaptive=None):
        """Pipelined inference over an iterable of chunks.

        While chunk *k* computes on device, chunk *k+1* is staged
        host→device into a ``ring``-slot staging ring and chunk *k−1*'s
        output drains device→host asynchronously. Yields host-resident
        output chunks in input order. Same-shape (or same-bucket) chunks
        reuse one compiled program.

        ``postprocess`` (optional callable ``Chunk -> T``) runs the host
        post-processing stage — e.g. watershed agglomeration, the stage
        the reference ships to separate CPU fleets
        (plugins/agglomerate.py:35-43) — in a background thread while the
        NEXT chunk's program executes on device, so host work hides
        behind chip time instead of serializing after it (VERDICT r4 #3).
        At most ``post_depth`` tasks in flight; abandoning the generator
        early cancels queued (not-yet-started) postprocess tasks.

        By default this routes through the adaptive scheduler
        (:func:`chunkflow_tpu.flow.scheduler.schedule_chunks`): the
        ``chunks`` iterable's own IO additionally runs
        ``prefetch_depth`` items ahead in a producer thread, and all
        depths widen under telemetry-driven control (docs/performance.md
        "Adaptive scheduler"). ``adaptive=False`` — or the
        ``CHUNKFLOW_SCHED=static`` kill switch — pins the PR 2
        double-buffered executor with the static depths given here.
        Outputs are bit-identical either way.
        """
        from chunkflow_tpu.flow.scheduler import (
            schedule_chunks,
            scheduler_mode,
        )

        if adaptive is None:
            adaptive = scheduler_mode() == "adaptive"
        if adaptive:
            return schedule_chunks(
                self, chunks, ring=ring, postprocess=postprocess,
                post_depth=post_depth, prefetch_depth=prefetch_depth,
            )
        from chunkflow_tpu.flow.pipeline import pipeline_chunks

        return pipeline_chunks(
            self, chunks, ring=ring, postprocess=postprocess,
            post_depth=post_depth,
        )

    def stage(self, chunk: Chunk) -> Chunk:
        """Start the chunk's async H2D transfer; returns a device-backed
        chunk whose payload buffer is OWNED BY THE PIPELINE — hand it to
        ``infer_async(..., consume=True)`` and drop the reference (the
        program donates and invalidates it). ``jax.device_put`` is async,
        so staging chunk k+1 overlaps chunk k's compute; narrow int
        dtypes ride the wire narrow (float conversion happens on device
        at infer time).

        The blank answer is taken here, where the payload is on the host
        (a pass over memory; on the device it would be a reduction queued
        behind every program in flight, and the dispatch would wait for
        it), and rides with the staged chunk (``Chunk.blank``). A blank
        chunk is not uploaded: the chunk returned shares the host
        payload. A chunk that arrives device-resident is returned as it
        is, and ``_infer`` asks the device."""
        if chunk.is_on_device:
            return chunk
        blank = self._blank_answer(chunk)
        staged = type(chunk)(chunk) if blank else chunk.device()
        staged.blank = blank
        return staged

    def infer_async(self, chunk: Chunk, crop=None, consume: bool = False
                    ) -> Chunk:
        """Dispatch the fused program and start the result's D2H copy
        without blocking; materialize later with ``.host()``. Building
        block for pipelined drivers (``stream``, flow/pipeline.py, CLI
        --async-depth). ``crop`` applies an explicit margin crop ON
        DEVICE before the copy starts, so discarded margin voxels never
        ride D2H. ``consume`` transfers ownership of a device-resident
        input buffer to the program (donation: the caller's array is
        dead after the call) — only pass it for buffers you staged
        yourself and will not touch again.

        Nothing here waits for the device while one more dispatch fits
        its memory: callers may run as many tasks ahead as their own
        count bounds allow (:meth:`_make_room` is the byte bound)."""
        if not chunk.blank:
            self._make_room(chunk)
        out = self._infer(chunk, block=False, consume=consume)
        if crop is not None:
            out = out.crop_margin(crop)
        arr = out.array
        if hasattr(arr, "copy_to_host_async"):
            arr.copy_to_host_async()
            self._ahead.append(weakref.ref(arr))
        return out

    # ------------------------------------------------------------------
    def _dispatch_bytes(self, chunk) -> int:
        """What one dispatch allocates on the device, by the gauges the
        program's trace sets: the accumulators, the chunk and the
        result."""
        result = (self._result_dtype().itemsize * self.num_output_channels
                  * int(np.prod(chunk.shape[-3:])))
        return (self._accumulator_bytes(chunk) + self._chunk_bytes(chunk)
                + result)

    @staticmethod
    def _device_room() -> Optional[int]:
        """Bytes neither in use nor reserved for the loaded programs'
        scratch on the fullest local device, or None where the backend
        states no limit (the CPU)."""
        import jax

        rooms = []
        for device in jax.local_devices():
            stats = device.memory_stats() or {}
            limit = int(stats.get("bytes_limit", 0) or 0)
            if limit > 0:
                rooms.append(
                    limit - int(stats.get("bytes_in_use", 0) or 0)
                    - int(stats.get("bytes_reserved", 0) or 0))
        return min(rooms) if rooms else None

    def _make_room(self, chunk: Chunk) -> None:
        """The byte bound on dispatching ahead. While results of earlier
        dispatches are not ready their programs' buffers stand on the
        device; one more dispatch goes beside them only if it fits what
        the device has left, and otherwise waits for the oldest of them
        (interpreter lock released). Callers hold this inside their
        ``pipeline/dispatch`` span, so the wait is named there. A task
        that fills the device (the production deployment) so stays one
        program ahead, a small one runs as far ahead as the caller's
        count bounds let it."""
        ahead = deque()
        for ref in self._ahead:
            result = ref()
            if result is not None and not result.is_ready():
                ahead.append(ref)
        self._ahead = ahead
        # how deep the device's queue is as this task joins it
        telemetry.gauge("pipeline/ahead_outputs", len(ahead))
        if not ahead:
            return
        need = self._dispatch_bytes(chunk)
        while ahead:
            room = self._device_room()
            if room is None or need <= room:
                return
            oldest = ahead.popleft()()
            if oldest is not None:
                telemetry.inc("pipeline/ahead_waits")
                oldest.block_until_ready()

    @property
    def _out_layer(self):
        return (
            LayerType.AFFINITY_MAP
            if self.num_output_channels == 3
            else LayerType.PROBABILITY_MAP
        )

    def _result_dtype(self) -> np.dtype:
        import ml_dtypes

        return np.dtype({
            "float32": np.float32,
            "bfloat16": ml_dtypes.bfloat16,
            "uint8": np.uint8,
        }[self.output_dtype])

    def _blank_output(self, chunk: Chunk) -> Chunk:
        """The dry-run / all-zero-input result: a zero chunk with the
        real path's channel count and dtype. Shared with the serving
        packer (chunkflow_tpu/serve/packer.py) so packed and per-chunk
        execution agree on the blank fast path too."""
        # channel count must match the real path, which drops the myelin
        # channel when mask_myelin_threshold is set
        nchan = self.num_output_channels
        if self.mask_myelin_threshold is not None:
            nchan -= 1
        out = Chunk.from_bbox(
            chunk.bbox,
            # match the real path's result dtype so a volume mixing
            # blank and real chunks stays dtype-consistent
            dtype=self._result_dtype(),
            nchannels=nchan,
            voxel_size=chunk.voxel_size,
        )
        out.layer_type = self._out_layer
        if self.crop_output_margin:
            out = out.crop_margin(self.crop_margin)
        return out

    def _postprocess_result(self, result, chunk: Chunk,
                            orig_zyx, run_zyx) -> Chunk:
        """Crop bucket padding, wrap, myelin-mask and margin-crop a raw
        program result — the single definition of "what happens after
        the blend", shared by :meth:`_infer` and the serving packer so
        the two paths cannot drift."""
        if run_zyx != orig_zyx:
            result = result[
                :, : orig_zyx[0], : orig_zyx[1], : orig_zyx[2]
            ]
        out = Chunk(
            result,
            voxel_offset=chunk.voxel_offset,
            voxel_size=chunk.voxel_size,
            layer_type=self._out_layer,
        )
        if self.mask_myelin_threshold is not None:
            out = out.mask_using_last_channel(
                threshold=self.mask_myelin_threshold
            )
        if self.crop_output_margin:
            out = out.crop_margin(self.crop_margin)
        return out

    def _blank_answer(self, chunk: Chunk) -> bool:
        """Whether the chunk takes the blank path, once a task: asked of
        the host payload where the chunk is on the host (``stage``,
        ``__call__``). Only a chunk that arrives device-resident (an
        upstream operator left it there) is asked on the device: a
        reduction queued behind the programs in flight, whose answer the
        host waits for."""
        with telemetry.span("inference/blank_check") as check:
            asks_device = chunk.is_on_device and not self.dry_run
            if asks_device:
                telemetry.inc("inference/device_blank_checks")
            blank = self.dry_run or chunk.all_zero()
            # which tasks took the blank path, for a reader of the stream
            check.annotate(blank=int(blank),
                           where="device" if asks_device else "host")
        telemetry.inc("inference/tasks")
        return blank

    @contract(chunk=Spec(ndim=(3, 4)))
    def _infer(self, chunk: Chunk, block: bool, consume: bool = False) -> Chunk:
        import jax
        import jax.numpy as jnp

        blank = chunk.blank
        if blank is None:
            blank = self._blank_answer(chunk)
        if blank:
            out = self._blank_output(chunk)
            # what a blank chunk costs downstream: its zeros are cropped,
            # masked and written like any result
            telemetry.inc("inference/blank_tasks")
            telemetry.gauge("inference/blank_output_bytes",
                            out.array.nbytes)
            return out

        orig_zyx = tuple(chunk.shape[-3:])
        run_zyx = self._run_shape(orig_zyx)

        use_fold = self._use_fold(run_zyx)
        if self.blend_mode == "fold" and not use_fold:
            # loud, not silent: numbers measured under this config belong
            # to the scatter fallback, not fold (same misattribution
            # guard as the pallas/fold selection errors)
            print(
                f"fold blend gated off for shape {run_zyx}: patch stacks "
                f"exceed CHUNKFLOW_BLEND_STACK_MAX_GB; using per-batch "
                f"scatter fallback",
                file=sys.stderr,
            )
        shard_engine = None
        if use_fold:
            if not self._fold_mesh_noted:
                self._fold_mesh_noted = True
                if self._resolve_shard_spec().kind != "single":
                    print(
                        "fold blend is a single-device program; the "
                        "configured mesh spec is ignored for fold "
                        "traffic (use blend='scatter' to shard)",
                        file=sys.stderr,
                    )
        else:
            shard_engine = self.shard_engine()
        grid = None
        if not use_fold:
            # the scatter grid; fold derives its own (and supports chunks
            # thinner than the input patch via padding, which
            # enumerate_patches rejects)
            grid = enumerate_patches(
                run_zyx,
                self.input_patch_size,
                self.output_patch_size,
                self.output_patch_overlap,
            )

        from chunkflow_tpu.core import profiling
        from chunkflow_tpu.ops import pallas_gather

        arr = chunk.array
        was_on_device = chunk.is_on_device
        if not was_on_device:
            arr = np.asarray(arr)
        # int images normalize to [0, 1] float32 (reference :395-399).
        # Transfer the NARROW dtype: a uint8 EM chunk rides H2D at 1/4
        # the bytes of a host-side float32 conversion. With the
        # device-resident front half (ISSUE 15, the default) the chunk
        # stays RAW past this point too — the selected gather leg
        # (ops/pallas_gather.py) converts inside the program (whole-chunk
        # on the XLA leg, per-tile in VMEM on the Pallas leg).
        # CHUNKFLOW_GATHER=off restores the eager pre-program conversion
        # below bit-identically (conversion and edge-padding commute
        # exactly with slicing); fold keeps it — its program family
        # contracts on float32 input.
        dt = np.dtype(chunk.dtype)
        raw_front = (
            not use_fold
            and pallas_gather.gather_mode() != "host"
            and pallas_gather.raw_eligible(dt)
        )
        if raw_front:
            arr = jnp.asarray(arr)
            h2d_nbytes = arr.nbytes
        elif dt.kind in "iu":
            scale = np.float32(1.0 / np.iinfo(dt).max)
            if dt.itemsize <= 4:
                h2d_nbytes = arr.nbytes
                arr = jnp.asarray(arr).astype(jnp.float32) * scale
            else:
                # 64-bit ints would silently wrap in jnp.asarray (x64
                # disabled downcasts to 32-bit first); convert on host
                arr = jnp.asarray(np.asarray(arr, dtype=np.float32)) * scale
                h2d_nbytes = arr.nbytes
        else:
            arr = jnp.asarray(arr, dtype=jnp.float32)
            h2d_nbytes = arr.nbytes
        if arr is chunk.array and not consume:
            # every inference program donates its chunk argument (GL005):
            # the buffer is dead after the call. A device-resident float32
            # chunk passes through jnp.asarray unchanged, so donating it
            # would invalidate the CALLER's array mid-flight — copy unless
            # the caller declared ownership transfer (consume=True, the
            # pipelined executor's staged ring slots).
            arr = arr.copy()
        if arr.ndim == 3:
            arr = arr[None]
        if run_zyx != orig_zyx:
            pad = [(0, 0)] + [
                (0, r - s) for r, s in zip(run_zyx, orig_zyx)
            ]
            # shape-bucket padding replicates the boundary plane so the
            # net sees plausible context instead of a zero wall
            arr = jnp.pad(arr, pad, mode="edge")

        if self._device_params is None:
            self._device_params = jax.device_put(self.engine.params)

        if not was_on_device:
            # the staging seam: per-chunk H2D bytes (transfer/h2d_*;
            # pipeline-staged chunks count in Chunk.device instead),
            # attributed to the program family about to consume them
            if use_fold:
                h2d_key = ("fold",)
            elif shard_engine is None:
                h2d_key = self._scatter_key()
            else:
                h2d_key = ("shard",)
            profiling.note_h2d(h2d_nbytes, key=h2d_key)

        if use_fold:
            result = self._run_fold(arr)
        elif shard_engine is None:
            in_starts, out_starts, valid = pad_to_batch(grid, self.batch_size)
            program = self._programs.get(self._scatter_key(),
                                         self._build_program)
            result = program(
                arr,
                jnp.asarray(in_starts),
                jnp.asarray(out_starts),
                jnp.asarray(valid),
                self._device_params,
            )
        else:
            result = self._run_sharded(arr, grid, shard_engine)
        if block:
            result.block_until_ready()
        return self._postprocess_result(result, chunk, orig_zyx, run_zyx)
