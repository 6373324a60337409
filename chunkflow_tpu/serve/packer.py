"""Cross-task patch packer: fill fixed device batches from ragged traffic.

The per-chunk fused program (inference/inferencer.py) pads every task's
patch list to a multiple of ``batch_size`` with validity-0 entries, so a
task with 3 patches and batch 8 runs the forward pass at 37% occupancy —
and under many small concurrent requests (the ROADMAP's "millions of
users" scenario) the device spends most of its cycles on padding. This
module drains patches from *all* in-flight tasks into one shared queue
and dispatches fixed ``[B, ci, *pin]`` batches that mix patches across
tasks, keeping occupancy near 1 regardless of request shapes — the
Ragged Paged Attention idiom (PAPERS.md) applied to patch grids, with
PipeFusion's observation that the patch, not the chunk, is the natural
scheduling unit.

Bit-identity contract (tested in tests/serve/test_packer.py): packed
outputs equal the per-chunk fused path's outputs **bitwise**. The fused
program is ``gather -> forward*bump*valid -> per-batch scatter-add ->
normalize``; the packer replays the same math as three steps with the
same grouping:

1. *host prep* — the chunk's int->float32 normalization and edge padding
   are IEEE-exact operations, mirrored on the host (conversion and
   padding are value-copies/roundings with identical results on host
   and device); patches are gathered by host slicing (exact);
2. *shared forward program* (``("serve_forward",)`` in the inferencer's
   ProgramCache — ONE trace for all traffic): computes
   ``forward(params, patches) * bump * valid`` for a mixed batch. A real
   patch's row multiplies by valid=1.0 exactly as in the fused program;
   filler rows are discarded;
3. *per-task scatter program* (``("serve_scatter", run_shape)`` — keyed
   by the PR 2 compile-cache shape bucket, so ragged chunks that bucket
   together share one trace; ``("serve_scatter_fused", run_shape, tag)``
   when the fused Pallas kernel is selected, so a CHUNKFLOW_PALLAS flip
   rebuilds rather than reuses): rebuilds the task's ``[n_pad, ...]``
   weighted stack (missing = padding rows are exact zeros, which is
   bitwise what the fused program scatter-adds for validity-0 entries),
   then replays the *same* scan-over-batches accumulation — same
   ``ops.blend.make_accumulate`` step (the weighted flavor: weight-patch
   contributions computed inside the step, in the fused kernel's VMEM
   pass when selected), same batch grouping, same order — and the same
   ``normalize_blend``.

Provenance: every queued patch carries its request and patch index; the
dispatcher writes each forward row back into its request's stack, so a
mixed batch scatters back to the right task's accumulation buffers.

Kill switch: ``CHUNKFLOW_SERVE=0`` — :meth:`PatchPacker.submit` routes
every request through the untouched per-chunk path (``inferencer(...)``),
bit-identically and without building any serve program. Requests that
the packed path does not cover (legacy ``sharding=`` inferencers, fold
blend, dry-run) take the same fallback automatically, loudly counted as
``serving/fallbacks``. Unified-mesh inferencers stay eligible: the
shared forward dispatches through ``engine.serve_forward_program()``,
which builds the data-sharded batch program for ``data=N``/spatial
meshes and — ``CHUNKFLOW_MESH=pipeline=N`` (ISSUE 19) — the micro-batch
stage ring over the engine's stage protocol, with the micro-batch count
derived from the packed batch's shape at trace time so the kill-switch
slot widening re-traces instead of mis-slicing a stale count.

Telemetry (docs/observability.md "Serving"): ``serving/occupancy`` gauge
+ histogram (real patches per dispatched batch slot), ``serving/
queue_age`` histogram, ``serving/patch_queue`` gauge, ``serving/batches``
/ ``serving/packed_patches`` / ``serving/filler_slots`` /
``serving/fallbacks`` counters, ``serving/forward`` / ``serving/scatter``
spans (host-side only, GL007).
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Optional

import numpy as np

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.inference.patching import enumerate_patches, pad_to_batch

__all__ = [
    "serve_enabled", "RequestExpired", "PackerClosed", "PendingResult",
    "PatchPacker",
]

_OFF_VALUES = ("0", "off", "false", "no")


def serve_enabled() -> bool:
    """The serving kill switch (``CHUNKFLOW_SERVE``, default on).
    Re-read per call so tests and long-lived workers can flip it; off
    means every request takes the per-chunk batching path bit-identically
    and no serve program is ever built."""
    return os.environ.get("CHUNKFLOW_SERVE", "1").lower() not in _OFF_VALUES


class RequestExpired(RuntimeError):
    """The request's deadline passed before its patches completed; its
    remaining queued patches are dropped (``serving/deadline_missed``)."""


class PackerClosed(RuntimeError):
    """The packer was shut down while the request was still queued."""


class PendingResult:
    """One submitted request's completion handle: ``result(timeout)``
    blocks until the output chunk (or the failure) is ready."""

    __slots__ = ("_event", "_result", "_error", "trace_id")

    def __init__(self, trace_id: Optional[str] = None):
        self._event = threading.Event()
        self._result: Optional[Chunk] = None
        self._error: Optional[BaseException] = None
        self.trace_id = trace_id

    @property
    def done(self) -> bool:
        return self._event.is_set()

    def _complete(self, chunk: Chunk) -> None:
        self._result = chunk
        self._event.set()

    def _fail(self, exc: BaseException) -> None:
        if not self._event.is_set():
            self._error = exc
            self._event.set()

    def result(self, timeout: Optional[float] = None) -> Chunk:
        if not self._event.wait(timeout):
            raise TimeoutError("request still in flight")
        if self._error is not None:
            raise self._error
        return self._result


class _Request:
    """Per-request provenance + accumulation state. With the
    device-resident front half (ISSUE 15, the default) the request's
    chunk lives in ``device_chunk`` — uploaded ONCE, raw dtype — and
    ``patches`` stays None; the host front half (``CHUNKFLOW_GATHER=
    off`` or a raw-ineligible dtype) keeps the gathered host ``patches``
    list instead."""

    __slots__ = (
        "chunk", "handle", "deadline", "trace_id", "orig_zyx", "run_zyx",
        "n", "n_pad", "in_starts", "out_starts", "valid", "patches",
        "device_chunk", "weighted", "remaining", "lock", "enqueued_t",
        "queued_since",
    )

    def __init__(self, chunk, handle, deadline, trace_id,
                 queued_since=None):
        self.chunk = chunk
        self.handle = handle
        self.deadline = deadline
        self.trace_id = trace_id
        self.lock = threading.Lock()
        self.enqueued_t = time.time()
        # start of the request's serving/queue span (its admission, when
        # the front-end hands that in); None once the span is recorded
        self.queued_since = (self.enqueued_t if queued_since is None
                             else queued_since)

    @property
    def expired(self) -> bool:
        return self.deadline is not None and time.time() > self.deadline


def _host_float32(chunk: Chunk) -> np.ndarray:
    """The chunk payload as ``[ci, z, y, x]`` float32 on the host,
    mirroring ``Inferencer._infer``'s on-device normalization bitwise:
    int images scale to [0, 1] by ``1/iinfo.max`` (int->f32 conversion
    is exact, the f32 multiply is the same IEEE operation on host and
    device); float inputs round to f32 with the same IEEE
    round-to-nearest the device conversion applies."""
    arr = np.asarray(chunk.array)
    dt = np.dtype(chunk.dtype)
    if dt.kind in "iu":
        scale = np.float32(1.0 / np.iinfo(dt).max)
        arr = arr.astype(np.float32) * scale
    else:
        arr = np.asarray(arr, dtype=np.float32)
    if arr.ndim == 3:
        arr = arr[None]
    return arr


class PatchPacker:
    """Continuous cross-task patch batching around one
    :class:`~chunkflow_tpu.inference.inferencer.Inferencer`.

    ``submit`` is thread-safe (the serving front-end calls it from HTTP
    handler threads and lifecycle worker threads alike); all device work
    runs on one dispatcher thread, so program build and dispatch never
    race. ``max_wait_ms`` bounds how long a partial batch waits for more
    traffic before dispatching underfull — the latency/occupancy knob.
    """

    def __init__(self, inferencer, max_wait_ms: float = 2.0,
                 max_queue_patches: int = 4096):
        self.inferencer = inferencer
        self.batch_size = int(inferencer.batch_size)
        self.max_wait_s = max(0.0, float(max_wait_ms) / 1e3)
        self.max_queue_patches = int(max_queue_patches)
        self._cv = threading.Condition()
        self._items: deque = deque()  # (request, patch_index, enqueue_t)
        self._stop = False
        self._thread: Optional[threading.Thread] = None

    # -- eligibility ----------------------------------------------------
    def _eligible(self) -> bool:
        """Packed execution covers the scatter path — the serving shape.
        Legacy ``sharding=`` inferencers, fold blend and the kill switch
        fall back to the per-chunk program. A unified mesh
        (``CHUNKFLOW_MESH``, parallel/engine.py) stays eligible: the
        packed forward itself shards across the chips of the slice."""
        inf = self.inferencer
        return (
            serve_enabled()
            and inf.sharding == "none"
            and inf.blend_mode == "scatter"
            and not inf.dry_run
        )

    def _shard_engine(self):
        """The unified mesh engine behind this inferencer, or None for
        single-device serving. Re-resolved per batch so the
        ``CHUNKFLOW_MESH=1`` kill switch drops serving back to one chip
        mid-stream."""
        getter = getattr(self.inferencer, "shard_engine", None)
        return getter() if getter is not None else None

    def _slots(self) -> int:
        """Patch slots per dispatched device batch: the per-chip batch
        times the chips of the mesh — a pod-slice serving plane packs
        ``n_chips`` times more traffic per dispatch at the same per-chip
        occupancy accounting."""
        engine = self._shard_engine()
        chips = engine.spec.n_devices if engine is not None else 1
        return self.batch_size * chips

    # -- submission -----------------------------------------------------
    def submit(self, chunk: Chunk, deadline: Optional[float] = None,
               trace_id: Optional[str] = None,
               queued_since: Optional[float] = None) -> PendingResult:
        """Queue one request's patches for packed execution; returns a
        :class:`PendingResult`. ``deadline`` is an absolute ``time.time``
        deadline: patches still queued past it are dropped and the
        request fails with :class:`RequestExpired`. ``queued_since``
        (``time.time()``, default now) is where the request's
        ``serving/queue`` span starts; it ends at the first device batch
        that holds one of the request's patches. Ineligible requests
        (kill switch, sharded, fold, dry-run) complete synchronously
        through the per-chunk path, bit-identically."""
        handle = PendingResult(trace_id)
        if not self._eligible():
            telemetry.inc("serving/fallbacks")
            try:
                handle._complete(self.inferencer(chunk))
            except BaseException as exc:
                handle._fail(exc)
            return handle
        if chunk.all_zero():
            # same blank fast path the per-chunk program takes
            try:
                handle._complete(self.inferencer._blank_output(chunk))
            except BaseException as exc:
                handle._fail(exc)
            return handle

        req = _Request(chunk, handle, deadline, trace_id, queued_since)
        try:
            self._prepare(req)
        except BaseException as exc:
            handle._fail(exc)
            return handle
        with self._cv:
            if self._stop:
                handle._fail(PackerClosed("packer is shut down"))
                return handle
            while (len(self._items) + req.n > self.max_queue_patches
                   and self._items and not self._stop):
                # bounded queue: submission backpressure rather than
                # unbounded host memory under a traffic spike. The
                # `self._items` term keeps the predicate satisfiable: a
                # single request larger than the whole bound is admitted
                # once the queue has drained, instead of waiting on a
                # condition that can never become true (a request with
                # n > max_queue_patches used to hang submit forever)
                self._cv.wait(0.05)
            if self._stop:
                handle._fail(PackerClosed("packer is shut down"))
                return handle
            now = time.time()
            for i in range(req.n):
                self._items.append((req, i, now))
            telemetry.gauge("serving/patch_queue", len(self._items))
            self._ensure_thread()
            self._cv.notify_all()
        return handle

    def infer(self, chunk: Chunk, deadline: Optional[float] = None,
              timeout: Optional[float] = None,
              trace_id: Optional[str] = None,
              queued_since: Optional[float] = None) -> Chunk:
        """Synchronous convenience wrapper around :meth:`submit`."""
        return self.submit(chunk, deadline=deadline, trace_id=trace_id,
                           queued_since=queued_since).result(timeout)

    def _prepare(self, req: _Request) -> None:
        """Request prep: bucket padding, grid enumeration, provenance
        bookkeeping — and the chunk's ONE trip to the device.

        Device front half (the default): the chunk uploads ONCE in its
        raw dtype (uint8 ships 1/4 the bytes of the old per-patch f32
        re-uploads), is edge-padded to the bucket shape on device, and
        batches later gather patch rows from it by index
        (:meth:`_gather_program`) — per-chunk H2D drops from
        ~(patch/stride)^3 x to 1x chunk size. The ``CHUNKFLOW_GATHER=
        off`` kill switch (or a raw-ineligible dtype) restores the host
        gather bit-identically: conversion, edge-padding and slicing are
        IEEE-exact value copies that commute, so both fronts hand the
        forward program bitwise-equal batches."""
        import jax.numpy as jnp

        from chunkflow_tpu.core import profiling
        from chunkflow_tpu.ops import pallas_gather

        inf = self.inferencer
        chunk = req.chunk
        req.orig_zyx = tuple(chunk.shape[-3:])
        req.run_zyx = inf._run_shape(req.orig_zyx)
        grid = enumerate_patches(
            req.run_zyx,
            inf.input_patch_size,
            inf.output_patch_size,
            inf.output_patch_overlap,
        )
        in_starts, out_starts, valid = pad_to_batch(grid, self.batch_size)
        req.n = grid.num_patches
        req.n_pad = len(valid)
        req.in_starts = in_starts
        req.out_starts = out_starts
        req.valid = valid
        pin = tuple(inf.input_patch_size)
        pout = tuple(inf.output_patch_size)
        co = inf.num_output_channels
        pad = [(0, 0)] + [
            (0, r - s) for r, s in zip(req.run_zyx, req.orig_zyx)
        ]
        device_front = (
            pallas_gather.gather_mode() != "host"
            and pallas_gather.raw_eligible(chunk.dtype)
        )
        if device_front:
            arr = chunk.array
            if not chunk.is_on_device:
                arr = np.asarray(arr)
                profiling.note_h2d(arr.nbytes, key=("serve_gather",))
            arr = jnp.asarray(arr)  # the request's ONE H2D, raw dtype
            if arr.ndim == 3:
                arr = arr[None]
            if req.run_zyx != req.orig_zyx:
                # same edge-replicate the per-chunk path applies for
                # bucketing — on the raw dtype (pad commutes with the
                # conversion exactly)
                arr = jnp.pad(arr, pad, mode="edge")
            prepare, _ = pallas_gather.make_gather(
                inf.num_input_channels, pin)
            # resident form per leg: f32 once for the XLA gather, raw +
            # alignment pad for the Pallas kernel — applied here so
            # batches don't re-run it per dispatch
            req.device_chunk = prepare(arr)
            req.patches = None
        else:
            arr = _host_float32(chunk)
            if req.run_zyx != req.orig_zyx:
                # same edge-replicate the device path applies for bucketing
                arr = np.pad(arr, pad, mode="edge")
            req.device_chunk = None
            req.patches = [
                arr[:, s[0]:s[0] + pin[0], s[1]:s[1] + pin[1],
                    s[2]:s[2] + pin[2]]
                for s in in_starts[:req.n]
            ]
        # padding rows stay exact zeros: bitwise what the fused program's
        # validity-0 entries contribute to the scatter-add. Under the
        # fused pipeline (ops/blend.fused_pipeline_mode, ISSUE 17) a
        # device-front request keeps this stack DEVICE-resident: forward
        # rows overlay it in place (_overlay_program) and the scatter
        # program consumes it directly, so the weighted stack never
        # crosses the PCIe link between forward and blend. The
        # separate-programs leg's D2H+H2D round trip of the same stack
        # is scored as hbm_intermediate bytes (core/profiling.py).
        from chunkflow_tpu.ops import blend as blend_ops

        if device_front and blend_ops.fused_pipeline_mode() != "off":
            req.weighted = jnp.zeros((req.n_pad, co) + pout,
                                     dtype=jnp.float32)
        else:
            req.weighted = np.zeros((req.n_pad, co) + pout,
                                    dtype=np.float32)
        req.remaining = req.n

    # -- dispatcher -----------------------------------------------------
    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="patch-packer",
            )
            self._thread.start()

    def _next_batch(self):
        """Collect up to ``batch_size`` queued patches; a partial batch
        waits ``max_wait_s`` (from its oldest item) for more traffic
        before dispatching underfull."""
        with self._cv:
            while True:
                if self._items:
                    slots = self._slots()
                    oldest_t = self._items[0][2]
                    if (len(self._items) >= slots or self._stop
                            or time.time() - oldest_t >= self.max_wait_s):
                        batch = [
                            self._items.popleft()
                            for _ in range(min(slots, len(self._items)))
                        ]
                        telemetry.gauge("serving/patch_queue",
                                        len(self._items))
                        self._cv.notify_all()
                        return batch
                    self._cv.wait(
                        max(0.0005,
                            self.max_wait_s - (time.time() - oldest_t)))
                    continue
                if self._stop:
                    return None
                self._cv.wait(0.1)

    def _forward_program(self):
        inf = self.inferencer

        def build():
            import jax
            import jax.numpy as jnp

            from chunkflow_tpu.inference.bump import bump_const

            bump = bump_const(tuple(inf.output_patch_size))

            def program(patches, valid, params):
                with jax.named_scope("forward"):
                    preds = inf._forward(params, patches)
                # the same weighting expression, in the same order, as
                # the fused program's forward_batch (ops/blend.py)
                with jax.named_scope("accumulate"):
                    return preds * bump[None, None] * \
                        valid[:, None, None, None, None]

            # the packed batch buffer is packer-owned and dead after the
            # call (GL005): donate it into the program
            return jax.jit(program, donate_argnums=(0,))

        from chunkflow_tpu.ops.blend import pipeline_key

        # the forward math itself is pipeline-independent, but the tag
        # joins anyway (the every-key convention): a flip must never
        # leave ANY serving program keyed as if nothing changed
        return inf._programs.get(("serve_forward",) + pipeline_key(),
                                 build)

    def _gather_program(self):
        """The device-front batch assembler: gathers one packed batch's
        rows for ONE request out of its resident chunk and overlays them
        onto the accumulating batch via exact selection (``jnp.where``
        keeps other requests' rows — and signed zeros — untouched).
        Rows this request does not own carry mask 0 and starts (0,0,0).
        Keyed by the gather selection (``CHUNKFLOW_GATHER`` flips
        rebuild); jit handles chunk-shape/slot-count polymorphism."""
        inf = self.inferencer

        def build():
            import jax
            import jax.numpy as jnp

            from chunkflow_tpu.ops import pallas_gather

            _, gather = pallas_gather.make_gather(
                inf.num_input_channels, tuple(inf.input_patch_size))

            def program(chunk_like, starts, rowmask, acc):
                rows = gather(chunk_like, starts)
                with jax.named_scope("gather"):
                    mask = rowmask[:, None, None, None, None]
                    return jnp.where(mask > 0, rows, acc)

            # acc is packer-owned and dead after the call (GL005); the
            # resident chunk is NOT donated — later batches gather from it
            return jax.jit(program, donate_argnums=(3,))

        from chunkflow_tpu.ops.blend import pipeline_key
        from chunkflow_tpu.ops.pallas_gather import gather_key

        return inf._programs.get(
            ("serve_gather",) + gather_key() + pipeline_key(), build)

    def _overlay_program(self):
        """The fused-pipeline row writeback: scatters one packed batch's
        forward rows into ONE request's DEVICE-resident weighted stack
        (``weighted.at[idx].set(rows)``), so the stack never rides
        D2H+H2D between the forward and the blend. Rows this request
        does not own carry an out-of-bounds index (the ``n_pad``
        sentinel) and are dropped by the scatter's default FILL_OR_DROP
        mode; owned indices are unique and SET (not added), so every
        row keeps its exact bits — including signed zeros — which is
        what keeps packed fused-pipeline output bitwise equal to the
        round-trip leg. Keyed by the pipeline selection so a
        ``CHUNKFLOW_FUSED_PIPELINE`` flip rebuilds; jit handles
        (n_pad, slots) shape polymorphism."""
        inf = self.inferencer

        def build():
            import jax

            def program(weighted, rows, idx):
                with jax.named_scope("accumulate"):
                    return weighted.at[idx].set(rows)

            # the stack is packer-owned and replaced in place across
            # batches (GL005): donate it into each overlay. ``rows`` is
            # NOT donated — one batch may overlay several requests.
            return jax.jit(program, donate_argnums=(0,))

        from chunkflow_tpu.ops.blend import pipeline_key

        return inf._programs.get(("serve_overlay",) + pipeline_key(),
                                 build)

    def _scatter_program(self, run_zyx, n_pad):
        inf = self.inferencer

        def build():
            import jax
            import jax.numpy as jnp
            from jax import lax

            from chunkflow_tpu.inference.bump import bump_const
            from chunkflow_tpu.ops.blend import (
                make_accumulate,
                normalize_blend,
            )

            pout = tuple(inf.output_patch_size)
            co = inf.num_output_channels
            B = self.batch_size
            bump = bump_const(pout)
            # the weighted flavor: the forward program already applied
            # bump*valid to these rows; the weight-buffer contribution
            # (bump * validity, f32) is computed inside the step — in
            # the fused Pallas kernel's VMEM pass when selected
            _, accumulate_weighted, pad_y, pad_x = make_accumulate(
                pout, bump)
            out_dtype = inf.output_dtype
            zyx_buf = (run_zyx[0], run_zyx[1] + pad_y, run_zyx[2] + pad_x)
            num_batches = n_pad // B

            def program(weighted, valid, out_starts):
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
                    out, weight = accumulate_weighted(
                        out, weight, w, v, s_out)
                    return (out, weight), None

                (out, weight), _ = lax.scan(
                    step, (out0, w0), jnp.arange(num_batches)
                )
                if pad_y or pad_x:
                    with jax.named_scope("accumulate"):
                        out = out[:, :, : run_zyx[1], : run_zyx[2]]
                        weight = weight[:, : run_zyx[1], : run_zyx[2]]
                return normalize_blend(out, weight, out_dtype)

            # the assembled weighted stack is packer-owned and dead
            # after the call (GL005): donate it
            return jax.jit(program, donate_argnums=(0,))

        from chunkflow_tpu.ops.blend import kernel_tag, pipeline_key

        tag = kernel_tag()
        key = (("serve_scatter", tuple(run_zyx)) if tag == "scatter"
               else ("serve_scatter_fused", tuple(run_zyx), tag))
        return inf._programs.get(key + pipeline_key(), build)

    def _loop(self) -> None:
        while True:
            batch = self._next_batch()
            if batch is None:
                return
            try:
                self._run_batch(batch)
            except BaseException as exc:  # noqa: BLE001 — fail, don't die
                for req, _, _ in batch:
                    req.handle._fail(exc)
                # dispatcher-plane failures get their own counter; the
                # front-end owns the per-request outcome counters
                # (serving/errors, serving/deadline_missed) — one count
                # per request no matter who detected the failure first
                telemetry.inc("serving/packer_errors")

    def _run_batch(self, batch) -> None:
        import jax
        import jax.numpy as jnp

        inf = self.inferencer
        now = time.time()
        live = []
        for item in batch:
            req, _, enq_t = item
            if req.handle.done:
                continue  # already failed/expired: drop its patches
            if req.expired:
                req.handle._fail(RequestExpired(
                    f"deadline passed {now - req.deadline:.3f}s ago with "
                    f"patches still queued"))
                continue
            telemetry.observe("serving/queue_age", now - enq_t)
            if req.queued_since is not None:
                # the first batch that holds one of its patches: the
                # request stops waiting for the device here
                telemetry.record_span("serving/queue", req.queued_since,
                                      trace_id=req.trace_id)
                req.queued_since = None
            live.append(item)
        if not live:
            return
        engine = self._shard_engine()
        chips = engine.spec.n_devices if engine is not None else 1
        slots = self.batch_size * chips
        if len(live) > slots:
            # the batch was collected under a wider mesh than the one in
            # effect now (kill-switch race): widen this dispatch to the
            # next shardable multiple instead of dropping rows
            per = self.batch_size * chips
            slots = -(-len(live) // per) * per
        pin = tuple(inf.input_patch_size)
        ci = inf.num_input_channels
        valid_np = np.zeros((slots,), dtype=np.float32)
        host_rows = []  # (row, req, idx): host-front requests
        dev_rows: dict = {}  # id(req) -> (req, [(row, idx), ...])
        for row, (req, idx, _) in enumerate(live):
            valid_np[row] = 1.0
            if req.patches is not None:
                host_rows.append((row, req, idx))
            else:
                dev_rows.setdefault(id(req), (req, []))[1].append(
                    (row, idx))

        from chunkflow_tpu.core import profiling

        # host-front rows (kill switch / raw-ineligible dtypes) assemble
        # on the host and ride H2D gathered, as before
        batch_np = None
        if host_rows or not dev_rows:
            batch_np = np.zeros((slots, ci) + pin, dtype=np.float32)
            for row, req, idx in host_rows:
                batch_np[row] = req.patches[idx]
        # per-chip occupancy: live patches over every chip's slots — the
        # same gauge the single-chip serving plane feeds, now spanning
        # the slice (docs/multichip.md "The three seams")
        occupancy = len(live) / slots
        telemetry.gauge("serving/occupancy", occupancy)
        telemetry.gauge("serving/chips", float(chips))
        telemetry.inc("serving/batches")
        telemetry.inc("serving/packed_patches", len(live))
        telemetry.inc("serving/filler_slots", slots - len(live))

        if inf._device_params is None:
            inf._device_params = jax.device_put(inf.engine.params)

        # assemble the device batch: host-front rows upload gathered (the
        # pre-ISSUE-15 structure, counted at the staging seam); device-
        # front rows gather out of each request's RESIDENT chunk — no
        # patch bytes cross the PCIe link
        if batch_np is not None and (host_rows or not dev_rows):
            if host_rows:
                profiling.note_h2d(batch_np.nbytes, key=("serve_forward",))
            batch_dev = jnp.asarray(batch_np)
        else:
            batch_dev = jnp.zeros((slots, ci) + pin, dtype=jnp.float32)
        for req, rows in dev_rows.values():
            starts = np.zeros((slots, 3), dtype=np.int32)
            mask = np.zeros((slots,), dtype=np.float32)
            for row, idx in rows:
                starts[row] = req.in_starts[idx]
                mask[row] = 1.0
            gather = self._gather_program()
            batch_dev = gather(
                req.device_chunk, jnp.asarray(starts),
                jnp.asarray(mask), batch_dev,
            )

        program = (engine.serve_forward_program() if engine is not None
                   else self._forward_program())
        host_stack_rows = sum(
            isinstance(req.weighted, np.ndarray) for req, _, _ in live
        )
        with telemetry.span("serving/forward", occupancy=round(occupancy, 3)):
            out = program(
                batch_dev, jnp.asarray(valid_np),
                inf._device_params,
            )
            # the separate-programs leg materializes the forward rows on
            # the host (the inter-stage weighted-stack round trip the
            # fused pipeline deletes); fused-pipeline requests keep
            # everything on device and skip the D2H entirely
            out_np = np.asarray(out) if host_stack_rows else None

        if host_stack_rows:
            row_bytes = int(np.prod(out.shape[1:])) * out.dtype.itemsize
            profiling.note_hbm_intermediate(
                host_stack_rows * row_bytes, key=("serve_forward",))

        # fused-pipeline requests: overlay forward rows onto each
        # request's DEVICE-resident weighted stack in place
        dev_stack: dict = {}
        for row, (req, idx, _) in enumerate(live):
            if not isinstance(req.weighted, np.ndarray):
                dev_stack.setdefault(id(req), (req, []))[1].append(
                    (row, idx))
        for req, pairs in dev_stack.values():
            idx_np = np.full((slots,), req.n_pad, dtype=np.int32)
            for row, idx in pairs:
                idx_np[row] = idx
            overlay = self._overlay_program()
            with req.lock:
                req.weighted = overlay(req.weighted, out,
                                       jnp.asarray(idx_np))

        done = []
        for row, (req, idx, _) in enumerate(live):
            with req.lock:
                if isinstance(req.weighted, np.ndarray):
                    req.weighted[idx] = out_np[row]
                if req.patches is not None:
                    req.patches[idx] = None  # free the gathered input early
                req.remaining -= 1
                if req.remaining == 0:
                    req.device_chunk = None  # release the resident chunk
                    done.append(req)
        for req in done:
            try:
                self._finalize(req)
            except BaseException as exc:  # noqa: BLE001
                req.handle._fail(exc)
                telemetry.inc("serving/packer_errors")

    def _finalize(self, req: _Request) -> None:
        """All of the request's patches are forwarded: replay the fused
        program's scan-over-batches accumulation and hand the result
        through the inferencer's shared post-processing."""
        import jax.numpy as jnp

        if req.expired:
            req.handle._fail(RequestExpired("deadline passed at finalize"))
            return
        program = self._scatter_program(req.run_zyx, req.n_pad)
        if isinstance(req.weighted, np.ndarray):
            # the separate-programs leg re-uploads the stack the forward
            # just downloaded — the second half of the inter-stage round
            # trip the fused pipeline deletes (~0 bytes on that leg)
            from chunkflow_tpu.core import profiling

            profiling.note_hbm_intermediate(
                req.weighted.nbytes, key=("serve_scatter",))
        with telemetry.span("serving/scatter"):
            result = program(
                jnp.asarray(req.weighted), jnp.asarray(req.valid),
                jnp.asarray(req.out_starts),
            )
            result.block_until_ready()
        req.weighted = None
        out = self.inferencer._postprocess_result(
            result, req.chunk, req.orig_zyx, req.run_zyx)
        shape = getattr(getattr(out, "array", None), "shape", None)
        if shape:
            voxels = 1
            for length in shape[-3:]:
                voxels *= int(length)
            telemetry.inc("inference/voxels", float(voxels))
        req.handle._complete(out)

    # -- teardown -------------------------------------------------------
    def close(self, drain: bool = True, timeout: float = 30.0) -> None:
        """Stop the dispatcher. ``drain=True`` (default) lets queued
        patches finish first; ``drain=False`` fails still-queued
        requests with :class:`PackerClosed`."""
        with self._cv:
            if not drain:
                while self._items:
                    req, _, _ = self._items.popleft()
                    req.handle._fail(PackerClosed("packer closed"))
            self._stop = True
            self._cv.notify_all()
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=timeout)
