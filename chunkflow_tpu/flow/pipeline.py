"""Double-buffered chunk pipeline: H2D staging / compute / D2H overlap.

The reference's production loop is strictly sequential per task — load,
forward, blend, save, repeat — with the device idle during every host
transfer (its acknowledged hot spot, SURVEY §3.2). PipeFusion (PAPERS.md)
shows patch-level pipelining of exactly this shape recovers the stalled
bandwidth. This module is the chunk-level analog, built on three facts of
the JAX execution model:

1. ``jax.device_put`` is asynchronous — staging chunk *k+1* host→device
   costs the host a call, not a wait, while chunk *k* computes; the
   staging ring ships each chunk ONCE, in its RAW dtype (uint8 at 1/4
   the bytes of float32 — conversion happens inside the program's
   device-resident front half, ops/pallas_gather.py), and every upload
   counts ``transfer/h2d_bytes``/``transfer/h2d_chunks`` at the
   ``Chunk.device`` seam;
2. dispatch is asynchronous — ``infer_async`` enqueues chunk *k*'s fused
   program and starts the result's ``copy_to_host_async`` without
   blocking;
3. the inference programs donate their chunk argument
   (``donate_argnums=(0,)``), so a staged ring slot's buffer is recycled
   into the program's accumulators instead of allocated per chunk — the
   ring is "pre-allocated" in the only sense an immutable-array runtime
   admits: XLA aliases, rather than reallocates, the slot.

Steady state, ring=2::

    host:    stage k+1 ──────▶ stage k+2 ─────▶ ...
    device:  compute k ───────▶ compute k+1 ──▶ ...
    D2H:     drain k−1 ───────▶ drain k ──────▶ ...

``block_until_ready`` happens only at drain time (inside ``.host()``),
when the async D2H copy has usually already landed.

Memory bound: at most ``ring`` staged inputs plus ``ring`` (or ``depth``,
for the task stage) in-flight outputs are device-resident. Sizing: ring=2
(double buffer) saturates whenever one phase dominates; ring=3 only helps
when stage/compute/drain times are all comparable — see
docs/performance.md "Sizing the ring".

Ownership contract: a chunk handed to :meth:`Inferencer.stage` becomes
PIPELINE-OWNED; the executor passes it to ``infer_async(consume=True)``
and the program donates (invalidates) its buffer. Callers keep ownership
of everything they pass in at the API surface (``pipeline_chunks`` stages
internally; it never donates caller arrays).

This module is the STATIC primitive layer: fixed depths, chosen by the
caller. flow/scheduler.py builds the adaptive unified scheduler on the
same spans and the same ownership contract (and reuses ``_drain_host``);
``CHUNKFLOW_SCHED=static`` routes everything back here bit-identically.
"""
from __future__ import annotations

import time
from collections import deque
from typing import Callable, Iterable, Iterator, Optional

from chunkflow_tpu.core import telemetry


def _drain_host(out):
    """Materialize a dispatched output on the host, attributing the wait:
    ``pipeline/compute`` is the block-until-the-program-finished portion
    (device still busy when the host arrived — a compute-bound pipeline
    accumulates its stall here), ``pipeline/drain`` the remaining D2H
    copy wait. Both are HOST-side waits around, never inside, the
    compiled program (GL007)."""
    arr = getattr(out, "array", None)
    if hasattr(arr, "block_until_ready"):
        with telemetry.span("pipeline/compute"):
            arr.block_until_ready()
    with telemetry.span("pipeline/drain"):
        host = out.host()
    # inference/voxels + the span totals give achieved Mvox/s per worker
    # (fleet-status, docs/observability.md "Device program view")
    shape = getattr(getattr(host, "array", None), "shape", None)
    if shape:
        voxels = 1
        for length in shape[-3:]:
            voxels *= int(length)
        telemetry.inc("inference/voxels", float(voxels))
    return host


def _device_pipeline(inferencer, chunks: Iterable, ring: int, crop=None):
    """Yield DEVICE-resident output chunks (D2H already riding) in input
    order, overlapping stage(k+1) / compute(k) / drain(k−1)."""
    ring = max(1, int(ring))
    staged: deque = deque()    # ring slots: (staged_chunk, pipeline_owned)
    draining: deque = deque()  # dispatched outputs, D2H in flight
    it = iter(chunks)
    exhausted = False
    while True:
        while not exhausted and len(staged) < ring:
            try:
                chunk = next(it)
            except StopIteration:
                exhausted = True
                break
            with telemetry.span("pipeline/stage"):
                slot = inferencer.stage(chunk)
            # donate only buffers this pipeline staged itself; a chunk
            # that arrived already device-resident (e.g. prefetch
            # --to-device) still belongs to the caller's task
            staged.append((slot, slot is not chunk))
            telemetry.gauge("pipeline/ring_occupancy", len(staged))
        if not staged:
            break
        # dispatch the oldest staged slot; an owned buffer is donated
        # into the program, freeing the ring slot in the same breath
        slot, owned = staged.popleft()
        with telemetry.span("pipeline/dispatch"):
            out = inferencer.infer_async(slot, crop=crop, consume=owned)
        draining.append(out)
        telemetry.gauge("pipeline/inflight", len(draining))
        while len(draining) >= ring:
            yield draining.popleft()
    while draining:
        yield draining.popleft()


def pipeline_chunks(
    inferencer,
    chunks: Iterable,
    ring: int = 2,
    crop=None,
    postprocess: Optional[Callable] = None,
    post_depth: int = 2,
) -> Iterator:
    """Run chunks through the double-buffered executor; yield results in
    input order.

    Without ``postprocess``: yields host-resident output chunks — the
    only blocking wait is the drain-time ``.host()``.

    With ``postprocess`` (callable ``Chunk -> T``): the drain wait AND
    the host post-processing stage both move to a background worker
    thread, overlapping the next chunk's device time (the native kernels
    release the GIL for the duration of the C call). Yields
    ``postprocess(chunk)`` results in input order, at most ``post_depth``
    in flight; abandoning the generator early cancels queued
    (not-yet-started) postprocess tasks — the one already running
    completes (a C call cannot be interrupted).
    """
    if postprocess is None:
        for out in _device_pipeline(inferencer, chunks, ring, crop=crop):
            yield _drain_host(out)
        return

    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=1) as pool:
        in_flight: deque = deque()
        try:
            for out in _device_pipeline(inferencer, chunks, ring, crop=crop):
                while len(in_flight) >= post_depth:
                    yield in_flight.popleft().result()
                # .host() inside the worker: the block-until-ready wait
                # ALSO moves off the dispatch thread (spans are
                # thread-safe; the compute/drain attribution rides along)
                in_flight.append(
                    pool.submit(lambda c=out: postprocess(_drain_host(c)))
                )
            while in_flight:
                yield in_flight.popleft().result()
        finally:
            # early close / error: don't run (or silently swallow)
            # abandoned host stages during executor shutdown
            for f in in_flight:
                f.cancel()


def stage_task_chunks(task: dict) -> dict:
    """Start the async H2D transfer of every chunk-like payload in a task
    dict (the flow-runtime unit of work). Shared by ``prefetch
    --to-device`` and the pipelined inference stage so "staging" means
    one thing everywhere."""
    for key, value in list(task.items()):
        if hasattr(value, "device") and hasattr(value, "is_on_device"):
            if not value.is_on_device:
                task[key] = value.device()
    return task


def pipelined_inference_stage(
    inferencer,
    depth: int = 2,
    ring: int = 2,
    input_name: str = "chunk",
    output_name: str = "chunk",
    op_name: str = "inference",
    crop=None,
    check: Optional[Callable] = None,
):
    """A flow-runtime stage (iterator of tasks -> iterator of tasks) that
    routes each task's chunk through the double-buffered executor.

    ``depth`` bounds dispatched-but-undrained outputs (the CLI's
    ``--async-depth`` contract); ``ring`` bounds staged-ahead inputs. At
    most ``ring + depth`` tasks are device-resident. ``check`` (e.g. the
    --patch-num grid assertion) runs before a task enters the ring.

    Ordering/failure contract (same as the synchronous path): results
    yield in input order; a ``None`` skip marker flushes all in-flight
    work first; a mid-stream exception flushes already-dispatched tasks
    downstream — they may already have side effects pending — then
    re-raises. Per-op timers measure stage-to-materialize wall time,
    which overlaps across tasks and so sums to more than elapsed time.
    """
    depth = max(1, int(depth))
    ring = max(1, int(ring))

    def stage_fn(stream):
        staged: deque = deque()   # (task, staged_chunk, owned, t0)
        pending: deque = deque()  # (task, device_out, t0)

        def finalize(entry):
            task, out, t0 = entry
            # crop already applied on device; _drain_host splits the wait
            # into pipeline/compute + pipeline/drain spans
            with telemetry.task_context(task.get("trace_id")):
                task[output_name] = _drain_host(out)
            task["log"]["timer"][op_name] = time.time() - t0
            task["log"]["compute_device"] = inferencer.compute_device
            return task

        def dispatch_one():
            task, slot, owned, t0 = staged.popleft()
            with telemetry.task_context(task.get("trace_id")), \
                    telemetry.span("pipeline/dispatch"):
                out = inferencer.infer_async(slot, crop=crop, consume=owned)
            pending.append((task, out, t0))
            telemetry.gauge("pipeline/inflight", len(pending))

        try:
            for task in stream:
                if task is None:
                    # preserve order: flush in-flight work before passing
                    # the skip marker downstream
                    while staged:
                        dispatch_one()
                    while pending:
                        yield finalize(pending.popleft())
                    yield task
                    continue
                chunk = task[input_name]
                if check is not None:
                    check(chunk)
                with telemetry.task_context(task.get("trace_id")), \
                        telemetry.span("pipeline/stage"):
                    slot = inferencer.stage(chunk)
                # donate only pipeline-staged buffers: a chunk that was
                # already device-resident stays valid in the task dict
                # (it may be read downstream under another name)
                staged.append((task, slot, slot is not chunk, time.time()))
                telemetry.gauge("pipeline/ring_occupancy", len(staged))
                if len(staged) >= ring:
                    # drain BEFORE dispatching so at most `depth` outputs
                    # are ever in flight (the documented memory bound)
                    while len(pending) >= depth:
                        yield finalize(pending.popleft())
                    dispatch_one()
        except Exception:
            # a mid-stream failure (bad grid, upstream error) must not
            # drop already-dispatched tasks the synchronous path would
            # have saved; push what completed downstream, then re-raise.
            # (except, not finally: a yield in finally would break
            # generator close(), which raises GeneratorExit here.)
            while staged:
                dispatch_one()
            while pending:
                yield finalize(pending.popleft())
            raise
        while staged:
            while len(pending) >= depth:
                yield finalize(pending.popleft())
            dispatch_one()
        while pending:
            yield finalize(pending.popleft())

    return stage_fn
