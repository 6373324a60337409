"""Unified adaptive pipeline scheduler: the full task lifecycle as one
bounded-queue executor with telemetry-driven depth control.

The worker's value proposition is keeping the accelerator busy while
petabyte-scale IO happens around it (PAPER §3: load → inference → save
per task), yet until this module the overlap machinery was three
independent mechanisms composed by hand — ``prefetch_stage``
(runtime.py), the double-buffered device pipeline (pipeline.py), and
``save --async-write`` — each with a fixed, hand-picked depth and no
shared backpressure. PR 3's stall attribution tells us *which* phase
dominates; nothing consumed that signal. This module closes the loop:

    upstream (load ops) ──► prefetch queue ──► H2D staging ring ──►
    device compute ──► D2H drain + host post-processing (worker pool)
    ──► downstream (save ops) ──► write-behind window (async commits)

Every arrow is a bounded queue; every bound is a **depth knob** a small
controller (:class:`DepthController`) widens at runtime by reading the
telemetry stall shares (core/telemetry.py) every N tasks:

=====================  =======================  =========================
dominant stall phase   meaning                  knob raised
=====================  =======================  =========================
scheduler/load         upstream IO starves us   ``prefetch`` (pull ahead)
pipeline/stage         H2D transfers wait       ``prefetch``
pipeline/dispatch      trace/compile            none (see retrace watchdog)
pipeline/compute       the chip is the limit    none — that's the goal
pipeline/drain         D2H + host side lag      ``post`` and ``write``
scheduler/post         host post ops lag        ``post``
scheduler/write        storage commits lag      ``write``
=====================  =======================  =========================

Growth is bounded by a hard host-memory watermark
(``CHUNKFLOW_SCHED_MEM_GB``, default 4): the controller estimates
resident bytes as (sum of depths) x (largest chunk seen) and refuses any
raise that would cross it — graceful fallback to the static initial
depths (``--async-depth`` / ``--prefetch-depth`` on the CLI). With
telemetry off (``CHUNKFLOW_TELEMETRY=0``) there is no stall signal, so
the depths simply stay static.

Kill switch: ``CHUNKFLOW_SCHED=static`` removes this module from the hot
path entirely — the CLI composes the PR 2 primitives exactly as before
(bit-identical, by construction), and ``Inferencer.stream`` falls back
to ``pipeline_chunks``. Outputs are bit-identical either way (same
compiled programs, same staging ownership contract); only wall-clock and
timer attribution differ.

Ownership contract is inherited from flow/pipeline.py: buffers staged by
the executor are donated into the program (``consume=True``); anything
that arrived already device-resident stays caller-owned.

The staging ring ships each chunk ONCE in its RAW dtype (ISSUE 15): the
host pad/convert phase no longer exists — shape-bucket padding and the
int->f32 normalization run device-side inside the program's gather front
(ops/pallas_gather.py), so a uint8 task crosses PCIe at 1/4 the float32
bytes and exactly 1x chunk size (``transfer/h2d_bytes`` at the
``Chunk.device`` seam is the proof).
"""
from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from typing import Callable, Iterable, Iterator, Optional

from chunkflow_tpu.core import profiling, telemetry
from chunkflow_tpu.flow.pipeline import _drain_host
from chunkflow_tpu.parallel.lifecycle import (
    surrender_task as _surrender_task,
    tag_culprit as _tag_culprit,
)
from chunkflow_tpu.testing import chaos

__all__ = [
    "scheduler_mode", "mem_watermark_bytes", "DepthController",
    "schedule_chunks", "scheduled_inference_stage", "write_behind_stage",
    "sample_device_memory", "reserve_host_bytes", "release_host_bytes",
    "external_resident_bytes",
]

_OFF_VALUES = ("static", "0", "off", "false", "no")


def scheduler_mode() -> str:
    """``adaptive`` (default) or ``static`` (``CHUNKFLOW_SCHED=static``
    kill switch: today's hand-composed pipeline, bit-identically).
    Re-read per call so tests and long-lived workers can flip it."""
    value = os.environ.get("CHUNKFLOW_SCHED", "adaptive").lower()
    return "static" if value in _OFF_VALUES else "adaptive"


def mem_watermark_bytes() -> int:
    """Hard host-memory watermark for adaptive depth growth
    (``CHUNKFLOW_SCHED_MEM_GB``, default 4 GB). The controller never
    widens a depth past it; a malformed value falls back to the
    default rather than disabling backpressure."""
    raw = os.environ.get("CHUNKFLOW_SCHED_MEM_GB", "")
    try:
        gb = float(raw) if raw else 4.0
    except ValueError:
        gb = 4.0
    return int(gb * (1 << 30))


# ---------------------------------------------------------------------------
# shared host-memory reservations (scheduler depths + serving admission)
# ---------------------------------------------------------------------------
_EXT_LOCK = threading.Lock()
_EXT_BYTES = 0


def reserve_host_bytes(nbytes: int) -> bool:
    """Reserve host-resident bytes against the scheduler's memory
    watermark on behalf of a plane *outside* the pipeline executor — the
    serving front-end reserves each admitted request's working set here
    (docs/serving.md "Backpressure"). Returns False (nothing reserved)
    when the reservation would cross ``CHUNKFLOW_SCHED_MEM_GB``; the
    caller should reject/shed rather than admit. The depth controller
    sees these reservations too (:meth:`DepthController._would_fit`), so
    a busy serving plane also holds pipeline depth growth — one
    watermark, every consumer."""
    global _EXT_BYTES
    nbytes = max(0, int(nbytes))
    with _EXT_LOCK:
        if _EXT_BYTES + nbytes > mem_watermark_bytes():
            return False
        _EXT_BYTES += nbytes
        total = _EXT_BYTES
    telemetry.gauge("scheduler/external_bytes", total)
    return True


def release_host_bytes(nbytes: int) -> None:
    """Return a :func:`reserve_host_bytes` reservation."""
    global _EXT_BYTES
    nbytes = max(0, int(nbytes))
    with _EXT_LOCK:
        _EXT_BYTES = max(0, _EXT_BYTES - nbytes)
        total = _EXT_BYTES
    telemetry.gauge("scheduler/external_bytes", total)


def external_resident_bytes() -> int:
    """Bytes currently reserved by non-pipeline planes (serving)."""
    with _EXT_LOCK:
        return _EXT_BYTES


def _controller_interval() -> int:
    """Tasks between controller ticks (``CHUNKFLOW_SCHED_INTERVAL``,
    default 4)."""
    try:
        return max(1, int(os.environ.get("CHUNKFLOW_SCHED_INTERVAL", "4")))
    except ValueError:
        return 4


#: initial depths when the caller does not override them; the CLI wires
#: --prefetch-depth / --async-depth in as initial values
DEFAULT_DEPTHS = {
    "prefetch": 2,  # tasks pulled ahead from upstream (load overlap)
    "ring": 2,      # staged-ahead H2D inputs (the PR 2 double buffer)
    "inflight": 2,  # dispatched-but-undrained device outputs
    "post": 2,      # drain + host post-processing tasks in the worker pool
    "write": 2,     # tasks with storage writes still in flight
    "storage": 8,   # driver requests in flight per cutout (volume/storage.py;
                    # floored at the live read_concurrency() in __init__)
}

#: growth ceilings — past these, more depth is more memory for no overlap
DEPTH_LIMITS = {
    "prefetch": 8, "ring": 4, "inflight": 8, "post": 4, "write": 8,
    "storage": 32,
}

#: stall phase -> knobs the controller widens when that phase dominates
PHASE_KNOBS = {
    "scheduler/load": ("prefetch", "storage"),
    "pipeline/stage": ("prefetch",),
    "pipeline/dispatch": (),  # compile time: a knob can't help (watchdog can)
    "pipeline/compute": (),   # device-bound is the design goal
    "pipeline/drain": ("post", "write"),
    "scheduler/post": ("post",),
    "scheduler/write": ("write",),
}


class DepthController:
    """Widens the dominant-stall stage's depth under a memory watermark.

    Pure decision logic: :meth:`tick` takes *cumulative* per-phase stall
    totals (seconds) and mutates :attr:`depths`; :meth:`observe_task`
    is the executor-facing wrapper that samples the process telemetry
    registry every ``interval`` completed tasks. Unit-testable on
    synthetic stall streams without any executor or clock.
    """

    PHASES = tuple(PHASE_KNOBS)

    def __init__(self, depths: Optional[dict] = None,
                 limits: Optional[dict] = None,
                 interval: Optional[int] = None,
                 watermark_bytes: Optional[int] = None,
                 min_share: float = 0.4):
        self.depths = dict(DEFAULT_DEPTHS)
        if depths:
            self.depths.update(
                {k: max(1, int(v)) for k, v in depths.items()})
        # a caller-raised initial depth also raises that knob's ceiling:
        # explicit static configuration outranks the built-in caps
        self.limits = {
            k: max(v, self.depths.get(k, 0))
            for k, v in dict(DEPTH_LIMITS, **(limits or {})).items()
        }
        # the storage knob mirrors the live per-cutout bound on driver
        # requests in flight (volume/storage.py): start from whatever the env
        # knob resolved to, so the first controller raise widens it
        # instead of clamping it back down
        from chunkflow_tpu.volume import storage as _vol_storage

        if not depths or "storage" not in depths:
            self.depths["storage"] = max(
                self.depths.get("storage", 1),
                _vol_storage.read_concurrency(),
            )
        self.limits["storage"] = max(
            self.limits.get("storage", 1), self.depths["storage"]
        )
        self.initial = dict(self.depths)
        self.interval = interval if interval else _controller_interval()
        self.watermark_bytes = (
            watermark_bytes if watermark_bytes is not None
            else mem_watermark_bytes()
        )
        self.min_share = min_share
        self.changes: list = []  # (task_index, knob, old, new)
        self._slot_bytes = 0
        self._tasks = 0
        # baseline at construction: deltas measure THIS run's stalls, not
        # whatever the process-global registry accumulated before us
        self._last_totals = telemetry.hist_totals(self.PHASES)

    # -- memory model ---------------------------------------------------
    def note_slot_bytes(self, nbytes: int) -> None:
        """Feed the observed chunk payload size; the watermark check uses
        the largest slot seen (conservative: every depth unit may hold
        one input and one output of that size)."""
        self._slot_bytes = max(self._slot_bytes, int(nbytes))

    def resident_slots(self) -> int:
        # the storage knob bounds the requests of ONE cutout, which
        # together never hold more than that cutout's blocks: no slot of
        # its own however deep it is
        return sum(
            v for k, v in self.depths.items() if k != "storage"
        )

    def _would_fit(self) -> bool:
        # 2x: each slot can pin an input and an output chunk at once;
        # serving-plane reservations (reserve_host_bytes) count against
        # the same watermark, so depth growth yields to live traffic
        per_slot = 2 * max(self._slot_bytes, 1)
        return ((self.resident_slots() + 1) * per_slot
                + external_resident_bytes() <= self.watermark_bytes)

    # -- decision -------------------------------------------------------
    def tick(self, totals: dict) -> list:
        """One controller step over *cumulative* per-phase stall totals.
        Returns the list of (knob, old, new) changes applied (empty when
        nothing dominates, the watermark blocks growth, or the dominant
        phase has no knob)."""
        deltas = {
            phase: max(0.0, float(totals.get(phase, 0.0))
                       - self._last_totals.get(phase, 0.0))
            for phase in self.PHASES
        }
        self._last_totals = {
            phase: float(totals.get(phase, self._last_totals.get(phase, 0.0)))
            for phase in self.PHASES
        }
        window = sum(deltas.values())
        if window <= 0.0:
            return []
        dominant = max(deltas, key=deltas.get)
        share = deltas[dominant] / window
        # anomaly feed (core/profiling.py): a dominant share that holds
        # above the capture threshold for K consecutive ticks triggers
        # one bounded profiler window — the bottleneck this controller
        # could not widen away is exactly what a trace should explain
        profiling.note_stall(dominant, share)
        if share < self.min_share:
            return []  # no clear bottleneck: depths are matched, stand pat
        applied = []
        for knob in PHASE_KNOBS[dominant]:
            old = self.depths[knob]
            if old >= self.limits[knob] or not self._would_fit():
                continue  # ceiling or watermark: graceful static fallback
            self.depths[knob] = old + 1
            if knob == "storage":
                # push the widened bound to the live storage plane
                # (volume/storage.py reads it once a cutout)
                from chunkflow_tpu.volume import storage as _vol_storage

                _vol_storage.set_read_concurrency(old + 1)
            applied.append((knob, old, old + 1))
            self.changes.append((self._tasks, knob, old, old + 1))
            telemetry.event(
                "depth_change", f"scheduler/{knob}", old=old, new=old + 1,
                tasks=self._tasks, dominant=dominant,
                share=round(deltas[dominant] / window, 3),
            )
            telemetry.gauge(f"scheduler/depth/{knob}", old + 1)
        return applied

    def observe_task(self) -> list:
        """Count one completed task; every ``interval`` tasks, read the
        telemetry registry and :meth:`tick`. With telemetry disabled the
        totals stay zero and the depths stay static — the documented
        graceful fallback."""
        self._tasks += 1
        if self._tasks % self.interval:
            return []
        return self.tick(telemetry.hist_totals(self.PHASES))


# ---------------------------------------------------------------------------
# bounded handoff queue with live-adjustable capacity
# ---------------------------------------------------------------------------
_END = object()


def _note_mesh(inferencer) -> None:
    """One scheduler/mesh event when the stream's inferencer runs the
    unified multi-chip engine (parallel/engine.py) — the whole pipeline
    (H2D staging, device compute, D2H drain) then overlaps across every
    chip of the slice, and the log-summary reader can attribute the
    stream's throughput to its mesh (docs/multichip.md)."""
    getter = getattr(inferencer, "shard_engine", None)
    if getter is None:
        return
    try:
        engine = getter()
    except Exception:
        return  # a malformed CHUNKFLOW_MESH fails at dispatch, loudly
    if engine is not None:
        telemetry.event(
            "scheduler", "mesh",
            mesh=engine.spec.describe(),
            devices=engine.spec.n_devices,
        )


def _is_end(item) -> bool:
    return isinstance(item, tuple) and len(item) == 2 and item[0] is _END


class _AdaptiveQueue:
    """Producer/consumer handoff whose capacity the controller can raise
    live (stdlib ``queue.Queue`` fixes ``maxsize`` at construction)."""

    def __init__(self, capacity: int):
        self._lock = threading.Lock()
        self._not_empty = threading.Condition(self._lock)
        self._not_full = threading.Condition(self._lock)
        self._items: deque = deque()
        self._capacity = max(1, int(capacity))
        self._closed = False

    def set_capacity(self, capacity: int) -> None:
        with self._lock:
            self._capacity = max(1, int(capacity))
            self._not_full.notify_all()

    def put(self, item) -> bool:
        """Bounded put; returns False once the consumer has closed the
        queue (producer should stop pulling upstream)."""
        with self._not_full:
            while len(self._items) >= self._capacity and not self._closed:
                self._not_full.wait(0.1)
            if self._closed:
                return False
            self._items.append(item)
            self._not_empty.notify()
            return True

    def get(self):
        with self._not_empty:
            while not self._items:
                self._not_empty.wait()
            item = self._items.popleft()
            self._not_full.notify()
            return item

    def close(self) -> None:
        """Consumer-side: unblock and retire the producer for good.
        Items still buffered are SURRENDERED, not dropped: a supervised
        task claimed after the failure handler's in-flight snapshot
        would otherwise leak its queue lease until the visibility
        timeout (lifecycle.surrender_task)."""
        with self._lock:
            self._closed = True
            leftovers = list(self._items)
            self._items.clear()
            self._not_full.notify_all()
            self._not_empty.notify_all()
        for item in leftovers:
            if not _is_end(item):
                _surrender_task(item)


def _pump(source: Iterator, q: _AdaptiveQueue) -> None:
    """Producer body: pull upstream (this is where load-operator IO
    actually runs) into the bounded queue; terminate with an (_END, exc)
    sentinel on every path so the consumer never blocks forever. An item
    refused because the consumer closed mid-pull is surrendered — it may
    be a queue task this thread claimed a breath after the chain-failure
    handler resolved the in-flight set (lifecycle.surrender_task)."""
    try:
        for item in source:
            if not q.put(item):
                _surrender_task(item)
                return  # consumer gone: stop pulling upstream
    except BaseException as exc:  # propagate to the consumer thread
        q.put((_END, exc))
        return
    q.put((_END, None))


def _start_pump(source: Iterable, capacity: int):
    q = _AdaptiveQueue(capacity)
    # the operators in front of `inference` (fetch, load, mask) run here:
    # a span's `thread` field says so by name
    thread = threading.Thread(
        target=_pump, args=(iter(source), q), daemon=True,
        name="scheduler-pump",
    )
    thread.start()
    return q, thread


def _chunk_nbytes(chunk) -> int:
    arr = getattr(chunk, "array", chunk)
    return int(getattr(arr, "nbytes", 0) or 0)


# ---------------------------------------------------------------------------
# device-memory gauges: the HBM watermark plane (sampled at drain time)
# ---------------------------------------------------------------------------
# A failed probe (no jax, or no local device reported memory_stats())
# used to latch the plane off for the process lifetime — one transient
# hiccup and device memory went dark forever (ISSUE 18 satellite).
# Instead the probe now backs off: after a failure the next
# ``_DEVICE_MEM_SKIPS_LEFT`` drains are free no-ops, then it re-probes,
# doubling the skip window per consecutive failure up to
# ``CHUNKFLOW_DEVICE_MEM_REPROBE`` drains (default 64) — a CPU backend
# pays a cheap probe every ~64 tasks, a TPU whose runtime stuttered once
# recovers within a few drains. Mutated without a lock on purpose: the
# worst race outcome is one extra (idempotent) probe, and the existing
# flag has always been lock-free.
_DEVICE_MEM_UNSUPPORTED = False   # currently backing off
_DEVICE_MEM_SKIPS_LEFT = 0        # drains to skip before the next re-probe
_DEVICE_MEM_FAILURES = 0          # consecutive failed probes


def _device_mem_reprobe_cap() -> int:
    raw = os.environ.get("CHUNKFLOW_DEVICE_MEM_REPROBE", "")
    try:
        return max(1, int(raw)) if raw else 64
    except ValueError:
        return 64


def _note_device_mem_failure() -> None:
    global _DEVICE_MEM_UNSUPPORTED, _DEVICE_MEM_SKIPS_LEFT, \
        _DEVICE_MEM_FAILURES
    _DEVICE_MEM_FAILURES += 1
    _DEVICE_MEM_SKIPS_LEFT = min(
        8 * (2 ** (_DEVICE_MEM_FAILURES - 1)), _device_mem_reprobe_cap()
    )
    _DEVICE_MEM_UNSUPPORTED = True


def sample_device_memory() -> None:
    """Fold per-chip ``jax.Device.memory_stats()`` into the HBM
    watermark plane, sampled at task drain time so memory pressure shows
    up in ``/metrics`` and ``log-summary`` next to the scheduler's host
    watermark:

    - ``device/chip/<i>/bytes_in_use`` / ``device/chip/<i>/peak_bytes``
      per reporting chip (rendered with a ``chip`` label on /metrics and
      sparklined by the timeseries ring — gauges ride the sampler for
      free);
    - ``device/chip/<i>/hbm_headroom`` = ``bytes_limit − bytes_in_use``
      when the backend reports a limit;
    - the historical ``device/bytes_in_use`` / ``device/peak_bytes``
      aggregates (summed over reporting chips), plus
      ``device/hbm_headroom`` — the WORST chip's headroom, the number
      that says how close the next allocation is to an OOM.

    Chips that fail to report are skipped (partial results stand);
    a probe where NO chip reports backs off per the module note above
    instead of latching the plane off forever."""
    global _DEVICE_MEM_UNSUPPORTED, _DEVICE_MEM_SKIPS_LEFT, \
        _DEVICE_MEM_FAILURES
    if not telemetry.enabled():
        return
    if _DEVICE_MEM_UNSUPPORTED:
        if _DEVICE_MEM_SKIPS_LEFT > 0:
            _DEVICE_MEM_SKIPS_LEFT -= 1
            return
        # skip window drained: fall through and re-probe
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        _note_device_mem_failure()
        return
    in_use_total = peak_total = 0
    headrooms = []
    sampled = False
    for i, device in enumerate(devices):
        try:
            stats = device.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue  # partial results: the other chips still report
        sampled = True
        in_use = int(stats.get("bytes_in_use", 0) or 0)
        peak = int(stats.get("peak_bytes_in_use",
                             stats.get("bytes_in_use", 0)) or 0)
        in_use_total += in_use
        peak_total += peak
        telemetry.chip_gauge("device", i, "bytes_in_use", in_use)
        telemetry.chip_gauge("device", i, "peak_bytes", peak)
        limit = int(stats.get("bytes_limit", 0) or 0)
        if limit > 0:
            headroom = max(0, limit - in_use)
            headrooms.append(headroom)
            telemetry.chip_gauge("device", i, "hbm_headroom", headroom)
    if not sampled:
        _note_device_mem_failure()
        return
    _DEVICE_MEM_UNSUPPORTED = False
    _DEVICE_MEM_FAILURES = 0
    _DEVICE_MEM_SKIPS_LEFT = 0
    telemetry.gauge("device/bytes_in_use", in_use_total)
    telemetry.gauge("device/peak_bytes", peak_total)
    if headrooms:
        telemetry.gauge("device/hbm_headroom", min(headrooms))


# ---------------------------------------------------------------------------
# chunk-level executor (powers Inferencer.stream)
# ---------------------------------------------------------------------------
def _adaptive_device_pipeline(inferencer, q: _AdaptiveQueue,
                              ctl: DepthController, crop=None):
    """Yield device-resident outputs (D2H riding) in input order, pulling
    inputs from the prefetch queue; ring/inflight bounds re-read from the
    controller every iteration so a mid-run widen takes effect."""
    staged: deque = deque()    # (slot, pipeline_owned)
    draining: deque = deque()  # dispatched outputs, D2H in flight
    exhausted = False
    while True:
        while not exhausted and len(staged) < ctl.depths["ring"]:
            with telemetry.span("scheduler/load"):
                item = q.get()
            if _is_end(item):
                exhausted = True
                if item[1] is not None:
                    raise item[1]  # upstream failure re-raises here
                break
            ctl.note_slot_bytes(_chunk_nbytes(item))
            with telemetry.span("pipeline/stage"):
                slot = inferencer.stage(item)
            # donate only buffers staged here; an already-device-resident
            # chunk stays caller-owned (same contract as flow/pipeline.py)
            staged.append((slot, slot is not item))
            telemetry.gauge("pipeline/ring_occupancy", len(staged))
        if not staged:
            break
        slot, owned = staged.popleft()
        with telemetry.span("pipeline/dispatch"):
            out = inferencer.infer_async(slot, crop=crop, consume=owned)
        draining.append(out)
        telemetry.gauge("pipeline/inflight", len(draining))
        while len(draining) >= ctl.depths["inflight"]:
            yield draining.popleft()
    while draining:
        yield draining.popleft()


def schedule_chunks(
    inferencer,
    chunks: Iterable,
    ring: int = 2,
    crop=None,
    postprocess: Optional[Callable] = None,
    post_depth: int = 2,
    prefetch_depth: int = 2,
    controller: Optional[DepthController] = None,
) -> Iterator:
    """Adaptive drop-in for :func:`flow.pipeline.pipeline_chunks`: same
    inputs, same input-order outputs, bit-identical results — plus an
    upstream prefetch thread (the ``chunks`` iterable's own IO runs
    ``prefetch_depth`` items ahead) and the drain + ``postprocess`` stage
    always running in a worker pool, with every depth under controller
    management. Abandoning the generator early cancels queued
    (not-yet-started) post tasks and retires the prefetch thread."""
    from concurrent.futures import ThreadPoolExecutor

    ctl = controller or DepthController(depths={
        "prefetch": prefetch_depth, "ring": ring, "inflight": ring,
        "post": post_depth,
    })
    _note_mesh(inferencer)
    q, thread = _start_pump(chunks, ctl.depths["prefetch"])
    in_flight: deque = deque()
    pool = ThreadPoolExecutor(max_workers=ctl.limits["post"])

    def finalize(out):
        host = _drain_host(out)
        if postprocess is None:
            return host
        with telemetry.span("scheduler/post"):
            return postprocess(host)

    def complete(future):
        result = future.result()
        ctl.observe_task()
        sample_device_memory()
        q.set_capacity(ctl.depths["prefetch"])
        return result

    try:
        for out in _adaptive_device_pipeline(inferencer, q, ctl, crop=crop):
            while len(in_flight) >= ctl.depths["post"]:
                yield complete(in_flight.popleft())
            in_flight.append(pool.submit(finalize, out))
        while in_flight:
            yield complete(in_flight.popleft())
    finally:
        # early close / error: stop the producer, drop queued host work
        q.close()
        for f in in_flight:
            f.cancel()
        pool.shutdown(wait=False)
        thread.join(timeout=5.0)


# ---------------------------------------------------------------------------
# task-level executor (powers the CLI inference stage)
# ---------------------------------------------------------------------------
def scheduled_inference_stage(
    inferencer,
    depth: int = 2,
    ring: int = 2,
    prefetch_depth: int = 2,
    input_name: str = "chunk",
    output_name: str = "chunk",
    op_name: str = "inference",
    crop=None,
    check: Optional[Callable] = None,
    postprocess: Optional[Callable] = None,
    controller: Optional[DepthController] = None,
):
    """The scheduler as a flow-runtime stage (iterator of tasks ->
    iterator of tasks): adaptive superset of
    :func:`flow.pipeline.pipelined_inference_stage`.

    Differences from the static stage: upstream stages run in a prefetch
    thread ``prefetch_depth`` tasks ahead (load IO overlaps device time
    without a separate ``prefetch`` command); the drain-and-materialize
    step (plus optional ``postprocess`` on the output chunk) runs in a
    worker pool so host post-processing hides behind the next task's
    device time; and every bound widens under the controller.

    Ordering/failure contract matches the static stage: results yield in
    input order; a ``None`` skip marker flushes all in-flight work first;
    a mid-stream exception flushes already-dispatched tasks downstream —
    they may already have side effects pending — then re-raises. A
    failing ``postprocess`` likewise flushes the surviving in-flight
    tasks before re-raising, so no staged device buffer or pending write
    is stranded.
    """
    ctl_arg = controller

    def stage_fn(stream):
        from concurrent.futures import ThreadPoolExecutor

        ctl = ctl_arg or DepthController(depths={
            "prefetch": prefetch_depth, "ring": ring, "inflight": depth,
        })
        _note_mesh(inferencer)
        q, thread = _start_pump(stream, ctl.depths["prefetch"])
        staged: deque = deque()     # (task, slot, owned, t0)
        pending: deque = deque()    # (task, device_out, t0)
        finishing: deque = deque()  # post-pool futures, input order
        pool = ThreadPoolExecutor(max_workers=ctl.limits["post"],
                                  thread_name_prefix="scheduler-post")

        def finalize(task, out, t0):
            # runs in the pool: compute/drain attribution rides along
            # (spans are thread-safe, the trace context is rebound from
            # the task here because contextvars do not follow work into
            # pool threads), the GIL is released inside the
            # block_until_ready / D2H waits. Chaos boundary: an injected
            # kill here surfaces through the future — the error-flush
            # path below pushes the survivors downstream first, and the
            # lifecycle supervisor contains the rest
            with telemetry.task_context(task.get("trace_id")):
                try:
                    chaos.chaos_point("scheduler/post")
                    result = _drain_host(out)
                    if postprocess is not None:
                        with telemetry.span("scheduler/post"):
                            result = postprocess(result)
                except BaseException as exc:
                    _tag_culprit(exc, task)
                    raise
            task[output_name] = result
            task["log"]["timer"][op_name] = time.time() - t0
            task["log"]["compute_device"] = inferencer.compute_device
            return task

        def dispatch_one():
            task, slot, owned, t0 = staged.popleft()
            with telemetry.task_context(task.get("trace_id")):
                try:
                    chaos.chaos_point("scheduler/dispatch")
                    with telemetry.span("pipeline/dispatch"):
                        out = inferencer.infer_async(
                            slot, crop=crop, consume=owned)
                except BaseException as exc:
                    _tag_culprit(exc, task)
                    raise
            pending.append((task, out, t0))
            telemetry.gauge("pipeline/inflight", len(pending))

        def submit_one():
            task, out, t0 = pending.popleft()
            finishing.append(pool.submit(finalize, task, out, t0))

        def complete():
            task = finishing.popleft().result()
            ctl.observe_task()
            sample_device_memory()
            q.set_capacity(ctl.depths["prefetch"])
            return task

        try:
            try:
                while True:
                    with telemetry.span("scheduler/load") as load:
                        item = q.get()
                        # the wait was for a task not yet known: it
                        # takes the task's id now that it is in hand
                        if isinstance(item, dict):
                            load.bind(item.get("trace_id"))
                    if _is_end(item):
                        if item[1] is not None:
                            raise item[1]
                        break
                    if item is None:
                        # preserve order: flush in-flight work before
                        # passing the skip marker downstream
                        while staged:
                            dispatch_one()
                        while pending:
                            submit_one()
                        while finishing:
                            yield complete()
                        yield None
                        continue
                    task = item
                    chunk = task[input_name]
                    if check is not None:
                        check(chunk)
                    ctl.note_slot_bytes(_chunk_nbytes(chunk))
                    with telemetry.task_context(task.get("trace_id")), \
                            telemetry.span("pipeline/stage"):
                        slot = inferencer.stage(chunk)
                    staged.append(
                        (task, slot, slot is not chunk, time.time()))
                    telemetry.gauge("pipeline/ring_occupancy", len(staged))
                    if len(staged) >= ctl.depths["ring"]:
                        # drain BEFORE dispatching so at most `inflight`
                        # outputs are device-resident (the memory bound)
                        while len(pending) >= ctl.depths["inflight"]:
                            submit_one()
                        dispatch_one()
                    while len(finishing) > ctl.depths["post"]:
                        yield complete()
            except Exception:
                # mid-stream failure (bad grid, upstream error, poisoned
                # post op): push everything that can still complete
                # downstream — the synchronous path would have saved it —
                # then re-raise the original. (except, not finally: a
                # yield in finally would break generator close().)
                while staged:
                    dispatch_one()
                while pending:
                    submit_one()
                while finishing:
                    try:
                        task = complete()
                    except Exception:
                        continue  # this task failed too; first error wins
                    yield task
                raise
            while staged:
                while len(pending) >= ctl.depths["inflight"]:
                    submit_one()
                dispatch_one()
            while pending:
                submit_one()
            while finishing:
                yield complete()
        finally:
            q.close()
            pool.shutdown(wait=False)
            thread.join(timeout=5.0)

    return stage_fn


# ---------------------------------------------------------------------------
# write-behind (terminal stage; commit-protocol draining)
# ---------------------------------------------------------------------------
def write_behind_stage(window: int = 2,
                       controller: Optional[DepthController] = None):
    """Bound tasks with in-flight async storage writes instead of
    blocking per task: up to ``window`` (controller knob ``write``) tasks
    ride with undurable writes while newer tasks compute; the oldest
    task's futures drain (``scheduler/write`` span) before it flows on.

    The ack-after-durable-write commit protocol holds: a task leaves
    this stage only with its writes durable, and every exit path —
    normal drain, downstream error, generator close — drains the
    remaining buffered futures (the hardened
    :func:`runtime.drain_pending_writes` collects all exceptions and
    re-raises the first). ``delete-task-in-queue`` drains its own task
    *before* acking as always, so queue-fed pipelines keep their
    per-task commit point; the window pays off in pipelines whose drain
    barrier is the pipeline end. Tasks without pending writes pass
    straight through when nothing is buffered."""
    from chunkflow_tpu.flow.runtime import drain_pending_writes

    ctl_arg = controller

    def stage_fn(stream):
        ctl = ctl_arg or DepthController(depths={"write": window})
        buffered: deque = deque()

        def drain_oldest():
            task = buffered.popleft()
            with telemetry.task_context(task.get("trace_id")), \
                    telemetry.span("scheduler/write"):
                drain_pending_writes(task)
            ctl.observe_task()
            return task

        try:
            for task in stream:
                if task is None or not task.get("pending_writes"):
                    # preserve order: anything buffered commits first
                    while buffered:
                        yield drain_oldest()
                    yield task
                    continue
                buffered.append(task)
                telemetry.gauge("scheduler/write_window", len(buffered))
                while len(buffered) > ctl.depths["write"]:
                    yield drain_oldest()
            while buffered:
                yield drain_oldest()
        except BaseException:
            # teardown with an error (or GeneratorExit) in flight: the
            # buffered tasks can no longer flow downstream, but their
            # writes must still commit — ack-after-durable-write does
            # not bend for error paths. The propagating exception wins;
            # drain failures are reported, not raised over it.
            while buffered:
                task = buffered.popleft()
                try:
                    drain_pending_writes(task)
                except Exception as exc:
                    print(
                        f"write-behind: pending write failed during "
                        f"teardown: {exc!r}", file=sys.stderr,
                    )
            raise

    return stage_fn
