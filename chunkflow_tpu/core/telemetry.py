"""Unified telemetry: counters, gauges, histograms, spans, JSONL events.

The paper's fleet story (3600 GPU nodes, 18 PB of output) rests on
knowing, per task and per operator, where wall-clock goes. The reference
ships only coarse per-task ``log['timer']`` dicts aggregated offline by
``log_summary``; our pipelined TPU port has far more internal state —
ring occupancy, stage/compute/drain stall time, program-cache builds vs.
hits — and none of it was visible anywhere. This module is the one
substrate every perf-sensitive layer reports into:

* a process-global registry of **counters** (:func:`inc`), **gauges**
  (:func:`gauge`) and **histograms** (:func:`observe`), aggregated
  in-process and snapshot-able at any time (:func:`snapshot`);
* a **span** tracer (``with span("inference/fold"):``) that both feeds
  the histogram registry and, when a metrics dir is configured
  (:func:`configure`, CLI ``--metrics-dir``), appends one JSONL event
  per span so offline tooling (``flow/log_summary.py``) can attribute
  pipeline stalls after the fact;
* an end-of-run :func:`summary_table` the CLI prints under ``-v``.

Design rules, in priority order:

1. **Never inside jit.** Telemetry is host-side bookkeeping; a
   ``time.perf_counter`` or counter increment inside a traced function
   would either concretize tracers or silently stop measuring (trace
   time is not run time). graftlint rule GL007 enforces this statically.
2. **Near-zero overhead, zero when off.** ``CHUNKFLOW_TELEMETRY=0``
   turns every entry point into an early-out: no locks, no allocation,
   no file IO, nothing emitted. Enabled-path span cost is two
   ``perf_counter`` calls plus one locked dict update; ids, the start
   time and the thread's name are made only while a sink is configured
   or a profiler session runs.
3. **Zero dependencies.** Events are plain JSON lines; aggregation
   needs nothing beyond the stdlib (pandas enters only in
   ``log_summary``'s optional pretty printing). ``jax`` is never
   imported from here: a span finds ``jax.profiler.TraceAnnotation``
   only in a process that has already imported jax.

Event schema (one JSON object per line; see docs/observability.md):

    {"kind": "span",    "name": "...", "t": <epoch end>, "dur_s": ...,
     "pid": ..., "t0": <epoch start>, "span_id": ..., "parent_id": ...,
     "thread": "...", ...attrs}
    {"kind": "gauge",   "name": "...", "t": <epoch>, "value": ...}
    {"kind": "snapshot", "t": <epoch>, "counters": {...}, "gauges": {...},
     "hists": {name: {count,total,min,max}}}

Span naming convention: ``<layer>/<phase>`` — ``pipeline/stage``,
``pipeline/compute``, ``pipeline/drain``, ``scheduler/load``,
``scheduler/post``, ``scheduler/write``, ``op/<operator-name>``,
``inference/<family>``. Counters likewise: ``compile_cache/builds``,
``pipeline/tasks``. The adaptive scheduler (flow/scheduler.py) both
*consumes* this stream (per-phase stall totals via :func:`hist_totals`
drive its depth controller) and *feeds* it: ``scheduler/depth/<knob>``
gauges and ``depth_change`` events record every widening decision.

Span trees (docs/observability.md "Span schema"): ``span_id`` is unique
in a worker's stream, ``parent_id`` is the span that was open on the
same context when this one started (``None`` for a task's top-level
spans: the task, its ``trace_id``, is the root). Both ride
``contextvars``, which do not follow work into pool threads: hand work
over with ``pool.submit(contextvars.copy_context().run, fn, ...)`` and
the spans it opens there keep the submitting span as parent and the
task's ``trace_id``. A span that waits for a task not yet known (a
queue fetch, the scheduler's load wait) takes the id once the item is
in hand: :meth:`_Span.bind`. While a ``jax.profiler`` session runs
(the benchmark's, an operator's ``/profile`` capture, an anomaly
capture) every span is also a ``TraceAnnotation`` of the same name on
its thread's line of the ``/host:CPU`` plane, on the profiler's clock:
a device-idle gap can be read against what the host was doing in it.

Fleet correlation (docs/observability.md "Fleet view"): every emitted
line is stamped with this process's :func:`worker_id` (stable host+pid
identity, ``CHUNKFLOW_WORKER_ID`` override for pid-namespaced
containers), and — while a task is in flight under
:func:`task_context` — with the task's ``trace_id``, the id minted when
the task was first submitted to a queue (parallel/queues.py). Merged
multi-worker JSONL therefore reconstructs a task's full history across
claim/retry/requeue hops between workers. The task context is a
``contextvars.ContextVar``: thread- and generator-safe on the host
side, and statically banned inside jitted code like every other
telemetry call (graftlint GL007).

Time series (docs/observability.md "SLO view"): the registry alone
answers "how much, total" — an SLO plane needs "how fast, lately".
:func:`start_timeseries` runs a bounded ring sampler in a daemon
thread: every ``CHUNKFLOW_TS_INTERVAL`` seconds it derives counter
*rates*, copies gauges, and estimates qhist p50/p99 into per-metric
``(t, value)`` rings of ``CHUNKFLOW_TS_POINTS`` points
(:func:`timeseries` reads them), flushes one ``timeseries``-kind event
— including the raw cumulative qhist buckets, which sum across workers
— to the JSONL stream so history survives worker death, and then runs
the registered :func:`add_tick_hook` callbacks (the SLO evaluator,
core/slo.py, rides here). ``CHUNKFLOW_TELEMETRY=0`` creates no sampler
thread, no rings, no events.
"""
from __future__ import annotations

import contextvars
import itertools
import json
import os
import re
import socket
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

__all__ = [
    "enabled", "configure", "configured_path", "inc", "gauge", "observe",
    "span", "record_span", "event", "snapshot", "flush", "reset",
    "summary_table",
    "hist_totals", "worker_id", "task_context", "current_trace_id",
    "profiler_session_active",
    "snapshot_interval", "add_flush_hook", "add_reset_hook",
    "observe_quantile", "quantile", "quantile_from_buckets",
    "QUANTILE_BOUNDS", "timeseries", "start_timeseries",
    "stop_timeseries", "timeseries_running", "add_tick_hook",
    "remove_tick_hook", "ts_interval", "ts_points",
    "chip_gauge", "CHIP_METRIC_RE",
]

#: Per-chip metric naming convention: ``<plane>/chip/<i>/<metric>``
#: (``device/chip/0/bytes_in_use``, ``shard/chip/3/voxels``). Every
#: consumer that wants to fold the chip index back out of the name —
#: the ``/metrics`` renderer turns it into a ``chip`` label, the
#: log-summary MESH block groups by it — matches against this one
#: regex so the convention cannot drift between emitters and readers.
CHIP_METRIC_RE = re.compile(
    r"^(?P<plane>[^/]+(?:/[^/]+)*)/chip/(?P<chip>\d+)/(?P<metric>.+)$")

_OFF_VALUES = ("0", "off", "false", "no")


def enabled() -> bool:
    """The kill switch, re-read per call so tests (and long-lived workers
    reacting to a config push) can flip it at runtime."""
    return os.environ.get("CHUNKFLOW_TELEMETRY", "1").lower() \
        not in _OFF_VALUES


# ---------------------------------------------------------------------------
# fleet identity + per-task trace context
# ---------------------------------------------------------------------------
_WORKER_ID: Optional[str] = None
_WORKER_ID_LOCK = threading.Lock()


def worker_id() -> str:
    """Stable identity of this worker process: ``<hostname>-<pid>``, or
    the ``CHUNKFLOW_WORKER_ID`` env override (pid-namespaced containers
    where every worker is pid 1, and tests simulating a fleet in one
    process). Cached after first use — double-checked under a lock,
    since the time-series sampler thread stamps events too; :func:`reset`
    clears the cache (a forked child should call
    :func:`configure`/:func:`reset` anyway — it must not inherit the
    parent's sink)."""
    global _WORKER_ID
    wid = _WORKER_ID
    if wid is None:
        with _WORKER_ID_LOCK:
            if _WORKER_ID is None:
                _WORKER_ID = (
                    os.environ.get("CHUNKFLOW_WORKER_ID")
                    or f"{socket.gethostname()}-{os.getpid()}"
                )
            wid = _WORKER_ID
    return wid


_TASK_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "chunkflow_trace_id", default=None
)


# the innermost open span of this context that has an id (spans make
# ids only while someone can read them: _Span.__enter__)
_SPAN_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "chunkflow_span_id", default=None
)
_SPAN_IDS = itertools.count(1)  # next() is atomic under the GIL


def current_trace_id() -> Optional[str]:
    """The trace id of the task currently in flight on this
    thread/context, or None outside any :func:`task_context`."""
    return _TASK_CTX.get()




class _TaskContext:
    """Scoped trace-id binding; ``trace_id=None`` is a no-op so an
    un-traced task never clobbers an enclosing context."""

    __slots__ = ("trace_id", "_token")

    def __init__(self, trace_id: Optional[str]):
        self.trace_id = trace_id
        self._token = None

    def __enter__(self):
        if self.trace_id is not None:
            self._token = _TASK_CTX.set(self.trace_id)
        return self

    def __exit__(self, *exc):
        if self._token is not None:
            _TASK_CTX.reset(self._token)
            self._token = None
        return False


def task_context(trace_id: Optional[str]):
    """Bind ``trace_id`` for the dynamic extent of a ``with`` block:
    every span/gauge/event emitted inside is stamped with it (plus
    :func:`worker_id`), so a task's history is reconstructable from
    merged multi-worker JSONL. Call sites hold the task dict or
    lifecycle object: the runtime operator wrapper, the adaptive
    scheduler's dispatch/finalize, the lifecycle claim/commit/release
    paths. Host-side only (GL007)."""
    return _TaskContext(trace_id)


def _stamp(payload: dict) -> dict:
    """Fleet-correlation stamp on an outgoing JSONL payload."""
    payload["worker"] = worker_id()
    trace_id = _TASK_CTX.get()
    if trace_id is not None:
        payload["trace_id"] = trace_id
    return payload


def snapshot_interval() -> int:
    """Tasks between periodic snapshot events in the supervised claim
    loop (``CHUNKFLOW_TELEMETRY_SNAPSHOT_EVERY``, default 8; 0
    disables). Without it a killed worker leaves no counter record —
    snapshots otherwise ride only the end-of-run flush()."""
    raw = os.environ.get("CHUNKFLOW_TELEMETRY_SNAPSHOT_EVERY", "")
    try:
        return max(0, int(raw)) if raw else 8
    except ValueError:
        return 8


def _max_sink_bytes() -> int:
    """JSONL rotation threshold (``CHUNKFLOW_TELEMETRY_MAX_MB``,
    default a generous 256 MB; <=0 disables rotation)."""
    raw = os.environ.get("CHUNKFLOW_TELEMETRY_MAX_MB", "")
    try:
        mb = float(raw) if raw else 256.0
    except ValueError:
        mb = 256.0
    return int(mb * (1 << 20))


def _keep_generations() -> int:
    """Total JSONL generations kept per worker, live file included
    (``CHUNKFLOW_TELEMETRY_KEEP``, default 2 = the live file plus one
    ``.1`` rotation; minimum 1 = rotation truncates outright). A long
    SLO run whose time-series history must survive rotation raises
    this — each extra generation is another ``CHUNKFLOW_TELEMETRY_MAX_MB``
    of history ``load_telemetry_dir`` can still read."""
    raw = os.environ.get("CHUNKFLOW_TELEMETRY_KEEP", "")
    try:
        return max(1, int(raw)) if raw else 2
    except ValueError:
        return 2


#: Upper bucket bounds (seconds) of the quantile histograms — log-spaced
#: from 1 ms to 2 min, with an implicit +inf overflow bucket. Chosen for
#: request-latency distributions (docs/serving.md): a serving p50 of a
#: few ms and a p99 of seconds both land mid-range. Fixed bounds (not
#: per-process sketches) are what make bucket counts summable across
#: workers in ``log-summary --fleet`` and renderable as a Prometheus
#: ``histogram`` (parallel/restapi.py).
QUANTILE_BOUNDS = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0,
)


def quantile_from_buckets(qhist: dict, q: float) -> Optional[float]:
    """Estimate the ``q``-quantile (0..1) from a snapshot-form quantile
    histogram ``{"count": n, "buckets": [..per-bound.., overflow]}`` by
    linear interpolation inside the covering bucket. Returns None for an
    empty histogram; the overflow bucket reports its lower bound (the
    estimate saturates at the largest tracked bound). Shared by
    ``log-summary`` (merged multi-worker buckets) and live reporting so
    every p50/p99 figure is computed one way."""
    count = qhist.get("count", 0)
    buckets = qhist.get("buckets") or []
    if not count or not buckets:
        return None
    rank = q * count
    seen = 0.0
    lower = 0.0
    for i, n in enumerate(buckets):
        upper = (QUANTILE_BOUNDS[i] if i < len(QUANTILE_BOUNDS)
                 else QUANTILE_BOUNDS[-1])
        if n and seen + n >= rank:
            if i >= len(QUANTILE_BOUNDS):
                return QUANTILE_BOUNDS[-1]  # overflow: saturate
            frac = (rank - seen) / n
            return lower + frac * (upper - lower)
        seen += n
        lower = upper
    return QUANTILE_BOUNDS[-1]


class _Registry:
    """Process-global metric state + optional JSONL sink. All mutation is
    behind one lock; the disabled path never takes it."""

    def __init__(self):
        self.lock = threading.Lock()
        self.counters: Dict[str, float] = {}
        self.gauges: Dict[str, float] = {}
        # name -> [count, total, min, max]
        self.hists: Dict[str, list] = {}
        # name -> [count, total, min, max, [bucket counts + overflow]]
        self.qhists: Dict[str, list] = {}
        self.sink = None
        self.sink_path: Optional[str] = None
        self.sink_bytes = 0
        self.max_sink_bytes = 0

    # -- metric updates (caller holds no lock) -------------------------
    def add_counter(self, name: str, n: float) -> None:
        with self.lock:
            self.counters[name] = self.counters.get(name, 0) + n

    def set_gauge(self, name: str, value: float) -> None:
        with self.lock:
            self.gauges[name] = value

    def add_hist(self, name: str, value: float) -> None:
        with self.lock:
            h = self.hists.get(name)
            if h is None:
                self.hists[name] = [1, value, value, value]
            else:
                h[0] += 1
                h[1] += value
                h[2] = min(h[2], value)
                h[3] = max(h[3], value)

    def add_qhist(self, name: str, value: float) -> None:
        with self.lock:
            h = self.qhists.get(name)
            if h is None:
                h = self.qhists[name] = [
                    0, 0.0, value, value,
                    [0] * (len(QUANTILE_BOUNDS) + 1),
                ]
            h[0] += 1
            h[1] += value
            h[2] = min(h[2], value)
            h[3] = max(h[3], value)
            for i, bound in enumerate(QUANTILE_BOUNDS):
                if value <= bound:
                    h[4][i] += 1
                    break
            else:
                h[4][-1] += 1  # overflow

    # -- sink ----------------------------------------------------------
    def emit(self, payload: dict) -> None:
        with self.lock:
            if self.sink is None:
                return
            line = json.dumps(payload) + "\n"
            try:
                self.sink.write(line)
            except (OSError, ValueError):
                # a full disk / closed sink must never take the pipeline
                # down; drop the event and keep computing
                self.sink = None
                return
            self.sink_bytes += len(line)
            if 0 < self.max_sink_bytes < self.sink_bytes:
                self._rotate_locked()

    def _rotate_locked(self) -> None:
        """Size-capped rotation (caller holds the lock): generations
        shift up one suffix (``<path>.1`` is the youngest rotation,
        ``<path>.N`` the oldest) and a fresh file opens at ``<path>``
        — a long-lived worker keeps at most ``CHUNKFLOW_TELEMETRY_KEEP``
        generations on disk (default 2: live + ``.1``), anything older
        is swept, including stale generations left by a previously
        higher KEEP. ``load_telemetry_dir`` reads every surviving
        generation oldest-first (flow/log_summary.py), so the
        time-series/SLO history window is KEEP × MAX_MB, not one file."""
        try:
            self.sink.close()
        except OSError:
            pass
        base = self.sink_path
        rotations = _keep_generations() - 1
        try:
            # shift from the oldest kept slot down so nothing clobbers
            for n in range(rotations, 1, -1):
                if os.path.exists(f"{base}.{n - 1}"):
                    os.replace(f"{base}.{n - 1}", f"{base}.{n}")
            if rotations >= 1:
                os.replace(base, base + ".1")
            else:
                os.remove(base)  # KEEP=1: truncate, keep no history
            n = rotations + 1
            while os.path.exists(f"{base}.{n}"):
                os.remove(f"{base}.{n}")
                n += 1
            self.sink = open(base, "a", buffering=1)
            self.sink_bytes = 0
        except OSError:
            self.sink = None  # unrotatable sink: stop emitting, keep computing


_REG = _Registry()


def configure(metrics_dir: Optional[str]) -> Optional[str]:
    """Open (or close, with None) the per-worker JSONL sink under
    ``metrics_dir``. Returns the file path in effect, or None when
    disabled — with ``CHUNKFLOW_TELEMETRY=0`` nothing is created, so an
    off run leaves no trace on disk. The file is named by
    :func:`worker_id` (host+pid by default, so one file per process as
    before); when it outgrows ``CHUNKFLOW_TELEMETRY_MAX_MB`` it rotates
    to a ``.1`` suffix."""
    with _REG.lock:
        if _REG.sink is not None:
            try:
                _REG.sink.close()
            except OSError:
                pass
            _REG.sink, _REG.sink_path = None, None
    if metrics_dir is None or not enabled():
        return None
    os.makedirs(metrics_dir, exist_ok=True)
    safe = "".join(
        ch if ch.isalnum() or ch in "._-" else "_" for ch in worker_id()
    )
    path = os.path.join(metrics_dir, f"telemetry-{safe}.jsonl")
    # line-buffered: each event line reaches the OS page cache as it is
    # emitted (no fsync — this is cheap), so a worker that dies by
    # SIGKILL / spot preemption still leaves its span and task events on
    # disk for crash-recovery trace reconstruction (parallel/fleet.py;
    # a block-buffered sink would lose the tail silently)
    sink = open(path, "a", buffering=1)
    try:
        existing = os.path.getsize(path)
    except OSError:
        existing = 0
    with _REG.lock:
        _REG.sink, _REG.sink_path = sink, path
        _REG.sink_bytes = existing
        _REG.max_sink_bytes = _max_sink_bytes()
    return path


def configured_path() -> Optional[str]:
    return _REG.sink_path


def inc(name: str, n: float = 1) -> None:
    """Increment a counter. Counters are aggregate-only: they ride the
    end-of-run snapshot event, not one line per increment."""
    if not enabled():
        return
    _REG.add_counter(name, n)


def gauge(name: str, value: float) -> None:
    """Record an instantaneous level (ring occupancy, queue depth). Kept
    as last-value in the registry AND folded into the histogram of the
    same name so mean occupancy is queryable offline; emits one event
    when a sink is configured."""
    if not enabled():
        return
    _REG.set_gauge(name, value)
    _REG.add_hist(name, value)
    if _REG.sink is not None:
        _REG.emit(_stamp({"kind": "gauge", "name": name, "t": time.time(),
                          "value": value}))


def chip_gauge(plane: str, chip: int, metric: str, value: float) -> None:
    """Record a per-chip instantaneous level under the
    ``<plane>/chip/<i>/<metric>`` convention (:data:`CHIP_METRIC_RE`).
    A thin veneer over :func:`gauge`, so per-chip values get everything
    plain gauges get — last-value registry entry, occupancy histogram,
    one JSONL event, and a ``gauge:<name>`` timeseries ring — while
    keeping the name shape readers can fold into a ``chip`` label."""
    gauge(f"{plane}/chip/{int(chip)}/{metric}", value)


def observe(name: str, value: float) -> None:
    """Fold a sample into a histogram without emitting an event."""
    if not enabled():
        return
    _REG.add_hist(name, value)


def observe_quantile(name: str, value: float) -> None:
    """Fold a sample (seconds) into a fixed-bound quantile histogram —
    the p50/p99 substrate for request latencies (docs/serving.md).
    Bucket counts ride the snapshot event (summable across workers) and
    render as a Prometheus ``histogram`` on ``/metrics``; no per-sample
    event is emitted."""
    if not enabled():
        return
    _REG.add_qhist(name, value)


def quantile(name: str, q: float) -> Optional[float]:
    """Live ``q``-quantile estimate (seconds) of a quantile histogram in
    this process's registry; None when the histogram has no samples (or
    telemetry is off)."""
    if not enabled():
        return None
    with _REG.lock:
        h = _REG.qhists.get(name)
        if h is None:
            return None
        snap = {"count": h[0], "buckets": list(h[4])}
    return quantile_from_buckets(snap, q)


def event(kind: str, name: str, **attrs) -> None:
    """Emit a free-form event line (sink configured and telemetry on)."""
    if not enabled() or _REG.sink is None:
        return
    payload = {"kind": kind, "name": name, "t": time.time()}
    payload.update(attrs)
    _REG.emit(_stamp(payload))


class _NullSpan:
    """The disabled span: a shared, stateless context manager."""

    __slots__ = ()
    duration = 0.0

    def bind(self, trace_id) -> None:
        pass

    def cancel(self) -> None:
        pass

    def annotate(self, **attrs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is imported


def _trace_annotation():
    """``jax.profiler.TraceAnnotation`` if this process has imported
    jax, else None: looked up in ``sys.modules``, never imported (design
    rule 3), and kept once found."""
    global _ANNOTATION
    if _ANNOTATION is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _ANNOTATION = getattr(profiler, "TraceAnnotation", None)
    return _ANNOTATION


def profiler_session_active() -> bool:
    """Whether a ``jax.profiler`` session is tracing in this process
    now, whoever started it (the flag every ``TraceAnnotation`` tests).
    False in a process that has not imported jax."""
    annotation = _trace_annotation()
    return annotation is not None and annotation.is_enabled()


def _emit_span(name: str, t0: float, dur_s: float, span_id: int,
               parent_id, trace_id, attrs) -> None:
    """One span record to the sink (the caller checked there is one)."""
    payload = {"kind": "span", "name": name, "t": time.time(),
               "dur_s": dur_s, "pid": os.getpid(), "t0": t0,
               "span_id": span_id, "parent_id": parent_id,
               "thread": threading.current_thread().name}
    if attrs:
        payload.update(attrs)
    _stamp(payload)
    if trace_id is not None:
        payload["trace_id"] = trace_id
    _REG.emit(payload)


class _Span:
    __slots__ = ("name", "attrs", "t0", "duration", "trace_id", "span_id",
                 "parent_id", "wall0", "cancelled", "_token", "_annotation")

    def __init__(self, name: str, attrs):
        self.name = name
        self.attrs = attrs
        self.duration = 0.0
        self.trace_id = None
        self.span_id = None
        self.cancelled = False
        self._annotation = None

    def cancel(self) -> None:
        """Record nothing when the block ends: for a span that turned
        out to time no work (a queue poll that found no task)."""
        self.cancelled = True

    def annotate(self, **attrs) -> None:
        """Attributes known only inside the block (how many requests a
        read turned out to need): on the span's record like those given
        at the start."""
        self.attrs.update(attrs)

    def bind(self, trace_id: Optional[str]) -> None:
        """Give the span the task it turned out to work for: a span
        that waits for a task not yet known (a queue fetch, the
        scheduler's load wait) calls this once the item is in hand.
        ``None`` leaves the enclosing :func:`task_context` in force."""
        if trace_id is not None:
            self.trace_id = trace_id
            if self._annotation is not None:
                self._annotation.set_metadata(trace_id=trace_id)

    def __enter__(self):
        tracing = profiler_session_active()
        if tracing or _REG.sink is not None:
            self.span_id = next(_SPAN_IDS)
            self.parent_id = _SPAN_CTX.get()
            self._token = _SPAN_CTX.set(self.span_id)
            self.wall0 = time.time()
            if tracing:
                # the same name, letter for letter, on this thread's
                # line of the profiler's /host:CPU plane
                self._annotation = _ANNOTATION(
                    self.name, span_id=self.span_id,
                    trace_id=_TASK_CTX.get() or "")
                self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.duration = time.perf_counter() - self.t0
        if not self.cancelled:
            _REG.add_hist(self.name, self.duration)
        if self.span_id is None:
            return False
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        try:
            _SPAN_CTX.reset(self._token)
        except ValueError:
            # closed on another context than it was opened on (a span
            # object carried across threads): nothing to restore there
            pass
        if _REG.sink is not None and not self.cancelled:
            _emit_span(self.name, self.wall0, self.duration, self.span_id,
                       self.parent_id, self.trace_id, self.attrs)
        return False


def span(name: str, **attrs):
    """Time a block: ``with span("pipeline/drain"): ...``. Feeds the
    histogram registry and (sink configured) emits one JSONL event with
    the span's start, id, parent and thread; while a ``jax.profiler``
    session runs it is a ``TraceAnnotation`` of the same name too. The
    span object exposes ``.duration`` after exit for callers that keep a
    legacy timer view, and :meth:`~_Span.bind` for a task known late."""
    if not enabled():
        return _NULL_SPAN
    return _Span(name, attrs)


def record_span(name: str, t0: float, trace_id: Optional[str] = None,
                **attrs) -> None:
    """Record a span that began at ``t0`` (``time.time()``) and ends
    now, for an interval no ``with`` block can cover because it starts
    on one thread and ends on another (a request's wait from admission
    to its first device batch). Same histogram and, with a sink, the
    same JSONL record as :func:`span`, top-level (``parent_id`` None);
    not a profiler annotation, which cannot be made after the fact."""
    if not enabled():
        return
    dur_s = time.time() - t0
    _REG.add_hist(name, dur_s)
    if _REG.sink is not None:
        _emit_span(name, t0, dur_s, next(_SPAN_IDS), None, trace_id, attrs)


def hist_totals(names) -> Dict[str, float]:
    """Cumulative histogram totals (seconds for span histograms) for the
    given names; 0.0 for a name with no samples yet. The adaptive
    scheduler's depth controller (flow/scheduler.py) polls per-phase
    stall totals through this every few tasks — one lock, no per-name
    dict rebuild — instead of materializing a full :func:`snapshot`.
    Disabled telemetry returns all-zero totals, which the controller
    reads as "no stall signal": depths stay at their static initial
    values (the documented graceful fallback)."""
    if not enabled():
        return {name: 0.0 for name in names}
    with _REG.lock:
        return {
            name: (_REG.hists[name][1] if name in _REG.hists else 0.0)
            for name in names
        }


def snapshot() -> dict:
    """Copy of all aggregated metrics:
    ``{"counters": {...}, "gauges": {...}, "hists": {name:
    {"count", "total", "min", "max", "mean"}}, "qhists": {name:
    {"count", "total", "min", "max", "buckets"}}}`` (``qhists`` only
    when quantile histograms were recorded — older streams stay
    schema-stable)."""
    with _REG.lock:
        hists = {
            name: {
                "count": h[0],
                "total": h[1],
                "min": h[2],
                "max": h[3],
                "mean": h[1] / h[0] if h[0] else 0.0,
            }
            for name, h in _REG.hists.items()
        }
        snap = {
            "counters": dict(_REG.counters),
            "gauges": dict(_REG.gauges),
            "hists": hists,
        }
        if _REG.qhists:
            snap["qhists"] = {
                name: {
                    "count": h[0],
                    "total": h[1],
                    "min": h[2],
                    "max": h[3],
                    "buckets": list(h[4]),
                }
                for name, h in _REG.qhists.items()
            }
        return snap


# ---------------------------------------------------------------------------
# time-series ring sampler (the SLO plane's history substrate)
# ---------------------------------------------------------------------------
def ts_interval() -> float:
    """Seconds between time-series samples (``CHUNKFLOW_TS_INTERVAL``,
    default 10.0; <=0 disables the sampler entirely)."""
    raw = os.environ.get("CHUNKFLOW_TS_INTERVAL", "")
    try:
        return float(raw) if raw else 10.0
    except ValueError:
        return 10.0


def ts_points() -> int:
    """Ring capacity per sampled metric (``CHUNKFLOW_TS_POINTS``,
    default 360 — an hour of history at the default interval)."""
    raw = os.environ.get("CHUNKFLOW_TS_POINTS", "")
    try:
        return max(2, int(raw)) if raw else 360
    except ValueError:
        return 360


# tick hooks survive sampler restarts (the sampler reads the list each
# tick); cleared by reset() — a hooked plane's state is per-run
_TICK_HOOKS: list = []


def add_tick_hook(fn) -> None:
    """Register ``fn(now: float)`` to run after every time-series
    sample (idempotent by identity) — how the SLO evaluator
    (core/slo.py) gets its periodic record/evaluate clock without a
    second thread. Hooks run outside all telemetry locks and are
    best-effort: a raising hook is dropped from that tick, never the
    pipeline."""
    if fn not in _TICK_HOOKS:
        _TICK_HOOKS.append(fn)


def remove_tick_hook(fn) -> None:
    try:
        _TICK_HOOKS.remove(fn)
    except ValueError:
        pass


class _TimeSeriesSampler:
    """Bounded in-memory (t, value) rings over the registry, fed by one
    daemon thread. Each sample derives counters-as-rates against the
    previous tick, copies gauges, and estimates qhist p50/p99; when a
    sink is configured it also flushes one ``timeseries``-kind event
    carrying the sampled values plus the raw cumulative qhist buckets
    (fixed bounds: summable across workers, so ``log-summary --slo``
    can reconstruct a fleet p99 timeline from merged JSONL alone)."""

    def __init__(self, interval: float, points: int):
        self.interval = max(0.01, float(interval))
        self.points = int(points)
        self._lock = threading.Lock()
        self._rings: Dict[str, deque] = {}
        self._prev: Optional[Tuple[float, dict]] = None
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        # baseline sample: establishes the counter snapshot rates are
        # derived against, so a run shorter than one interval still
        # gets a meaningful sample out of the final flush()
        try:
            self.sample()
        except Exception:
            pass
        self._thread = threading.Thread(
            target=self._run, name="chunkflow-timeseries", daemon=True,
        )
        self._thread.start()

    def _run(self) -> None:
        while not self._stop_evt.wait(self.interval):
            if not enabled():
                continue  # mid-run disable: stop sampling, keep idling
            try:
                self.sample()
            except Exception:
                pass  # a sampling hiccup must never take a worker down

    def sample(self, now: Optional[float] = None) -> Dict[str, float]:
        """One sample tick (the thread's body; tests and flush() call it
        directly). Returns the sampled ``{name: value}`` map."""
        if now is None:
            now = time.time()
        snap = snapshot()
        qhists = snap.get("qhists") or {}
        values: Dict[str, float] = {}
        with self._lock:
            prev = self._prev
            if prev is not None and now > prev[0]:
                dt = now - prev[0]
                for name, value in snap["counters"].items():
                    values[f"rate:{name}"] = round(
                        (value - prev[1].get(name, 0.0)) / dt, 6)
            self._prev = (now, dict(snap["counters"]))
            for name, value in snap["gauges"].items():
                values[f"gauge:{name}"] = value
            for name, h in qhists.items():
                p50 = quantile_from_buckets(h, 0.5)
                if p50 is not None:
                    values[f"p50:{name}"] = p50
                    values[f"p99:{name}"] = quantile_from_buckets(h, 0.99)
            for name, value in values.items():
                ring = self._rings.get(name)
                if ring is None:
                    ring = self._rings[name] = deque(maxlen=self.points)
                ring.append((now, value))
        if values or qhists:
            event(
                "timeseries", "timeseries/sample", interval_s=self.interval,
                values=values,
                qhists={
                    name: {"count": h["count"], "buckets": h["buckets"]}
                    for name, h in qhists.items()
                },
            )
        for hook in list(_TICK_HOOKS):
            try:
                hook(now)
            except Exception:
                pass
        return values

    def series(self) -> Dict[str, List[Tuple[float, float]]]:
        with self._lock:
            return {name: list(ring) for name, ring in self._rings.items()}

    def stop(self, timeout: float = 5.0) -> None:
        self._stop_evt.set()
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=timeout)


_SAMPLER_LOCK = threading.Lock()
_SAMPLER: Optional[_TimeSeriesSampler] = None


def start_timeseries(interval: Optional[float] = None,
                     points: Optional[int] = None):
    """Start the time-series sampler thread (idempotent: an already
    running sampler is returned as-is). Returns None — creating **no
    thread and no rings** — when telemetry is disabled or the interval
    knob is <=0; the CLI calls this whenever a metrics dir is
    configured, so every instrumented run gets history for free."""
    global _SAMPLER
    if not enabled():
        return None
    if interval is None:
        interval = ts_interval()
    if interval <= 0:
        return None
    with _SAMPLER_LOCK:
        if _SAMPLER is not None:
            return _SAMPLER
        sampler = _TimeSeriesSampler(interval,
                                     ts_points() if points is None
                                     else points)
        _SAMPLER = sampler
    sampler.start()
    return sampler


def stop_timeseries() -> None:
    """Stop and join the sampler thread (reset() calls this)."""
    global _SAMPLER
    with _SAMPLER_LOCK:
        sampler, _SAMPLER = _SAMPLER, None
    if sampler is not None:
        sampler.stop()


def timeseries_running() -> bool:
    return _SAMPLER is not None


def timeseries() -> Dict[str, List[Tuple[float, float]]]:
    """Copy of the per-metric ``[(t, value), ...]`` rings — ``rate:<counter>``,
    ``gauge:<name>``, ``p50:<qhist>``/``p99:<qhist>`` — or ``{}`` when no
    sampler is running (telemetry off, or never started)."""
    sampler = _SAMPLER
    if sampler is None:
        return {}
    return sampler.series()


# Layer hooks: other observability planes (core/profiling.py's program
# cost ledger) ride the same flush/reset lifecycle without telemetry
# importing them (this module stays zero-dependency). Flush hooks get
# the metrics dir in effect (None when no sink); both hook kinds are
# best-effort — a failing hook must never take the pipeline down.
_FLUSH_HOOKS: list = []
_RESET_HOOKS: list = []


def add_flush_hook(fn) -> None:
    """Register ``fn(metrics_dir_or_None)`` to run at every
    :func:`flush` (idempotent by identity). Skipped entirely when
    telemetry is disabled — the kill switch silences hooked planes too."""
    if fn not in _FLUSH_HOOKS:
        _FLUSH_HOOKS.append(fn)


def add_reset_hook(fn) -> None:
    """Register ``fn()`` to run at every :func:`reset` (idempotent by
    identity) so hooked planes drop their per-run state with ours."""
    if fn not in _RESET_HOOKS:
        _RESET_HOOKS.append(fn)


def flush() -> None:
    """Write the aggregate snapshot as a final event and flush the sink.
    Counters (builds/hits, task counts) reach the JSONL stream here —
    they are aggregate-only during the run."""
    if not enabled():
        return
    # one last time-series sample (and SLO tick) so a run shorter than
    # the sampling interval still leaves history + a final evaluation
    sampler = _SAMPLER
    if sampler is not None:
        try:
            sampler.sample()
        except Exception:
            pass
    metrics_dir = (
        os.path.dirname(_REG.sink_path) if _REG.sink_path else None
    )
    for hook in list(_FLUSH_HOOKS):
        try:
            hook(metrics_dir)
        except Exception:
            pass
    snap = snapshot()
    if _REG.sink is not None:
        _REG.emit(_stamp({"kind": "snapshot", "t": time.time(),
                          "pid": os.getpid(), **snap}))
        with _REG.lock:
            if _REG.sink is not None:
                try:
                    _REG.sink.flush()
                except OSError:
                    pass


def reset() -> None:
    """Clear all metrics, close the sink, stop the time-series sampler,
    and drop the cached worker identity (tests; each CLI invocation is
    one process, so production never needs this)."""
    global _WORKER_ID
    stop_timeseries()
    _TICK_HOOKS.clear()
    with _REG.lock:
        _REG.counters.clear()
        _REG.gauges.clear()
        _REG.hists.clear()
        _REG.qhists.clear()
        if _REG.sink is not None:
            try:
                _REG.sink.close()
            except OSError:
                pass
        _REG.sink, _REG.sink_path = None, None
        _REG.sink_bytes = 0
    with _WORKER_ID_LOCK:
        _WORKER_ID = None
    for hook in list(_RESET_HOOKS):
        try:
            hook()
        except Exception:
            pass


# -- end-of-run reporting ----------------------------------------------
def summary_table() -> str:
    """Fixed-width end-of-run table of spans (count/total/mean/max),
    counters and last-value gauges — the CLI prints this under ``-v``.
    Empty string when nothing was recorded."""
    snap = snapshot()
    lines = []
    if snap["hists"]:
        lines.append(
            f"  {'span':<28} {'count':>7} {'total_s':>9} {'mean_s':>9} "
            f"{'max_s':>9}"
        )
        for name in sorted(snap["hists"]):
            h = snap["hists"][name]
            lines.append(
                f"  {name:<28} {h['count']:>7} {h['total']:>9.3f} "
                f"{h['mean']:>9.4f} {h['max']:>9.4f}"
            )
    if snap.get("qhists"):
        lines.append(
            f"  {'latency hist':<28} {'count':>7} {'p50_s':>9} {'p99_s':>9}"
        )
        for name in sorted(snap["qhists"]):
            h = snap["qhists"][name]
            p50 = quantile_from_buckets(h, 0.5)
            p99 = quantile_from_buckets(h, 0.99)
            lines.append(
                f"  {name:<28} {h['count']:>7} "
                f"{p50 if p50 is not None else 0.0:>9.4f} "
                f"{p99 if p99 is not None else 0.0:>9.4f}"
            )
    if snap["counters"]:
        lines.append(f"  {'counter':<28} {'value':>7}")
        for name in sorted(snap["counters"]):
            value = snap["counters"][name]
            lines.append(f"  {name:<28} {value:>7g}")
    if snap["gauges"]:
        lines.append(f"  {'gauge (last)':<28} {'value':>7}")
        for name in sorted(snap["gauges"]):
            lines.append(f"  {name:<28} {snap['gauges'][name]:>7g}")
    if not lines:
        return ""
    return "\n".join(["telemetry summary:"] + lines)
