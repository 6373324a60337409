"""Task queues: the distributed communication backend.

Parity target: reference lib/aws/sqs_queue.py — a queue of bbox strings with
visibility timeout, ack-after-write commit, and batch send. Workers never
talk to each other; the queue plus object storage is the whole protocol
(communication-free task parallelism — the right design for chunked
inference, kept here deliberately instead of collectives).

Beyond the reference's happy path, every backend speaks the full task
lifecycle protocol consumed by ``parallel/lifecycle.py``
(docs/fault_tolerance.md):

* :meth:`QueueBase.renew` — lease heartbeat: extend a claimed task's
  visibility timeout so a slow chunk is not double-claimed mid-compute
  (SQS ``ChangeMessageVisibility``);
* :meth:`QueueBase.nack` — immediate visibility release of a claimed
  task (graceful preemption: a SIGTERM'd worker hands its task back
  instead of letting the timeout expire);
* :meth:`QueueBase.receive_count` — per-task delivery count (memory:
  dict; file: sidecar count next to the claimed entry; SQS:
  ``ApproximateReceiveCount``), the retry accounting substrate;
* :meth:`QueueBase.dead_letter` / :meth:`dead_letters` /
  :meth:`requeue_dead` — a poison task that keeps failing moves to a
  dead-letter store carrying its failure reason, inspectable and
  requeueable via the CLI (``chunkflow dead-letter``).

Backends:
- ``memory://name``  — in-process, for tests (fixes the reference's
  untestable-SQS gap);
- ``file:///dir``    — a directory of task files with atomic rename claims
  and mtime-based visibility timeout; safe across processes/hosts on a
  shared filesystem (SLURM-style clusters);
- ``sqs://name``     — AWS SQS via boto3, gated on the library being
  importable (not baked into this image).

Distributed tracing (docs/observability.md "Fleet view"): every task
submitted through :meth:`QueueBase.send_messages` is wrapped in a JSON
envelope carrying a freshly minted ``trace_id``. The envelope is the
*wire* format only — :meth:`receive` unwraps it, so consumers keep
seeing the plain bbox-string body — and it survives every lifecycle
hop: claim, nack, janitor requeue, dead-letter, ``requeue_dead``
(:func:`pack_task` is idempotent, so a requeued envelope keeps its
original id). :meth:`QueueBase.trace_id` exposes the claimed task's id
so the lifecycle layer can stamp telemetry with it
(``telemetry.task_context``). Pre-envelope bodies (an old queue on
disk) still work: they unwrap to themselves with no trace id.
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time
import uuid
from typing import Dict, Iterator, List, Optional, Tuple

from chunkflow_tpu.core import telemetry


def new_trace_id() -> str:
    """A fresh 32-hex trace id, minted once per task submission."""
    return uuid.uuid4().hex


_ENVELOPE_PREFIX = '{"chunkflow"'


def pack_task(body: str, trace_id: Optional[str] = None) -> str:
    """Wrap a task body in the traced wire envelope. Idempotent: a body
    that is already an envelope is returned unchanged, preserving its
    original trace id across requeue/dead-letter round trips."""
    if unpack_task(body)[1] is not None:
        return body
    if trace_id is None:
        trace_id = new_trace_id()
    return json.dumps({"chunkflow": 1, "body": body, "trace_id": trace_id})


def unpack_task(raw: str) -> Tuple[str, Optional[str]]:
    """``(body, trace_id)`` from a wire payload; a non-envelope payload
    (pre-tracing queue contents) unwraps to ``(raw, None)``."""
    if raw.startswith(_ENVELOPE_PREFIX):
        try:
            env = json.loads(raw)
        except ValueError:
            return raw, None
        if isinstance(env, dict) and "body" in env:
            return str(env["body"]), env.get("trace_id")
    return raw, None


def _fetch_span(receive):
    """``queue/fetch`` around a backend's :meth:`QueueBase.receive`: the
    span cannot know its task when it starts, so it takes the delivery's
    trace id once the claim is in hand. A poll that finds the queue
    empty fetched nothing and records nothing: an idle worker polls many
    times a second."""
    @functools.wraps(receive)
    def spanned(self):
        with telemetry.span("queue/fetch") as sp:
            item = receive(self)
            if item is None:
                sp.cancel()
            else:
                sp.bind(self.trace_id(item[0]))
        return item
    return spanned


def _ack_span(delete):
    """``queue/ack`` around a backend's :meth:`QueueBase.delete`, under
    the task the handle was delivered for."""
    @functools.wraps(delete)
    def spanned(self, handle):
        with telemetry.task_context(self.trace_id(handle)), \
                telemetry.span("queue/ack"):
            return delete(self, handle)
    return spanned


class QueueBase:
    """handle/body iteration + ack/lease/dead-letter protocol shared by
    all backends. A backend decorates its ``receive`` with
    :func:`_fetch_span` and its ``delete`` with :func:`_ack_span`."""

    visibility_timeout: float = 1800.0

    def send_messages(self, bodies: List[str]) -> None:
        raise NotImplementedError

    def receive(self) -> Optional[Tuple[str, str]]:
        """One (handle, body) or None when empty."""
        raise NotImplementedError

    # -- distributed tracing --------------------------------------------
    def _pack_bodies(self, bodies: List[str]) -> List[str]:
        """Envelope each outgoing body (idempotent) and emit one
        ``queue/submit`` event per task — submission is where a trace
        begins, so the submitter's JSONL anchors every timeline."""
        packed = []
        for body in bodies:
            wire = pack_task(body)
            packed.append(wire)
            plain, trace_id = unpack_task(wire)
            telemetry.inc("queue/sent")
            telemetry.event(
                "task", "queue/submit", queue=self.describe(),
                body=plain, trace_id=trace_id,
            )
        return packed

    def _note_receive(self, handle: str, trace_id: Optional[str]) -> None:
        if not hasattr(self, "_traces"):
            self._traces: Dict[str, Optional[str]] = {}
        self._traces[handle] = trace_id
        telemetry.inc("queue/receives")

    def trace_id(self, handle: str) -> Optional[str]:
        """Trace id of a claimed task (None when the delivery carried
        no envelope)."""
        return getattr(self, "_traces", {}).get(handle)

    @staticmethod
    def _present(entry: dict) -> dict:
        """Dead-letter entry for display: the stored body stays in wire
        format (so requeue preserves the trace), the listed copy shows
        the plain body plus its trace id."""
        body, trace_id = unpack_task(entry.get("body", ""))
        shown = dict(entry)
        shown["body"] = body
        if trace_id is not None:
            shown.setdefault("trace_id", trace_id)
        return shown

    def describe(self) -> str:
        """Human-readable queue identity for events and fleet-status."""
        return getattr(self, "name", None) or getattr(self, "dir", "") \
            or type(self).__name__

    def stats(self) -> dict:
        """Live queue state for the fleet-status dashboard:
        ``{"pending", "inflight", "dead", "receives"}``; None for a
        field the backend cannot report cheaply."""
        try:
            pending: Optional[int] = len(self)  # type: ignore[arg-type]
        except (TypeError, NotImplementedError):
            pending = None
        return {"pending": pending, "inflight": None, "dead": None,
                "receives": None}

    def delete(self, handle: str) -> None:
        """Ack: permanently remove a claimed task (the commit point)."""
        raise NotImplementedError

    # -- lifecycle protocol (parallel/lifecycle.py) ---------------------
    def renew(self, handle: str, timeout: Optional[float] = None) -> None:
        """Extend the claim on ``handle`` so it stays invisible for
        another ``timeout`` seconds (default: the queue's visibility
        timeout) from now. The lease heartbeat for in-compute tasks."""
        raise NotImplementedError

    def nack(self, handle: str, refund: bool = True) -> bool:
        """Release the claim immediately: the task becomes visible to
        other workers right away (preemption / fast retry) instead of
        after the visibility timeout. Returns whether a claim was
        actually released (False when the handle already expired, was
        acked, or was janitored back — the work is safe elsewhere).

        With ``refund=True`` (the default, for *first-party* nacks) a
        nacked delivery is a *handback*, not a failure: backends that
        can (memory, file) decrement the receive count so preemption /
        bystander-surrender hops do not burn the retry budget — under
        frequent spot preemption a healthy task would otherwise be
        dead-lettered as a "crash loop" without ever failing.
        ``refund=False`` requeues while *preserving* the count
        (janitor-style): the third-party release path for workers that
        died or wedged, whose deliveries must keep counting toward the
        crash-loop bound. SQS cannot decrement
        ``ApproximateReceiveCount`` either way; size ``--max-retries``
        generously there (the SQS redrive-policy convention)."""
        raise NotImplementedError

    def force_release(self, handles, refund: bool = False) -> int:
        """Third-party nack: release claims a DEAD worker is still
        holding, by handle, so its tasks reappear now instead of after
        the visibility timeout. The fleet supervisor calls this when it
        evicts or reaps a worker, using the lease handles the worker
        last reported over ``/healthz`` (parallel/fleet.py).

        ``refund`` defaults to False: an unexpected or quarantined exit
        is a crash-shaped delivery, and refunding its receive count
        would make the crash-loop bound (lifecycle: ``receives >
        max_retries``) unreachable — a poison task that kills every
        worker it lands on would be redelivered forever. Keep the
        refund for first-party preemption/surrender nacks only.

        Per-handle errors are swallowed — a handle may have expired,
        been janitored back, or belong to a re-claimed task, all of
        which mean the work is already safe. Returns how many claims
        were actually released (no-op nacks are not counted)."""
        released = 0
        for handle in handles or ():
            try:
                if self.nack(handle, refund=refund):
                    released += 1
            except Exception:
                continue
        return released

    def receive_count(self, handle: str) -> int:
        """How many times the claimed task has been delivered, this
        delivery included. 1 on first claim; best-effort (0 when the
        backend cannot tell)."""
        return 0

    def dead_letter(self, handle: str, reason: str = "") -> None:
        """Move a claimed poison task to the dead-letter store with its
        failure reason; it will never be delivered again until an
        operator requeues it."""
        raise NotImplementedError

    def dead_letters(self) -> List[dict]:
        """List dead-letter entries as ``{"body", "reason", "receives",
        "t"}`` dicts (non-destructive where the backend allows)."""
        raise NotImplementedError

    def requeue_dead(self) -> int:
        """Move every dead-letter entry back to pending with a fresh
        retry budget; returns how many were requeued."""
        raise NotImplementedError

    # polling iteration with bounded retries on empty
    max_empty_retries = 3
    retry_sleep = 1.0

    def __iter__(self) -> Iterator[Tuple[str, str]]:
        empty = 0
        while True:
            item = self.receive()
            if item is None:
                empty += 1
                if empty > self.max_empty_retries:
                    return
                time.sleep(self.retry_sleep)
                continue
            empty = 0
            yield item


class MemoryQueue(QueueBase):
    """In-process queue with visibility timeout semantics.

    Thread-safe: one MemoryQueue is drained by several worker THREADS at
    once (the serving front-end's LocalBackend runs a claim loop per
    worker thread, the lifecycle heartbeat renews leases from its own
    thread). ``receive`` in particular is a compound
    claim-and-make-invisible — unlocked, two threads could claim the
    same handle (double execution) or crash on the second ``del``, so
    every compound state transition holds ``_lock``.
    """

    _registry: Dict[str, "MemoryQueue"] = {}
    _registry_lock = threading.Lock()

    def __init__(self, name: str, visibility_timeout: float = 1800.0):
        self.name = name
        self.visibility_timeout = visibility_timeout
        self.pending: Dict[str, str] = {}
        # handle -> (body, visibility deadline): invisible until deadline
        self.invisible: Dict[str, Tuple[str, float]] = {}
        self.receives: Dict[str, int] = {}
        self.dead: Dict[str, dict] = {}
        self.retry_sleep = 0.01
        self._lock = threading.Lock()

    @classmethod
    def open(cls, name: str, visibility_timeout: float = 1800.0) -> "MemoryQueue":
        with cls._registry_lock:
            if name not in cls._registry:
                cls._registry[name] = cls(name, visibility_timeout)
            else:
                # a reopen with a different timeout is a reconfiguration,
                # not a no-op: silently keeping the first value would give
                # lease renewal / requeue tests (and real workers) a
                # different timeout than they asked for
                cls._registry[name].visibility_timeout = visibility_timeout
            return cls._registry[name]

    def send_messages(self, bodies: List[str]) -> None:
        packed = self._pack_bodies(bodies)  # telemetry outside the lock
        with self._lock:
            for body in packed:
                self.pending[uuid.uuid4().hex] = body

    def _requeue_expired(self) -> None:
        """Caller holds ``_lock``."""
        now = time.time()
        expired = [h for h, (_, deadline) in self.invisible.items()
                   if now > deadline]
        for h in expired:
            body, _ = self.invisible.pop(h)
            self.pending[h] = body

    @_fetch_span
    def receive(self) -> Optional[Tuple[str, str]]:
        with self._lock:
            self._requeue_expired()
            if not self.pending:
                return None
            handle, wire = next(iter(self.pending.items()))
            del self.pending[handle]
            self.invisible[handle] = (
                wire, time.time() + self.visibility_timeout
            )
            self.receives[handle] = self.receives.get(handle, 0) + 1
        body, trace_id = unpack_task(wire)
        self._note_receive(handle, trace_id)
        return handle, body

    @_ack_span
    def delete(self, handle: str) -> None:
        with self._lock:
            self.invisible.pop(handle, None)
            self.pending.pop(handle, None)
            self.receives.pop(handle, None)
            getattr(self, "_traces", {}).pop(handle, None)

    def renew(self, handle: str, timeout: Optional[float] = None) -> None:
        with self._lock:
            entry = self.invisible.get(handle)
            if entry is None:
                return  # already expired/acked: nothing to extend
            timeout = self.visibility_timeout if timeout is None else timeout
            self.invisible[handle] = (entry[0], time.time() + timeout)

    def nack(self, handle: str, refund: bool = True) -> bool:
        with self._lock:
            entry = self.invisible.pop(handle, None)
            if entry is None:
                return False  # already acked or expired: nothing to release
            self.pending[handle] = entry[0]
            if refund:
                # a first-party handback is not a failed attempt (see
                # QueueBase.nack); third-party force_release preserves the
                # count so crash deliveries accrue
                count = self.receives.get(handle, 0)
                if count > 0:
                    self.receives[handle] = count - 1
            return True

    def receive_count(self, handle: str) -> int:
        with self._lock:
            return self.receives.get(handle, 0)

    def dead_letter(self, handle: str, reason: str = "") -> None:
        with self._lock:
            entry = self.invisible.pop(handle, None)
            body = entry[0] if entry else self.pending.pop(handle, None)
            if body is None:
                return
            self.dead[handle] = {
                "body": body, "reason": reason,
                "receives": self.receives.pop(handle, 0), "t": time.time(),
            }

    def dead_letters(self) -> List[dict]:
        with self._lock:
            return [self._present(entry) for entry in self.dead.values()]

    def requeue_dead(self) -> int:
        with self._lock:
            count = 0
            for handle, entry in list(self.dead.items()):
                del self.dead[handle]
                # the stored body is still the wire envelope: the requeued
                # task keeps its original trace id, fresh retry budget
                self.pending[handle] = entry["body"]
                count += 1
            return count

    def stats(self) -> dict:
        with self._lock:
            self._requeue_expired()
            return {
                "pending": len(self.pending),
                "inflight": len(self.invisible),
                "dead": len(self.dead),
                "receives": sum(self.receives.values()),
            }

    def __len__(self) -> int:
        with self._lock:
            self._requeue_expired()
            return len(self.pending)


class FileQueue(QueueBase):
    """Directory-backed queue; atomic rename is the claim operation.

    Layout: ``<dir>/pending/<id>`` holds the body; claiming renames it to
    ``<dir>/claimed/<id>``; delete removes the claimed file. A janitor pass
    returns claimed files older than the visibility timeout to pending —
    so crashed workers' tasks reappear, same as SQS. The lifecycle
    extensions ride the same layout: ``<dir>/counts/<id>`` is the
    delivery-count sidecar of a claimed entry (it survives janitor
    requeues, so retry accounting sees crashed attempts too) and
    ``<dir>/dead/<id>`` holds dead-lettered tasks as JSON
    ``{body, reason, receives, t}``.

    An id starts with the time the task was sent (nanoseconds, zero
    padded), so ``receive``, which claims the first pending name in
    sorted order, hands out the oldest task first: tasks leave in the
    order they were sent, as far as one host's clock tells; under a
    random id a task could lie pending for any number of claims while
    younger ones overtook it.
    """

    def __init__(self, directory: str, visibility_timeout: float = 1800.0):
        self.dir = directory
        self.pending_dir = os.path.join(directory, "pending")
        self.claimed_dir = os.path.join(directory, "claimed")
        self.counts_dir = os.path.join(directory, "counts")
        self.dead_dir = os.path.join(directory, "dead")
        for d in (self.pending_dir, self.claimed_dir,
                  self.counts_dir, self.dead_dir):
            os.makedirs(d, exist_ok=True)
        self.visibility_timeout = visibility_timeout

    def send_messages(self, bodies: List[str]) -> None:
        for body in self._pack_bodies(bodies):
            name = f"{time.time_ns():020d}-{uuid.uuid4().hex}"
            tmp = os.path.join(self.dir, f".tmp-{name}")
            with open(tmp, "w") as f:
                f.write(body)
            os.rename(tmp, os.path.join(self.pending_dir, name))

    def _requeue_expired(self) -> None:
        now = time.time()
        for name in os.listdir(self.claimed_dir):
            path = os.path.join(self.claimed_dir, name)
            try:
                if now - os.path.getmtime(path) > self.visibility_timeout:
                    os.rename(path, os.path.join(self.pending_dir, name))
            except OSError:
                pass  # another janitor/worker won the race
        # a writer that crashed mid-stage leaves .tmp-* files behind
        # forever (queue root: send_messages; counts dir: _write_count);
        # sweep the stale ones (older than the visibility timeout, so
        # an in-progress write is safe)
        for d in (self.dir, self.counts_dir):
            for name in os.listdir(d):
                if not name.startswith(".tmp-"):
                    continue
                path = os.path.join(d, name)
                try:
                    if now - os.path.getmtime(path) > self.visibility_timeout:
                        os.remove(path)
                except OSError:
                    pass

    def _write_count(self, name: str, count: int) -> bool:
        """Atomically (re)write a delivery-count sidecar — staged to a
        temp file then renamed, so a concurrent reader never sees a
        half-written (empty) count."""
        tmp = os.path.join(self.counts_dir, f".tmp-{uuid.uuid4().hex}")
        try:
            with open(tmp, "w") as f:
                f.write(str(count))
            os.rename(tmp, os.path.join(self.counts_dir, name))
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass
            return False
        return True

    def _bump_count(self, name: str) -> int:
        count = self._read_count(name) + 1
        self._write_count(name, count)
        return count

    def _read_count(self, name: str) -> int:
        try:
            with open(os.path.join(self.counts_dir, name)) as f:
                return int(f.read().strip() or 0)
        except (OSError, ValueError):
            return 0

    @_fetch_span
    def receive(self) -> Optional[Tuple[str, str]]:
        self._requeue_expired()
        for name in sorted(os.listdir(self.pending_dir)):
            src = os.path.join(self.pending_dir, name)
            dst = os.path.join(self.claimed_dir, name)
            try:
                os.rename(src, dst)  # atomic claim
            except OSError:
                continue  # raced with another worker
            os.utime(dst)
            self._bump_count(name)
            with open(dst) as f:
                body, trace_id = unpack_task(f.read())
            self._note_receive(name, trace_id)
            return name, body
        return None

    @_ack_span
    def delete(self, handle: str) -> None:
        for path in (os.path.join(self.claimed_dir, handle),
                     os.path.join(self.counts_dir, handle)):
            try:
                os.remove(path)
            except FileNotFoundError:
                pass
        getattr(self, "_traces", {}).pop(handle, None)

    def renew(self, handle: str, timeout: Optional[float] = None) -> None:
        timeout = self.visibility_timeout if timeout is None else timeout
        path = os.path.join(self.claimed_dir, handle)
        # expiry is mtime + visibility_timeout: place the mtime so the
        # claim lives exactly `timeout` seconds from now
        stamp = time.time() + timeout - self.visibility_timeout
        try:
            os.utime(path, (stamp, stamp))
        except OSError:
            pass  # expired and re-claimed elsewhere: lease is lost

    def nack(self, handle: str, refund: bool = True) -> bool:
        # a first-party handback is not a failed attempt (see
        # QueueBase.nack); janitor requeues after a CRASH never pass
        # here, and third-party force_release passes refund=False, so
        # crash deliveries keep counting toward the crash-loop bound.
        # The refund lands BEFORE the rename makes the task visible
        # again: while the claim file exists no other worker can
        # re-claim and bump, so this read-modify-write cannot overwrite
        # a newer delivery's count (decrement-after-rename raced
        # exactly that way).
        refunded = False
        if refund:
            count = self._read_count(handle)
            if count > 0:
                refunded = self._write_count(handle, count - 1)
        try:
            os.rename(os.path.join(self.claimed_dir, handle),
                      os.path.join(self.pending_dir, handle))
        except OSError:
            if refunded:  # the janitor beat us to it: the count stands
                self._bump_count(handle)
            return False
        return True

    def receive_count(self, handle: str) -> int:
        return self._read_count(handle)

    def dead_letter(self, handle: str, reason: str = "") -> None:
        claimed = os.path.join(self.claimed_dir, handle)
        try:
            with open(claimed) as f:
                body = f.read()
        except OSError:
            return  # lost the claim: someone else owns the task now
        entry = {"body": body, "reason": reason,
                 "receives": self._read_count(handle), "t": time.time()}
        tmp = os.path.join(self.dir, f".tmp-dead-{handle}")
        with open(tmp, "w") as f:
            json.dump(entry, f)
        os.rename(tmp, os.path.join(self.dead_dir, handle))
        self.delete(handle)

    def dead_letters(self) -> List[dict]:
        entries = []
        for name in sorted(os.listdir(self.dead_dir)):
            try:
                with open(os.path.join(self.dead_dir, name)) as f:
                    entries.append(self._present(json.load(f)))
            except (OSError, ValueError):
                continue
        return entries

    def requeue_dead(self) -> int:
        count = 0
        for name in sorted(os.listdir(self.dead_dir)):
            path = os.path.join(self.dead_dir, name)
            try:
                with open(path) as f:
                    entry = json.load(f)
            except (OSError, ValueError):
                continue
            # the stored body is the wire envelope; pack_task inside
            # send_messages is idempotent, so the trace id survives
            self.send_messages([entry["body"]])
            try:
                os.remove(path)
            except OSError:
                continue
            count += 1
        return count

    def stats(self) -> dict:
        self._requeue_expired()
        receives = 0
        for name in os.listdir(self.counts_dir):
            if name.startswith(".tmp-"):  # a writer died mid-stage
                continue
            receives += self._read_count(name)
        return {
            "pending": len(os.listdir(self.pending_dir)),
            "inflight": len(os.listdir(self.claimed_dir)),
            "dead": len(os.listdir(self.dead_dir)),
            "receives": receives,
        }

    def __len__(self) -> int:
        return len(os.listdir(self.pending_dir))


class SQSQueue(QueueBase):
    """AWS SQS backend (requires boto3 + credentials; not in this image).

    ``client`` injection exists for tests: the lifecycle/batch-send
    surfaces are exercised against a fake client without boto3."""

    def __init__(self, name: str, visibility_timeout: int = 1800,
                 client=None):
        if client is None:
            try:
                import boto3
            except ImportError as e:
                raise RuntimeError(
                    "sqs:// queues need boto3, which is not installed; "
                    "use file:// or memory:// queues instead"
                ) from e
            client = boto3.client("sqs")
        self.client = client
        self.name = name
        self.visibility_timeout = visibility_timeout
        resp = self.client.create_queue(
            QueueName=name,
            Attributes={"VisibilityTimeout": str(visibility_timeout)},
        )
        self.queue_url = resp["QueueUrl"]
        self._dead_url: Optional[str] = None
        self._receive_counts: Dict[str, int] = {}

    def _send_batch(self, entries: List[dict]) -> None:
        resp = self.client.send_message_batch(
            QueueUrl=self.queue_url, Entries=entries
        )
        failed = resp.get("Failed") or []
        if not failed:
            return
        # partial-batch failure is a *success* response carrying Failed
        # entries — dropping them silently loses tasks. Retry the failed
        # subset once (throttling is transient), then raise.
        failed_ids = {f["Id"] for f in failed}
        retry = [e for e in entries if e["Id"] in failed_ids]
        resp = self.client.send_message_batch(
            QueueUrl=self.queue_url, Entries=retry
        )
        failed = resp.get("Failed") or []
        if failed:
            raise IOError(
                f"SQS send_message_batch failed for {len(failed)} "
                f"message(s) after retry: "
                + "; ".join(
                    f"{f.get('Id')}: {f.get('Code')} {f.get('Message', '')}"
                    for f in failed
                )
            )

    def send_messages(self, bodies: List[str]) -> None:
        bodies = self._pack_bodies(bodies)
        for i in range(0, len(bodies), 10):  # SQS batch limit
            entries = [
                {"Id": str(j), "MessageBody": body}
                for j, body in enumerate(bodies[i : i + 10])
            ]
            self._send_batch(entries)

    @_fetch_span
    def receive(self) -> Optional[Tuple[str, str]]:
        resp = self.client.receive_message(
            QueueUrl=self.queue_url, MaxNumberOfMessages=1,
            WaitTimeSeconds=20,
            AttributeNames=["ApproximateReceiveCount"],
        )
        messages = resp.get("Messages", [])
        if not messages:
            return None
        msg = messages[0]
        # transport integrity check (reference sqs_queue.py:95-100)
        expected = msg.get("MD5OfBody")
        if expected:
            import hashlib

            got = hashlib.md5(msg["Body"].encode()).hexdigest()
            if got != expected:
                raise IOError(
                    f"SQS body md5 mismatch: got {got}, expected {expected}"
                )
        handle = msg["ReceiptHandle"]
        try:
            self._receive_counts[handle] = int(
                (msg.get("Attributes") or {}).get("ApproximateReceiveCount", 0)
            )
        except (TypeError, ValueError):
            self._receive_counts[handle] = 0
        self._bodies = getattr(self, "_bodies", {})
        self._bodies[handle] = msg["Body"]  # wire format: dead-letter re-sends it
        body, trace_id = unpack_task(msg["Body"])
        self._note_receive(handle, trace_id)
        return handle, body

    @_ack_span
    def delete(self, handle: str) -> None:
        self.client.delete_message(QueueUrl=self.queue_url, ReceiptHandle=handle)
        self._receive_counts.pop(handle, None)
        getattr(self, "_bodies", {}).pop(handle, None)
        getattr(self, "_traces", {}).pop(handle, None)

    def renew(self, handle: str, timeout: Optional[float] = None) -> None:
        timeout = self.visibility_timeout if timeout is None else timeout
        self.client.change_message_visibility(
            QueueUrl=self.queue_url, ReceiptHandle=handle,
            VisibilityTimeout=int(timeout),
        )

    def nack(self, handle: str, refund: bool = True) -> bool:
        # SQS cannot decrement ApproximateReceiveCount: `refund` is
        # accepted for protocol compatibility but has no effect
        self.renew(handle, 0)
        return True

    def receive_count(self, handle: str) -> int:
        return self._receive_counts.get(handle, 0)

    def _dead_queue_url(self) -> str:
        if self._dead_url is None:
            # short nonzero visibility: dead_letters() below drains to
            # empty to list, so entries must go invisible between
            # receives (or the listing loop would never terminate) and
            # reappear shortly after
            resp = self.client.create_queue(
                QueueName=f"{self.name}-dead",
                Attributes={"VisibilityTimeout": "300"},
            )
            self._dead_url = resp["QueueUrl"]
        return self._dead_url

    def dead_letter(self, handle: str, reason: str = "") -> None:
        body = getattr(self, "_bodies", {}).get(handle)
        if body is None:
            return  # not a task this client received
        entry = {"body": body, "reason": reason,
                 "receives": self.receive_count(handle), "t": time.time()}
        self.client.send_message(
            QueueUrl=self._dead_queue_url(), MessageBody=json.dumps(entry)
        )
        self.delete(handle)

    def _drain_dead(self):
        while True:
            resp = self.client.receive_message(
                QueueUrl=self._dead_queue_url(), MaxNumberOfMessages=10,
                WaitTimeSeconds=0,
            )
            messages = resp.get("Messages", [])
            if not messages:
                return
            for msg in messages:
                try:
                    entry = json.loads(msg["Body"])
                except ValueError:
                    entry = {"body": msg["Body"], "reason": "", "receives": 0}
                yield msg["ReceiptHandle"], entry

    def dead_letters(self) -> List[dict]:
        # SQS has no non-destructive listing: receive-to-empty instead;
        # the entries go invisible for the dead queue's short visibility
        # timeout and then reappear (listing never loses them)
        return [self._present(entry) for _, entry in self._drain_dead()]

    def requeue_dead(self) -> int:
        count = 0
        for handle, entry in self._drain_dead():
            self.send_messages([entry["body"]])
            self.client.delete_message(
                QueueUrl=self._dead_queue_url(), ReceiptHandle=handle
            )
            count += 1
        return count

    def stats(self) -> dict:
        out = {"pending": None, "inflight": None, "dead": None,
               "receives": sum(self._receive_counts.values()) or None}
        try:
            resp = self.client.get_queue_attributes(
                QueueUrl=self.queue_url,
                AttributeNames=["ApproximateNumberOfMessages",
                                "ApproximateNumberOfMessagesNotVisible"],
            )
            attrs = resp.get("Attributes") or {}
            out["pending"] = int(attrs.get("ApproximateNumberOfMessages", 0))
            out["inflight"] = int(
                attrs.get("ApproximateNumberOfMessagesNotVisible", 0))
        except Exception:
            pass  # older fakes / restricted IAM: depth stays unknown
        try:
            resp = self.client.get_queue_attributes(
                QueueUrl=self._dead_queue_url(),
                AttributeNames=["ApproximateNumberOfMessages"],
            )
            out["dead"] = int((resp.get("Attributes") or {})
                              .get("ApproximateNumberOfMessages", 0))
        except Exception:
            pass
        return out


def open_queue(spec: str, visibility_timeout: float = 1800.0) -> QueueBase:
    """Open a queue from a ``scheme://name`` spec (bare paths mean file://)."""
    if spec.startswith("memory://"):
        return MemoryQueue.open(spec[len("memory://"):], visibility_timeout)
    if spec.startswith("sqs://"):
        return SQSQueue(spec[len("sqs://"):], int(visibility_timeout))
    if spec.startswith("file://"):
        spec = spec[len("file://"):]
    return FileQueue(spec, visibility_timeout)
