import os
import time

import pytest

from chunkflow_tpu.parallel.queues import (
    FileQueue,
    MemoryQueue,
    SQSQueue,
    open_queue,
    unpack_task,
)


class TestMemoryQueue:
    def test_send_receive_delete(self):
        q = MemoryQueue("t1", visibility_timeout=100)
        q.send_messages(["a", "b", "c"])
        assert len(q) == 3
        handle, body = q.receive()
        assert body in {"a", "b", "c"}
        assert len(q) == 2  # claimed message is invisible
        q.delete(handle)
        bodies = {q.receive()[1], q.receive()[1]}
        assert len(bodies) == 2
        assert q.receive() is None

    def test_visibility_timeout_requeues(self):
        q = MemoryQueue("t2", visibility_timeout=0.05)
        q.send_messages(["task"])
        handle, _ = q.receive()
        assert q.receive() is None
        time.sleep(0.1)
        handle2, body = q.receive()  # crashed-worker task reappears
        assert body == "task"
        q.delete(handle2)
        time.sleep(0.1)
        assert q.receive() is None

    def test_iteration_drains(self):
        q = MemoryQueue("t3")
        q.retry_sleep = 0.01
        q.send_messages([str(i) for i in range(5)])
        seen = []
        for handle, body in q:
            seen.append(body)
            q.delete(handle)
        assert sorted(seen) == [str(i) for i in range(5)]


class TestFileQueue:
    def test_send_receive_delete(self, tmp_path):
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=100)
        q.send_messages(["0-4_0-4_0-4", "4-8_0-4_0-4"])
        assert len(q) == 2
        handle, body = q.receive()
        assert body.count("_") == 2
        assert len(q) == 1
        q.delete(handle)
        assert not os.path.exists(os.path.join(q.claimed_dir, handle))

    def test_crashed_worker_task_reappears(self, tmp_path):
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=0.05)
        q.send_messages(["task"])
        q.receive()  # claim without ack = simulated crash
        assert len(q) == 0
        time.sleep(0.1)
        handle, body = q.receive()
        assert body == "task"

    def test_two_workers_no_double_claim(self, tmp_path):
        q1 = FileQueue(str(tmp_path / "q"), visibility_timeout=100)
        q2 = FileQueue(str(tmp_path / "q"), visibility_timeout=100)
        q1.send_messages(["a", "b"])
        r1 = q1.receive()
        r2 = q2.receive()
        assert r1[1] != r2[1]
        assert q1.receive() is None


def test_open_queue_schemes(tmp_path):
    assert isinstance(open_queue("memory://x"), MemoryQueue)
    assert isinstance(open_queue(str(tmp_path / "fq")), FileQueue)
    assert isinstance(open_queue("file://" + str(tmp_path / "fq2")), FileQueue)


# ---------------------------------------------------------------------------
# lifecycle protocol: renew / nack / receive counts / dead-letter
# (parallel/lifecycle.py rides these; docs/fault_tolerance.md)
# ---------------------------------------------------------------------------
class TestMemoryQueueLifecycle:
    def test_reopen_updates_visibility_timeout(self):
        """A reopen with a different timeout reconfigures the registered
        queue instead of silently keeping the first value (regression:
        MemoryQueue.open ignored the argument on reopen)."""
        q1 = MemoryQueue.open("reopen-vt", visibility_timeout=100)
        q2 = MemoryQueue.open("reopen-vt", visibility_timeout=0.05)
        assert q2 is q1
        assert q1.visibility_timeout == 0.05
        q1.send_messages(["task"])
        q1.receive()
        time.sleep(0.1)
        assert q1.receive() is not None  # the NEW timeout governs expiry

    def test_renew_extends_lease(self):
        q = MemoryQueue("renew", visibility_timeout=0.1)
        q.send_messages(["task"])
        handle, _ = q.receive()
        time.sleep(0.06)
        q.renew(handle)  # heartbeat: another 0.1s from now
        time.sleep(0.06)
        assert q.receive() is None  # still leased
        time.sleep(0.1)
        assert q.receive() is not None  # lease finally expired

    def test_renew_custom_timeout_is_backoff(self):
        q = MemoryQueue("renew2", visibility_timeout=100)
        q.send_messages(["task"])
        handle, _ = q.receive()
        q.renew(handle, 0.05)  # re-claim for a short backoff window
        assert q.receive() is None
        time.sleep(0.1)
        assert q.receive() is not None

    def test_nack_releases_immediately(self):
        q = MemoryQueue("nack", visibility_timeout=100)
        q.send_messages(["task"])
        handle, _ = q.receive()
        assert q.receive() is None
        q.nack(handle)
        handle2, body = q.receive()
        assert body == "task" and handle2 == handle

    def test_receive_count_accumulates_across_redeliveries(self):
        q = MemoryQueue("counts", visibility_timeout=100)
        q.send_messages(["task"])
        handle, _ = q.receive()
        assert q.receive_count(handle) == 1
        # a crash-shaped redelivery (lease expiry) burns an attempt...
        wire, _deadline = q.invisible[handle]
        q.invisible[handle] = (wire, 0.0)
        handle, _ = q.receive()
        assert q.receive_count(handle) == 2
        # ...but a polite nack is a handback and refunds it
        q.nack(handle)
        handle, _ = q.receive()
        assert q.receive_count(handle) == 2
        q.delete(handle)
        assert q.receive_count(handle) == 0  # budget cleared with the ack

    def test_force_release_preserves_receive_count(self):
        """Supervisor force-release of a dead worker's lease is a
        crash-shaped handback: the receive count must keep accruing so
        a poison task that kills every worker still walks into the
        crash-loop bound instead of being redelivered forever."""
        q = MemoryQueue("force", visibility_timeout=100)
        q.send_messages(["task"])
        handle, _ = q.receive()
        assert q.receive_count(handle) == 1
        assert q.force_release([handle]) == 1
        handle, _ = q.receive()
        assert q.receive_count(handle) == 2  # delivery accrued
        # the first-party refund path still exists for preemption
        assert q.nack(handle, refund=True) is True
        handle, _ = q.receive()
        assert q.receive_count(handle) == 2

    def test_force_release_counts_only_real_releases(self):
        """A nack on an already-acked/expired handle is a no-op and
        must not inflate the released count (fleet/leases_nacked)."""
        q = MemoryQueue("force-noop", visibility_timeout=100)
        q.send_messages(["task"])
        handle, _ = q.receive()
        q.delete(handle)
        assert q.nack(handle) is False
        assert q.force_release([handle, "ghost"]) == 0

    def test_dead_letter_and_requeue(self):
        q = MemoryQueue("dead", visibility_timeout=100)
        q.send_messages(["poison"])
        handle, _ = q.receive()
        q.dead_letter(handle, reason="boom")
        assert len(q) == 0 and q.receive() is None
        entries = q.dead_letters()
        assert len(entries) == 1
        assert entries[0]["body"] == "poison"
        assert entries[0]["reason"] == "boom"
        assert entries[0]["receives"] == 1
        assert q.requeue_dead() == 1
        assert q.dead_letters() == []
        handle, body = q.receive()
        assert body == "poison"
        assert q.receive_count(handle) == 1  # fresh retry budget


class TestFileQueueLifecycle:
    def test_renew_extends_lease(self, tmp_path):
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=0.1)
        q.send_messages(["task"])
        handle, _ = q.receive()
        time.sleep(0.06)
        q.renew(handle)
        time.sleep(0.06)
        assert q.receive() is None
        time.sleep(0.1)
        assert q.receive() is not None

    def test_nack_releases_immediately(self, tmp_path):
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=100)
        q.send_messages(["task"])
        handle, _ = q.receive()
        q.nack(handle)
        assert len(q) == 1
        assert q.receive()[1] == "task"

    def test_force_release_preserves_receive_count(self, tmp_path):
        """Same crash-loop substrate as the memory backend: a
        third-party release keeps the sidecar count."""
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=100)
        q.send_messages(["task"])
        handle, _ = q.receive()
        assert q.receive_count(handle) == 1
        assert q.force_release([handle]) == 1
        handle, _ = q.receive()
        assert q.receive_count(handle) == 2

    def test_nack_refund_lands_before_release(self, tmp_path,
                                              monkeypatch):
        """The refund is written while the claim file still exists, so
        no other worker can re-claim (and bump) mid-decrement — the
        old decrement-after-rename overwrote a new delivery's count
        with the stale value, silently erasing retry-budget burns."""
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=100)
        q.send_messages(["task"])
        handle, _ = q.receive()
        seen = {}
        real_rename = os.rename

        def spy(src, dst):
            if (os.path.dirname(src) == q.claimed_dir
                    and os.path.basename(src) == handle):
                seen["count_at_release"] = q._read_count(handle)
            return real_rename(src, dst)

        monkeypatch.setattr(os, "rename", spy)
        assert q.nack(handle) is True
        assert seen["count_at_release"] == 0  # refunded pre-visibility

    def test_nack_on_lost_claim_rolls_refund_back(self, tmp_path):
        """When the janitor (or an ack elsewhere) already took the
        claim, the handback never happened: nack reports False and the
        pre-applied refund is restored."""
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=100)
        q.send_messages(["task"])
        handle, _ = q.receive()
        os.remove(os.path.join(q.claimed_dir, handle))  # claim lost
        assert q.nack(handle) is False
        assert q._read_count(handle) == 1  # the count stands

    def test_receive_count_survives_crash_requeue(self, tmp_path):
        """The sidecar count survives a janitor requeue, so retry
        accounting sees attempts that died without recording a failure
        (the crash-loop guard's substrate)."""
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=0.05)
        q.send_messages(["task"])
        handle, _ = q.receive()
        assert q.receive_count(handle) == 1
        time.sleep(0.1)  # claim expires: simulated worker death
        handle, _ = q.receive()
        assert q.receive_count(handle) == 2

    def test_dead_letter_and_requeue(self, tmp_path):
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=100)
        q.send_messages(["poison"])
        handle, _ = q.receive()
        q.dead_letter(handle, reason="bad bbox")
        assert q.receive() is None
        assert not os.listdir(q.claimed_dir)
        entries = q.dead_letters()
        assert len(entries) == 1
        assert entries[0]["body"] == "poison"
        assert entries[0]["reason"] == "bad bbox"
        # a second FileQueue on the same dir (another worker / the CLI)
        # sees and requeues the same dead letters
        q2 = FileQueue(str(tmp_path / "q"), visibility_timeout=100)
        assert q2.requeue_dead() == 1
        assert q2.dead_letters() == []
        assert q2.receive()[1] == "poison"

    def test_janitor_sweeps_stale_tmp_files(self, tmp_path):
        """A sender that crashes mid-send_messages leaks .tmp-* staging
        files; the janitor removes the stale ones (older than the
        visibility timeout) but never an in-progress send's fresh one."""
        q = FileQueue(str(tmp_path / "q"), visibility_timeout=0.05)
        stale = os.path.join(q.dir, ".tmp-deadbeef")
        fresh = os.path.join(q.dir, ".tmp-inprogress")
        with open(stale, "w") as f:
            f.write("half a task")
        old = time.time() - 10
        os.utime(stale, (old, old))
        with open(fresh, "w") as f:
            f.write("being written right now")
        q._requeue_expired()
        assert not os.path.exists(stale)
        assert os.path.exists(fresh)
        assert len(q) == 0  # the torn task never becomes pending


# ---------------------------------------------------------------------------
# SQS backend against a fake client (boto3 is not in this image)
# ---------------------------------------------------------------------------
class FakeSQSClient:
    """Minimal in-memory stand-in for boto3's SQS client: enough surface
    for the batch-send retry and lifecycle paths."""

    def __init__(self, fail_batches=0, fail_ids=()):
        self.queues = {}
        self.fail_batches = fail_batches  # how many send batches report Failed
        self.fail_ids = set(fail_ids)
        self.send_batch_calls = []

    def create_queue(self, QueueName, Attributes=None):
        url = f"fake://{QueueName}"
        self.queues.setdefault(url, {"messages": [], "receives": {}})
        return {"QueueUrl": url}

    def send_message_batch(self, QueueUrl, Entries):
        self.send_batch_calls.append([e["Id"] for e in Entries])
        failed = []
        for entry in Entries:
            if self.fail_batches > 0 and entry["Id"] in self.fail_ids:
                failed.append({
                    "Id": entry["Id"], "Code": "Throttled",
                    "Message": "try later",
                })
            else:
                self.queues[QueueUrl]["messages"].append(entry["MessageBody"])
        if failed:
            self.fail_batches -= 1
        return {"Failed": failed} if failed else {}

    def send_message(self, QueueUrl, MessageBody, **_):
        self.queues[QueueUrl]["messages"].append(MessageBody)
        return {}

    def receive_message(self, QueueUrl, MaxNumberOfMessages=1, **_):
        q = self.queues[QueueUrl]
        messages = []
        for body in q["messages"][:MaxNumberOfMessages]:
            q["messages"].remove(body)
            handle = f"rh-{len(q['receives'])}-{body[:12]}"
            q["receives"][handle] = q["receives"].get(handle, 0) + 1
            q.setdefault("inflight", {})[handle] = body
            messages.append({
                "ReceiptHandle": handle, "Body": body,
                "Attributes": {
                    "ApproximateReceiveCount": str(q["receives"][handle])
                },
            })
        return {"Messages": messages} if messages else {}

    def delete_message(self, QueueUrl, ReceiptHandle):
        self.queues[QueueUrl]["receives"].pop(ReceiptHandle, None)
        self.queues[QueueUrl].get("inflight", {}).pop(ReceiptHandle, None)

    def change_message_visibility(self, QueueUrl, ReceiptHandle,
                                  VisibilityTimeout):
        self.last_visibility = (ReceiptHandle, VisibilityTimeout)
        if VisibilityTimeout == 0:
            # a real SQS nack makes the message deliverable again NOW;
            # the fake otherwise consumes on receive
            q = self.queues[QueueUrl]
            body = q.get("inflight", {}).pop(ReceiptHandle, None)
            if body is not None:
                q["messages"].append(body)

    def get_queue_attributes(self, QueueUrl, AttributeNames=()):
        q = self.queues[QueueUrl]
        return {"Attributes": {
            "ApproximateNumberOfMessages": str(len(q["messages"])),
            "ApproximateNumberOfMessagesNotVisible": str(len(q["receives"])),
        }}


class TestSQSQueue:
    def test_partial_batch_failure_retried_once(self):
        """send_message_batch can return Failed entries in a *success*
        response; dropping them silently loses tasks (regression). The
        failed subset is retried once, then the send raises."""
        client = FakeSQSClient(fail_batches=1, fail_ids={"1"})
        q = SQSQueue("jobs", client=client)
        q.send_messages(["a", "b", "c"])
        # first call sends all three, retry call resends only Id 1
        assert client.send_batch_calls == [["0", "1", "2"], ["1"]]
        # stored bodies are the traced wire envelopes; the task payloads
        # inside are intact
        assert sorted(
            unpack_task(m)[0] for m in client.queues[q.queue_url]["messages"]
        ) == ["a", "b", "c"]

    def test_partial_batch_failure_raises_after_retry(self):
        client = FakeSQSClient(fail_batches=2, fail_ids={"0"})
        q = SQSQueue("jobs2", client=client)
        with pytest.raises(IOError, match="Throttled"):
            q.send_messages(["a", "b"])

    def test_receive_count_from_attributes(self):
        client = FakeSQSClient()
        q = SQSQueue("jobs3", client=client)
        q.send_messages(["task"])
        handle, body = q.receive()
        assert body == "task"
        assert q.receive_count(handle) == 1

    def test_renew_and_nack_change_visibility(self):
        client = FakeSQSClient()
        q = SQSQueue("jobs4", client=client, visibility_timeout=300)
        q.send_messages(["task"])
        handle, _ = q.receive()
        q.renew(handle)
        assert client.last_visibility == (handle, 300)
        q.renew(handle, 25)
        assert client.last_visibility == (handle, 25)
        q.nack(handle)
        assert client.last_visibility == (handle, 0)

    def test_dead_letter_carries_reason(self):
        # NOTE: the fake consumes on receive (no visibility-restore), so
        # listing and requeueing are asserted in separate tests; real SQS
        # restores listed entries after the dead queue's short timeout
        client = FakeSQSClient()
        q = SQSQueue("jobs5", client=client)
        q.send_messages(["poison"])
        handle, _ = q.receive()
        q.dead_letter(handle, reason="boom")
        entries = q.dead_letters()
        assert len(entries) == 1
        assert entries[0]["body"] == "poison"
        assert entries[0]["reason"] == "boom"
        assert entries[0]["receives"] == 1

    def test_dead_letter_requeue(self):
        client = FakeSQSClient()
        q = SQSQueue("jobs6", client=client)
        q.send_messages(["poison"])
        handle, _ = q.receive()
        q.dead_letter(handle, reason="boom")
        assert q.requeue_dead() == 1
        handle, body = q.receive()
        assert body == "poison"


class TestMemoryQueueConcurrency:
    def test_concurrent_receive_claims_each_task_exactly_once(self):
        """Regression (concurrency plane): ``receive`` is a compound
        claim-and-make-invisible. Unlocked, two LocalBackend worker
        threads could claim the same handle (double execution) or die
        on the second ``del``; under the queue lock every task is
        claimed exactly once across racing threads."""
        import threading

        q = MemoryQueue("t-concurrent-claims", visibility_timeout=100)
        n_tasks, n_threads = 300, 8
        q.send_messages([f"task-{i}" for i in range(n_tasks)])
        claimed, errors = [], []
        claimed_lock = threading.Lock()

        def worker():
            while True:
                try:
                    item = q.receive()
                except Exception as exc:  # noqa: BLE001 — the regression
                    errors.append(exc)
                    return
                if item is None:
                    return
                with claimed_lock:
                    claimed.append(item)

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors, f"receive raced: {errors[:3]}"
        bodies = sorted(body for _h, body in claimed)
        assert bodies == sorted(f"task-{i}" for i in range(n_tasks))
        handles = [h for h, _b in claimed]
        assert len(set(handles)) == len(handles)  # no double-claims


def test_file_queue_hands_out_the_oldest_task_first(tmp_path):
    """Tasks leave a file queue in the order they were sent, whatever is
    pending at once (ISSUE 37: the benchmark's feeder offers a blank task
    every sixth, and the worker has to meet them six apart)."""
    from chunkflow_tpu.parallel.queues import open_queue

    queue = open_queue(f"file://{tmp_path}/q")
    bodies = [f"task-{i}" for i in range(40)]
    queue.send_messages(bodies[:25])
    got = []
    for _ in range(10):
        handle, body = queue.receive()
        got.append(body)
        queue.delete(handle)
    for body in bodies[25:]:          # three at a time, as a feeder does
        queue.send_messages([body])
    while True:
        claimed = queue.receive()
        if claimed is None:
            break
        got.append(claimed[1])
        queue.delete(claimed[0])
    assert got == bodies
