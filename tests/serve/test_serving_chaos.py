"""Serving chaos acceptance (ISSUE 9): a REAL worker process SIGKILLed
mid-request. The request rides the PR 5 lifecycle — lease expiry,
redelivery to a fresh worker, exactly-once commit through the ledger —
and the client still gets one correct response."""
import json
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.inference import Inferencer
from chunkflow_tpu.serve.frontend import (
    AdmissionController,
    ServingService,
    SpoolBackend,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture
def clean(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    yield monkeypatch
    telemetry.reset()


def _spawn_worker(spool: str, slow_plugin: str, log_path: str):
    """One external serving worker: the standard supervised
    fetch/compute/save/ack chain over the spool queue — exactly the
    chain a fleet-run would spawn."""
    cmd = [
        sys.executable, "-m", "chunkflow_tpu.flow.cli",
        "fetch-task-from-queue", "-q", os.path.join(spool, "queue"),
        "-v", "3", "-r", "60", "--poll-interval", "0.25",
        "--max-retries", "20", "--lease-renew", "1.0",
        "--backoff-base", "0.01", "--backoff-cap", "0.1",
        "--ledger", os.path.join(spool, "ledger"),
        "load-h5", "-f", os.path.join(spool, "in") + os.sep,
        "plugin", "--name", slow_plugin,
        "inference", "-s", "4", "8", "8", "-v", "1", "2", "2",
        "-c", "1", "-f", "identity", "--no-crop-output-margin",
        "save-h5", "--file-name", os.path.join(spool, "out") + os.sep,
        "delete-task-in-queue",
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="",
               PYTHONPATH=REPO_ROOT)
    log = open(log_path, "ab")
    try:
        return subprocess.Popen(cmd, env=env, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    finally:
        log.close()


def test_worker_sigkill_mid_request_completes_exactly_once(
        clean, tmp_path):
    """POST-shaped request -> spool queue -> worker A claims it ->
    SIGKILL worker A mid-compute -> the lease expires, worker B claims
    the redelivery, completes, commits -> the front-end answers 200
    with the bit-exact result; exactly one ledger marker, one output
    file, a clean queue."""
    spool = str(tmp_path / "spool")
    slow = str(tmp_path / "slow.py")
    with open(slow, "w") as f:
        # a wide, honest kill window on any box
        f.write("import time\n\n\ndef execute(chunk):\n"
                "    time.sleep(1.0)\n    return chunk\n")

    backend = SpoolBackend(spool, visibility_timeout=3.0, poll_s=0.05)
    service = ServingService(
        backend, admission=AdmissionController(max_inflight=4),
        default_deadline_s=120.0,
    )
    rng = np.random.default_rng(6)
    arr = rng.random((8, 16, 16)).astype(np.float32)
    reference = Inferencer(
        input_patch_size=(4, 8, 8), output_patch_overlap=(1, 2, 2),
        num_output_channels=1, framework="identity",
        crop_output_margin=False, batch_size=1,
    )
    ref = np.asarray(reference(Chunk(arr)).array)

    import base64

    body = json.dumps({
        "shape": list(arr.shape),
        "dtype": "float32",
        "data_b64": base64.b64encode(arr.tobytes()).decode(),
        "deadline_s": 110.0,
    }).encode()

    response = {}

    def post():
        response["status"], response["payload"] = service.handle(
            "POST", "/infer", body)

    worker_a = _spawn_worker(spool, slow, str(tmp_path / "worker-a.log"))
    worker_b = None
    client = threading.Thread(target=post, daemon=True)
    try:
        client.start()
        # wait until worker A actually CLAIMS the request (in-flight on
        # the queue), then kill it inside the 1 s slow-plugin window
        deadline = time.time() + 60
        while time.time() < deadline:
            stats = backend.queue.stats()
            if stats.get("inflight"):
                break
            time.sleep(0.02)
        else:
            raise AssertionError("worker A never claimed the request")
        os.kill(worker_a.pid, signal.SIGKILL)  # crash-shaped death
        assert worker_a.wait(timeout=10) == -signal.SIGKILL
        # the claim is now a dead lease; a fresh worker must recover it
        worker_b = _spawn_worker(spool, slow,
                                 str(tmp_path / "worker-b.log"))
        client.join(timeout=120)
        assert not client.is_alive(), "request never completed"
        assert response["status"] == 200, response
        got = np.frombuffer(
            base64.b64decode(response["payload"]["data_b64"]),
            dtype=response["payload"]["dtype"],
        ).reshape(response["payload"]["shape"])
        assert np.array_equal(got, ref), "recovered result diverged"
        # exactly once: one ledger marker, one output file
        ledger_dir = os.path.join(spool, "ledger")
        marks = [n for n in os.listdir(ledger_dir)
                 if n.endswith(".done")]
        assert len(marks) == 1, marks
        outs = [n for n in os.listdir(os.path.join(spool, "out"))
                if n.endswith(".h5")]
        assert len(outs) == 1, outs
        # queue clean: nothing pending/in-flight/dead-lettered
        for _ in range(100):
            stats = backend.queue.stats()
            if not stats.get("pending") and not stats.get("inflight"):
                break
            time.sleep(0.1)
        assert not stats.get("pending"), stats
        assert not backend.queue.dead_letters()
    finally:
        for proc in (worker_a, worker_b):
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
        backend.close()
