"""Serving front-end: POST /infer over real HTTP, admission-reject and
deadline-expiry paths, lifecycle retry containment, port-0 ephemeral
listeners + endpoint files, the Prometheus serving histogram, and the
SERVING blocks in log-summary (ISSUE 9)."""
import base64
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.inference import Inferencer
from chunkflow_tpu.serve.frontend import (
    AdmissionController,
    AdmissionRejected,
    LocalBackend,
    ServingRequest,
    ServingService,
    start_serving,
)
from chunkflow_tpu.serve.packer import RequestExpired
from chunkflow_tpu.testing import chaos


@pytest.fixture
def clean(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    monkeypatch.delenv("CHUNKFLOW_SERVE", raising=False)
    monkeypatch.delenv("CHUNKFLOW_SCHED_MEM_GB", raising=False)
    telemetry.reset()
    chaos.reset()
    yield monkeypatch
    chaos.reset()
    telemetry.reset()


def make_inferencer():
    return Inferencer(
        input_patch_size=(4, 16, 16),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=3,
        framework="identity",
        batch_size=4,
        crop_output_margin=False,
    )


def infer_body(arr, deadline_s=20.0, **extra):
    payload = {
        "shape": list(arr.shape),
        "dtype": arr.dtype.name,
        "data_b64": base64.b64encode(
            np.ascontiguousarray(arr).tobytes()).decode(),
        "deadline_s": deadline_s,
    }
    payload.update(extra)
    return json.dumps(payload).encode()


def decode_response(payload):
    return np.frombuffer(
        base64.b64decode(payload["data_b64"]), dtype=payload["dtype"]
    ).reshape(payload["shape"])


# ---------------------------------------------------------------------------
# the full HTTP path
# ---------------------------------------------------------------------------
def test_post_infer_end_to_end_http(clean):
    """Real sockets end to end: port 0 binds ephemeral, POST /infer
    returns the bit-exact per-chunk result with a trace id, /serving
    reports the latency quantiles, and the request committed exactly
    once through the lifecycle layer."""
    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=2)
    service = ServingService(backend, default_deadline_s=30.0)
    server = start_serving(service, host="127.0.0.1", port=0)
    port = server.server_address[1]
    assert port > 0
    try:
        rng = np.random.default_rng(0)
        arr = rng.random((6, 20, 28)).astype(np.float32)
        ref = np.asarray(inferencer(Chunk(arr)).array)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/infer",
            data=infer_body(arr), method="POST")
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            payload = json.loads(resp.read())
        assert np.array_equal(decode_response(payload), ref)
        assert payload["trace_id"]
        assert payload["latency_s"] > 0
        # /serving rides the same listener
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/serving", timeout=10) as resp:
            stats = json.loads(resp.read())
        assert stats["requests"] == 1
        assert stats["completed"] == 1
        assert stats["latency_p50_s"] > 0
        # /metrics renders the latency histogram + serving counters
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as resp:
            text = resp.read().decode()
        assert "chunkflow_serving_latency_bucket" in text
        from chunkflow_tpu.parallel.restapi import serving_stats

        parsed = serving_stats(text)
        assert parsed["completed"] == 1
        assert parsed["p50_s"] is not None
        # exactly-once commit through the lifecycle layer
        snap = telemetry.snapshot()
        assert snap["counters"].get("tasks/committed") == 1
        assert len(backend.ledger) == 1
    finally:
        backend.close()
        server.shutdown()
        server.server_close()


def test_one_request_is_one_span_tree(clean, tmp_path):
    """ISSUE 23: from before the body is read to after the response is
    written a request is `serving/http`; inside it lie decode, request,
    two encodes (array -> base64, payload -> JSON) and send; the wait
    from admission to the first device batch is `serving/queue`, closed
    on the packer thread. All share the id the response reports."""
    path = telemetry.configure(str(tmp_path))
    backend = LocalBackend(make_inferencer(), workers=1)
    service = ServingService(backend, default_deadline_s=30.0)
    server = start_serving(service, host="127.0.0.1", port=0)
    port = server.server_address[1]
    try:
        arr = np.random.default_rng(1).random((6, 20, 28)).astype(np.float32)
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/infer", data=infer_body(arr),
            method="POST")
        with urllib.request.urlopen(req, timeout=60) as resp:
            payload = json.loads(resp.read())
        # a route nobody times rides the same handler
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/serving", timeout=10) as resp:
            assert resp.status == 200
    finally:
        backend.close()
        server.shutdown()
        server.server_close()
    with open(path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    mine = [e for e in events if e["kind"] == "span"
            and e.get("trace_id") == payload["trace_id"]]
    by_name = {}
    for event in mine:
        by_name.setdefault(event["name"], []).append(event)
    assert {"serving/http", "serving/decode", "serving/request",
            "serving/queue", "serving/encode", "serving/send",
            "queue/fetch", "queue/ack", "lifecycle/claim",
            "lifecycle/commit"} <= set(by_name)
    (http,) = by_name["serving/http"]
    assert http["parent_id"] is None
    children = [e for name in ("serving/decode", "serving/request",
                               "serving/encode", "serving/send")
                for e in by_name[name]]
    assert len(by_name["serving/encode"]) == 2
    assert all(e["parent_id"] == http["span_id"] for e in children)
    assert all(http["t0"] <= e["t0"] and e["t"] <= http["t"] + 0.005
               for e in children)
    # the queue wait starts at admission, inside the request, and ends
    # on the packer's thread
    (queued,) = by_name["serving/queue"]
    (request,) = by_name["serving/request"]
    assert queued["thread"] == "patch-packer"
    assert request["t0"] - 0.05 <= queued["t0"] <= request["t"]
    assert queued["t"] <= request["t"] + 0.005
    ids = {e["span_id"] for e in events if e["kind"] == "span"}
    assert all(e["parent_id"] is None or e["parent_id"] in ids
               for e in mine)


def test_uint8_request_round_trip(clean):
    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=1)
    service = ServingService(backend)
    try:
        rng = np.random.default_rng(4)
        arr = (rng.random((8, 32, 32)) * 255).astype(np.uint8)
        ref = np.asarray(inferencer(Chunk(arr)).array)
        status, payload = service.handle("POST", "/infer", infer_body(arr))
        assert status == 200
        assert np.array_equal(decode_response(payload), ref)
    finally:
        backend.close()


# ---------------------------------------------------------------------------
# admission control + backpressure
# ---------------------------------------------------------------------------
def test_admission_rejects_past_max_inflight(clean):
    admission = AdmissionController(max_inflight=0)
    with pytest.raises(AdmissionRejected) as err:
        admission.admit(1024)
    assert err.value.reason == "inflight"
    assert telemetry.snapshot()["counters"][
        "serving/rejected_admission"] == 1


def test_admission_reject_is_clean_429_not_worker_death(clean):
    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=1)
    service = ServingService(
        backend, admission=AdmissionController(max_inflight=0))
    try:
        arr = np.zeros((4, 16, 16), dtype=np.float32)
        status, payload = service.handle("POST", "/infer", infer_body(arr))
        assert status == 429
        assert payload["reason"] == "inflight"
        # the server still works once capacity exists
        service.admission.max_inflight = 4
        rng = np.random.default_rng(1)
        arr = rng.random((8, 32, 32)).astype(np.float32)
        status, payload = service.handle("POST", "/infer", infer_body(arr))
        assert status == 200
    finally:
        backend.close()


def test_memory_watermark_backpressure(clean):
    """Admission shares the adaptive scheduler's host-memory watermark:
    a tiny CHUNKFLOW_SCHED_MEM_GB rejects the request with reason
    'memory' instead of admitting it into an OOM."""
    clean.setenv("CHUNKFLOW_SCHED_MEM_GB", "0.000001")  # ~1 KB
    admission = AdmissionController(max_inflight=8)
    with pytest.raises(AdmissionRejected) as err:
        admission.admit(1 << 20)
    assert err.value.reason == "memory"
    assert telemetry.snapshot()["counters"]["serving/rejected_memory"] == 1
    # and the depth controller sees serving reservations too
    clean.setenv("CHUNKFLOW_SCHED_MEM_GB", "4")
    from chunkflow_tpu.flow.scheduler import (
        DepthController,
        release_host_bytes,
        reserve_host_bytes,
    )

    ctl = DepthController(watermark_bytes=1 << 20)
    ctl.note_slot_bytes(1 << 10)
    assert ctl._would_fit()
    assert reserve_host_bytes(1 << 20)  # hog the whole watermark
    try:
        assert not ctl._would_fit()
    finally:
        release_host_bytes(1 << 20)
    assert ctl._would_fit()


def test_malformed_requests_get_400(clean):
    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=1)
    service = ServingService(backend)
    try:
        for body in (
            None,
            b"not json",
            json.dumps({"shape": [4, 16, 16]}).encode(),  # no data
            json.dumps({"shape": [0, 16, 16], "dtype": "uint8",
                        "data_b64": ""}).encode(),
            json.dumps({"shape": [4, 16, 16], "dtype": "float64",
                        "data_b64": ""}).encode(),
            json.dumps({"shape": [4, 16, 16], "dtype": "uint8",
                        "data_b64": "AAAA"}).encode(),  # size mismatch
        ):
            status, payload = service.handle("POST", "/infer", body)
            assert status == 400, body
            assert "error" in payload
    finally:
        backend.close()


# ---------------------------------------------------------------------------
# deadlines
# ---------------------------------------------------------------------------
class _StallBackend:
    """A backend that never completes anything: the deadline clock is
    the only way out."""

    def submit(self, record):
        pass

    def wait(self, record, timeout):
        return record.wait(timeout)

    def close(self):
        pass


def test_deadline_miss_is_clean_504(clean):
    service = ServingService(_StallBackend(), default_deadline_s=0.2)
    arr = np.zeros((4, 16, 16), dtype=np.float32)
    t0 = time.time()
    status, payload = service.handle(
        "POST", "/infer", infer_body(arr, deadline_s=0.2))
    assert status == 504
    assert time.time() - t0 < 5.0
    counters = telemetry.snapshot()["counters"]
    assert counters["serving/deadline_missed"] == 1
    # a miss is shed load, not an error
    assert counters.get("serving/errors", 0) == 0


def test_serving_request_outcome_is_first_wins_and_counted_once(clean):
    record = ServingRequest(None, deadline=time.time() + 10)
    assert record.fail(RequestExpired("late"))
    assert not record.fail(RequestExpired("later"))
    assert not record.complete("result")
    counters = telemetry.snapshot()["counters"]
    assert counters["serving/deadline_missed"] == 1
    assert counters.get("serving/completed", 0) == 0


# ---------------------------------------------------------------------------
# lifecycle containment: transient failures retry, requests complete once
# ---------------------------------------------------------------------------
def test_transient_compute_failure_retries_via_lifecycle(clean):
    """A chaos kill at the serving compute boundary is contained by the
    lifecycle layer: the request retries with backoff and completes
    exactly once — the worker does not die, the client sees one 200."""
    chaos.configure("once=serving/compute")
    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=1, max_retries=3,
                           backoff_base=0.01, backoff_cap=0.05)
    service = ServingService(backend, default_deadline_s=30.0)
    try:
        rng = np.random.default_rng(8)
        arr = rng.random((8, 32, 32)).astype(np.float32)
        ref = np.asarray(inferencer(Chunk(arr)).array)
        status, payload = service.handle("POST", "/infer", infer_body(arr))
        assert status == 200
        assert np.array_equal(decode_response(payload), ref)
        counters = telemetry.snapshot()["counters"]
        assert counters.get("chaos/injected", 0) == 1
        assert counters.get("tasks/retried", 0) == 1
        assert counters.get("serving/completed") == 1
        assert counters.get("tasks/committed") == 1
        assert len(backend.ledger) == 1  # exactly one commit marker
    finally:
        backend.close()


def test_poison_request_dead_letters_and_fails_cleanly(clean):
    """A request that fails permanently every time exhausts its retry
    budget and dead-letters; the client gets a clean error, the server
    keeps serving."""
    chaos.configure("seed=1:rate=1.0:points=serving/compute")
    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=1, max_retries=1,
                           backoff_base=0.01, backoff_cap=0.02)
    service = ServingService(backend, default_deadline_s=15.0)
    try:
        arr = np.random.default_rng(0).random((4, 16, 16)) \
            .astype(np.float32)
        status, payload = service.handle("POST", "/infer", infer_body(arr))
        assert status in (500, 504)
        chaos.reset()
        status, payload = service.handle("POST", "/infer", infer_body(arr))
        assert status == 200
    finally:
        backend.close()


# ---------------------------------------------------------------------------
# port 0 + endpoint files (the fleet-supervisor discovery path)
# ---------------------------------------------------------------------------
def test_metrics_exporter_port0_reports_bound_port(clean):
    from chunkflow_tpu.parallel.restapi import (
        bound_port,
        start_metrics_exporter,
    )

    server = start_metrics_exporter(0, host="127.0.0.1")
    try:
        port = bound_port(server)
        assert port and port > 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=10) as resp:
            assert json.loads(resp.read())["status"] == "ok"
    finally:
        server.shutdown()
        server.server_close()


def test_endpoint_file_write_read_merge(clean, tmp_path):
    from chunkflow_tpu.parallel.restapi import (
        read_endpoint_file,
        write_endpoint_file,
    )

    clean.setenv("CHUNKFLOW_WORKER_ID", "fleet-w007")
    telemetry.reset()  # drop the cached worker id
    path = write_endpoint_file(str(tmp_path), metrics_port=18080)
    assert path is not None
    record = read_endpoint_file(str(tmp_path), "fleet-w007")
    assert record["metrics_port"] == 18080
    assert record["worker"] == "fleet-w007"
    # a later write (the serving listener) merges, not clobbers
    write_endpoint_file(str(tmp_path), serving_port=18081)
    record = read_endpoint_file(str(tmp_path), "fleet-w007")
    assert record["metrics_port"] == 18080
    assert record["serving_port"] == 18081
    assert read_endpoint_file(str(tmp_path), "nobody") is None


def test_endpoint_file_respects_kill_switch(clean, tmp_path):
    from chunkflow_tpu.parallel.restapi import write_endpoint_file

    clean.setenv("CHUNKFLOW_TELEMETRY", "0")
    assert write_endpoint_file(str(tmp_path), metrics_port=1) is None
    assert not list(tmp_path.iterdir())


def test_fleet_discovers_port_from_endpoint_file(clean, tmp_path):
    """The supervisor resolves an ephemeral-spawned worker's bound port
    from its endpoint file instead of pre-picking (racy) ports."""
    from chunkflow_tpu.parallel.fleet import FleetSupervisor, WorkerHandle
    from chunkflow_tpu.parallel.restapi import write_endpoint_file

    clean.setenv("CHUNKFLOW_WORKER_ID", "fleet-w001")
    telemetry.reset()
    write_endpoint_file(str(tmp_path), metrics_port=23456)
    clean.delenv("CHUNKFLOW_WORKER_ID")
    telemetry.reset()

    supervisor = FleetSupervisor.__new__(FleetSupervisor)
    supervisor.metrics_dir = str(tmp_path)

    class _Proc:
        pid = 4242

        def poll(self):
            return None

    worker = WorkerHandle("fleet-w001", None, _Proc(), [])
    assert worker.to_record()["endpoint"] is None
    assert supervisor._discover_port(worker) == 23456
    assert worker.port == 23456
    assert worker.to_record()["endpoint"] == "127.0.0.1:23456"
    # unknown worker: stays undiscovered (probation handles it)
    other = WorkerHandle("fleet-w999", None, _Proc(), [])
    assert supervisor._discover_port(other) is None


# ---------------------------------------------------------------------------
# SERVING blocks: log-summary + fleet summary
# ---------------------------------------------------------------------------
def test_log_summary_serving_block(clean, tmp_path, capsys):
    from chunkflow_tpu.flow.log_summary import (
        print_fleet_summary,
        print_telemetry_summary,
    )

    telemetry.configure(str(tmp_path))
    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=1)
    service = ServingService(backend)
    try:
        rng = np.random.default_rng(2)
        for i in range(3):
            arr = rng.random((6, 20, 28)).astype(np.float32)
            status, _ = service.handle("POST", "/infer", infer_body(arr))
            assert status == 200
    finally:
        backend.close()
    telemetry.flush()
    telemetry.configure(None)
    agg = print_telemetry_summary(str(tmp_path))
    out = capsys.readouterr().out
    assert "serving (docs/serving.md):" in out
    assert "serving/requests" in out
    assert "latency p50" in out
    assert agg["counters"]["serving/completed"] == 3
    assert agg["qhists"]["serving/latency"]["count"] == 3
    # per-worker SERVING line in the fleet view
    print_fleet_summary(str(tmp_path))
    out = capsys.readouterr().out
    assert "serving: requests=3" in out


def test_cli_serve_end_to_end(clean, tmp_path):
    """The `chunkflow serve` entry point: ephemeral port published via
    the endpoint file, a live POST /infer answered, graceful drain at
    --max-runtime with the summary line."""
    from click.testing import CliRunner

    from chunkflow_tpu.flow.cli import main as cli_main
    from chunkflow_tpu.parallel.restapi import read_endpoint_file

    clean.setenv("CHUNKFLOW_WORKER_ID", "serve-cli-test")
    metrics_dir = tmp_path / "metrics"
    runner = CliRunner()
    result = {}

    def run_cli():
        result["run"] = runner.invoke(
            cli_main,
            [
                "--metrics-dir", str(metrics_dir),
                "serve", "--port", "0", "--host", "127.0.0.1",
                "-p", "4", "16", "16",
                "--framework", "identity", "-c", "1",
                "--batch-size", "2", "--serve-workers", "1",
                "--max-runtime", "15",
            ],
            catch_exceptions=False,
        )

    thread = threading.Thread(target=run_cli, daemon=True)
    thread.start()
    port = None
    deadline = time.time() + 12
    while time.time() < deadline:
        record = read_endpoint_file(str(metrics_dir), "serve-cli-test")
        if record and record.get("serving_port"):
            port = record["serving_port"]
            break
        time.sleep(0.1)
    assert port, "serve never published its bound port"
    arr = np.random.default_rng(0).random((8, 32, 32)) \
        .astype(np.float32)
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/infer",
        data=infer_body(arr, deadline_s=10.0), method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        assert resp.status == 200
        payload = json.loads(resp.read())
    assert payload["shape"] == [1, 8, 32, 32]
    thread.join(timeout=30)
    assert not thread.is_alive(), "serve did not exit at --max-runtime"
    out = result["run"].output
    assert "serving: http://127.0.0.1:" in out
    assert "serve drained:" in out
    assert result["run"].exit_code == 0


# ---------------------------------------------------------------------------
# volume-reference requests ride the shared BlockCache (ISSUE 15 satellite)
# ---------------------------------------------------------------------------
def test_volume_reference_request_rides_block_cache(clean, tmp_path):
    """A request naming a precomputed volume + bbox instead of inline
    data: the serving plane cuts the chunk out itself through
    PrecomputedVolume.cutout — block-decomposed reads riding the shared
    hot-block LRU (docs/storage.md) — and the result is bit-exact with
    the same region posted inline. A second overlapping request hits the
    cache instead of the store."""
    pytest.importorskip("tensorstore")
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume
    from chunkflow_tpu.volume.storage import reset_shared_cache

    clean.delenv("CHUNKFLOW_STORAGE_CACHE_MB", raising=False)
    reset_shared_cache()
    vol = PrecomputedVolume.create(
        str(tmp_path / "vol"),
        volume_size=(16, 48, 48),
        voxel_size=(40, 4, 4),
        voxel_offset=(0, 0, 0),
        dtype="uint8",
        block_size=(8, 16, 16),
    )
    source = Chunk.create((16, 48, 48), dtype=np.uint8,
                          voxel_size=(40, 4, 4))
    vol.save(source)
    # drop the write-through-populated cache so the FIRST serving load
    # demonstrably reads the store (misses), and the second hits
    reset_shared_cache()

    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=1)
    service = ServingService(backend)
    try:
        body = json.dumps({
            "volume_path": str(tmp_path / "vol"),
            "bbox_start": [0, 0, 0],
            "bbox_size": [8, 32, 48],
            "deadline_s": 30.0,
        }).encode()
        status, payload = service.handle("POST", "/infer", body)
        assert status == 200, payload
        inline = np.asarray(source.array)[:8, :32, :48]
        ref_status, ref_payload = service.handle(
            "POST", "/infer", infer_body(inline))
        assert ref_status == 200
        assert np.array_equal(decode_response(payload),
                              decode_response(ref_payload))

        misses_before = telemetry.snapshot()["counters"].get(
            "storage/misses", 0)
        assert misses_before > 0  # the first load really hit the store
        status, _ = service.handle("POST", "/infer", body)
        assert status == 200
        counters = telemetry.snapshot()["counters"]
        # the repeat load is served from the shared hot-block LRU: hits
        # accrue, misses do not
        assert counters.get("storage/hits", 0) > 0
        assert counters.get("storage/misses", 0) == misses_before
        # one cached volume handle, reused across requests
        assert len(service._volumes) == 1
    finally:
        backend.close()
        reset_shared_cache()


def test_volume_reference_request_validation(clean, tmp_path):
    """Volume-reference request validation is a clean 400: bad bbox,
    mixing inline data with a volume ref, an unreadable dataset, and an
    over-bound bbox all fail without touching the worker pool."""
    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=1)
    service = ServingService(backend, max_body_mb=1.0)
    try:
        def post(payload):
            return service.handle(
                "POST", "/infer", json.dumps(payload).encode())

        status, payload = post({"volume_path": str(tmp_path / "nope"),
                                "bbox_start": [0, 0, 0],
                                "bbox_size": [8, 16, 16]})
        assert status == 400 and "cannot open volume" in payload["error"]
        status, payload = post({"volume_path": "x", "bbox_start": [0, 0],
                                "bbox_size": [8, 16, 16]})
        assert status == 400 and "bbox_start" in payload["error"]
        status, payload = post({"volume_path": "x",
                                "bbox_start": [0, 0, 0],
                                "bbox_size": [8, 16, 0]})
        assert status == 400 and "bbox_size" in payload["error"]
        status, payload = post({"volume_path": "x",
                                "bbox_start": [0, 0, 0],
                                "bbox_size": [8, 16, 16],
                                "data_b64": "AAAA"})
        assert status == 400 and "mutually exclusive" in payload["error"]
        status, payload = post({"volume_path": "x",
                                "bbox_start": [0, 0, 0],
                                "bbox_size": [8, 16, 16],
                                "mip": -1})
        assert status == 400 and "mip" in payload["error"]
    finally:
        backend.close()


def test_volume_reference_over_bound_bbox_rejected(clean, tmp_path):
    """A bbox implying more bytes than the request bound is refused
    BEFORE any store read."""
    pytest.importorskip("tensorstore")
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    PrecomputedVolume.create(
        str(tmp_path / "vol"),
        volume_size=(16, 48, 48),
        voxel_size=(40, 4, 4),
        voxel_offset=(0, 0, 0),
        dtype="uint8",
        block_size=(8, 16, 16),
    )
    inferencer = make_inferencer()
    backend = LocalBackend(inferencer, workers=1)
    service = ServingService(backend, max_body_mb=0.00001)
    try:
        status, payload = service.handle("POST", "/infer", json.dumps({
            "volume_path": str(tmp_path / "vol"),
            "bbox_start": [0, 0, 0],
            "bbox_size": [8, 32, 48],
        }).encode())
        assert status == 400
        assert "over the" in payload["error"]
    finally:
        backend.close()
