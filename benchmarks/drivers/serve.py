"""Driver of the ``serve`` kind: an open loop of independent clients.

``serve`` runs in the main thread of this process on a port the driver
picked, as it does for a user, and is ended the way a user ends it:
SIGINT. A client in threads of the same process (one process holds the
chip) first sends one request of every shape (warm-up: every program the
traffic uses compiles or loads here, and counts as set-up), then replays
one recorded arrival sequence: Poisson arrivals at the traffic file's
fixed ``rate_per_s``, each request's shape drawn by ``shapes``' weights,
all drawn once from the file's ``schedule_seed`` and so the same in every
run. Some tens of requests fit a window, and a sequence drawn anew from
``--seed`` would make the runs differ by how many large requests they
happened to hold, not by the system. ``--seed`` makes every request's
voxels. A request is timed from when it was *due* to its last byte read,
so a stall costs every request behind it. Requests due in the first
``ramp_s`` seconds are sent and not counted.

A request that is refused (429), fails, or is answered past the command's
default deadline counts in ``failed`` and enters the latencies at the
deadline. The answer to the first counted request of ``check_shape`` is
kept and compared, whole, against the plain reference after the window.

The traffic file's parameters: ``rate_per_s``, ``schedule_seed``,
``shapes`` (``shape``, ``weight``), ``check_shape``, ``ramp_s``,
``client_threads``, ``serve_workers``, ``trace``, ``env``, ``rehearse``.
"""
import base64
import http.client
import json
import queue as queue_module
import signal
import socket
import threading
import time

import numpy as np

from cfbench import check, program
from cfbench.run_record import RunRecord

DEADLINE_S = 30.0             # serve --default-deadline-s
WARMUP_DEADLINE_S = 1100.0    # a cold warm-up request compiles


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def make_plan(seed: int, rate: float, horizon: float, weights) -> list:
    """[(due offset in seconds, shape index)]: Poisson arrivals."""
    rng = np.random.default_rng(seed)
    plan, t = [], 0.0
    p = np.asarray(weights, float) / sum(weights)
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= horizon:
            return plan
        plan.append((t, int(rng.choice(len(p), p=p))))


class Payload:
    """One shape's request body, encoded once, sent many times with a
    voxel offset of its own so that no two requests are the same."""

    def __init__(self, rng, shape):
        self.shape = tuple(shape)
        self.array = rng.integers(0, 256, self.shape, dtype=np.uint8)
        self.head = (f'{{"shape": {list(self.shape)}, "dtype": "uint8", '
                     f'"voxel_offset": [0, 0, ').encode()
        self.tail = b'], "data_b64": "' + base64.b64encode(
            self.array.tobytes()) + b'"}'

    def body(self, index: int, deadline_s: float = None) -> bytes:
        """Request ``index`` of this shape; the command's default deadline
        unless one is given (warm-up, which may compile)."""
        head = self.head
        if deadline_s is not None:
            head = f'{{"deadline_s": {deadline_s}, '.encode() + head[1:]
        return head + str(index * self.shape[2]).encode() + self.tail


def post(port: int, body: bytes, timeout: float):
    """(status, raw answer bytes); the clock stops at the last byte."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/infer", body=body,
                     headers={"Content-Type": "application/json"})
        response = conn.getresponse()
        return response.status, response.read()
    finally:
        conn.close()


def wait_until_up(port: int, timeout: float = 600.0) -> None:
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=2)
            conn.request("GET", "/healthz")
            conn.getresponse().read()
            conn.close()
            return
        except OSError:
            time.sleep(0.1)
    raise RuntimeError("serve never answered /healthz")


def decode_answer(raw: bytes) -> np.ndarray:
    payload = json.loads(raw)
    return np.frombuffer(base64.b64decode(payload["data_b64"]),
                         dtype=payload["dtype"]).reshape(payload["shape"])


class Client(threading.Thread):
    def __init__(self, ctx, port):
        super().__init__(name="bench-client", daemon=True)
        self.ctx, self.port = ctx, port
        traffic = ctx.traffic
        rng = np.random.default_rng(ctx.seed)
        self.payloads = [Payload(rng, s["shape"]) for s in traffic["shapes"]]
        self.check_index = [p.shape for p in self.payloads].index(
            tuple(traffic["check_shape"]))
        self.ramp = float(traffic["ramp_s"])
        self.plan = make_plan(
            int(traffic["schedule_seed"]),
            float(traffic["rate_per_s"]),
            self.ramp + float(ctx.seconds),
            [s["weight"] for s in traffic["shapes"]])
        self.results = []        # (due, sent, done, status, shape index)
        self.check_raw = None
        self.window = None
        self.profiler = None
        self.error = None
        self.lock = threading.Lock()

    def sender(self, inbox) -> None:
        while True:
            item = inbox.get()
            if item is None:
                return
            number, due, shape_index = item
            body = self.payloads[shape_index].body(number)
            sent = time.time()
            try:
                status, raw = post(self.port, body, DEADLINE_S + 30)
            except OSError:
                status, raw = 0, b""
            done = time.time()
            with self.lock:
                self.results.append((due, sent, done, status, shape_index))
                if (status == 200 and self.check_raw is None
                        and shape_index == self.check_index
                        and due >= self.window[0]):
                    self.check_raw = (number, raw)

    def run(self) -> None:
        try:
            wait_until_up(self.port)
            # warm-up: every shape once, alone; programs compile or load
            for index, payload in enumerate(self.payloads):
                status, _ = post(self.port, payload.body(0, WARMUP_DEADLINE_S),
                                 WARMUP_DEADLINE_S + 30)
                if status != 200:
                    raise RuntimeError(
                        f"warm-up request {payload.shape} -> {status}")
            origin = time.time() + 0.1
            self.window = (origin + self.ramp,
                           origin + self.ramp + float(self.ctx.seconds))
            if self.ctx.trace:
                self.profiler = self.ctx.start_profiler_thread(
                    self.window, self.ctx.traffic["trace"])
            inbox = queue_module.Queue()
            senders = [threading.Thread(target=self.sender, args=(inbox,),
                                        daemon=True)
                       for _ in range(int(self.ctx.traffic["client_threads"]))]
            for thread in senders:
                thread.start()
            for number, (offset, shape_index) in enumerate(self.plan, 1):
                delay = origin + offset - time.time()
                if delay > 0:
                    time.sleep(delay)
                inbox.put((number, origin + offset, shape_index))
            for _ in senders:
                inbox.put(None)
            for thread in senders:
                thread.join()
        except BaseException as exc:
            self.error = exc
        finally:
            signal.raise_signal(signal.SIGINT)


def run(ctx) -> RunRecord:
    config, traffic = ctx.config, ctx.traffic
    if "serve" not in config["args"]:
        raise SystemExit(
            f"benchmarks: configuration {config['name']} has no 'serve' "
            f"arguments: the serve command cannot select its model")
    port = free_port()
    client = Client(ctx, port)
    client.start()
    head = ["--metrics-dir", ctx.metrics_dir] if ctx.trace else []
    program.chunkflow(
        *head,
        "serve", "--port", port, "--host", "127.0.0.1",
        "--input-patch-size", *config["patch"],
        "--output-patch-overlap", *config["overlap"],
        "--num-output-channels", config["model"]["out_channels"],
        *ctx.resolve_args(config["args"]["serve"]),
        "--batch-size", config["batch"],
        "--serve-workers", traffic["serve_workers"],
    )
    client.join(timeout=120)
    if client.profiler is not None:
        client.profiler.join()
    if client.error is not None:
        raise client.error
    if client.is_alive() or client.window is None:
        raise SystemExit("benchmarks: the serving client did not finish")

    record = RunRecord(cell=ctx.cell, config=config, traffic=traffic,
                       device=ctx.device, window=client.window)
    start, end = client.window
    counted = [r for r in client.results if start <= r[0] < end]
    deadline_ms = DEADLINE_S * 1000.0
    latencies, failed = [], 0
    for due, sent, done, status, _ in counted:
        latency = (done - due) * 1000.0
        if status != 200 or latency > deadline_ms:
            failed += 1
            latency = deadline_ms
        latencies.append(latency)
    record.attempted, record.failed = len(counted), failed
    statuses: dict = {}
    for r in counted:
        statuses[r[3]] = statuses.get(r[3], 0) + 1
    record.notes.append(f"{len(counted)} requests due in the window, "
                        f"statuses {statuses}")
    record.notes.append(
        "latencies in the order due, ms (shape index): " + " ".join(
            f"{l:.0f}({r[4]})" for l, r in sorted(
                zip(latencies, counted), key=lambda lr: lr[1][0])))
    half = (start + end) / 2
    for label, part in (("first", [l for l, r in zip(latencies, counted)
                                   if r[0] < half]),
                        ("second", [l for l, r in zip(latencies, counted)
                                    if r[0] >= half])):
        if part:
            record.notes.append(
                f"{label} half of the window: {len(part)} requests, mean "
                f"latency {sum(part) / len(part):.0f} ms")
    for index, payload in enumerate(client.payloads):
        service = sorted((r[2] - r[1]) * 1000.0 for r in counted
                         if r[4] == index and r[3] == 200)
        if service:
            record.notes.append(
                f"shape {payload.shape}: {len(service)} answered, sent to "
                f"last byte min {service[0]:.0f} / median "
                f"{service[len(service) // 2]:.0f} / max {service[-1]:.0f} ms")
    record.client = {
        "setup_s": start - ctx.t0,
        "latencies_ms": latencies,
        "late_ms": [(sent - due) * 1000.0 for due, sent, *_ in counted],
    }

    # the comparison that decides `correct`, outside the window
    if client.check_raw is None:
        record.notes.append("no request of check_shape was answered")
        return record
    answer = decode_answer(client.check_raw[1])
    answer = answer.reshape((-1,) + answer.shape[-3:])
    payload = client.payloads[client.check_index]
    ctx.memory_peaks()     # before the reference's programs load
    want, n_patches = check.reference_output(
        ctx, payload.array, ((0, 0, 0), payload.shape))
    check.judge(
        record, answer, want,
        f"one {payload.shape} answer vs {n_patches} reference patches",
        {"every request answered in time": failed == 0})
    return record
