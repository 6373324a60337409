"""Dispatch ahead under a byte bound (ISSUE 39). With the blank answer
taken at staging nothing in ``infer_async`` waits for the device, so the
executors run as far ahead as their count bounds let them; the
Inferencer's byte bound (``_make_room``) holds a dispatch back only while
earlier results are not ready and one more does not fit the device, and
then waits for the oldest of them.

The device is a fake whose results become ready on demand: the first
``block_until_ready`` makes a result ready and a shared log says when, so
the order of dispatches and waits is read off the log."""
import gc
import threading
import time

import numpy as np
import pytest

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.flow.pipeline import pipelined_inference_stage
from chunkflow_tpu.flow.runtime import new_task
from chunkflow_tpu.flow.scheduler import scheduled_inference_stage
from chunkflow_tpu.inference import Inferencer

SHAPE = (8, 32, 32)
STAGES = pytest.mark.parametrize("make_stage", [
    scheduled_inference_stage, pipelined_inference_stage,
], ids=["scheduled", "static"])


@pytest.fixture(autouse=True)
def clean_registry(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    monkeypatch.delenv("CHUNKFLOW_SCHED", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


class _Result:
    """What a program returns before it has run."""

    def __init__(self, k, log, gate):
        self.k, self.log, self.gate, self.ready = k, log, gate, False
        self.nbytes = 3 * int(np.prod(SHAPE)) * 4

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        assert self.gate.wait(timeout=30)
        if not self.ready:
            self.ready = True
            self.log.append(("ready", self.k))
        return self

    def copy_to_host_async(self):
        pass


class _Out:
    """The chunk ``_infer`` returns around such a result."""

    def __init__(self, array):
        self.array = array

    def crop_margin(self, crop):
        return self

    def host(self):
        self.array.block_until_ready()
        return Chunk(np.full((3,) + SHAPE, self.array.k, dtype=np.float32))


class _FakeDevice(Inferencer):
    """The real ``stage`` and ``infer_async`` over programs that never
    run by themselves, on a device with ``room`` bytes left (None: the
    backend states no limit, as the CPU's)."""

    def __init__(self, room=None):
        super().__init__(
            input_patch_size=(4, 16, 16), num_output_channels=3,
            framework="identity", batch_size=2, crop_output_margin=False)
        self.log, self.room = [], room
        # cleared, it holds every result back whoever asks for it
        self.gate = threading.Event()
        self.gate.set()

    def _infer(self, chunk, block, consume=False):
        if chunk.blank:
            return super()._infer(chunk, block, consume)
        k = self.dispatched()
        # the results not ready as this one joins the device's queue
        self.log.append(("dispatch", k, len(self._ahead)))
        return _Out(_Result(k, self.log, self.gate))

    def dispatched(self):
        return sum(1 for entry in self.log if entry[0] == "dispatch")

    def _device_room(self):
        return self.room

    def order(self):
        return [entry[:2] for entry in self.log]


def _chunks(n):
    rng = np.random.default_rng(n)
    return [Chunk(rng.integers(1, 256, SHAPE, dtype=np.uint8),
                  voxel_offset=(8 * i, 0, 0)) for i in range(n)]


def _tasks(n):
    tasks = []
    for i, chunk in enumerate(_chunks(n)):
        task = new_task()
        task["chunk"], task["i"] = chunk, i
        tasks.append(task)
    return tasks


def _waits():
    return telemetry.snapshot()["counters"].get("pipeline/ahead_waits", 0)


@STAGES
def test_dispatch_runs_ahead_of_results_that_are_not_ready(make_stage):
    device = _FakeDevice()
    stage = make_stage(device, depth=2, ring=2, op_name="inf")
    out = list(stage(iter(_tasks(6))))
    assert [t["i"] for t in out] == list(range(6))
    for task in out:
        assert float(task["chunk"].array[0, 0, 0, 0]) == task["i"]
    order = device.order()
    # nobody waited for result 0 before task 1 went to the device
    assert order.index(("dispatch", 1)) < order.index(("ready", 0))
    depths = [entry[2] for entry in device.log if entry[0] == "dispatch"]
    assert depths[:2] == [0, 1] and max(depths) >= 1
    # every result was made ready by its drain, none by the bound
    assert _waits() == 0
    assert telemetry.snapshot()["gauges"]["pipeline/ahead_outputs"] >= 1


@pytest.mark.parametrize("make_stage, bound", [
    (scheduled_inference_stage, 5),  # inflight 2 + post 2 + 1
    (pipelined_inference_stage, 2),  # depth
], ids=["scheduled", "static"])
def test_with_no_result_ready_dispatch_stops_at_the_count_bound(
        make_stage, bound):
    device = _FakeDevice()
    device.gate.clear()
    stage = make_stage(device, depth=2, ring=2, op_name="inf")
    out = []
    worker = threading.Thread(
        target=lambda: out.extend(stage(iter(_tasks(9)))), daemon=True)
    worker.start()
    try:
        deadline = time.monotonic() + 20
        while device.dispatched() < bound and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.3)  # it would have gone further by now
        assert device.dispatched() == bound
        assert not any(entry[0] == "ready" for entry in device.log)
    finally:
        device.gate.set()
        worker.join(timeout=30)
    assert not worker.is_alive()
    assert [t["i"] for t in out] == list(range(9))
    assert _waits() == 0


@STAGES
def test_under_a_small_limit_a_task_waits_for_the_result_before_it(
        make_stage):
    device = _FakeDevice(room=1024)  # no dispatch fits beside another
    stage = make_stage(device, depth=2, ring=2, op_name="inf")
    out = list(stage(iter(_tasks(6))))
    assert [t["i"] for t in out] == list(range(6))
    order = device.order()
    for k in range(5):
        assert order.index(("ready", k)) < order.index(("dispatch", k + 1))
    # the bound engaged before every dispatch but the first, and each
    # task joined an empty queue
    assert _waits() == 5
    assert all(entry[2] == 0 for entry in device.log
               if entry[0] == "dispatch")


@STAGES
def test_a_limit_with_room_for_every_dispatch_never_waits(make_stage):
    device = _FakeDevice(room=1 << 40)
    stage = make_stage(device, depth=2, ring=2, op_name="inf")
    assert len(list(stage(iter(_tasks(5))))) == 5
    order = device.order()
    assert order.index(("dispatch", 1)) < order.index(("ready", 0))
    assert _waits() == 0


@STAGES
@pytest.mark.parametrize("room", [None, 1024], ids=["no-limit", "small"])
def test_order_and_the_none_flush_hold_under_the_bound(make_stage, room):
    device = _FakeDevice(room=room)
    tasks = _tasks(5)
    tasks.insert(2, None)
    stage = make_stage(device, depth=2, ring=2, op_name="inf")
    out = list(stage(iter(tasks)))
    assert [t["i"] if t else None for t in out] == [0, 1, None, 2, 3, 4]
    order = device.order()
    # the marker flushed what was in flight: both results before task 2
    assert order.index(("ready", 1)) < order.index(("dispatch", 2))


@STAGES
@pytest.mark.parametrize("room", [None, 1024], ids=["no-limit", "small"])
def test_the_error_flush_holds_under_the_bound(make_stage, room):
    device = _FakeDevice(room=room)

    def check(chunk):
        if tuple(chunk.voxel_offset)[0] == 24:  # fourth task
            raise RuntimeError("bad grid")

    stage = make_stage(device, depth=2, ring=2, op_name="inf", check=check)
    got = []
    with pytest.raises(RuntimeError, match="bad grid"):
        for task in stage(iter(_tasks(6))):
            got.append(task["i"])
    assert got == [0, 1, 2]


@pytest.mark.parametrize("adaptive", [True, False],
                         ids=["adaptive", "static"])
def test_stream_gets_the_bound_from_the_same_place(adaptive):
    device = _FakeDevice(room=1024)
    out = list(device.stream(_chunks(5), adaptive=adaptive))
    assert [float(c.array[0, 0, 0, 0]) for c in out] == [0, 1, 2, 3, 4]
    order = device.order()
    for k in range(4):
        assert order.index(("ready", k)) < order.index(("dispatch", k + 1))
    assert _waits() == 4


def test_a_blank_task_neither_waits_nor_joins_the_queue():
    device = _FakeDevice(room=1024)
    first = device.infer_async(device.stage(_chunks(1)[0]), consume=True)
    blank = device.stage(Chunk(np.zeros(SHAPE, dtype=np.uint8)))
    out = device.infer_async(blank, consume=True)
    # the real blank path answered: no dispatch, no wait for the first
    assert isinstance(out, Chunk) and not np.any(out.array)
    assert device.order() == [("dispatch", 0)]
    assert not first.array.ready and _waits() == 0


def test_a_result_the_caller_dropped_is_not_waited_for():
    device = _FakeDevice(room=1024)
    out = device.infer_async(device.stage(_chunks(1)[0]), consume=True)
    del out
    gc.collect()
    device.infer_async(device.stage(_chunks(2)[1]), consume=True)
    assert device.order() == [("dispatch", 0), ("dispatch", 1)]
    assert _waits() == 0


def test_one_dispatch_is_sized_by_the_gauges_the_trace_sets():
    """The bound's need is what the program's own trace says it holds:
    accumulators and chunk (the two gauges) and the result."""
    inferencer = Inferencer(
        input_patch_size=(4, 16, 16), output_patch_overlap=(2, 8, 8),
        num_output_channels=3, framework="identity", batch_size=2,
        crop_output_margin=False)
    chunk = _chunks(1)[0]
    out = inferencer(chunk)
    gauges = telemetry.snapshot()["gauges"]
    assert inferencer._dispatch_bytes(chunk) == (
        gauges["inference/accumulator_bytes"]
        + gauges["inference/chunk_bytes"] + out.array.nbytes)
    # the CPU states no limit: the count bounds are all there is
    assert Inferencer._device_room() is None
    inferencer.infer_async(inferencer.stage(chunk), consume=True)
    inferencer.infer_async(inferencer.stage(chunk), consume=True)
    assert _waits() == 0
