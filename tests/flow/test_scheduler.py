"""Unified adaptive pipeline scheduler (flow/scheduler.py, ISSUE 4):
the scheduled path must be a pure wall-time optimization — bit-identical
outputs, input order, same failure semantics as the serial and static
paths — with depth growth driven by the telemetry stall signal, bounded
by the host-memory watermark, and fully disabled by the
``CHUNKFLOW_SCHED=static`` kill switch."""
import time

import numpy as np
import pytest

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.flow import scheduler
from chunkflow_tpu.flow.runtime import drain_pending_writes, new_task
from chunkflow_tpu.flow.scheduler import (
    DEFAULT_DEPTHS,
    DepthController,
    schedule_chunks,
    scheduled_inference_stage,
    scheduler_mode,
    write_behind_stage,
)
from chunkflow_tpu.inference import Inferencer


@pytest.fixture(autouse=True)
def clean_registry(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    monkeypatch.delenv("CHUNKFLOW_SCHED", raising=False)
    monkeypatch.delenv("CHUNKFLOW_SCHED_MEM_GB", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


def _inferencer(**kwargs):
    defaults = dict(
        input_patch_size=(4, 16, 16),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=3,
        framework="identity",
        batch_size=2,
        crop_output_margin=False,
    )
    defaults.update(kwargs)
    return Inferencer(**defaults)


def _chunks(shapes, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Chunk(rng.random(s).astype(np.float32), voxel_offset=(8 * i, 0, 0))
        for i, s in enumerate(shapes)
    ]


# mixed aligned + ragged-edge shapes: the regime where retrace/donation
# bugs hide (same fixture philosophy as test_pipeline_executor.py)
RAGGED_SHAPES = [(8, 32, 32), (5, 17, 18), (8, 32, 32), (7, 30, 20)]


def _task(chunk, i):
    task = new_task()
    task["chunk"] = chunk
    task["i"] = i
    return task


# ---------------------------------------------------------------------------
# bit-identical output contract
# ---------------------------------------------------------------------------
def test_schedule_chunks_bit_identical_to_serial_ragged():
    inferencer = _inferencer(shape_bucket=(8, 16, 16))
    chunks = _chunks(RAGGED_SHAPES)
    serial = [np.asarray(inferencer(c).array) for c in chunks]
    scheduled = list(schedule_chunks(inferencer, iter(chunks)))
    assert len(scheduled) == len(chunks)
    for src, ref, out in zip(chunks, serial, scheduled):
        assert not out.is_on_device
        assert tuple(out.voxel_offset) == tuple(src.voxel_offset)
        # bit-identical, not allclose: both paths run the SAME compiled
        # program; scheduling must not perturb a single ulp
        np.testing.assert_array_equal(np.asarray(out.array), ref)


def test_schedule_chunks_bit_identical_uint8_output():
    inferencer = _inferencer(output_dtype="uint8")
    chunks = _chunks(RAGGED_SHAPES, seed=3)
    serial = [np.asarray(inferencer(c).array) for c in chunks]
    scheduled = list(schedule_chunks(inferencer, iter(chunks)))
    for ref, out in zip(serial, scheduled):
        assert np.asarray(out.array).dtype == np.uint8
        np.testing.assert_array_equal(np.asarray(out.array), ref)


def test_stream_adaptive_vs_static_bit_identical(monkeypatch):
    """Inferencer.stream must yield byte-for-byte the same chunks whether
    it routes through the adaptive scheduler or (CHUNKFLOW_SCHED=static)
    the PR 2 double-buffered executor."""
    inferencer = _inferencer(shape_bucket=(8, 16, 16))
    chunks = _chunks(RAGGED_SHAPES, seed=5)
    adaptive = [np.asarray(o.array) for o in inferencer.stream(iter(chunks))]
    monkeypatch.setenv("CHUNKFLOW_SCHED", "static")
    assert scheduler_mode() == "static"
    static = [np.asarray(o.array) for o in inferencer.stream(iter(chunks))]
    for a, b in zip(adaptive, static):
        np.testing.assert_array_equal(a, b)


def test_stream_static_mode_bypasses_scheduler(monkeypatch):
    """The kill switch must remove the scheduler from the hot path
    entirely, not just pin its depths."""
    monkeypatch.setenv("CHUNKFLOW_SCHED", "static")

    def boom(*args, **kwargs):
        raise AssertionError("static mode must not touch schedule_chunks")

    monkeypatch.setattr(scheduler, "schedule_chunks", boom)
    inferencer = _inferencer()
    chunks = _chunks([(8, 32, 32)])
    out = list(inferencer.stream(iter(chunks)))
    assert len(out) == 1


# ---------------------------------------------------------------------------
# task-level stage: order, skip markers, failure semantics
# ---------------------------------------------------------------------------
def test_scheduled_stage_order_skip_markers_and_timers():
    inferencer = _inferencer()
    chunks = _chunks(RAGGED_SHAPES, seed=7)
    serial = [np.asarray(inferencer(c).array) for c in chunks]
    tasks = [_task(c, i) for i, c in enumerate(chunks)]
    tasks.insert(2, None)  # skip marker mid-stream
    stage = scheduled_inference_stage(inferencer, depth=2, op_name="inf")
    out = list(stage(iter(tasks)))
    assert [t["i"] if t else None for t in out] == [0, 1, None, 2, 3]
    for task in out:
        if task is None:
            continue
        np.testing.assert_array_equal(
            np.asarray(task["chunk"].array), serial[task["i"]]
        )
        assert not task["chunk"].is_on_device
        assert task["log"]["timer"]["inf"] >= 0
        assert task["log"]["compute_device"]


def test_scheduled_stage_flushes_dispatched_on_error():
    """Same contract as the static stage: a mid-stream failure must not
    drop tasks that were already dispatched."""
    inferencer = _inferencer()
    chunks = _chunks([(8, 32, 32)] * 3, seed=9)

    def check(chunk):
        if tuple(chunk.voxel_offset)[0] == 16:  # third task
            raise RuntimeError("bad grid")

    stage = scheduled_inference_stage(
        inferencer, depth=2, op_name="inf", check=check
    )
    got = []
    with pytest.raises(RuntimeError, match="bad grid"):
        for task in stage(iter(_task(c, i) for i, c in enumerate(chunks))):
            got.append(task["i"])
    assert got == [0, 1]


def test_scheduled_stage_failing_post_op_flushes_survivors():
    """A failing post op must not strand staged device buffers or other
    tasks' results: the surviving in-flight tasks flush downstream, then
    the post failure re-raises."""
    inferencer = _inferencer()
    chunks = _chunks([(8, 32, 32)] * 4, seed=11)

    def post(chunk):
        if tuple(chunk.voxel_offset)[0] == 8:  # second task's output
            raise RuntimeError("poisoned post")
        return chunk

    stage = scheduled_inference_stage(
        inferencer, depth=1, ring=1, op_name="inf", postprocess=post,
    )
    got = []
    with pytest.raises(RuntimeError, match="poisoned post"):
        for task in stage(iter(_task(c, i) for i, c in enumerate(chunks))):
            got.append(task["i"])
    # task 0 completed before the poison; tasks 2..3 were in flight when
    # the failure surfaced and must still come out (the synchronous path
    # would have finished them); task 1 is the failure itself
    assert 1 not in got
    assert got == sorted(got)
    assert 0 in got


def test_scheduled_stage_upstream_exception_propagates():
    inferencer = _inferencer()

    def source():
        yield _task(_chunks([(8, 32, 32)])[0], 0)
        raise RuntimeError("upstream boom")

    stage = scheduled_inference_stage(inferencer, depth=2, op_name="inf")
    got = []
    with pytest.raises(RuntimeError, match="upstream boom"):
        for task in stage(source()):
            got.append(task["i"])
    assert got == [0]


@pytest.mark.parametrize("telemetry_env", ["1", "0"])
def test_scheduler_smoke_full_stage_chain(monkeypatch, telemetry_env):
    """Tier-1 smoke (ISSUE 4 satellite): 3 synthetic tasks through the
    FULL chain — source → scheduled inference (+post pool) → async write
    attach → write-behind — with order, results, and durable writes all
    checked, with the telemetry plane on and with its kill switch set
    (the controllers then have no stall signal and stand pat)."""
    from concurrent.futures import ThreadPoolExecutor

    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", telemetry_env)
    inferencer = _inferencer()
    chunks = _chunks([(8, 32, 32)] * 3, seed=13)
    serial = [np.asarray(inferencer(c).array) for c in chunks]
    committed = []
    pool = ThreadPoolExecutor(max_workers=2)

    def source(stream):
        for _seed in stream:
            for i, c in enumerate(chunks):
                yield _task(c, i)

    def attach_write(stream):
        for task in stream:
            task.setdefault("pending_writes", []).append(
                pool.submit(lambda i=task["i"]: committed.append(i)))
            yield task

    stages = [
        source,
        scheduled_inference_stage(inferencer, postprocess=lambda c: c,
                                  controller=DepthController(),
                                  op_name="inf"),
        attach_write,
        write_behind_stage(controller=DepthController()),
    ]
    stream = iter([new_task()])
    for s in stages:
        stream = s(stream)
    out = list(stream)
    assert [t["i"] for t in out] == [0, 1, 2]
    for task in out:
        assert not task.get("pending_writes")  # durable before yield
        np.testing.assert_array_equal(
            np.asarray(task["chunk"].array), serial[task["i"]]
        )
    assert sorted(committed) == [0, 1, 2]


# ---------------------------------------------------------------------------
# adaptive controller
# ---------------------------------------------------------------------------
def _drive(ctl, phase, n_tasks=10, stall_s=0.05):
    """Feed ``n_tasks`` synthetic tasks whose stall stream is dominated
    by ``phase`` through the real telemetry registry."""
    for _ in range(n_tasks):
        telemetry.observe(phase, stall_s)
        telemetry.observe("pipeline/compute", stall_s / 20)
        ctl.observe_task()


def test_controller_stage_dominant_raises_prefetch_within_10_tasks():
    ctl = DepthController(watermark_bytes=1 << 40)
    _drive(ctl, "pipeline/stage", n_tasks=10)
    assert ctl.depths["prefetch"] > DEFAULT_DEPTHS["prefetch"]
    assert ctl.changes, "controller never adapted"
    first_change_task = ctl.changes[0][0]
    assert first_change_task <= 10


def test_controller_load_dominant_raises_prefetch():
    ctl = DepthController(watermark_bytes=1 << 40)
    _drive(ctl, "scheduler/load", n_tasks=10)
    assert ctl.depths["prefetch"] > DEFAULT_DEPTHS["prefetch"]


def test_controller_drain_dominant_grows_write_pool():
    ctl = DepthController(watermark_bytes=1 << 40)
    _drive(ctl, "pipeline/drain", n_tasks=10)
    assert ctl.depths["write"] > DEFAULT_DEPTHS["write"]
    assert ctl.depths["post"] > DEFAULT_DEPTHS["post"]


def test_controller_compute_dominant_stands_pat():
    """Device-bound is the design goal: no knob to turn."""
    ctl = DepthController(watermark_bytes=1 << 40)
    _drive(ctl, "pipeline/compute", n_tasks=12)
    assert ctl.depths == ctl.initial
    assert not ctl.changes


def test_controller_balanced_stream_stands_pat():
    """No phase above min_share: depths are matched, nothing widens."""
    ctl = DepthController(watermark_bytes=1 << 40)
    for _ in range(12):
        for phase in ("pipeline/stage", "pipeline/compute",
                      "pipeline/drain", "scheduler/post"):
            telemetry.observe(phase, 0.01)
        ctl.observe_task()
    assert ctl.depths == ctl.initial


def test_controller_respects_memory_watermark():
    """Backpressure: under a tiny watermark no depth ever rises past the
    static initials — the documented graceful fallback."""
    ctl = DepthController(watermark_bytes=1024)
    ctl.note_slot_bytes(64 << 20)  # one 64 MB chunk seen
    _drive(ctl, "pipeline/stage", n_tasks=20)
    assert ctl.depths == ctl.initial
    assert not ctl.changes


def test_controller_env_watermark(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_SCHED_MEM_GB", "0.000001")
    ctl = DepthController()
    assert ctl.watermark_bytes == int(0.000001 * (1 << 30))
    ctl.note_slot_bytes(1 << 20)
    _drive(ctl, "pipeline/stage", n_tasks=8)
    assert ctl.depths == ctl.initial


def test_controller_respects_depth_ceilings():
    ctl = DepthController(interval=1, watermark_bytes=1 << 40)
    _drive(ctl, "pipeline/stage", n_tasks=50)
    assert ctl.depths["prefetch"] == ctl.limits["prefetch"]


def test_controller_static_when_telemetry_off(monkeypatch):
    """CHUNKFLOW_TELEMETRY=0 removes the stall signal; depths must stay
    static rather than adapt on garbage."""
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    ctl = DepthController(watermark_bytes=1 << 40)
    _drive(ctl, "pipeline/stage", n_tasks=12)
    assert ctl.depths == ctl.initial


def test_controller_emits_depth_change_events(tmp_path):
    telemetry.configure(str(tmp_path))
    ctl = DepthController(watermark_bytes=1 << 40)
    _drive(ctl, "pipeline/stage", n_tasks=8)
    telemetry.flush()
    from chunkflow_tpu.flow.log_summary import (
        load_telemetry_dir,
        summarize_telemetry,
    )

    agg = summarize_telemetry(load_telemetry_dir(str(tmp_path)))
    assert agg["depth_changes"], "no depth_change events in the stream"
    change = agg["depth_changes"][0]
    assert change["name"] == "scheduler/prefetch"
    assert change["new"] == change["old"] + 1
    assert agg["gauges"]["scheduler/depth/prefetch"]["last"] >= change["new"]


def test_queue_capacity_widens_live():
    q = scheduler._AdaptiveQueue(1)
    assert q.put("a")
    q.set_capacity(3)
    assert q.put("b")
    assert q.put("c")
    assert [q.get(), q.get(), q.get()] == ["a", "b", "c"]
    q.close()
    assert not q.put("d")  # closed queue refuses new work


# ---------------------------------------------------------------------------
# write-behind + drain hardening
# ---------------------------------------------------------------------------
def test_drain_pending_writes_drains_every_future_and_reraises_first():
    """ISSUE 4 satellite: an exception mid-drain must not abandon the
    remaining futures — all drained, first error re-raised."""
    drained = []

    class _Write:
        def __init__(self, tag, exc=None):
            self.tag = tag
            self.exc = exc

        def result(self):
            drained.append(self.tag)
            if self.exc is not None:
                raise self.exc

    task = {"pending_writes": [
        _Write("w0"),
        _Write("w1", RuntimeError("first poison")),
        _Write("w2", ValueError("second poison")),
        _Write("w3"),
    ]}
    with pytest.raises(RuntimeError, match="first poison"):
        drain_pending_writes(task)
    assert drained == ["w0", "w1", "w2", "w3"]  # every future drained
    assert "pending_writes" not in task


def test_write_behind_overlaps_and_preserves_order():
    """With a window of 2, task k's commit must not block task k+1's
    arrival; tasks yield in order with writes durable."""
    from concurrent.futures import ThreadPoolExecutor

    pool = ThreadPoolExecutor(max_workers=4)
    log = []

    def tasks():
        for i in range(5):
            t = new_task()
            t["i"] = i
            t["pending_writes"] = [pool.submit(time.sleep, 0.01)]
            log.append(("in", i))
            yield t

    out = []
    for task in write_behind_stage(window=2)(tasks()):
        log.append(("out", task["i"]))
        out.append(task["i"])
        assert not task.get("pending_writes")
    assert out == [0, 1, 2, 3, 4]
    # write-behind: tasks 0..2 all arrived (writes riding) before task
    # 0's commit was forced — the serial path would interleave strictly
    assert log.index(("out", 0)) > log.index(("in", 2))


def test_write_behind_passes_markers_and_unwritten_tasks_through():
    t0 = new_task()
    t0["i"] = 0
    out = list(write_behind_stage(window=2)(iter([t0, None])))
    assert out[0] is t0 and out[1] is None


def test_write_behind_drains_buffered_writes_on_downstream_abandon():
    """Closing the consumer mid-stream must still commit buffered writes
    (ack-after-durable-write holds on every exit path)."""
    committed = []

    class _Write:
        def __init__(self, i):
            self.i = i

        def result(self):
            committed.append(self.i)

    def tasks():
        for i in range(4):
            t = new_task()
            t["i"] = i
            t["pending_writes"] = [_Write(i)]
            yield t

    gen = write_behind_stage(window=3)(tasks())
    next(gen)  # pulls several tasks into the window
    gen.close()
    assert committed == sorted(committed)
    assert len(committed) >= 2  # the buffered tasks' writes committed


def test_write_behind_drains_remaining_on_upstream_error():
    committed = []

    class _Write:
        def __init__(self, i):
            self.i = i

        def result(self):
            committed.append(self.i)

    def tasks():
        for i in range(3):
            t = new_task()
            t["i"] = i
            t["pending_writes"] = [_Write(i)]
            yield t
        raise RuntimeError("upstream died")

    with pytest.raises(RuntimeError, match="upstream died"):
        list(write_behind_stage(window=8)(tasks()))
    assert sorted(committed) == [0, 1, 2]


def test_process_stream_adaptive_appends_write_behind(monkeypatch):
    """End-of-pipeline commit protocol under the adaptive default: tasks
    reach the drain barrier already durable, and static mode behaves
    identically from the outside."""
    from chunkflow_tpu.flow.runtime import process_stream

    for mode in ("adaptive", "static"):
        monkeypatch.setenv("CHUNKFLOW_SCHED", mode)
        committed = []

        class _Write:
            def result(self):
                committed.append(True)

        def source(stream):
            for _seed in stream:
                for _ in range(3):
                    t = new_task()
                    t["pending_writes"] = [_Write()]
                    yield t

        count = process_stream([source])
        assert count == 3, mode
        assert len(committed) == 3, mode


# ---------------------------------------------------------------------------
# CLI integration: static kill switch is the legacy composition
# ---------------------------------------------------------------------------
def test_cli_inference_static_vs_adaptive_bit_identical(monkeypatch, tmp_path):
    import h5py
    from click.testing import CliRunner

    from chunkflow_tpu.flow.cli import main

    runner = CliRunner()
    outs = {}
    for mode in ("adaptive", "static"):
        monkeypatch.setenv("CHUNKFLOW_SCHED", mode)
        out = tmp_path / f"{mode}.h5"
        result = runner.invoke(main, [
            "generate-tasks", "-c", "16", "48", "48",
            "--roi-stop", "16", "96", "48",
            "create-chunk", "--size", "16", "48", "48", "--pattern", "sin",
            "inference", "-s", "8", "24", "24", "-v", "2", "8", "8",
            "-c", "1", "-f", "identity", "--no-crop-output-margin",
            "--async-depth", "2", "--prefetch-depth", "2",
            "save-h5", "--file-name", str(out),
        ], catch_exceptions=False)
        assert result.exit_code == 0, result.output
        with h5py.File(out, "r") as f:
            key = [k for k in f if "voxel" not in k and "layer" not in k][0]
            outs[mode] = f[key][:]
    np.testing.assert_array_equal(outs["adaptive"], outs["static"])


def test_scheduler_mode_env_values(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_SCHED", raising=False)
    assert scheduler_mode() == "adaptive"
    for value in ("static", "0", "off", "STATIC"):
        monkeypatch.setenv("CHUNKFLOW_SCHED", value)
        assert scheduler_mode() == "static", value
    monkeypatch.setenv("CHUNKFLOW_SCHED", "adaptive")
    assert scheduler_mode() == "adaptive"


def test_mem_watermark_malformed_falls_back(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_SCHED_MEM_GB", "not-a-number")
    assert scheduler.mem_watermark_bytes() == 4 << 30


# ---------------------------------------------------------------------------
# lease-leak guard: tasks dropped during chain teardown are surrendered
# ---------------------------------------------------------------------------
class _FakeLifecycle:
    def __init__(self):
        self.surrenders = 0

    def surrender(self):
        self.surrenders += 1
        return "surrendered"


def test_pump_drop_and_close_surrender_claimed_tasks():
    """The chain-rebuild race (observed in the lifecycle chaos
    acceptance run): after a contained failure resolves the in-flight
    set, the prefetch pump can claim ONE more task before noticing the
    consumer closed, and tasks buffered in the handoff queue may have
    been claimed after the snapshot too. Both must be surrendered —
    dropped-on-the-floor claims leak their lease until the visibility
    timeout and lose the task for the run."""
    from chunkflow_tpu.flow.scheduler import _AdaptiveQueue, _pump

    buffered, in_hand, never_pulled = (
        _FakeLifecycle(), _FakeLifecycle(), _FakeLifecycle(),
    )
    q = _AdaptiveQueue(1)

    def source():
        yield {"lifecycle": buffered}    # fills the queue
        q.close()                        # consumer dies between pulls
        yield {"lifecycle": in_hand}     # put() refused -> surrender
        yield {"lifecycle": never_pulled}  # pump must have stopped

    _pump(iter(source()), q)
    assert buffered.surrenders == 1     # drained + surrendered at close
    assert in_hand.surrenders == 1      # refused put -> surrendered
    assert never_pulled.surrenders == 0  # never claimed, never touched


def test_prefetch_stage_surrenders_buffered_tasks_on_early_close():
    """Same guard for the static-path prefetch stage (runtime.py)."""
    from chunkflow_tpu.flow.runtime import prefetch_stage

    lcs = [_FakeLifecycle() for _ in range(4)]

    def source():
        for lc in lcs:
            yield {"lifecycle": lc, "log": {"timer": {}}}

    stage = prefetch_stage(depth=2)
    stream = stage(source())
    first = next(stream)        # one task delivered downstream
    stream.close()              # downstream dies; buffered tasks remain
    delivered = first["lifecycle"]
    assert delivered.surrenders == 0  # delivered tasks are NOT touched
    surrendered = sum(lc.surrenders for lc in lcs if lc is not delivered)
    # whatever the worker managed to buffer before close was handed back
    assert surrendered >= 1
    assert all(lc.surrenders <= 1 for lc in lcs)
