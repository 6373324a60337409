"""The blank answer is taken where the chunk is on the host (ISSUE 39):
``Inferencer.stage`` asks the host payload before the upload and carries
the answer with the staged chunk, so ``_infer`` asks the device only of a
chunk that arrives device-resident. One span, one ``inference/tasks`` a
task either way."""
import json

import numpy as np
import pytest

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.flow.pipeline import pipelined_inference_stage
from chunkflow_tpu.flow.runtime import new_task
from chunkflow_tpu.flow.scheduler import scheduled_inference_stage
from chunkflow_tpu.inference import Inferencer

SHAPE = (8, 32, 32)


class _Stream:
    """A telemetry sink of one test's own: ``spans`` reads what was
    written under a name, ``restart`` drops everything so far (a serial
    reference computed first) and opens the next file."""

    def __init__(self, directory):
        self.directory = directory
        self.files = 0
        self.restart()

    def restart(self):
        telemetry.reset()
        self.files += 1
        self.path = telemetry.configure(
            str(self.directory / f"sink{self.files}"))

    def spans(self, name="inference/blank_check"):
        with open(self.path) as f:
            return [e for e in map(json.loads, f)
                    if e["kind"] == "span" and e["name"] == name]


@pytest.fixture
def stream(monkeypatch, tmp_path):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    monkeypatch.delenv("CHUNKFLOW_SCHED", raising=False)
    yield _Stream(tmp_path)
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


def _image(seed=0, dtype=np.uint8):
    rng = np.random.default_rng(seed)
    if np.dtype(dtype).kind == "f":
        return Chunk(rng.random(SHAPE).astype(dtype))
    return Chunk(rng.integers(1, 256, SHAPE, dtype=dtype))


def _counters():
    return telemetry.snapshot()["counters"]


def _no_device_reduction(monkeypatch):
    import jax.numpy as jnp

    def boom(*args, **kwargs):
        raise AssertionError("the blank check went to the device")

    monkeypatch.setattr(jnp, "any", boom)


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_a_host_chunk_of_zeros_is_blank_at_staging_and_not_uploaded(
        stream, monkeypatch, dtype):
    inferencer = _inferencer()
    chunk = Chunk(np.zeros(SHAPE, dtype=dtype), voxel_offset=(8, 0, 0))
    _no_device_reduction(monkeypatch)
    staged = inferencer.stage(chunk)
    # the pipeline owns what stage returns, and it is not the device's
    assert staged is not chunk and staged.blank is True
    assert not staged.is_on_device and staged.array is chunk.array
    out = inferencer.infer_async(staged, consume=True)
    assert out.shape == (3,) + SHAPE and out.dtype == np.float32
    assert not out.is_on_device and not np.any(out.array)
    assert tuple(out.voxel_offset) == (8, 0, 0)
    counters = _counters()
    assert counters["inference/tasks"] == 1
    assert counters["inference/blank_tasks"] == 1
    assert counters.get("inference/device_blank_checks", 0) == 0
    assert counters.get("transfer/h2d_bytes", 0) == 0
    assert counters.get("transfer/h2d_chunks", 0) == 0
    (check,) = stream.spans()
    assert (check["blank"], check["where"]) == (1, "host")


@pytest.mark.parametrize("dtype", [np.uint8, np.float32])
def test_a_staged_chunk_is_never_asked_on_the_device(
        stream, monkeypatch, dtype):
    inferencer = _inferencer()
    chunk = _image(3, dtype)
    serial = np.asarray(inferencer(chunk).array)
    stream.restart()
    _no_device_reduction(monkeypatch)
    staged = inferencer.stage(chunk)
    assert staged.is_on_device and staged.blank is False
    out = inferencer.infer_async(staged, consume=True)
    np.testing.assert_array_equal(np.asarray(out.array), serial)
    counters = _counters()
    assert counters["inference/tasks"] == 1
    assert counters.get("inference/blank_tasks", 0) == 0
    assert counters.get("inference/device_blank_checks", 0) == 0
    assert counters["transfer/h2d_bytes"] == chunk.array.nbytes


@pytest.mark.parametrize("zeros", [False, True])
def test_a_chunk_that_arrives_on_the_device_is_asked_there(stream, zeros):
    inferencer = _inferencer()
    host = Chunk(np.zeros(SHAPE, dtype=np.uint8)) if zeros else _image(5)
    arrived = host.device()
    staged = inferencer.stage(arrived)
    # caller-owned, not staged here: no answer rides with it
    assert staged is arrived and staged.blank is None
    out = inferencer.infer_async(staged)
    assert bool(np.any(np.asarray(out.array))) is not zeros
    counters = _counters()
    assert counters["inference/device_blank_checks"] == 1
    assert counters["inference/tasks"] == 1
    assert counters.get("inference/blank_tasks", 0) == int(zeros)
    (check,) = stream.spans()
    assert (check["blank"], check["where"]) == (int(zeros), "device")


def test_the_device_leg_really_reduces_on_the_device(stream, monkeypatch):
    inferencer = _inferencer()
    arrived = _image(6).device()
    _no_device_reduction(monkeypatch)
    with pytest.raises(AssertionError, match="went to the device"):
        inferencer.infer_async(arrived)


def test_a_host_chunk_called_directly_is_asked_on_the_host(
        stream, monkeypatch):
    inferencer = _inferencer()
    _no_device_reduction(monkeypatch)
    out = inferencer(_image(7))
    assert np.any(np.asarray(out.array))
    blank = inferencer(Chunk(np.zeros(SHAPE, dtype=np.uint8)))
    assert not np.any(blank.array)
    assert [(c["blank"], c["where"]) for c in stream.spans()] == [
        (0, "host"), (1, "host")]
    counters = _counters()
    assert counters["inference/tasks"] == 2
    assert counters["inference/blank_tasks"] == 1
    assert counters.get("inference/device_blank_checks", 0) == 0


def test_a_dry_run_stages_nothing_and_asks_nobody(stream, monkeypatch):
    inferencer = _inferencer(dry_run=True)
    _no_device_reduction(monkeypatch)
    staged = inferencer.stage(_image(8))
    assert staged.blank is True and not staged.is_on_device
    out = inferencer.infer_async(staged, consume=True)
    assert not np.any(out.array)
    # a device chunk under dry_run is not reduced either
    assert not np.any(inferencer.infer_async(_image(9).device()).array)
    counters = _counters()
    assert counters.get("transfer/h2d_bytes", 0) == _image(9).array.nbytes
    assert counters.get("inference/device_blank_checks", 0) == 0
    assert [c["where"] for c in stream.spans()] == ["host", "host"]


def test_the_answer_does_not_follow_a_derived_chunk(stream):
    inferencer = _inferencer()
    staged = inferencer.stage(Chunk(np.zeros(SHAPE, dtype=np.uint8)))
    assert staged.blank is True
    assert staged.crop_margin((1, 2, 2)).blank is None
    assert staged.clone().blank is None
    assert Chunk(staged).blank is None


def _task(chunk, i):
    task = new_task()
    task["chunk"] = chunk
    task["i"] = i
    return task


@pytest.mark.parametrize("make_stage", [
    scheduled_inference_stage, pipelined_inference_stage,
], ids=["scheduled", "static"])
def test_a_blank_task_in_a_stream_costs_no_upload_and_no_device_check(
        stream, monkeypatch, make_stage):
    inferencer = _inferencer()
    chunks = [_image(11), Chunk(np.zeros(SHAPE, dtype=np.uint8)),
              _image(12), _image(13)]
    serial = [np.asarray(inferencer(c).array) for c in chunks]
    stream.restart()
    _no_device_reduction(monkeypatch)
    stage = make_stage(inferencer, depth=2, op_name="inf")
    out = list(stage(iter(_task(c, i) for i, c in enumerate(chunks))))
    assert [t["i"] for t in out] == [0, 1, 2, 3]
    for task in out:
        np.testing.assert_array_equal(
            np.asarray(task["chunk"].array), serial[task["i"]])
    assert not np.any(out[1]["chunk"].array)
    counters = _counters()
    assert counters["inference/tasks"] == 4
    assert counters["inference/blank_tasks"] == 1
    assert counters["transfer/h2d_chunks"] == 3
    assert counters.get("inference/device_blank_checks", 0) == 0
    # the check lies under staging now, and no longer under dispatch
    by_id = {e["span_id"]: e["name"]
             for name in ("pipeline/stage", "pipeline/dispatch")
             for e in stream.spans(name)}
    checks = stream.spans()
    assert [c["blank"] for c in checks] == [0, 1, 0, 0]
    assert {by_id[c["parent_id"]] for c in checks} == {"pipeline/stage"}
