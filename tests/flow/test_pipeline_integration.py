"""Flagship integration test: the full production worker pipeline against
local-disk precomputed volumes (analog of the reference's
tests/flow/test_flow.py::test_inference_pipeline).

Builds input volume, coarse input mask (mip 1), output volume, coarse
output mask, runs:
    fetch-task -> load-precomputed(+margin) -> mask(in) -> inference
    (identity) -> crop-margin -> mask(out) -> save-precomputed
and asserts masked regions are zero and unmasked output ~= input.
"""
import numpy as np
import pytest
from click.testing import CliRunner

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core.bbox import BoundingBox
from chunkflow_tpu.flow.cli import main
from chunkflow_tpu.volume.precomputed import PrecomputedVolume


@pytest.fixture
def world(tmp_path):
    rng = np.random.default_rng(0)
    size = (32, 64, 64)
    image = Chunk(
        rng.integers(1, 255, size).astype(np.uint8), voxel_size=(1, 1, 1)
    )
    input_vol = PrecomputedVolume.from_chunk(
        image, str(tmp_path / "img"), block_size=(16, 16, 16)
    )

    # input mask at mip 1 (2x coarser in yx): zero out a corner
    mask_arr = np.ones((32, 32, 32), dtype=np.uint8)
    mask_arr[:, :8, :8] = 0  # masks yx < 16 at mip 0
    mask_vol = PrecomputedVolume.from_chunk(
        Chunk(mask_arr, voxel_size=(1, 2, 2)),
        str(tmp_path / "mask"),
        block_size=(16, 16, 16),
    )

    output_vol = PrecomputedVolume.create(
        str(tmp_path / "out"),
        volume_size=size,
        voxel_size=(1, 1, 1),
        dtype="float32",
        layer_type="image",
        block_size=(16, 16, 16),
    )
    return dict(
        tmp_path=tmp_path,
        image=image,
        input_vol=input_vol,
        mask_vol=mask_vol,
        output_vol=output_vol,
    )


def test_full_worker_pipeline(world):
    qdir = str(world["tmp_path"] / "queue")
    runner = CliRunner()

    # enqueue one interior task
    result = runner.invoke(
        main,
        [
            "generate-tasks", "-c", "16", "32", "32",
            "--roi-start", "8", "16", "16",
            "--grid-size", "1", "1", "1",
            "--queue-name", qdir,
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0

    result = runner.invoke(
        main,
        [
            "fetch-task-from-queue", "-q", qdir,
            "load-precomputed", "-v", world["input_vol"].path,
            "--expand-margin-size", "4", "8", "8",
            "mask", "-v", world["mask_vol"].path,
            "inference",
            "--framework", "identity",
            "--input-patch-size", "12", "24", "24",
            "--output-patch-size", "8", "16", "16",
            "--output-patch-overlap", "4", "8", "8",
            "--num-output-channels", "1",
            "--batch-size", "2",
            "crop-margin",
            "mask", "-v", world["mask_vol"].path,
            "save-precomputed", "-v", world["output_vol"].path,
            "delete-task-in-queue",
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output

    bbox = BoundingBox((8, 16, 16), (24, 48, 48))
    out = world["output_vol"].cutout(bbox)
    got = np.asarray(out.array).squeeze()
    expected = (
        np.asarray(world["image"].cutout(bbox).array).astype(np.float32) / 255.0
    )

    # masked corner (y<16 and x<16 at mip0... here the corner yx<16) is zero
    # the task bbox starts at y=16, x=16, so nothing in it is masked; check
    # output matches input
    np.testing.assert_allclose(got, expected, rtol=1e-3, atol=1e-3)

    # timing log uploaded next to the volume
    import os

    log_dir = os.path.join(str(world["tmp_path"] / "out"), "log")
    logs = os.listdir(log_dir)
    assert len(logs) == 1 and logs[0].endswith(".json")


def test_masked_region_zeroed(world):
    """Task overlapping the masked corner: masked voxels must be zero."""
    runner = CliRunner()
    result = runner.invoke(
        main,
        [
            "generate-tasks", "-c", "16", "32", "32",
            "--roi-start", "0", "0", "0",
            "--grid-size", "1", "1", "1",
            "load-precomputed", "-v", world["input_vol"].path,
            "mask", "-v", world["mask_vol"].path,
            "save-precomputed", "-v", world["output_vol"].path,
        ],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    out = world["output_vol"].cutout(BoundingBox((0, 0, 0), (16, 32, 32)))
    got = np.asarray(out.array).squeeze()
    assert np.all(got[:, :16, :16] == 0)  # masked corner
    assert np.any(got[:, 16:, 16:] != 0)  # rest survived


def test_skip_by_blocks_resume(world):
    """Second run of the same task skips via has_all_blocks."""
    runner = CliRunner()
    args = [
        "-v",
        "generate-tasks", "-c", "16", "16", "16",
        "--roi-start", "0", "0", "0", "--grid-size", "1", "1", "1",
        "skip-task-by-blocks-in-volume", "-v", world["output_vol"].path,
        "load-precomputed", "-v", world["input_vol"].path,
        "save-precomputed", "-v", world["output_vol"].path,
    ]
    r1 = runner.invoke(main, args, catch_exceptions=False)
    assert r1.exit_code == 0
    assert "save-precomputed" in r1.output
    r2 = runner.invoke(main, args, catch_exceptions=False)
    # second run: task skipped before load
    assert "save-precomputed" not in r2.output


def test_worker_stream_rebuilds_one_span_tree_per_task(world):
    """ISSUE 23: the README worker chain over a queue, --async-depth 2,
    with a sink: every span that worked for a task carries the task's
    trace_id (also the ones that waited for a task not yet known:
    queue/fetch, scheduler/load), every parent_id names a span of the
    same stream, and the spans run from the claim to the ack."""
    import json

    from chunkflow_tpu.core import telemetry

    tmp_path = world["tmp_path"]
    qdir = str(tmp_path / "queue")
    runner = CliRunner()
    result = runner.invoke(main, [
        "generate-tasks", "-c", "8", "16", "16",
        "--roi-start", "8", "16", "16", "--grid-size", "1", "1", "2",
        "--queue-name", qdir], catch_exceptions=False)
    assert result.exit_code == 0
    telemetry.reset()
    try:
        result = runner.invoke(main, [
            "--metrics-dir", str(tmp_path / "metrics"),
            "fetch-task-from-queue", "-q", qdir, "--retry-times", "1",
            "--poll-interval", "0.05",
            "load-precomputed", "-v", world["input_vol"].path,
            "--expand-margin-size", "4", "8", "8",
            "inference", "--framework", "identity",
            "--input-patch-size", "12", "24", "24",
            "--output-patch-size", "8", "16", "16",
            "--output-patch-overlap", "4", "8", "8",
            "--num-output-channels", "1", "--batch-size", "2",
            "--async-depth", "2",
            "crop-margin",
            "save-precomputed", "-v", world["output_vol"].path,
            "delete-task-in-queue"], catch_exceptions=False)
        assert result.exit_code == 0, result.output
    finally:
        telemetry.reset()
    (stream,) = (tmp_path / "metrics").glob("telemetry-*.jsonl")
    with open(stream) as f:
        spans = [e for e in map(json.loads, f) if e["kind"] == "span"]
    ids = {e["span_id"] for e in spans}
    assert len(ids) == len(spans)
    assert all(e["parent_id"] is None or e["parent_id"] in ids
               for e in spans)
    tasks = {}
    for event in spans:
        if event.get("trace_id"):
            tasks.setdefault(event["trace_id"], []).append(event)
    assert len(tasks) == 2
    by_id = {e["span_id"]: e for e in spans}
    for events in tasks.values():
        names = {e["name"] for e in events}
        assert {"queue/fetch", "op/load-precomputed", "storage/read",
                "storage/decode", "scheduler/load", "pipeline/stage",
                "pipeline/dispatch", "inference/blank_check",
                "pipeline/compute", "pipeline/drain", "op/crop-margin",
                "op/save-precomputed", "storage/write",
                "op/delete-task-in-queue", "queue/ack"} <= names
        # scheduler/load is the consumer's wait FOR the task: it can
        # begin before the task was claimed
        first = min((e for e in events if e["name"] != "scheduler/load"),
                    key=lambda e: e["t0"])
        last = max(events, key=lambda e: e["t"])
        assert first["name"] == "queue/fetch"
        assert last["name"] in ("pipeline/ack_writes", "queue/ack",
                                "op/delete-task-in-queue")
        # a child lies inside its parent, on the parent's task
        for event in events:
            parent = by_id.get(event["parent_id"])
            if parent is not None:
                assert parent["trace_id"] == event["trace_id"]
                assert parent["t0"] <= event["t0"] + 1e-3
                assert event["t"] <= parent["t"] + 5e-3
        nested = {e["name"]: by_id[e["parent_id"]]["name"]
                  for e in events if e["parent_id"] is not None}
        assert nested["storage/read"] == "op/load-precomputed"
        assert nested["storage/write"] == "op/save-precomputed"
        # the answer is taken where the chunk is on the host: at staging
        assert nested["inference/blank_check"] == "pipeline/stage"
        assert nested["queue/ack"] == "op/delete-task-in-queue"
    # what carries no task: the wait that ended with the stream's end
    assert {e["name"] for e in spans if not e.get("trace_id")} <= {
        "scheduler/load", "compile_cache/build"}
