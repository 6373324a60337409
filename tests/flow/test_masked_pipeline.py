"""The masked production pipeline over a volume with blank regions (ISSUE
37): ``load-precomputed > mask > inference > crop-margin > mask >
save-precomputed`` through the real command chain on a ``file://``
volume, against the plain reference ``benchmarks/reference/
rsunet_masked.py`` (numpy masks around the float32 ``highest`` forward,
blended in float64 by ``benchmarks/cfbench/blend.py``), which shares no
code with ``ops/mask.py``, ``ops/blend.py`` or ``Inferencer``. Small
sizes, seeded random weights, CPU."""
import json
import os
import sys

import numpy as np
import pytest

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import profiling, telemetry
from chunkflow_tpu.core.bbox import BoundingBox
from chunkflow_tpu.ops import mask as mask_ops
from chunkflow_tpu.volume.precomputed import PrecomputedVolume

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(CHECKOUT, "benchmarks")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from cfbench import blend, catalog, masked_volume, program, volume  # noqa: E402

PATCH, OVERLAP = (8, 32, 32), (2, 8, 8)
FACTOR = (1, 4, 4)
# four tasks tiling x: 0 whole, 1 and 3 cut by the masks' edge, 2 blank
GEOMETRY = volume.Geometry(
    patch=PATCH, overlap=OVERLAP, margin=(1, 4, 4), block=(6, 24, 24),
    grid=(2, 2, 3), n_tasks=4)
KIND = {0: "whole", 1: "edge", 2: "blank", 3: "edge"}
SEED = 11


def read_stream(metrics_dir):
    events = program.read_events(metrics_dir)
    return program.read_spans(events), program.read_counters(events)


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """The chain run once over the four tasks, then once more with
    ``skip-task-by-blocks-in-volume`` in front."""
    work = str(tmp_path_factory.mktemp("masked"))
    g = GEOMETRY
    layout = masked_volume.MaskLayout(g, FACTOR, (2,))
    assert [layout.kind(i) for i in range(g.n_tasks)] == list(KIND.values())
    volume.write_volume(f"file://{work}/image", SEED, g)
    masks = [f"file://{work}/image-mask", f"file://{work}/output-mask"]
    for path in masks:
        layout.write(path, (8, 8, 8))
    out_path = f"file://{work}/out"
    program.chunkflow(
        "create-info", "-v", out_path, "--volume-size", *g.roi,
        "--voxel-offset", *g.margin, "--num-channels", 3,
        "--dtype", "float32", "--block-size", *g.block)
    from chunkflow_tpu.parallel.queues import open_queue

    bodies = [BoundingBox.from_delta(g.task_start(i), g.task).string
              for i in range(g.n_tasks)]

    def chain(metrics_dir, *front):
        os.makedirs(os.path.join(work, "queue", "pending"), exist_ok=True)
        open_queue(f"file://{work}/queue").send_messages(bodies)
        program.chunkflow(
            "--metrics-dir", metrics_dir,
            "fetch-task-from-queue", "-q", f"file://{work}/queue",
            "--retry-times", 2, "--poll-interval", 0.05,
            *front,
            "load-precomputed", "-v", f"file://{work}/image",
            "--expand-margin-size", *g.margin,
            "mask", "-v", masks[0],
            "inference", "--framework", "flax", "--model-variant", "rsunet",
            "--dtype", "bfloat16", "--input-patch-size", *PATCH,
            "--output-patch-overlap", *OVERLAP,
            "--num-output-channels", 3, "--batch-size", 4,
            "--async-depth", 2,
            "crop-margin",
            "mask", "-v", masks[1],
            "save-precomputed", "-v", out_path,
            "delete-task-in-queue")
        return read_stream(metrics_dir)

    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    try:
        first = chain(os.path.join(work, "metrics-1"))
        # the outputs, before the second run could touch them
        vol = PrecomputedVolume(out_path)
        outputs = {
            i: np.asarray(vol.cutout(
                BoundingBox.from_delta(g.task_start(i), g.task),
                fill_missing=False).array)
            for i in range(g.n_tasks)}
        second = chain(os.path.join(work, "metrics-2"),
                       "skip-task-by-blocks-in-volume", "-v", out_path)
    finally:
        telemetry.reset()
        monkeypatch.undo()
    return dict(work=work, layout=layout, vol=vol, outputs=outputs,
                first=first, second=second)


@pytest.fixture(scope="module")
def reference_of(world):
    """``index -> (y or None, keep_out)`` over the task's whole box, from
    the plain reference given the engine's own seeded parameters."""
    from chunkflow_tpu.inference.engines import create_flax_engine

    plain = catalog.load_module("reference", "rsunet_masked")
    forward = plain.make_forward({"model": {"final_activation": "sigmoid"}})
    params = create_flax_engine(
        "", None, PATCH, 1, 3, dtype="bfloat16",
        model_variant="rsunet").params
    g, layout = GEOMETRY, world["layout"]
    mask = layout.mask()

    def one_patch(window):
        out = forward(params, window[None, ..., None])
        return np.moveaxis(np.asarray(out[0]), -1, 0)

    def blended(image, box):
        return blend.blend_box(image, PATCH, OVERLAP, box, one_patch)

    box = (g.margin, tuple(c - m for c, m in zip(g.chunk_in, g.margin)))
    cache = {}

    def of(index):
        if index not in cache:
            want, kept, _ = plain.output(
                volume.seeded_task_input(SEED, g, index), mask, mask,
                FACTOR, (0, 0, index * g.task[2]), box, blended)
            cache[index] = (want, kept)
        return cache[index]

    return of


def tolerance():
    with open(os.path.join(BENCH_DIR, "configs",
                           "rsunet-superhuman-masked.json")) as f:
        return json.load(f)["tolerance"]


@pytest.mark.parametrize("index", [0, 1, 3])
def test_a_task_equals_the_reference(world, reference_of, index):
    """(a) a whole task, (b) a task cut by the masks' edge on either
    side: within the anchor's tolerance of the plain reference, and
    exactly zero where either mask is zero."""
    got = world["outputs"][index]
    want, kept = reference_of(index)
    assert want is not None and got.shape == want.shape
    assert kept.all() == (KIND[index] == "whole")
    gap = np.abs(got - want)
    limits = tolerance()
    assert gap.max() <= limits["max_abs_diff"]
    assert gap[:, kept].mean() <= limits["mean_abs_diff"]
    assert got[:, kept].std() > 1e-3
    if KIND[index] == "edge":
        assert 0.4 < 1.0 - kept.mean() < 0.6
        assert not got[:, ~kept].any()


def test_a_blank_task_is_blank_to_the_reference(world, reference_of):
    want, kept = reference_of(2)
    assert want is None and not kept.any()
    assert not world["outputs"][2].any()


def test_a_blank_task_commits_all_its_blocks_as_zeros(world):
    g, vol = GEOMETRY, world["vol"]
    box = BoundingBox.from_delta(g.task_start(2), g.task)
    names = vol.block_names(box)
    directory = world["work"] + "/out"
    assert len(names) == 2 * 2 * 3
    assert all(os.path.exists(os.path.join(directory, n)) for n in names)
    assert vol.has_all_blocks(box)
    _, counters = world["first"]
    # the blank task's 12 and half of each edge task's
    assert counters["storage/zero_blocks_written"] >= 12 + 2 * 4


def test_a_blank_task_runs_no_forward_and_uploads_nothing(world):
    spans, counters = world["first"]
    assert counters["inference/tasks"] == 4
    assert counters["inference/blank_tasks"] == 1
    assert counters["inference/blank_output_bytes"] > 0
    assert counters["mask/h2d_bytes"] == 0
    assert counters["mask/zeroed_voxels"] > 0
    blank = [s["trace_id"] for s in spans
             if s["name"] == "inference/blank_check" and s.get("blank") == 1]
    assert len(blank) == 1
    mine = [s["name"] for s in spans if s.get("trace_id") == blank[0]]
    assert "pipeline/compute" not in mine
    assert {"mask/cutout", "mask/apply", "storage/write",
            "queue/ack"} <= set(mine)
    others = {s["trace_id"] for s in spans if s["name"] == "pipeline/compute"}
    assert len(others) == 3 and blank[0] not in others


def test_a_second_run_skips_every_task_by_its_blocks(world):
    """The resume rule: with every task's blocks in the volume, the blank
    task's zeros among them, ``skip-task-by-blocks-in-volume`` lets no
    task through."""
    spans, counters = world["second"]
    assert counters.get("inference/tasks", 0) == 0
    assert not [s for s in spans if s["name"] in (
        "storage/write", "mask/apply", "pipeline/dispatch")]


def test_the_mask_spans_carry_the_task_and_the_thread(world):
    """An operator in front of ``inference`` runs on the scheduler's pump
    thread, one behind it on the main thread; both under the task's
    ``trace_id``."""
    spans, _ = world["first"]
    tasks = {s["trace_id"] for s in spans if s["name"] == "queue/ack"}
    assert len(tasks) == 4
    for name in ("mask/cutout", "mask/apply"):
        mine = [s for s in spans if s["name"] == name]
        assert len(mine) == 8 and {s["trace_id"] for s in mine} == tasks
        threads = sorted(s["thread"] for s in mine)
        assert threads == ["MainThread"] * 4 + ["scheduler-pump"] * 4
    for s in spans:
        if s["name"] == "mask/cutout":
            assert s["blocks"] > 0 and s["bytes"] > 0
        if s["name"] == "mask/apply":
            assert s["device"] == 0 and s["voxels"] > 0


# (d) coarse factors and a box that does not start on a coarse voxel's edge
CASES = [
    ((1, 1, 1), (0, 0, 0), (6, 20, 24)),
    ((1, 32, 32), (0, 0, 0), (5, 64, 96)),
    ((1, 32, 32), (3, 40, 70), (5, 50, 61)),
    ((2, 4, 4), (1, 6, 3), (7, 21, 18)),
]


@pytest.mark.parametrize("device", [False, True])
@pytest.mark.parametrize("channels", [None, 3])
@pytest.mark.parametrize("factor, start, shape", CASES)
def test_maskout_equals_the_reference_mask(factor, start, shape, channels,
                                           device):
    plain = catalog.load_module("reference", "rsunet_masked")
    rng = np.random.default_rng(3)
    coarse = rng.integers(0, 2, [-(-(a + n) // f) + 1 for a, n, f
                                 in zip(start, shape, factor)],
                          dtype=np.uint8)
    full = shape if channels is None else (channels, *shape)
    array = rng.random(full, dtype=np.float32) + 0.5
    chunk = Chunk(array, voxel_offset=start)
    if device:
        chunk = chunk.device()
    out = mask_ops.maskout(chunk, Chunk(coarse, voxel_size=factor))
    kept = plain.keep(coarse, factor, start, shape)
    assert out.is_on_device == device
    assert 0 < kept.mean() < 1
    np.testing.assert_array_equal(np.asarray(out.array), array * kept)
    assert tuple(out.voxel_offset) == tuple(start)
    assert mask_ops.zeroed_voxels(
        *mask_ops.coarse_window(chunk, Chunk(coarse, voxel_size=factor)),
        shape) == int((~kept).sum())


@pytest.mark.parametrize("value, same", [(1, True), (0, False)])
def test_a_uniform_window_takes_no_multiply(value, same):
    chunk = Chunk(np.ones((4, 8, 8), np.float32))
    mask = Chunk(np.full((4, 2, 2), value, np.uint8), voxel_size=(1, 4, 4))
    out = mask_ops.maskout(chunk, mask)
    assert (out is chunk) == same
    assert np.asarray(out.array).all() == same


def test_a_mask_that_does_not_cover_the_chunk_is_refused():
    chunk = Chunk(np.ones((4, 8, 8), np.float32), voxel_offset=(0, 0, 4))
    mask = Chunk(np.ones((4, 2, 2), np.uint8), voxel_size=(1, 4, 4))
    with pytest.raises(ValueError, match="does not cover"):
        mask_ops.maskout(chunk, mask)


def test_the_mask_program_is_built_once_a_shape(tmp_path, monkeypatch):
    """(e) a device chunk is masked by one cached program a shape, which
    goes through the program cache (``compile_cache/build``,
    ``programs.json``) under the scope ``mask``, and only the coarse
    window is uploaded."""
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.configure(str(tmp_path))
    try:
        mask_ops._PROGRAMS.clear()
        builds = mask_ops._PROGRAMS.builds
        rng = np.random.default_rng(0)
        coarse = Chunk(rng.integers(0, 2, (4, 3, 3), dtype=np.uint8),
                       voxel_size=(1, 4, 4))
        for seed in range(3):
            array = np.random.default_rng(seed).random(
                (3, 4, 12, 12), dtype=np.float32)
            out = mask_ops.maskout(Chunk(array).device(), coarse)
            assert out.is_on_device
        assert mask_ops._PROGRAMS.builds - builds == 1
        mask_ops.maskout(Chunk(np.ones((4, 8, 12), np.float32)).device(),
                         coarse)
        assert mask_ops._PROGRAMS.builds - builds == 2
        entries = [e for e in profiling.catalog() if e["label"] == "mask"]
        assert len(entries) == 2
        assert all(e["op_scopes"].get("mask") for e in entries)
        assert sorted(e["calls"] for e in entries) == [1, 3]
        telemetry.flush()
        spans, counters = read_stream(str(tmp_path))
        built = [s for s in spans if s["name"] == "compile_cache/build"
                 and s.get("label") == "mask"]
        assert len(built) == 2
        # three windows of 4x3x3 and one of 4x2x3, a byte a voxel
        assert counters["mask/h2d_bytes"] == 3 * 36 + 24
        applied = [s for s in spans if s["name"] == "mask/apply"]
        assert [s["device"] for s in applied] == [1] * 4
    finally:
        telemetry.reset()
        mask_ops._PROGRAMS.clear()


def test_the_tutorials_masked_command_line_is_the_cells_chain():
    """docs/tutorial.md's masked example: every command exists and
    parses, and the chain is the one the benchmark's masked cell runs, at
    the anchor's geometry."""
    import re
    import shlex

    from chunkflow_tpu.flow import cli

    with open(os.path.join(CHECKOUT, "docs", "tutorial.md")) as f:
        text = f.read()
    block = re.search(
        r"<!-- masked-deployment -->\n```bash\n(.*?)```", text, re.S)
    words = shlex.split(block.group(1).replace("\\\n", " "))
    assert words[0] == "chunkflow"
    starts = [i for i, w in enumerate(words) if w in cli.main.commands]
    chain = [words[i] for i in starts]
    assert chain == ["fetch-task-from-queue", "load-precomputed", "mask",
                     "inference", "crop-margin", "mask", "save-precomputed",
                     "delete-task-in-queue"]
    parsed = []
    for i, j in zip(starts, starts[1:] + [len(words)]):
        parsed.append(cli.main.commands[words[i]].make_context(
            words[i], list(words[i + 1:j])).params)
    with open(os.path.join(BENCH_DIR, "configs",
                           "rsunet-superhuman-masked.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", "volume-masked.json")) as f:
        traffic = json.load(f)
    got = parsed[3]
    assert list(got["input_patch_size"]) == config["patch"]
    assert list(got["output_patch_overlap"]) == config["overlap"]
    assert got["batch_size"] == config["batch"]
    assert got["num_output_channels"] == config["model"]["out_channels"]
    assert got["async_depth"] == traffic["async_depth"]
    assert list(parsed[1]["expand_margin_size"]) == traffic["margin"]
    assert parsed[2]["volume_path"] != parsed[5]["volume_path"]
    assert parsed[2]["fill_missing"] and parsed[5]["fill_missing"]


def test_the_masked_traffic_is_what_its_file_says():
    """4 of 24 tasks blank, 7 cut by the masks' edge, 13 whole, 31% of
    the voxels masked; the rehearsal's first steady tasks are one of
    each kind."""
    with open(os.path.join(BENCH_DIR, "configs",
                           "rsunet-superhuman-masked.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", "volume-masked.json")) as f:
        traffic = json.load(f)

    def layout(config, traffic):
        g = volume.Geometry(
            patch=tuple(config["patch"]), overlap=tuple(config["overlap"]),
            margin=tuple(traffic["margin"]), block=tuple(traffic["block"]),
            grid=tuple(traffic["patch_grid"]), n_tasks=traffic["tasks"])
        return g, masked_volume.MaskLayout(
            g, tuple(traffic["masks"]["factor"]),
            tuple(traffic["masks"]["blank_tasks"]))

    g, full = layout(config, traffic)
    kinds = [full.kind(i) for i in range(g.n_tasks)]
    assert g.chunk_in == (36, 832, 1216) and g.task == (32, 768, 1152)
    assert [i for i, k in enumerate(kinds) if k == "blank"] == [5, 11, 17, 23]
    assert [i for i, k in enumerate(kinds) if k == "edge"] == [
        4, 6, 10, 12, 16, 18, 22]
    assert kinds.count("whole") == 13
    masked = sum(min(x1, g.size[2] - g.margin[2]) - x0
                 for x0, x1 in full.zero_ranges()) / g.roi[2]
    assert 0.30 < masked < 0.32
    assert full.edge_box(6) == ((10, 128, 512), (26, 320, 704))
    assert full.mask().shape == (36, 26, 866)
    g, small = layout({**config, **config["rehearse"]},
                      {**traffic, **traffic["rehearse"]})
    assert {small.kind(i) for i in (2, 3, 5)} == {"whole", "edge", "blank"}
    assert set(traffic["masks"]) == set(traffic["rehearse"]["masks"])
