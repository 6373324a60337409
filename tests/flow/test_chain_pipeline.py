"""Upstream's worker command without the masks (ISSUE 42):
``load-precomputed > normalize-contrast --levels-path > inference >
crop-margin > save-precomputed --create-thumbnail --upload-log`` through
the real command chain on a ``file://`` volume, against the plain
reference ``benchmarks/reference/rsunet_chain.py`` (integer tables from
the same sidecars, the float32 ``highest`` forward on the normalized
chunk blended in float64 by ``benchmarks/cfbench/blend.py``, the
thumbnail as integer arithmetic on the committed result), which shares no
code with ``ops/contrast.py``, ``ops/downsample.py``,
``AffinityMap.quantize`` or ``Inferencer``. Small sizes, seeded random
weights, CPU."""
import json
import os
import sys

import numpy as np
import pytest

from chunkflow_tpu.chunk import AffinityMap
from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.chunk.image import Image
from chunkflow_tpu.core import profiling, telemetry
from chunkflow_tpu.core.bbox import BoundingBox
from chunkflow_tpu.ops import contrast
from chunkflow_tpu.ops import downsample as downsample_ops
from chunkflow_tpu.volume.precomputed import PrecomputedVolume

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(CHECKOUT, "benchmarks")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from cfbench import blend, catalog, levels, program, volume  # noqa: E402

PATCH, OVERLAP = (8, 32, 32), (2, 8, 8)
# three tasks of 12x48x72 tiling x; a thumbnail of three levels
GEOMETRY = volume.Geometry(
    patch=PATCH, overlap=OVERLAP, margin=(1, 4, 4), block=(6, 24, 24),
    grid=(2, 2, 3), n_tasks=3)
THUMBNAIL_MIP, THUMBNAIL_BLOCK = 3, (12, 6, 9)
SEED = 2147483659


def plain():
    return catalog.load_module("reference", "rsunet_chain")


def chain_driver():
    return catalog.load_module("drivers", "worker_chain")


def config():
    with open(os.path.join(BENCH_DIR, "configs",
                           "rsunet-superhuman-chain.json")) as f:
        return json.load(f)


def read_stream(metrics_dir):
    events = program.read_events(metrics_dir)
    return program.read_spans(events), program.read_counters(events)


def make_volumes(work, geometry=GEOMETRY, seed=SEED):
    """Input volume with its sidecars, output volume with its thumbnail
    layer, and the tasks on a queue."""
    from chunkflow_tpu.parallel.queues import open_queue

    g = geometry
    histograms = levels.write_volume_and_levels(f"file://{work}/image",
                                                seed, g)
    out_path = f"file://{work}/out"
    program.chunkflow(
        "create-info", "-v", out_path, "--volume-size", *g.roi,
        "--voxel-offset", *g.margin, "--num-channels", 3,
        "--dtype", "float32", "--block-size", *g.block)
    thumbnail = chain_driver().Thumbnail(g, THUMBNAIL_MIP, THUMBNAIL_BLOCK)
    thumbnail.create(out_path)
    os.makedirs(os.path.join(work, "queue", "pending"), exist_ok=True)
    open_queue(f"file://{work}/queue").send_messages(
        [BoundingBox.from_delta(g.task_start(i), g.task).string
         for i in range(g.n_tasks)])
    return histograms, out_path, thumbnail


def run_chain(work, out_path, *save_options, metrics_dir=None):
    g = GEOMETRY
    head = ["--metrics-dir", metrics_dir] if metrics_dir else []
    program.chunkflow(
        *head,
        "fetch-task-from-queue", "-q", f"file://{work}/queue",
        "--retry-times", 2, "--poll-interval", 0.05,
        "load-precomputed", "-v", f"file://{work}/image",
        "--expand-margin-size", *g.margin,
        "normalize-contrast",
        "--levels-path", f"file://{work}/image/levels/0",
        "inference", "--framework", "flax", "--model-variant", "rsunet",
        "--dtype", "bfloat16", "--input-patch-size", *PATCH,
        "--output-patch-overlap", *OVERLAP,
        "--num-output-channels", 3, "--batch-size", 4,
        "--async-depth", 2,
        "crop-margin",
        "save-precomputed", "-v", out_path, "--create-thumbnail",
        "--upload-log", *save_options,
        "delete-task-in-queue")


@pytest.fixture(scope="module")
def world(tmp_path_factory):
    """The chain run once over the three tasks."""
    work = str(tmp_path_factory.mktemp("chain"))
    g = GEOMETRY
    histograms, out_path, thumbnail = make_volumes(work)
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    contrast.clear_tables()
    try:
        run_chain(work, out_path,
                  metrics_dir=os.path.join(work, "metrics"))
        spans, counters = read_stream(os.path.join(work, "metrics"))
    finally:
        telemetry.reset()
        monkeypatch.undo()
    vol = PrecomputedVolume(out_path)
    outputs = {
        i: np.asarray(vol.cutout(
            BoundingBox.from_delta(g.task_start(i), g.task),
            fill_missing=False).array)
        for i in range(g.n_tasks)}
    return dict(work=work, vol=vol, outputs=outputs, spans=spans,
                counters=counters, histograms=histograms,
                thumbnail=thumbnail)


# ---- normalize-contrast ---------------------------------------------------
def random_histogram(kind, rng):
    if kind == "noise":
        return rng.integers(0, 5000, 256)
    if kind == "narrow":           # all of the section in a few values
        h = np.zeros(256, np.int64)
        h[100:110] = rng.integers(1, 1000, 10)
        return h
    if kind == "spike":            # lo == hi
        h = np.zeros(256, np.int64)
        h[77] = 12345
        return h
    if kind == "black":            # only pure black: an empty histogram
        h = np.zeros(256, np.int64)
        h[0] = 999
        return h
    if kind == "ties":             # shares that sit on the fractions
        h = np.zeros(256, np.int64)
        h[[10, 11, 50, 200, 201]] = [1, 0, 98, 0, 1]
        return h
    raise ValueError(kind)


@pytest.mark.parametrize("settings", [
    (0.01, 0.01, 1, 255), (0.0, 0.0, 0, 255), (0.05, 0.2, 10, 200),
    (0.01, 0.01, 128, 128)])
@pytest.mark.parametrize("kind", ["noise", "narrow", "spike", "black",
                                  "ties"])
def test_the_table_equals_the_references(kind, settings):
    rng = np.random.default_rng(5)
    for _ in range(4):
        h = random_histogram(kind, rng)
        got = contrast.lookup_table(h, *settings)
        want = plain().lookup_table(h.tolist(), *settings)
        assert got.dtype == np.uint8
        np.testing.assert_array_equal(got, want)
        assert contrast.clamping_values(h, *settings[:2]) == \
            plain().clamping_values(h.tolist(), *settings[:2])
        assert got.min() >= settings[2] and got.max() <= settings[3]
        assert (np.diff(got.astype(int)) >= 0).all()


@pytest.mark.parametrize("device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("index", [0, 2])
def test_the_normalized_chunk_is_the_references_bit_for_bit(
        world, index, device):
    """Both legs: a host chunk by ``np.take`` a section, a device chunk
    by the cached program; the task's own input chunk through the
    volume's own sidecars."""
    g = GEOMETRY
    image = volume.seeded_task_input(SEED, g, index)
    chunk = Image(image, voxel_offset=(0, 0, index * g.task[2]))
    if device:
        chunk = chunk.device()
    got = chunk.normalize_contrast(
        levels_path=f"file://{world['work']}/image/levels/0")
    want = plain().normalized(
        image, levels.read_levels(
            os.path.join(world["work"], "image", "levels", "0"), g.size[0]),
        config()["normalize"])
    assert got.is_on_device == device and isinstance(got, Image)
    assert np.asarray(got.array).dtype == np.uint8
    np.testing.assert_array_equal(np.asarray(got.array), want)
    assert (want != image).mean() > 0.5        # the tables do something
    assert tuple(got.voxel_offset) == (0, 0, index * g.task[2])


@pytest.mark.parametrize("device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("shape, z0, settings", [
    ((5, 9, 11), 3, (0.01, 0.01, 1, 255)),
    ((2, 4, 6, 7), 0, (0.1, 0.3, 20, 90)),
    ((1, 1, 1), 7, (0.0, 0.0, 0, 255)),
])
def test_sections_go_through_their_own_tables(tmp_path, shape, z0, settings,
                                              device):
    """The table of global section ``voxel_offset.z + i``, every channel
    of a 4D chunk alike, under other fractions and ranges."""
    rng = np.random.default_rng(9)
    histograms = rng.integers(0, 1000, (12, 256))
    levels.write_levels(str(tmp_path), histograms)
    array = rng.integers(0, 256, shape, dtype=np.uint8)
    chunk = Chunk(array, voxel_offset=(z0, 5, 6))
    if device:
        chunk = chunk.device()
    contrast.clear_tables()
    got = contrast.normalize_contrast_by_levels(chunk, str(tmp_path),
                                                *settings)
    want = np.empty_like(array)
    for i in range(shape[-3]):
        table = plain().lookup_table(histograms[z0 + i].tolist(), *settings)
        want[..., i, :, :] = table[array[..., i, :, :]]
    assert got.is_on_device == device
    np.testing.assert_array_equal(np.asarray(got.array), want)


def test_a_missing_sidecar_is_an_error(tmp_path):
    levels.write_levels(str(tmp_path), np.ones((3, 256), np.int64))
    contrast.clear_tables()
    chunk = Chunk(np.zeros((4, 4, 4), np.uint8))
    with pytest.raises(FileNotFoundError, match="no levels file .*/3"):
        contrast.normalize_contrast_by_levels(chunk, str(tmp_path))


def test_a_float_chunk_is_refused_with_levels_and_stretched_without(
        tmp_path):
    levels.write_levels(str(tmp_path), np.ones((4, 256), np.int64))
    array = np.random.default_rng(0).random((4, 8, 8), dtype=np.float32)
    with pytest.raises(TypeError, match="uint8"):
        Image(array).normalize_contrast(levels_path=str(tmp_path))
    out = np.asarray(Image(array).normalize_contrast().array)
    assert out.dtype == np.uint8 and out.min() >= 1 and out.max() == 255


def test_the_tables_are_read_once_and_cached(tmp_path, monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.configure(str(tmp_path / "metrics"))
    try:
        levels.write_levels(str(tmp_path / "levels"),
                            np.random.default_rng(1).integers(
                                0, 100, (6, 256)))
        contrast.clear_tables()
        path = str(tmp_path / "levels")
        chunk = Chunk(np.zeros((4, 4, 4), np.uint8), voxel_offset=(1, 0, 0))
        for _ in range(3):
            contrast.normalize_contrast_by_levels(chunk, path)
        # other fractions are other tables
        contrast.normalize_contrast_by_levels(chunk, path, 0.02, 0.02)
        telemetry.flush()
        spans, counters = read_stream(str(tmp_path / "metrics"))
        assert counters["normalize/level_reads"] == 8
        assert counters["normalize/table_cache_hits"] == 8
        reads = [(s["sections"], s["reads"], s["cache_hits"])
                 for s in spans if s["name"] == "normalize/levels"]
        assert reads == [(4, 4, 0), (4, 0, 4), (4, 0, 4), (4, 4, 0)]
    finally:
        telemetry.reset()
        contrast.clear_tables()


def test_the_normalize_program_is_built_once_a_shape(tmp_path, monkeypatch):
    """A device chunk is normalized by one cached program a shape, which
    goes through the program cache under the scope
    ``normalize_contrast``."""
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.configure(str(tmp_path))
    try:
        contrast._PROGRAMS.clear()
        builds = contrast._PROGRAMS.builds
        tables = np.random.default_rng(0).integers(
            0, 256, (4, 256)).astype(np.uint8)
        for seed in range(3):
            array = np.random.default_rng(seed).integers(
                0, 256, (4, 6, 6), dtype=np.uint8)
            out = contrast.normalize_sections(Chunk(array).device(), tables)
            assert out.is_on_device
        assert contrast._PROGRAMS.builds - builds == 1
        entries = [e for e in profiling.catalog()
                   if e["label"] == "normalize_contrast"]
        assert len(entries) == 1 and entries[0]["calls"] == 3
        assert entries[0]["op_scopes"].get("normalize_contrast")
        telemetry.flush()
        spans, _ = read_stream(str(tmp_path))
        assert [s["device"] for s in spans
                if s["name"] == "normalize/apply"] == [1, 1, 1]
    finally:
        telemetry.reset()
        contrast._PROGRAMS.clear()


# ---- the result -----------------------------------------------------------
@pytest.fixture(scope="module")
def reference_of(world):
    """``index -> y`` over the task's whole box, from the plain reference
    given the engine's own seeded parameters."""
    from chunkflow_tpu.inference.engines import create_flax_engine

    forward = plain().make_forward(
        {"model": {"final_activation": "sigmoid"}})
    params = create_flax_engine(
        "", None, PATCH, 1, 3, dtype="bfloat16",
        model_variant="rsunet").params
    g = GEOMETRY
    histograms = levels.read_levels(
        os.path.join(world["work"], "image", "levels", "0"), g.size[0])

    def one_patch(window):
        out = forward(params, window[None, ..., None])
        return np.moveaxis(np.asarray(out[0]), -1, 0)

    def blended(image, box):
        return blend.blend_box(image, PATCH, OVERLAP, box, one_patch)

    box = (g.margin, tuple(c - m for c, m in zip(g.chunk_in, g.margin)))
    cache = {}

    def of(index, raw=False):
        if (index, raw) not in cache:
            image = volume.seeded_task_input(SEED, g, index)
            cache[index, raw] = (
                blended(image, box)[0] if raw else plain().output(
                    image, histograms, config()["normalize"], box,
                    blended)[0])
        return cache[index, raw]

    return of


@pytest.mark.parametrize("index", [0, 1, 2])
def test_a_task_equals_the_reference(world, reference_of, index):
    """The result is within the configuration's two bounds of the plain
    reference on the *normalized* chunk."""
    got, want = world["outputs"][index], reference_of(index)
    assert got.shape == want.shape
    gap = np.abs(got - want)
    limits = config()["tolerance"]
    assert gap.max() <= limits["max_abs_diff"]
    assert gap.mean() <= limits["mean_abs_diff"]
    assert got.std() > 1e-3


def test_a_result_of_the_raw_image_is_outside_the_bounds(world,
                                                         reference_of):
    """What the bounds tell apart: the reference on the chunk as it was
    loaded misses the committed result by more than the mean bound."""
    gap = np.abs(world["outputs"][1] - reference_of(1, raw=True))
    assert gap.mean() > config()["tolerance"]["mean_abs_diff"]


# ---- the thumbnail --------------------------------------------------------
@pytest.mark.parametrize("level", [1, 2, 3])
@pytest.mark.parametrize("index", [0, 1, 2])
def test_a_thumbnail_level_equals_the_reference(world, index, level):
    """Every level of every task, over the task's whole box divided by
    the level's factor: within one grey level of the reference's integer
    arithmetic on the committed result."""
    thumb = world["thumbnail"]
    layer = world["vol"].thumbnail_layer()
    box = thumb.level_box(index, level)
    assert tuple(box.shape) == (12, 48 >> level, 72 >> level)
    got = np.asarray(layer.cutout(box, mip=level,
                                  fill_missing=False).array)
    want = plain().thumbnail_levels(world["outputs"][index], level)[-1]
    assert got.dtype == np.uint8 and got.shape == want.shape
    gap = np.abs(got.astype(int) - want.astype(int))
    assert gap.max() <= config()["thumbnail_limits"]["thumbnail_max_abs"]
    assert (gap == 0).mean() > 0.95
    assert got.std() > 0.5


def test_the_thumbnail_layer_holds_nothing_else(world):
    """Levels 1..3 and no level 0; the result's own volume has one
    scale and got no pyramid."""
    directory = os.path.join(world["work"], "out", "thumbnail")
    layer = world["vol"].thumbnail_layer()
    keys = [scale["key"] for scale in layer.info["scales"]]
    per_task = sum((12 // 12) * ((48 >> k) // 6) * ((72 >> k) // 9)
                   for k in (1, 2, 3))
    assert per_task == 16 + 4 + 1
    written = {key: len(os.listdir(os.path.join(directory, key)))
               for key in keys if os.path.isdir(os.path.join(directory, key))}
    assert written == {keys[1]: 48, keys[2]: 12, keys[3]: 3}
    assert world["vol"].num_mips == 1
    assert world["counters"]["thumbnail/blocks_written"] == 3 * per_task


def test_every_task_left_its_log(world):
    g = GEOMETRY
    log_dir = os.path.join(world["work"], "out", "log")
    names = {BoundingBox.from_delta(g.task_start(i), g.task).string + ".json"
             for i in range(g.n_tasks)}
    assert set(os.listdir(log_dir)) == names
    for name in names:
        with open(os.path.join(log_dir, name)) as f:
            record = json.load(f)
        assert record["bbox"] + ".json" == name
        assert {"normalize-contrast", "inference", "crop-margin"} <= \
            set(record["timer"])


def test_the_spans_carry_the_task_and_the_thread(world):
    """``normalize-contrast`` stands in front of ``inference`` and runs
    on the scheduler's pump thread; the thumbnail is made on a thread of
    ``save-precomputed``'s own beside the result's write and written,
    like the log, on the main thread; all under the task's ``trace_id``,
    the log's write after the thumbnail's."""
    spans = world["spans"]
    tasks = {s["trace_id"] for s in spans if s["name"] == "queue/ack"}
    assert len(tasks) == 3
    where = {"normalize/levels": "scheduler-pump",
             "normalize/apply": "scheduler-pump",
             "thumbnail/quantize": "thumbnail_0",
             "thumbnail/downsample": "thumbnail_0",
             "thumbnail/write": "MainThread",
             "storage/log_write": "MainThread"}
    for name, thread in where.items():
        mine = [s for s in spans if s["name"] == name]
        assert len(mine) == 3 and {s["trace_id"] for s in mine} == tasks
        assert {s["thread"] for s in mine} == {thread}
    for s in spans:
        if s["name"] == "normalize/apply":
            assert s["device"] == 0 and s["voxels"] == 14 * 56 * 80
        if s["name"] == "thumbnail/downsample":
            assert s["device"] == 0 and s["levels"] == 3
        if s["name"] == "thumbnail/write":
            assert s["blocks"] == 21
            assert s["bytes"] == 12 * (24 * 36 + 12 * 18 + 6 * 9)
    for task in tasks:
        mine = {s["name"]: s for s in spans if s.get("trace_id") == task}
        assert mine["thumbnail/write"]["t"] <= mine["storage/log_write"]["t"]
        assert mine["storage/log_write"]["t"] <= mine["queue/ack"]["t"]


def test_nothing_of_the_chain_goes_up_for_the_operators_sake(world):
    """The sections are read once for the whole run, and the thumbnail
    uploads nothing: both operators meet host chunks in the worker
    chain."""
    counters = world["counters"]
    sections = GEOMETRY.size[0]
    assert counters["normalize/level_reads"] == sections
    assert counters["normalize/table_cache_hits"] == 2 * sections
    assert counters["thumbnail/h2d_bytes"] == 0
    assert counters["inference/tasks"] == 3
    built = {s.get("label") for s in world["spans"]
             if s["name"] == "compile_cache/build"}
    assert built and not built & {"thumbnail", "normalize_contrast"}


class _FailedWrite:
    def result(self):
        raise OSError("injected: the block did not reach the store")


@pytest.mark.parametrize("mode", ["sync", "async"])
def test_a_failed_thumbnail_write_leaves_the_task_unacked(tmp_path, mode,
                                                          monkeypatch):
    """No ack before the thumbnail is durable: with a write of one level
    of the second task failing (at the call, or under ``--async-write``
    at the barrier in front of the ack), that task stays in the queue
    and leaves no log; the task before it is committed whole."""
    g = GEOMETRY
    work = str(tmp_path)
    _, out_path, _ = make_volumes(work)
    save = PrecomputedVolume.save
    second = g.task_start(1)[2] // 4

    def failing(self, chunk, mip=0, wait=True, **kwargs):
        if (self.path.endswith("/thumbnail") and mip == 2
                and chunk.voxel_offset[2] == second):
            if wait:
                raise OSError("injected: the block did not reach the store")
            return _FailedWrite()
        return save(self, chunk, mip=mip, wait=wait, **kwargs)

    monkeypatch.setattr(PrecomputedVolume, "save", failing)
    with pytest.raises(OSError, match="injected"):
        run_chain(work, out_path,
                  *(["--async-write"] if mode == "async" else []))
    queue = os.path.join(work, "queue")
    assert len(os.listdir(os.path.join(queue, "claimed"))) >= 1
    logs = os.listdir(os.path.join(work, "out", "log"))
    first, failed = (
        BoundingBox.from_delta(g.task_start(i), g.task).string + ".json"
        for i in (0, 1))
    assert first in logs and failed not in logs
    left = sum(len(os.listdir(os.path.join(queue, sub)))
               for sub in ("pending", "claimed"))
    assert left == 2          # the failed task and the one behind it


def test_create_thumbnail_without_the_layer_names_setup_env(tmp_path):
    PrecomputedVolume.create(
        str(tmp_path / "out"), volume_size=(8, 16, 16), voxel_size=(1, 1, 1),
        dtype="float32", num_channels=3, block_size=(8, 8, 8))
    with pytest.raises(FileNotFoundError, match="setup-env"):
        program.chunkflow(
            "create-chunk", "--size", 8, 16, 16,
            "save-precomputed", "-v", str(tmp_path / "out"),
            "--create-thumbnail")


@pytest.mark.parametrize("wait", [True, False], ids=["sync", "async"])
def test_a_box_of_small_blocks_as_one_write_leaves_the_same_blocks(
        tmp_path, wait, monkeypatch):
    """``save(per_block=False)``, the thumbnail levels' path: the driver
    is handed the aligned box whole and leaves the files, bytes and
    read-back a future a block leaves; the span says ``whole``."""
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.configure(str(tmp_path / "metrics"))
    try:
        array = np.random.default_rng(2).integers(
            1, 255, (12, 24, 36), dtype=np.uint8)
        chunk = Chunk(array, voxel_offset=(1, 6, 9))
        roots = {}
        for per_block in (True, False):
            root = tmp_path / f"layer-{per_block}"
            layer = PrecomputedVolume.create(
                str(root), volume_size=(12, 48, 72), voxel_size=(1, 1, 1),
                voxel_offset=(1, 0, 0), block_size=(12, 6, 9))
            write = layer.save(chunk, wait=wait, per_block=per_block)
            assert (write is None) == wait
            if write is not None:
                write.result()
            back = layer.cutout(chunk.bbox, fill_missing=False)
            np.testing.assert_array_equal(np.asarray(back.array), array)
            key = layer.info["scales"][0]["key"]
            roots[per_block] = {
                name: (root / key / name).read_bytes()
                for name in os.listdir(root / key)}
            assert len(roots[per_block]) == layer.block_count(chunk.bbox) \
                == 16
        assert roots[True] == roots[False]
        telemetry.flush()
        spans, counters = read_stream(str(tmp_path / "metrics"))
        assert [s["mode"] for s in spans if s["name"] == "storage/write"] \
            == ["aligned", "whole"]
        assert counters["storage/aligned_writes"] == 2
    finally:
        telemetry.reset()


# ---- the device legs of the thumbnail ------------------------------------
@pytest.mark.parametrize("mode", ["xy", "z"])
def test_quantize_is_the_same_on_the_host_and_on_the_device(mode):
    rng = np.random.default_rng(4)
    array = (rng.random((3, 5, 12, 14), dtype=np.float32) * 1.2 - 0.1)
    chunk = AffinityMap(array, voxel_offset=(1, 2, 3))
    host, device = chunk.quantize(mode), chunk.device().quantize(mode)
    assert device.is_on_device and not host.is_on_device
    np.testing.assert_array_equal(np.asarray(device.array), host.array)
    source = array[1:3].mean(axis=0, dtype=np.float32) if mode == "xy" \
        else array[0]
    np.testing.assert_array_equal(
        host.array, np.clip(source * 255.0, 0, 255).astype(np.uint8))
    if mode == "xy":
        gap = np.abs(host.array.astype(int) - plain().grey(array))
        assert gap.max() <= 1
    assert tuple(host.voxel_offset) == (1, 2, 3)


@pytest.mark.parametrize("dtype", ["uint8", "int16", "float32"])
@pytest.mark.parametrize("factor, shape", [
    ((1, 2, 2), (6, 16, 24)), ((2, 2, 2), (2, 4, 9, 13)),
    ((1, 2, 2), (3, 7, 10))])
def test_the_pyramid_is_the_same_on_the_host_and_on_the_device(
        dtype, factor, shape):
    rng = np.random.default_rng(6)
    array = (rng.random(shape) * 200).astype(dtype)
    chunk = Chunk(array, voxel_offset=(2, 4, 6), voxel_size=(40, 4, 4))
    host = downsample_ops.average_pyramid(chunk, factor, 2)
    device = downsample_ops.average_pyramid(chunk.device(), factor, 2)
    for h, d in zip(host, device):
        assert d.is_on_device and not h.is_on_device
        np.testing.assert_array_equal(np.asarray(d.array), h.array)
        assert h.array.dtype == np.dtype(dtype)
        assert d.voxel_offset == h.voxel_offset
        assert d.voxel_size == h.voxel_size
    assert tuple(host[1].voxel_offset) == tuple(
        v // f // f for v, f in zip((2, 4, 6), factor))
    if dtype == "uint8" and factor == (1, 2, 2) and len(shape) == 3:
        want = plain().pooled(array[:, :shape[1] - shape[1] % 2,
                                    :shape[2] - shape[2] % 2])
        np.testing.assert_array_equal(host[0].array, want)


def test_the_thumbnail_programs_are_built_once_a_shape(tmp_path,
                                                       monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.configure(str(tmp_path))
    try:
        downsample_ops._PROGRAMS.clear()
        builds = downsample_ops._PROGRAMS.builds
        for seed in range(3):
            array = np.random.default_rng(seed).random(
                (3, 4, 16, 16), dtype=np.float32)
            grey = AffinityMap(array).device().quantize()
            levels_ = downsample_ops.average_pyramid(grey, (1, 2, 2), 3)
            assert [tuple(l.shape) for l in levels_] == [
                (4, 8, 8), (4, 4, 4), (4, 2, 2)]
        assert downsample_ops._PROGRAMS.builds - builds == 1
        entries = [e for e in profiling.catalog()
                   if e["label"] == "thumbnail"]
        assert sorted(e["calls"] for e in entries) == [3, 3]
        assert all(e["op_scopes"].get("thumbnail") for e in entries)
    finally:
        telemetry.reset()
        downsample_ops._PROGRAMS.clear()


# ---- the documents and the cell ------------------------------------------
def test_deploy_yml_carries_the_cells_chain():
    """distributed/kubernetes/deploy.yml's worker command: every command
    exists and parses, and the chain is the one the benchmark's chain
    cell runs (with upstream's masks around it)."""
    import yaml

    from chunkflow_tpu.flow import cli

    path = os.path.join(CHECKOUT, "distributed", "kubernetes", "deploy.yml")
    with open(path) as f:
        text = f.read()
    container = yaml.safe_load(text)["spec"]["template"]["spec"][
        "containers"][0]
    assert container["command"][-1] == "chunkflow_tpu.flow.cli"
    words = container["args"]
    starts = [i for i, w in enumerate(words) if w in cli.main.commands]
    chain = [words[i] for i in starts]
    assert [c for c in chain if c != "mask"] == [
        "fetch-task-from-queue", "load-precomputed", "normalize-contrast",
        "inference", "crop-margin", "save-precomputed",
        "delete-task-in-queue"]
    parsed = {}
    for i, j in zip(starts, starts[1:] + [len(words)]):
        parsed[words[i]] = cli.main.commands[words[i]].make_context(
            words[i], list(words[i + 1:j])).params
    assert parsed["normalize-contrast"]["levels_path"]
    assert parsed["save-precomputed"]["create_thumbnail"]
    assert parsed["save-precomputed"]["upload_log"]
    assert "setup-env" in text and "thumbnail" in text


def test_the_chain_traffic_is_what_its_file_says():
    """The anchor's task, a thumbnail of six levels in setup-env's
    blocks (1365 a task, 9.4 MB), every level's box on the layer's block
    grid; the rehearsal's three levels likewise."""
    with open(os.path.join(BENCH_DIR, "traffic", "volume-chain.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", "volume.json")) as f:
        anchor = json.load(f)
    for key in ("patch_grid", "margin", "block", "tasks", "warmup_tasks",
                "queue_depth", "async_depth", "trace", "env", "mesh"):
        assert traffic[key] == anchor[key]
    with open(os.path.join(BENCH_DIR, "configs",
                           "rsunet-superhuman.json")) as f:
        anchor_config = json.load(f)
    for key in ("model", "patch", "overlap", "batch", "engine", "args",
                "tolerance"):
        assert config()[key] == anchor_config[key]
    driver = chain_driver()
    for cfg, mix, blocks, nbytes in (
            (config(), traffic, 1365, 9434880),
            ({**config(), **config()["rehearse"]},
             {**traffic, **traffic["rehearse"]}, 21, 13608)):
        g = volume.Geometry(
            patch=tuple(cfg["patch"]), overlap=tuple(cfg["overlap"]),
            margin=tuple(mix["margin"]), block=tuple(mix["block"]),
            grid=tuple(mix["patch_grid"]), n_tasks=mix["tasks"])
        thumb = driver.Thumbnail(g, mix["thumbnail"]["mip"],
                                 tuple(mix["thumbnail"]["block"]))
        # setup-env's rule: the task over (1, 2**mip, 2**mip)
        cell = 2 ** thumb.mip
        assert thumb.block == (g.task[0], g.task[1] // cell,
                               g.task[2] // cell)
        count = size = 0
        for level in range(1, thumb.mip + 1):
            for index in (0, 1, g.n_tasks - 1):
                box = thumb.level_box(index, level)
                offset = [m // f for m, f in zip(
                    g.margin, (1, 2 ** level, 2 ** level))]
                assert all((a - o) % b == 0 and n % b == 0
                           for a, o, n, b in zip(box.start, offset,
                                                 box.shape, thumb.block))
            shape = tuple(thumb.level_box(0, level).shape)
            count += int(np.prod([n // b for n, b
                                  in zip(shape, thumb.block)]))
            size += int(np.prod(shape))
        assert (count, size) == (blocks, nbytes)
        lo, hi = thumb.check_box()
        assert all(b <= t for b, t in zip(hi, g.task))
    assert g.task == (12, 48, 72)
