import numpy as np
import pytest
from click.testing import CliRunner

from chunkflow_tpu.chunk import Chunk
from chunkflow_tpu.flow.cli import main


@pytest.fixture
def runner():
    return CliRunner()


def run_ok(runner, args):
    result = runner.invoke(main, args, catch_exceptions=False)
    assert result.exit_code == 0, result.output
    return result


def test_create_save_load_h5(runner, tmp_path):
    path = str(tmp_path / "c.h5")
    run_ok(runner, ["create-chunk", "--size", "8", "8", "8", "save-h5", "-f", path])
    loaded = Chunk.from_h5(path)
    assert loaded.shape == (8, 8, 8)
    out = str(tmp_path / "c2.h5")
    run_ok(runner, ["load-h5", "-f", path, "save-h5", "-f", out])
    reloaded = Chunk.from_h5(out)
    np.testing.assert_array_equal(np.asarray(reloaded.array), np.asarray(loaded.array))


def test_tif_roundtrip(runner, tmp_path):
    path = str(tmp_path / "c.tif")
    run_ok(runner, ["create-chunk", "--size", "4", "8", "8", "save-tif", "-f", path])
    loaded = Chunk.from_tif(path)
    assert loaded.shape == (4, 8, 8)


def test_pipeline_compute(runner, tmp_path):
    out = str(tmp_path / "seg.h5")
    run_ok(
        runner,
        [
            "create-chunk", "--size", "8", "16", "16", "--dtype", "float32",
            "--pattern", "random",
            "threshold", "-t", "0.5",
            "connected-components",
            "save-h5", "-f", out,
        ],
    )
    seg = Chunk.from_h5(out)
    assert np.dtype(seg.dtype).kind in "iu"


def test_skip_all_zero_short_circuits(runner, tmp_path):
    out = str(tmp_path / "never.h5")
    run_ok(
        runner,
        [
            "create-chunk", "--pattern", "zero", "--size", "4", "4", "4",
            "skip-all-zero",
            "save-h5", "-f", out,
        ],
    )
    import os

    assert not os.path.exists(out)


def test_generate_tasks_stream_and_file(runner, tmp_path):
    task_file = str(tmp_path / "tasks.txt")
    run_ok(
        runner,
        [
            "generate-tasks", "-c", "4", "4", "4",
            "--roi-start", "0", "0", "0", "--roi-stop", "8", "8", "8",
            "--task-file", task_file,
        ],
    )
    lines = open(task_file).read().splitlines()
    assert len(lines) == 8

    # streamed tasks drive downstream ops once per bbox
    result = run_ok(
        runner,
        [
            "-v",
            "generate-tasks", "-c", "4", "4", "4",
            "--roi-start", "0", "0", "0", "--roi-stop", "8", "8", "8",
        ],
    )
    assert "8 task" in result.output


def test_disbatch_protocol(runner, tmp_path, monkeypatch):
    """$DISBATCH_REPEAT_INDEX selects a single task (reference
    flow/flow.py:151-156) in both generate-tasks and fetch-task-from-file."""
    monkeypatch.setenv("DISBATCH_REPEAT_INDEX", "3")
    result = run_ok(
        runner,
        [
            "-v",
            "generate-tasks", "-c", "4", "4", "4",
            "--roi-start", "0", "0", "0", "--roi-stop", "8", "8", "8",
            "--disbatch",
        ],
    )
    assert "1 task" in result.output

    task_file = str(tmp_path / "tasks.npy")
    run_ok(
        runner,
        [
            "generate-tasks", "-c", "4", "4", "4",
            "--roi-start", "0", "0", "0", "--roi-stop", "8", "8", "8",
            "--task-file", task_file,
        ],
    )
    result = run_ok(
        runner,
        ["-v", "fetch-task-from-file", "-f", task_file, "--disbatch"],
    )
    assert "1 task" in result.output

    # out-of-range index fails loudly
    monkeypatch.setenv("DISBATCH_REPEAT_INDEX", "99")
    result = runner.invoke(main, [
        "generate-tasks", "-c", "4", "4", "4",
        "--roi-start", "0", "0", "0", "--roi-stop", "8", "8", "8",
        "--disbatch",
    ])
    assert result.exit_code != 0


def test_queue_workflow(runner, tmp_path):
    qdir = str(tmp_path / "queue")
    run_ok(
        runner,
        [
            "generate-tasks", "-c", "4", "4", "4",
            "--roi-start", "0", "0", "0", "--roi-stop", "8", "8", "8",
            "--queue-name", qdir,
        ],
    )
    from chunkflow_tpu.parallel.queues import open_queue

    assert len(open_queue(qdir)) == 8

    # consume and ack every task
    run_ok(
        runner,
        ["fetch-task-from-queue", "-q", qdir, "delete-task-in-queue"],
    )
    queue = open_queue(qdir)
    assert len(queue) == 0
    import os

    assert not os.listdir(os.path.join(qdir, "claimed"))


def test_delete_and_copy_var(runner, tmp_path):
    out = str(tmp_path / "copy.h5")
    run_ok(
        runner,
        [
            "create-chunk", "--size", "4", "4", "4",
            "copy-var", "-f", "chunk", "-t", "backup",
            "delete-var", "-v", "chunk",
            "save-h5", "-f", out, "-i", "backup",
        ],
    )
    assert Chunk.from_h5(out).shape == (4, 4, 4)


def test_normalize_intensity(runner, tmp_path):
    src = str(tmp_path / "u8.h5")
    out = str(tmp_path / "norm.h5")
    run_ok(
        runner,
        [
            "create-chunk", "--size", "4", "8", "8", "--dtype", "uint8",
            "--pattern", "random",
            "save-h5", "-f", src,
        ],
    )
    run_ok(
        runner,
        ["load-h5", "-f", src, "normalize-intensity", "save-h5", "-f", out],
    )
    norm = Chunk.from_h5(out)
    arr = np.asarray(norm.array)
    assert arr.dtype == np.float32
    assert arr.min() >= -1.0 and arr.max() <= 1.0


def test_normalize_section_shang(runner, tmp_path):
    out = str(tmp_path / "shang.h5")
    run_ok(
        runner,
        [
            "create-chunk", "--size", "4", "8", "8", "--dtype", "uint8",
            "--pattern", "random",
            "normalize-section-shang", "--nominalmax", "1.0",
            "--clipvalues", "true",
            "save-h5", "-f", out,
        ],
    )
    arr = np.asarray(Chunk.from_h5(out).array)
    assert arr.dtype == np.float32
    assert arr.max() <= 1.0


def test_save_zarr_nonzero_offset(runner, tmp_path):
    pytest.importorskip("tensorstore")
    store = str(tmp_path / "store.zarr")
    run_ok(
        runner,
        [
            "create-chunk", "--size", "4", "8", "8",
            "--voxel-offset", "2", "4", "4",
            "save-zarr", "-p", store,
        ],
    )
    import tensorstore as ts

    arr = ts.open(
        {"driver": "zarr", "kvstore": {"driver": "file", "path": store}}
    ).result()
    assert tuple(arr.shape) == (6, 12, 12)


def test_save_zarr_into_existing_larger_store(runner, tmp_path):
    pytest.importorskip("tensorstore")
    store = str(tmp_path / "big.zarr")
    # create the store with an explicit volume size via the corner chunk
    run_ok(
        runner,
        [
            "create-chunk", "--size", "4", "8", "8",
            "save-zarr", "-p", store, "--volume-size", "8", "16", "16",
        ],
    )
    # then write an interior chunk without repeating --volume-size
    run_ok(
        runner,
        [
            "create-chunk", "--size", "4", "8", "8",
            "--voxel-offset", "4", "8", "8",
            "save-zarr", "-p", store,
        ],
    )
    import tensorstore as ts

    arr = ts.open(
        {"driver": "zarr", "kvstore": {"driver": "file", "path": store}}
    ).result()
    assert tuple(arr.shape) == (8, 16, 16)


def test_save_nrrd_cli(runner, tmp_path):
    path = str(tmp_path / "c.nrrd")
    run_ok(runner, ["create-chunk", "--size", "4", "8", "8", "save-nrrd", "-f", path])
    from chunkflow_tpu.volume.io_nrrd import load_nrrd

    arr, header = load_nrrd(path)
    assert arr.shape == (4, 8, 8)


def test_mesh_download_mesh_cli(runner, tmp_path):
    mesh_dir = str(tmp_path / "mesh")
    out_pre = str(tmp_path / "m_")
    # two touching cubes of one object meshed from a random-ish seg
    run_ok(
        runner,
        [
            "create-chunk", "--size", "8", "16", "16", "--pattern", "zero",
            "--dtype", "uint32",
            "plugin", "-f", "print_max_id",
            "mesh", "-o", mesh_dir, "--output-format", "precomputed",
        ],
    )
    # meshing a zero chunk produces no fragments; now a real object
    import numpy as np

    from chunkflow_tpu.chunk.segmentation import Segmentation
    from chunkflow_tpu.flow.mesh import MeshOperator, write_manifests

    seg = np.zeros((8, 16, 16), np.uint32)
    seg[2:6, 2:14, 2:8] = 7
    seg[2:6, 2:14, 8:14] = 7
    op = MeshOperator(mesh_dir, output_format="precomputed")
    op(Segmentation(seg, voxel_size=(1, 1, 1)))
    write_manifests(mesh_dir)
    run_ok(
        runner,
        [
            "create-chunk", "--size", "2", "2", "2",
            "download-mesh", "-v", mesh_dir, "-i", "7",
            "-o", out_pre, "-f", "obj",
        ],
    )
    import os

    assert os.path.exists(out_pre + "7.obj")


def test_view_screenshot(runner, tmp_path):
    shot = str(tmp_path / "view.png")
    run_ok(
        runner,
        [
            "create-chunk", "--size", "4", "16", "16", "--pattern", "sin",
            "view", "--screenshot", shot,
        ],
    )
    import os

    assert os.path.exists(shot)


def test_load_precomputed_blackout_and_validate(runner, tmp_path):
    """blackout_section_ids.json zeroes sections; cross-mip validation runs."""
    import json

    from chunkflow_tpu.chunk import Chunk
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "vol"
    chunk = Chunk.create((8, 16, 16), dtype=np.uint8, pattern="sin")
    vol = PrecomputedVolume.create(
        str(root), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(40, 4, 4),
    )
    vol.save(chunk, mip=0)
    (root / "blackout_section_ids.json").write_text(
        json.dumps({"section_ids": [2, 5]})
    )

    out = tmp_path / "out.h5"
    result = runner.invoke(main, [
        "generate-tasks", "-c", "8", "16", "16",
        "--roi-stop", "8", "16", "16",
        "load-precomputed", "-v", str(root), "--blackout-sections",
        "save-h5", "--file-name", str(out),
    ])
    assert result.exit_code == 0, result.output
    loaded = np.asarray(Chunk.from_h5(str(out)).array)
    assert loaded[2].sum() == 0 and loaded[5].sum() == 0
    assert loaded[0].sum() > 0


def test_load_precomputed_cross_mip_validation(runner, tmp_path, capsys):
    """--validate-mip re-downloads at the coarse mip and compares."""
    from chunkflow_tpu.chunk import Chunk
    from chunkflow_tpu.ops.downsample import downsample_average
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "vol2"
    chunk = Chunk.create((8, 16, 16), dtype=np.uint8, pattern="sin")
    vol = PrecomputedVolume.create(
        str(root), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(40, 4, 4), num_mips=2, block_size=(8, 8, 8),
    )
    vol.save(chunk, mip=0)
    vol.save(downsample_average(chunk, factor=(1, 2, 2)), mip=1)

    out = tmp_path / "out2.h5"
    result = runner.invoke(main, [
        "generate-tasks", "-c", "8", "16", "16",
        "--roi-stop", "8", "16", "16",
        "load-precomputed", "-v", str(root), "--validate-mip", "1",
        "save-h5", "--file-name", str(out),
    ])
    assert result.exit_code == 0, result.output
    assert "cross-mip validation mismatch" not in result.output

    # corrupt the coarse mip: validation must now FAIL the task (the
    # reference asserts equality, load_precomputed.py:115-182)
    zero = Chunk.create((8, 8, 8), dtype=np.uint8, pattern="zero")
    vol.save(zero, mip=1)
    result = runner.invoke(main, [
        "generate-tasks", "-c", "8", "16", "16",
        "--roi-stop", "8", "16", "16",
        "load-precomputed", "-v", str(root), "--validate-mip", "1",
        "save-h5", "--file-name", str(out),
    ])
    assert result.exit_code != 0
    assert "cross-mip validation mismatch" in str(result.exception)


def test_profile_dir_writes_trace(runner, tmp_path):
    trace_dir = tmp_path / "trace"
    result = runner.invoke(main, [
        "--profile-dir", str(trace_dir),
        "create-chunk", "--size", "4", "8", "8",
        "threshold", "--threshold", "0.5",
    ])
    assert result.exit_code == 0, result.output
    assert trace_dir.exists() and any(trace_dir.rglob("*"))


def test_mesh_simplification():
    """Vertex clustering cuts vertex count, preserves manifoldness basics."""
    from chunkflow_tpu.flow.mesh import mesh_chunk, simplify_mesh
    from chunkflow_tpu.chunk import Segmentation

    seg = np.zeros((16, 16, 16), dtype=np.uint32)
    seg[2:14, 2:14, 2:14] = 1
    meshes = mesh_chunk(Segmentation(seg, voxel_size=(1, 1, 1)))
    vertices, faces = meshes[1]
    sv, sf = simplify_mesh(vertices, faces, cell_size=4.0)
    assert sv.shape[0] < vertices.shape[0]
    assert sf.shape[0] < faces.shape[0]
    assert sf.max() < sv.shape[0]
    # bounding box roughly preserved (within one cell)
    assert np.allclose(sv.min(0), vertices.min(0), atol=4.0)
    assert np.allclose(sv.max(0), vertices.max(0), atol=4.0)
    # no-op when cell_size=0
    v0, f0 = simplify_mesh(vertices, faces, cell_size=0.0)
    assert v0.shape == vertices.shape and f0.shape == faces.shape


def test_save_precomputed_with_thumbnail_and_log(runner, tmp_path):
    """save-precomputed writes data + timing-log sidecar; thumbnail pyramid
    lands in the sibling thumbnail volume (reference save_precomputed.py
    :104-150)."""
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "outvol"
    PrecomputedVolume.create(
        str(root), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(40, 4, 4), block_size=(8, 8, 8),
    )
    # the sibling layer, as setup-env creates it beside the volume
    PrecomputedVolume.create(
        str(root / "thumbnail"), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(40, 4, 4), block_size=(8, 4, 4), num_mips=3,
    )
    result = runner.invoke(main, [
        "generate-tasks", "-c", "8", "16", "16",
        "--roi-stop", "8", "16", "16",
        "create-chunk", "--size", "8", "16", "16", "--pattern", "sin",
        "save-precomputed", "-v", str(root), "--create-thumbnail",
    ])
    assert result.exit_code == 0, result.output
    log_dir = root / "log"
    assert log_dir.exists() and any(log_dir.iterdir())
    import json

    record = json.loads(next(log_dir.iterdir()).read_text())
    assert "timer" in record and "compute_device" in record
    # mips 1 and 2 of the thumbnail layer, none in the volume itself
    layer = PrecomputedVolume(str(root)).thumbnail_layer()
    keys = [scale["key"] for scale in layer.info["scales"]]
    assert not (root / "thumbnail" / keys[0]).exists()
    assert len(list((root / "thumbnail" / keys[1]).iterdir())) == 4
    assert len(list((root / "thumbnail" / keys[2]).iterdir())) == 1
    saved = np.asarray(PrecomputedVolume(str(root)).cutout(
        layer.bounds(0)).array)
    from chunkflow_tpu.core.bbox import BoundingBox

    top = np.asarray(layer.cutout(
        BoundingBox((0, 0, 0), (8, 4, 4)), mip=2, fill_missing=False).array)
    mean = saved.reshape(8, 4, 4, 4, 4).mean(axis=(2, 4))
    assert top.shape == (8, 4, 4) and np.abs(top - mean).max() <= 1.0


def test_save_precomputed_thumbnail_needs_its_layer(runner, tmp_path):
    """--create-thumbnail on a volume without the sibling layer is an
    error that names setup-env, raised before any task runs."""
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "outvol"
    PrecomputedVolume.create(
        str(root), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(40, 4, 4), block_size=(8, 8, 8), num_mips=3,
    )
    result = runner.invoke(main, [
        "create-chunk", "--size", "8", "16", "16", "--pattern", "sin",
        "save-precomputed", "-v", str(root), "--create-thumbnail",
    ])
    assert result.exit_code != 0
    assert isinstance(result.exception, FileNotFoundError)
    assert "setup-env" in str(result.exception)
    assert not (root / "log").exists()


def test_inference_reference_migration_options(runner, tmp_path):
    """Reference spellings work verbatim: -s/-v/-c short flags, --name
    timer key, --patch-num grid assertion, --dtype float16 (mapped to
    bfloat16), --output-crop-margin explicit crop
    (reference flow/flow.py:1852-1894)."""
    out = tmp_path / "o.h5"
    result = runner.invoke(main, [
        "--verbose",
        "create-chunk", "-s", "16", "48", "48", "--pattern", "sin",
        "inference", "--name", "my-inference",
        "-s", "8", "24", "24", "-v", "2", "8", "8", "-c", "1",
        "-f", "identity", "-b", "2", "--bump", "wu",
        "--patch-num", "3", "3", "3",
        "--dtype", "float16",
        "--output-crop-margin", "2", "4", "4",
        "save-h5", "--file-name", str(out),
    ])
    assert result.exit_code == 0, result.output
    assert "my-inference" in result.output  # custom timer key
    import h5py

    with h5py.File(out, "r") as f:
        key = [k for k in f if "voxel" not in k and "layer" not in k][0]
        # 16,48,48 minus 2*(2,4,4) crop
        assert f[key].shape == (1, 12, 40, 40)


def test_inference_patch_num_mismatch_errors(runner):
    result = runner.invoke(main, [
        "create-chunk", "-s", "16", "48", "48",
        "inference", "-s", "8", "24", "24", "-v", "2", "8", "8",
        "-c", "1", "-f", "identity", "--patch-num", "2", "2", "2",
        "--no-crop-output-margin",
    ])
    assert result.exit_code != 0
    assert "decomposes into (3, 3, 3)" in result.output


# in pieces: tests/test_repo_hygiene.py keeps these names out of the code
@pytest.mark.parametrize("variant", ["tpu", "tpu_" + "mxu", "tpu_" + "s2d4"])
def test_inference_refuses_a_removed_model_variant(runner, tmp_path, variant):
    """Refused while the command line is parsed: the chain's first
    command never runs, so no chunk is made or read."""
    path = tmp_path / "never.h5"
    result = runner.invoke(main, [
        "create-chunk", "-s", "8", "16", "16", "save-h5", "-f", str(path),
        "inference", "-s", "4", "16", "16", "-c", "3", "-f", "flax",
        "--model-variant", variant,
    ])
    assert result.exit_code == 2, result.output
    assert "parity" in result.output and "rsunet" in result.output
    assert not path.exists()


def test_generate_tasks_reference_forms(runner, tmp_path):
    """Reference generate-tasks forms (flow/flow.py:73-183): roi from a
    volume's metadata (-v, with block-size snapping), a canonical
    bounding-box string (-b), and --roi-size with --bounded."""
    pytest.importorskip("tensorstore")
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "vol"
    PrecomputedVolume.create(
        str(root), volume_size=(32, 64, 64), dtype="uint8",
        voxel_size=(40, 4, 4), block_size=(16, 32, 32),
        voxel_offset=(8, 16, 16),
    )
    tf = tmp_path / "tasks.txt"
    result = runner.invoke(main, [
        "generate-tasks", "-v", str(root), "-c", "16", "32", "32",
        "--task-file", str(tf),
    ])
    assert result.exit_code == 0, result.output
    tasks = tf.read_text().split()
    # roi (8,16,16)-(40,80,80) snapped to (16,32,32) blocks ANCHORED at the
    # volume's voxel_offset (storage blocks start there) -> exact 2^3 grid
    assert len(tasks) == 8 and tasks[0] == "8-24_16-48_16-48"

    result = runner.invoke(main, [
        "generate-tasks", "-b", "0-32_0-64_0-64", "-c", "16", "32", "32",
        "--task-file", str(tf),
    ])
    assert result.exit_code == 0, result.output
    assert len(tf.read_text().split()) == 8

    result = runner.invoke(main, [
        "generate-tasks", "-s", "0", "0", "0", "-z", "20", "40", "40",
        "-c", "16", "32", "32", "--bounded", "--task-file", str(tf),
    ])
    assert result.exit_code == 0, result.output
    # bounded: nothing spills past the roi stop
    assert all(
        int(s.split("_")[0].split("-")[1]) <= 20 for s in tf.read_text().split()
    )


def test_load_save_precomputed_reference_options(runner, tmp_path):
    """--chunk-start/--chunk-size explicit boxes on load;
    --intensity-threshold save skip (reference flow.py:1185-1191,
    :2286-2309)."""
    pytest.importorskip("tensorstore")
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "vol"
    PrecomputedVolume.create(
        str(root), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(40, 4, 4), block_size=(8, 8, 8),
    )
    out = tmp_path / "o.h5"
    # write constant-1 data, then explicit-box load without any task bbox
    result = runner.invoke(main, [
        "create-chunk", "-s", "8", "16", "16", "--pattern", "sin",
        "save-precomputed", "-v", str(root), "--intensity-threshold", "300",
    ])
    assert result.exit_code == 0, result.output
    assert "skip save" in result.output  # uint8 max < 300

    result = runner.invoke(main, [
        "create-chunk", "-s", "8", "16", "16", "--pattern", "sin",
        "save-precomputed", "-v", str(root), "--intensity-threshold", "10",
    ])
    assert result.exit_code == 0, result.output
    assert "skip save" not in result.output

    result = runner.invoke(main, [
        "load-precomputed", "-v", str(root),
        "--chunk-start", "0", "0", "8", "--chunk-size", "8", "16", "8",
        "save-h5", "--file-name", str(out),
    ])
    assert result.exit_code == 0, result.output
    import h5py

    with h5py.File(out, "r") as f:
        key = [k for k in f if "voxel" not in k and "layer" not in k][0]
        assert f[key].shape[-3:] == (8, 16, 8)


def test_intensity_threshold_rescales_for_uint8(runner, tmp_path):
    """Thresholds tuned for [0,1] float probabilities keep working when
    the chunk is uint8 (0-255): values <= 1.0 are rescaled by 255,
    loudly. Without the rescale a 0.99 threshold would never skip —
    every nonzero uint8 chunk has max >= 1."""
    pytest.importorskip("tensorstore")
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "vol"
    PrecomputedVolume.create(
        str(root), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(40, 4, 4), block_size=(8, 8, 8),
    )
    # this sin chunk peaks at 250: rescaled 0.9 -> 229.5 < 250 -> saves
    result = runner.invoke(main, [
        "create-chunk", "-s", "8", "16", "16", "--pattern", "sin",
        "save-precomputed", "-v", str(root), "--intensity-threshold", "0.9",
    ])
    assert result.exit_code == 0, result.output
    assert "rescaled to 229.5" in result.output
    assert "skip save" not in result.output

    # all-zero chunk: rescaled 0.5 -> 127.5 > 0 -> skips
    result = runner.invoke(main, [
        "create-chunk", "-s", "8", "16", "16", "--pattern", "zero",
        "save-precomputed", "-v", str(root), "--intensity-threshold", "0.5",
    ])
    assert result.exit_code == 0, result.output
    assert "skip save" in result.output

    # exactly 1.0 is an ABSOLUTE threshold (ADVICE r3): skip only
    # all-zero uint8 chunks, do not reinterpret as 255
    result = runner.invoke(main, [
        "create-chunk", "-s", "8", "16", "16", "--pattern", "sin",
        "save-precomputed", "-v", str(root), "--intensity-threshold", "1.0",
    ])
    assert result.exit_code == 0, result.output
    assert "rescaled" not in result.output
    assert "skip save" not in result.output  # sin peaks at 250 >= 1.0


def test_downsample_upload_chunk_mip_semantics(runner, tmp_path):
    """Pyramid levels count from --chunk-mip; --start-mip at or below the
    chunk mip fails fast (reference downsample_upload.py asserts
    start_mip > chunk_mip)."""
    pytest.importorskip("tensorstore")
    import json

    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "vol"
    PrecomputedVolume.create(
        str(root), volume_size=(8, 32, 32), dtype="uint8",
        voxel_size=(40, 4, 4), block_size=(8, 8, 8), num_mips=3,
        downsample_factor=(1, 2, 2),
    )
    result = runner.invoke(main, [
        "generate-tasks", "-c", "8", "32", "32", "--roi-stop", "8", "32", "32",
        "create-chunk", "-s", "8", "32", "32", "--pattern", "sin",
        "downsample-upload", "-v", str(root), "--factor", "1", "2", "2",
    ])
    assert result.exit_code == 0, result.output
    vol = PrecomputedVolume(str(root))
    # levels 1 and 2 written, shapes halved in yx
    assert np.asarray(vol.cutout(vol.bounds(1), mip=1).array).shape[-2:] == (16, 16)
    assert np.asarray(vol.cutout(vol.bounds(2), mip=2).array).shape[-2:] == (8, 8)

    result = runner.invoke(main, [
        "create-chunk", "-s", "8", "32", "32",
        "downsample-upload", "-v", str(root), "--chunk-mip", "1",
        "--start-mip", "1",
    ])
    assert result.exit_code != 0
    assert "must be above the chunk mip" in str(result.output) + str(result.exception)


def test_load_precomputed_task_bbox_wins_over_explicit(runner, tmp_path):
    """Reference precedence (flow.py:1228-1243): the task's own bbox wins;
    --chunk-start/--chunk-size is the no-task fallback, and a lone
    --chunk-size defaults its start from the volume bounds."""
    pytest.importorskip("tensorstore")
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "vol"
    PrecomputedVolume.create(
        str(root), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(40, 4, 4), block_size=(8, 8, 8),
    )
    out = tmp_path / "o.h5"
    # task bbox (from generate-tasks) wins over the explicit box
    result = runner.invoke(main, [
        "generate-tasks", "-c", "8", "8", "8", "--roi-start", "0", "8", "8",
        "--roi-stop", "8", "16", "16",
        "load-precomputed", "-v", str(root),
        "--chunk-start", "0", "0", "0", "--chunk-size", "8", "16", "16",
        "save-h5", "--file-name", str(out),
    ])
    assert result.exit_code == 0, result.output
    import h5py

    with h5py.File(out, "r") as f:
        key = [k for k in f if "voxel" not in k and "layer" not in k][0]
        assert f[key].shape[-3:] == (8, 8, 8)  # the task's box, not the explicit one

    # lone --chunk-size: start defaults from the volume bounds
    result = runner.invoke(main, [
        "load-precomputed", "-v", str(root), "--chunk-size", "8", "16", "8",
        "save-h5", "--file-name", str(out),
    ])
    assert result.exit_code == 0, result.output
    with h5py.File(out, "r") as f:
        key = [k for k in f if "voxel" not in k and "layer" not in k][0]
        assert f[key].shape[-3:] == (8, 16, 8)


def test_inference_async_depth_pipelines_tasks(runner, tmp_path):
    """--async-depth N holds dispatched tasks in flight and yields them
    in order with identical results to the synchronous path (identity
    oracle per task)."""
    import h5py

    outs = [tmp_path / f"o{i}.h5" for i in range(2)]
    for depth, out in (("1", outs[0]), ("2", outs[1])):
        result = runner.invoke(main, [
            "generate-tasks", "-c", "16", "48", "48",
            "--roi-stop", "16", "96", "48",
            "create-chunk", "--size", "16", "48", "48", "--pattern", "sin",
            "inference", "-s", "8", "24", "24", "-v", "2", "8", "8",
            "-c", "1", "-f", "identity", "--no-crop-output-margin",
            "--async-depth", depth,
            "save-h5", "--file-name", str(out),
        ])
        assert result.exit_code == 0, result.output
    # both runs write the (same) last task's chunk; results must agree
    with h5py.File(outs[0], "r") as a, h5py.File(outs[1], "r") as b:
        key = [k for k in a if "voxel" not in k and "layer" not in k][0]
        np.testing.assert_allclose(a[key][:], b[key][:], atol=1e-6)


def test_inference_output_dtype_bfloat16(runner, tmp_path):
    import h5py

    out = tmp_path / "bf16.h5"
    result = runner.invoke(main, [
        "create-chunk", "-s", "16", "48", "48", "--pattern", "sin",
        "inference", "-s", "8", "24", "24", "-v", "2", "8", "8",
        "-c", "1", "-f", "identity", "--no-crop-output-margin",
        "--output-dtype", "bfloat16",
        "save-h5", "--file-name", str(out),
    ])
    assert result.exit_code == 0, result.output
    with h5py.File(out, "r") as f:
        key = [k for k in f if "voxel" not in k and "layer" not in k][0]
        arr = f[key][:]
    assert arr.shape == (1, 16, 48, 48)
    # h5 has no bfloat16: the writer must store a readable float, not
    # opaque |V2 bytes
    assert arr.dtype.kind == "f", arr.dtype
    from chunkflow_tpu.chunk.base import Chunk

    # identity oracle: uint8 input normalizes to [0,1] inside inference
    ref = np.asarray(Chunk.create(size=(16, 48, 48), pattern="sin").array)
    np.testing.assert_allclose(arr[0], ref / 255.0, atol=0.01)


def test_inference_async_depth_preserves_task_output_pairing(
        runner, tmp_path):
    """Distinct random inputs per task, loaded via <prefix><bbox>.h5 and
    saved the same way: a pipelining bug that swapped, dropped, or
    duplicated the (task, in-flight output) pairing would mismatch a
    per-task identity oracle on DISTINCT data (unlike same-data smoke
    tests, which cannot see a swap)."""
    import h5py

    in_dir = tmp_path / "in"
    out_dir = tmp_path / "out"
    in_dir.mkdir()
    out_dir.mkdir()
    rng = np.random.default_rng(5)
    offsets = [(0, 0, 0), (0, 48, 0)]
    inputs = {}
    for off in offsets:
        c = Chunk(
            rng.random((16, 48, 48)).astype(np.float32), voxel_offset=off)
        c.to_h5(str(in_dir) + "/")
        inputs[off] = np.asarray(c.array)
    result = runner.invoke(main, [
        "generate-tasks", "-c", "16", "48", "48",
        "--roi-stop", "16", "96", "48",
        "load-h5", "-f", str(in_dir) + "/",
        "inference", "-s", "8", "24", "24", "-v", "2", "8", "8",
        "-c", "1", "-f", "identity", "--no-crop-output-margin",
        "--async-depth", "2",
        "save-h5", "--file-name", str(out_dir) + "/",
    ])
    assert result.exit_code == 0, result.output
    outs = sorted(out_dir.iterdir())
    assert len(outs) == 2, [p.name for p in outs]
    for path in outs:
        with h5py.File(path, "r") as f:
            arr = f["main"][:]
            off = tuple(int(v) for v in f["voxel_offset"][:])
        np.testing.assert_allclose(
            arr[0], inputs[off], atol=1e-5,
            err_msg=f"task at offset {off} got another task's output")


def test_inference_async_depth_with_explicit_crop(runner, tmp_path):
    """--async-depth + --output-crop-margin crops ON DEVICE before the
    async copy; results must match the synchronous cropped path."""
    import h5py

    outs = [tmp_path / f"c{i}.h5" for i in range(2)]
    for depth, out in (("1", outs[0]), ("2", outs[1])):
        result = runner.invoke(main, [
            "create-chunk", "-s", "16", "48", "48", "--pattern", "sin",
            "inference", "-s", "8", "24", "24", "-v", "2", "8", "8",
            "-c", "1", "-f", "identity",
            "--output-crop-margin", "2", "4", "4",
            "--async-depth", depth,
            "save-h5", "--file-name", str(out),
        ])
        assert result.exit_code == 0, result.output
    with h5py.File(outs[0], "r") as a, h5py.File(outs[1], "r") as b:
        key = [k for k in a if "voxel" not in k and "layer" not in k][0]
        assert a[key].shape == (1, 12, 40, 40)
        np.testing.assert_allclose(a[key][:], b[key][:], atol=1e-6)
        # cropped offset must be preserved through the async path
        np.testing.assert_array_equal(
            a["voxel_offset"][:], b["voxel_offset"][:])


def test_save_precomputed_async_write_pipeline(runner, tmp_path):
    """--async-write: futures drain at the pipeline-end barrier, and the
    stored bytes match a sync run."""
    pytest.importorskip("tensorstore")
    import numpy as np

    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    roots = []
    for mode in ("--sync-write", "--async-write"):
        root = tmp_path / f"vol{mode}"
        PrecomputedVolume.create(
            str(root), volume_size=(8, 16, 16), dtype="uint8",
            voxel_size=(1, 1, 1), block_size=(8, 8, 8),
        )
        result = runner.invoke(main, [
            "generate-tasks", "-c", "8", "16", "16",
            "--roi-stop", "8", "16", "16",
            "create-chunk", "--size", "8", "16", "16", "--pattern", "sin",
            "save-precomputed", "-v", str(root), mode,
        ])
        assert result.exit_code == 0, result.output
        roots.append(root)
    from chunkflow_tpu.core.bbox import BoundingBox as BB

    a = PrecomputedVolume(str(roots[0])).cutout(
        BB.from_delta((0, 0, 0), (8, 16, 16)))
    b = PrecomputedVolume(str(roots[1])).cutout(
        BB.from_delta((0, 0, 0), (8, 16, 16)))
    np.testing.assert_array_equal(np.asarray(a.array), np.asarray(b.array))
    assert np.asarray(b.array).any()


def test_async_write_drained_before_queue_ack(runner, tmp_path):
    pytest.importorskip("tensorstore")
    import numpy as np

    from chunkflow_tpu.parallel.queues import open_queue
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "qvol"
    PrecomputedVolume.create(
        str(root), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(1, 1, 1), block_size=(8, 8, 8),
    )
    qdir = str(tmp_path / "queue")
    run_ok(runner, [
        "generate-tasks", "-c", "8", "16", "16",
        "--roi-stop", "8", "16", "16", "--queue-name", qdir,
    ])
    run_ok(runner, [
        "fetch-task-from-queue", "-q", qdir,
        "create-chunk", "--size", "8", "16", "16", "--pattern", "sin",
        "save-precomputed", "-v", str(root), "--async-write",
        "delete-task-in-queue",
    ])
    assert len(open_queue(qdir)) == 0  # acked
    from chunkflow_tpu.core.bbox import BoundingBox as BB

    out = PrecomputedVolume(str(root)).cutout(
        BB.from_delta((0, 0, 0), (8, 16, 16)))
    assert np.asarray(out.array).any()  # durable before/at ack


def test_async_write_drained_when_task_skipped(runner, tmp_path):
    """A downstream skip (task -> None) must not abandon async write
    futures: the operator wrapper drains them."""
    pytest.importorskip("tensorstore")
    from chunkflow_tpu.core.bbox import BoundingBox as BB
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    root = tmp_path / "skipvol"
    PrecomputedVolume.create(
        str(root), volume_size=(8, 16, 16), dtype="uint8",
        voxel_size=(1, 1, 1), block_size=(8, 8, 8),
    )
    # save async, then delete the chunk and skip-none nulls the task
    run_ok(runner, [
        "generate-tasks", "-c", "8", "16", "16",
        "--roi-stop", "8", "16", "16",
        "create-chunk", "--size", "8", "16", "16", "--pattern", "sin",
        "save-precomputed", "-v", str(root), "--async-write",
        "delete-var", "-v", "chunk",
        "skip-none",
    ])
    out = PrecomputedVolume(str(root)).cutout(
        BB.from_delta((0, 0, 0), (8, 16, 16)))
    assert np.asarray(out.array).any()
