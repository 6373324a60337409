"""The flax engine with an output patch smaller than its input patch (the
upstream production deployment's geometry) through ``Inferencer``, against
the benchmark's plain reference: ``benchmarks/reference/rsunet_crop.py``
(float32 ``highest`` forward, then the central crop) blended in numpy
float64 by ``benchmarks/cfbench/crop_blend.py``, which shares no code with
the program. Small sizes, seeded random weights, CPU."""
import os
import re
import shlex
import sys

import numpy as np
import pytest

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import profiling, telemetry
from chunkflow_tpu.inference import Inferencer
from chunkflow_tpu.inference.engines import create_flax_engine

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(CHECKOUT, "benchmarks")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from cfbench import catalog, crop_blend  # noqa: E402

PIN, POUT, OVERLAP = (8, 32, 32), (4, 16, 16), (2, 8, 8)
MARGIN = (2, 8, 8)
ALIGNED = (10, 40, 40)      # 2x2x2 patches on the stride 2x8x8
SNAPPED = (11, 42, 45)      # 3x3x3, the last of each axis snapped flush
# a margin wide enough that the RSUNet leaves part of level 1 out as well
# (models/rsunet.py decoder_cone); 2x2x2 patches on the stride 2x24x24
PIN_WIDE, POUT_WIDE, MARGIN_WIDE = (8, 80, 80), (4, 32, 32), (2, 24, 24)
ALIGNED_WIDE = (10, 104, 104)


def make_inferencer(**kwargs):
    defaults = dict(
        input_patch_size=PIN, output_patch_size=POUT,
        output_patch_overlap=OVERLAP, num_output_channels=4,
        framework="flax", model_variant="rsunet", batch_size=3)
    defaults.update(kwargs)
    return Inferencer(**defaults)


def image(shape, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, shape, dtype=np.uint8)


def plain_output(inferencer, array, pout=POUT, pin=PIN):
    """The blended output over the chunk's whole output frame, from the
    plain reference given the engine's own parameters."""
    reference = catalog.load_module("reference", "rsunet_crop")
    forward = reference.make_forward(
        {"model": {"final_activation": "sigmoid"}, "output_patch": pout})
    params = inferencer.engine.params

    def one_patch(window):
        out = forward(params, window[None, ..., None])
        return np.moveaxis(np.asarray(out[0]), -1, 0)

    margin = [(i - o) // 2 for i, o in zip(pin, pout)]
    box = (tuple(margin),
           tuple(s - m for s, m in zip(array.shape, margin)))
    return crop_blend.blend_box(array, pin, pout, OVERLAP, box, one_patch)


@pytest.mark.parametrize("channels, dtype, shape, batch", [
    (1, "float32", ALIGNED, 4),     # 8 patches, 2 batches
    (4, "float32", ALIGNED, 3),     # 8 patches, a padded last batch
    (4, "float32", SNAPPED, 9),     # 27 patches, 3 batches
    (1, "float32", SNAPPED, 6),     # 27 patches, padded
    (4, "bfloat16", ALIGNED, 8),
    (1, "bfloat16", ALIGNED, 5),
    (1, "bfloat16", SNAPPED, 3),
    (4, "bfloat16", SNAPPED, 4),
])
def test_flax_engine_with_a_cropped_output_patch_equals_the_reference(
        channels, dtype, shape, batch):
    inferencer = make_inferencer(num_output_channels=channels, dtype=dtype,
                                 batch_size=batch)
    array = image(shape, seed=channels + batch)
    out = inferencer(Chunk(array, voxel_offset=(7, 5, 3)))
    want, n_patches = plain_output(inferencer, array)
    assert n_patches == (8 if shape == ALIGNED else 27)
    got = np.asarray(out.array, np.float64)
    assert got.shape == want.shape == (channels,) + tuple(
        s - 2 * m for s, m in zip(shape, MARGIN))
    assert tuple(out.voxel_offset) == (7 + 2, 5 + 8, 3 + 8)
    assert want.std() > 1e-3
    gap = np.abs(got - want)
    # float32 on the CPU: rounding only. bfloat16 activations: 8 bits
    max_bound, mean_bound = ((5e-5, 5e-6) if dtype == "float32"
                             else (8e-3, 1e-3))
    assert gap.max() < max_bound and gap.mean() < mean_bound
    # a patch put one output stride off, or not cropped, reads ~0.05


@pytest.mark.parametrize("channels, dtype, batch", [
    (3, "float32", 4), (1, "bfloat16", 3), (4, "bfloat16", 8)])
def test_a_cone_that_cuts_two_levels_equals_the_reference(
        channels, dtype, batch):
    """As above at 8x80x80 -> 4x32x32: the decoder's levels 0 and 1 run
    on the output patch's cone of dependence only."""
    inferencer = make_inferencer(
        input_patch_size=PIN_WIDE, output_patch_size=POUT_WIDE,
        num_output_channels=channels, dtype=dtype, batch_size=batch)
    array = image(ALIGNED_WIDE, seed=channels)
    out = inferencer(Chunk(array))
    want, n_patches = plain_output(inferencer, array, POUT_WIDE, PIN_WIDE)
    assert n_patches == 8
    got = np.asarray(out.array, np.float64)
    assert got.shape == want.shape == (channels,) + tuple(
        s - 2 * m for s, m in zip(ALIGNED_WIDE, MARGIN_WIDE))
    assert tuple(out.voxel_offset) == MARGIN_WIDE
    assert want.std() > 1e-3
    gap = np.abs(got - want)
    max_bound, mean_bound = ((5e-5, 5e-6) if dtype == "float32"
                             else (8e-3, 1e-3))
    assert gap.max() < max_bound and gap.mean() < mean_bound


def test_lower_precision_fails_the_float32_bounds():
    """The bounds above tell the two precisions apart: bfloat16
    activations read well over what float32 is held to."""
    inferencer = make_inferencer(dtype="bfloat16", num_output_channels=1)
    array = image(ALIGNED, seed=3)
    got = np.asarray(inferencer(Chunk(array)).array, np.float64)
    want, _ = plain_output(inferencer, array)
    assert np.abs(got - want).mean() > 5e-6


@pytest.mark.parametrize("crop_output_margin", [True, False])
def test_identity_and_flax_engines_agree_on_geometry(crop_output_margin):
    array = image(SNAPPED)
    boxes = []
    for framework in ("identity", "flax"):
        inferencer = make_inferencer(
            framework=framework, num_output_channels=1,
            crop_output_margin=crop_output_margin)
        out = inferencer(Chunk(array, voxel_offset=(1, 2, 3)))
        boxes.append((tuple(out.shape), tuple(out.voxel_offset),
                      inferencer.patch_grid_shape(array.shape)))
    assert boxes[0] == boxes[1]
    assert boxes[0][2] == (3, 3, 3)
    # the identity engine gives the input back inside the output frame
    identity = make_inferencer(framework="identity", num_output_channels=1)
    out = np.asarray(identity(Chunk(array)).array)
    inner = array[2:-2, 8:-8, 8:-8].astype(np.float32) / 255
    assert np.allclose(out[0], inner, atol=1e-6)


def test_equal_sizes_crop_nothing_bit_for_bit():
    """``m = 0`` is the same call: the engine's jaxpr is the one it was
    without the keyword, and ``Inferencer`` gives the same bits."""
    import jax
    import jax.numpy as jnp

    was = create_flax_engine("", None, PIN, 1, 3, model_variant="rsunet")
    now = create_flax_engine("", None, PIN, 1, 3, model_variant="rsunet",
                             output_patch_size=PIN)
    x = jnp.zeros((2, 1) + PIN, jnp.float32)
    assert str(jax.make_jaxpr(was.apply)(was.params, x)) == \
        str(jax.make_jaxpr(now.apply)(now.params, x))

    array = image(SNAPPED, seed=1)
    outs = []
    for size in (None, PIN):
        inferencer = make_inferencer(output_patch_size=size,
                                     num_output_channels=3)
        outs.append(np.asarray(inferencer(Chunk(array)).array))
    assert outs[0].shape == (3,) + SNAPPED
    assert np.array_equal(outs[0], outs[1])
    # and it is the uncropped reference's blend
    want, _ = plain_output(make_inferencer(output_patch_size=PIN,
                                           num_output_channels=3),
                           array, pout=PIN)
    assert np.abs(outs[0] - want).max() < 5e-5


@pytest.mark.parametrize("pout", [(5, 16, 16), (4, 16, 34), (8, 32, 31)])
def test_odd_or_negative_difference_raises_at_construction(pout):
    with pytest.raises(ValueError) as raised:
        create_flax_engine("", None, PIN, 1, 3, model_variant="rsunet",
                           output_patch_size=pout)
    assert str(PIN) in str(raised.value) and str(pout) in str(raised.value)


def test_the_packer_gives_the_per_chunk_result():
    from chunkflow_tpu.serve.packer import PatchPacker

    inferencer = make_inferencer(batch_size=4)
    chunks = [Chunk(image(shape, seed=i), voxel_offset=(16 * i, 0, 0))
              for i, shape in enumerate((ALIGNED, SNAPPED, PIN))]
    refs = [np.asarray(inferencer(c).array) for c in chunks]
    packer = PatchPacker(inferencer, max_wait_ms=1.0)
    try:
        outs = [h.result(timeout=120)
                for h in [packer.submit(c) for c in chunks]]
    finally:
        packer.close()
    for ref, out, chunk in zip(refs, outs, chunks):
        assert tuple(out.voxel_offset) == tuple(
            o + m for o, m in zip(chunk.voxel_offset, MARGIN))
        assert np.array_equal(np.asarray(out.array), ref)


@pytest.mark.parametrize("shape", [ALIGNED, SNAPPED])
def test_a_data_mesh_gives_the_single_device_result(shape):
    array = image(shape, seed=2)
    single = make_inferencer(batch_size=2, mesh="1")
    meshed = make_inferencer(batch_size=2, mesh="data=2")
    want = single(Chunk(array))
    got = meshed(Chunk(array))
    assert meshed.shard_engine() is not None
    assert tuple(got.voxel_offset) == tuple(want.voxel_offset) == MARGIN
    assert np.array_equal(np.asarray(got.array), np.asarray(want.array))


def test_the_program_says_what_it_holds(monkeypatch, tmp_path):
    """The four geometry gauges, and the same four on the program's entry
    in ``programs.json`` (docs/observability.md)."""
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    try:
        telemetry.configure(str(tmp_path))
        inferencer = make_inferencer(num_output_channels=4, batch_size=5)
        inferencer(Chunk(image(SNAPPED)))
        voxels = int(np.prod(SNAPPED))
        want = {"output_patch_share": 0.125, "patches_per_task": 27,
                "accumulator_bytes": 4 * 5 * voxels, "chunk_bytes": voxels}
        gauges = telemetry.snapshot()["gauges"]
        assert {k: gauges[f"inference/{k}"] for k in want} == want
        (entry,) = [e for e in profiling.catalog()
                    if e["label"] == "inferencer"]
        assert {k: entry[k] for k in want} == want
        # with output patch = input patch the share reads 1
        make_inferencer(output_patch_size=None)(Chunk(image(PIN)))
        assert telemetry.snapshot()["gauges"][
            "inference/output_patch_share"] == 1.0
    finally:
        telemetry.reset()


@pytest.mark.parametrize("pin, pout, cut", [
    (PIN, POUT, (True, False)),              # level 0 alone
    (PIN_WIDE, POUT_WIDE, (True, True)),     # level 1 as well
    (PIN, PIN, (False, False)),              # no margin: 1.0 / 1.0 / 1.0
])
def test_the_program_says_how_much_of_the_decoder_it_runs(
        monkeypatch, tmp_path, pin, pout, cut):
    """``forward/dec{i}_voxel_share`` and ``forward/flops_share`` of the
    RSUNet's trace, on the patch program's ``programs.json`` entry."""
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    try:
        telemetry.configure(str(tmp_path))
        make_inferencer(input_patch_size=pin, output_patch_size=pout)(
            Chunk(image(pin)))
        (entry,) = [e for e in profiling.catalog()
                    if e["label"] == "inferencer"]
        gauges = telemetry.snapshot()["gauges"]
        shares = [entry["dec0_voxel_share"], entry["dec1_voxel_share"],
                  entry["flops_share"]]
        assert shares == [gauges["forward/dec0_voxel_share"],
                          gauges["forward/dec1_voxel_share"],
                          gauges["forward/flops_share"]]
        assert [share < 1 for share in shares] == [*cut, any(cut)]
        assert all(0.2 < share <= 1.0 for share in shares)
        assert entry["x_fold"] == 4 and entry["x_fold_1"] == 2
    finally:
        telemetry.reset()


MODEL_FILE = os.path.join(BENCH_DIR, "configs", "rsunet-deepem.model.py")


@pytest.mark.parametrize("model_path, variant, itself", [
    ("", "rsunet", True),
    (MODEL_FILE, "parity", True),   # a user file that returns the RSUNet
    ("", "parity", False),
])
def test_a_module_that_takes_the_output_patch_is_handed_it(
        model_path, variant, itself):
    """The engine asks the module, not its name: one whose ``__call__``
    takes ``output_patch_size`` returns the output patch itself, every
    other module's whole prediction is cropped by the engine; either way
    ``apply`` gives the central part of the whole forward."""
    import jax
    import jax.numpy as jnp

    engine = create_flax_engine(model_path, None, PIN, 1, 3,
                                model_variant=variant,
                                output_patch_size=POUT)
    whole = create_flax_engine(model_path, None, PIN, 1, 3,
                               model_variant=variant)
    batch = jnp.asarray(image((2, 1) + PIN), jnp.float32) / 255
    telemetry.reset()
    try:
        got = np.asarray(jax.jit(engine.apply)(engine.params, batch))
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.reset()
    want = np.asarray(jax.jit(whole.apply)(engine.params, batch))
    assert got.shape == (2, 3) + POUT
    assert np.abs(got - want[:, :, 2:6, 8:24, 8:24]).max() <= 1e-6
    assert (gauges.get("forward/dec0_voxel_share", 1.0) < 1) == itself


def test_the_tutorials_production_command_line_parses():
    """docs/tutorial.md's production example: every command of the chain
    exists, and ``inference`` parses to the deployment's geometry."""
    from chunkflow_tpu.flow import cli

    with open(os.path.join(CHECKOUT, "docs", "tutorial.md")) as f:
        text = f.read()
    block = re.search(
        r"<!-- production-deployment -->\n```bash\n(.*?)```", text, re.S)
    words = shlex.split(block.group(1).replace("\\\n", " "))
    assert words[0] == "chunkflow"
    names = set(cli.main.commands)
    starts = [i for i, w in enumerate(words) if w in names]
    chain = [words[i] for i in starts]
    assert chain == ["fetch-task-from-queue", "load-precomputed",
                     "inference", "crop-margin", "save-precomputed",
                     "delete-task-in-queue"]
    parsed = {}
    for i, j in zip(starts, starts[1:] + [len(words)]):
        command = cli.main.commands[words[i]]
        parsed[words[i]] = command.make_context(
            words[i], list(words[i + 1:j])).params
    got = parsed["inference"]
    assert tuple(got["input_patch_size"]) == (20, 256, 256)
    assert tuple(got["output_patch_size"]) == (16, 192, 192)
    assert tuple(got["output_patch_overlap"]) == (2, 32, 32)
    assert tuple(got["patch_num"]) == (14, 9, 9)
    assert (got["num_output_channels"], got["batch_size"]) == (4, 6)
    assert (got["framework"], got["model_variant"], got["dtype"]) == (
        "flax", "rsunet", "bfloat16")
    margin = tuple(parsed["load-precomputed"]["expand_margin_size"])
    crop = tuple((i - o) // 2 for i, o in zip(
        got["input_patch_size"], got["output_patch_size"]))
    assert margin == tuple(c + m for c, m in zip(crop, (8, 96, 96)))
    # the geometry is one the engine accepts
    inferencer = Inferencer(
        input_patch_size=(8, 32, 32), output_patch_size=(4, 16, 16),
        output_patch_overlap=(2, 8, 8), framework="identity")
    assert tuple(inferencer.crop_margin) == (2, 8, 8)
