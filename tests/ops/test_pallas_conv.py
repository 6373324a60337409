"""The convolution kernel that builds the x halo in VMEM
(ops/pallas_conv.py), interpreted on the CPU, against ``XFoldConv``'s XLA
path: the kernel alone, its edges, the rule that says which blocks take
it (models/rsunet.py ``kernel_takes``), and the whole ``RSUNet`` with and
without it on the same parameters."""
import itertools
import re

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from chunkflow_tpu.core import profiling, telemetry  # noqa: E402
from chunkflow_tpu.models import rsunet  # noqa: E402
from chunkflow_tpu.ops import pallas_conv  # noqa: E402

BF16_STEP = 2.0 ** -7  # the spacing of bfloat16 values in [1, 2)


def as_bfloat16_values(key, shape, scale=1.0):
    """float32 numbers that bfloat16 holds exactly: the kernel's operand
    rounding then changes nothing, and a product of two is exact."""
    x = scale * jax.random.normal(key, shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def xla_path(x, kernel, bias, fold):
    """What ``XFoldConv`` emits: the block-banded convolution, then the
    bias, in ``x``'s dtype."""
    kz, ky, _ = kernel.shape[:3]
    folded, x_pad = rsunet.fold_kernel(kernel.astype(x.dtype), fold)
    y = lax.conv_general_dilated(
        x, folded, window_strides=(1, 1, 1),
        padding=(((kz - 1) // 2, kz // 2), ((ky - 1) // 2, ky // 2), x_pad),
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    return y + jnp.tile(bias.astype(x.dtype), fold)


def kernel_path(x, kernel, bias, fold, **epilogue):
    channels = x.shape[-1] // fold
    folded, _ = rsunet.fold_kernel(kernel.astype(x.dtype), fold)
    centre, halo = pallas_conv.halo_kernels(folded, channels)
    return pallas_conv.folded_conv(
        x, centre, halo, jnp.tile(bias.astype(x.dtype), fold),
        channels=channels, interpret=True, **epilogue)


@pytest.mark.parametrize("batch", [4, 6])
@pytest.mark.parametrize("window", [(1, 3, 3), (3, 3, 3)])
@pytest.mark.parametrize("fold, channels", [(4, 28), (8, 16)])
@pytest.mark.parametrize("dtype, blocks", [
    ("float32", 16), ("float32", 48), ("bfloat16", 16), ("bfloat16", 48)])
def test_kernel_equals_the_xla_path(dtype, blocks, fold, channels, window,
                                    batch):
    """float32: to the order of one sum (the operands hold bfloat16
    values, as the chip's default rounds them); bfloat16: to the rounding
    of one sum. 48 x blocks: whole tiles of the operands, and no multiple
    of 32."""
    keys = jax.random.split(jax.random.PRNGKey(batch), 3)
    x = as_bfloat16_values(
        keys[0], (batch, 3, 5, blocks, fold * channels)).astype(dtype)
    kernel = as_bfloat16_values(keys[1], (*window, channels, channels), 0.1)
    bias = as_bfloat16_values(keys[2], (channels,))
    want = np.asarray(xla_path(x, kernel, bias, fold), np.float32)
    got = np.asarray(kernel_path(x, kernel, bias, fold), np.float32)
    assert got.shape == want.shape and got.dtype == want.dtype
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:  # one step of the sum, carried through the bias's rounding
        room = 2 * np.abs(want) + np.abs(np.tile(np.asarray(bias), fold))
        assert np.all(np.abs(got - want) <= BF16_STEP * room)


@pytest.mark.parametrize("dtype, blocks", [
    ("float32", 8), ("float32", 24), ("bfloat16", 8), ("bfloat16", 40)])
def test_x_blocks_that_are_no_whole_operand_tiles_are_refused(dtype, blocks):
    """The operands are bfloat16 whatever the activations are: x blocks
    that are no multiple of 16 are the rule's to decline (``kernel_takes``),
    and the kernel refuses them rather than pack half a tile."""
    x = jnp.zeros((1, 1, 4, blocks, 112), dtype)
    kernel = jnp.zeros((3, 3, 3, 28, 28))
    with pytest.raises(AssertionError):
        kernel_path(x, kernel, jnp.zeros((28,)), 4)


@pytest.mark.parametrize("fold, channels", [(4, 28), (8, 16), (2, 36)])
def test_the_halo_weights_are_the_outer_block_taps(fold, channels):
    """Pure data movement: centre and halo hold every entry of the
    folded kernel's three block taps, and nothing else but zeros."""
    kernel = jax.random.normal(jax.random.PRNGKey(0),
                               (3, 3, 3, channels, channels + 4))
    folded, _ = rsunet.fold_kernel(kernel, fold)
    centre, halo = pallas_conv.halo_kernels(folded, channels)
    folded, centre, halo = map(np.asarray, (folded, centre, halo))
    assert np.array_equal(centre, folded[:, :, 1])
    lanes = fold * channels
    assert np.array_equal(halo[:, :, lanes - channels:],
                          folded[:, :, 0, lanes - channels:])
    assert np.array_equal(halo[:, :, :channels], folded[:, :, 2, :channels])
    # what the halo leaves out of the outer taps is zero there
    assert not folded[:, :, 0, :lanes - channels].any()
    assert not folded[:, :, 2, channels:].any()
    assert not halo[:, :, channels:lanes - channels].any()


EDGES = list(itertools.product((0, -1), repeat=3))


@pytest.mark.parametrize("dtype, blocks", [("float32", 16), ("bfloat16", 16)])
@pytest.mark.parametrize("corner", EDGES)
def test_a_one_hot_at_an_edge_reads_the_published_tap_or_zero(dtype, blocks,
                                                              corner):
    """'SAME' padding is exact on z, y and x: a single one in a corner
    voxel (the first or last plane, row, and position of the first or
    last x block) puts the published tap at every neighbour inside the
    array and nothing anywhere else."""
    fold, channels, shape = 4, 28, (3, 4, blocks * 4)
    kernel = np.asarray(as_bfloat16_values(
        jax.random.PRNGKey(1), (3, 3, 3, channels, channels)))
    at = tuple(n - 1 if c else 0 for c, n in zip(corner, shape))
    x = np.zeros((1, *shape, channels), np.float32)
    x[(0, *at, 5)] = 1.0
    want = np.zeros((1, *shape, channels), np.float32)
    for tap in itertools.product(range(3), repeat=3):
        # tap t of the kernel takes the input at p to the output at p+1-t
        to = tuple(p + 1 - t for p, t in zip(at, tap))
        if all(0 <= p < n for p, n in zip(to, shape)):
            want[(0, *to)] = kernel[(*tap, 5)]
    got = kernel_path(rsunet.fold_x(jnp.asarray(x, dtype), fold),
                      jnp.asarray(kernel), jnp.zeros((channels,)), fold)
    got = np.asarray(rsunet.unfold_x(got, fold), np.float32)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_epilogue_is_the_blocks(dtype):
    """Bias, the folded batch norm, the residual and the ReLU after one
    rounding of the sum, as ``RSBlock`` applies them."""
    fold, channels = 4, 28
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    shape = (2, 3, 4, 16, fold * channels)
    x = as_bfloat16_values(keys[0], shape).astype(dtype)
    residual = as_bfloat16_values(keys[1], shape).astype(dtype)
    kernel = as_bfloat16_values(keys[2], (3, 3, 3, channels, channels), 0.1)
    bias = as_bfloat16_values(keys[3], (channels,))
    scale, shift = (
        jnp.tile(as_bfloat16_values(key, (channels,)), fold).astype(dtype)
        for key in keys[4:])
    want = np.asarray(jax.nn.relu(
        xla_path(x, kernel, bias, fold) * scale + shift + residual),
        np.float32)
    got = np.asarray(kernel_path(
        x, kernel, bias, fold, scale=scale, shift=shift, residual=residual,
        relu=True), np.float32)
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    else:  # XLA rounds after each of the four steps, the kernel once
        assert np.all(np.abs(got - want)
                      <= 4 * BF16_STEP * np.maximum(np.abs(want), 4.0))
    assert got.min() == 0.0


# fold, channels in, features, dtype, (z, y, x blocks), backend -> takes
RULE = [
    # level 0 of the four superhuman cells, batch 4 and 6: enc0 and dec0
    (4, 28, 28, "bfloat16", (20, 256, 64), "tpu", True),
    # level 0 of rsunet-deepem: float32 activations, 128 lanes; level 1s
    (8, 16, 16, "float32", (20, 256, 32), "tpu", True),
    (2, 28, 36, "bfloat16", (20, 128, 64), "tpu", True),
    (4, 32, 32, "float32", (20, 128, 32), "tpu", True),
    (4, 28, 28, "float16", (20, 256, 64), "tpu", False),
    # the production cone's dec0: 50 x blocks are no whole bfloat16 tiles
    (4, 28, 28, "bfloat16", (20, 200, 50), "tpu", False),
    # float32 activations too: the operands are bfloat16, two x blocks a
    # packed word, whatever the activations' own tile is
    (4, 28, 28, "float32", (20, 256, 8), "tpu", False),
    (8, 16, 16, "float32", (20, 256, 24), "tpu", False),
    (4, 32, 32, "float32", (20, 128, 40), "tpu", False),
    # a 512x512 patch: ten planes of 16 MiB pass what a call may take of
    # the VMEM; half of it in y still fits
    (4, 28, 28, "bfloat16", (20, 512, 128), "tpu", False),
    (8, 16, 16, "float32", (20, 512, 64), "tpu", False),
    (4, 28, 28, "bfloat16", (20, 256, 128), "tpu", True),
    # off a TPU backend nothing takes it
    (4, 28, 28, "bfloat16", (20, 256, 64), "cpu", False),
    (4, 28, 28, "bfloat16", (20, 256, 64), "gpu", False),
    # unfolded levels, and a fold whose lanes pass one MXU tile
    (1, 48, 48, "bfloat16", (10, 64, 64), "tpu", False),
    (4, 28, 36, "bfloat16", (20, 128, 32), "tpu", False),
]


@pytest.mark.parametrize(
    "fold, channels, features, dtype, extents, backend, takes", RULE)
def test_the_rule_is_a_function_of_what_the_code_sees(
        fold, channels, features, dtype, extents, backend, takes):
    assert rsunet.kernel_takes(fold, channels, features, jnp.dtype(dtype),
                               extents, backend) is takes


def test_no_option_selects_the_kernel(monkeypatch):
    """No environment variable reaches the rule: the Pallas switches of
    the blend and gather kernels leave it where it is."""
    args = (4, 28, 28, jnp.dtype("bfloat16"), (20, 256, 64))
    for value in ("1", "interpret", "0"):
        monkeypatch.setenv("CHUNKFLOW_PALLAS", value)
        monkeypatch.setenv("CHUNKFLOW_FUSED_PIPELINE", value)
        assert rsunet.kernel_takes(*args, "tpu")
        assert not rsunet.kernel_takes(*args, "cpu")


WIDTHS = {"superhuman": (28, 36, 48), "deepem": (16, 32, 64)}
DOWN = ((1, 2, 2), (2, 2, 2))


def _model_and_input(widths, dtype, interpret, x_extent=64):
    model = rsunet.RSUNet(width=WIDTHS[widths], down_factors=DOWN,
                          dtype=jnp.dtype(dtype), interpret=interpret)
    x = jax.random.uniform(jax.random.PRNGKey(0), (2, 4, 16, x_extent, 1))
    return model, x


def _params(widths, x):
    model = rsunet.RSUNet(width=WIDTHS[widths], down_factors=DOWN)
    params = model.init(jax.random.PRNGKey(1), x[:1])
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    # biases and batch norm terms that do something
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(key, leaf.shape) if leaf.ndim == 1
        else leaf for leaf, key in zip(leaves, keys)])


def test_on_a_cpu_backend_the_forward_is_the_parents(monkeypatch):
    """The rule declines every block off a TPU, and the lowered module
    is, to the letter, the one of a forward in which nothing can take the
    kernel."""
    model, x = _model_and_input("superhuman", "bfloat16", False)
    params = _params("superhuman", x)

    def lowered():
        return jax.jit(model.apply).lower(params, x).as_text()

    ours = lowered()
    assert "pallas" not in ours and "kernel_convolution" not in ours
    monkeypatch.setattr(rsunet, "kernel_takes", lambda *args: False)
    assert lowered() == ours


@pytest.fixture
def rounded_operands(monkeypatch):
    """XLA's convolutions of ``models/rsunet.py`` with their operands
    rounded to bfloat16, as the chip's default rounds a float32
    convolution's and as the kernel rounds its own: what the kernel is
    held to in float32 (a cast there and back XLA would drop)."""
    import types

    def convolution(x, kernel, *args, **kwargs):
        def rounded(v):
            return lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        return lax.conv_general_dilated(rounded(x), rounded(kernel), *args,
                                        **kwargs)

    shim = types.SimpleNamespace(**{
        name: getattr(lax, name) for name in dir(lax)
        if not name.startswith("__")})
    shim.conv_general_dilated = convolution
    monkeypatch.setattr(rsunet, "lax", shim)


@pytest.mark.parametrize("fold, channels, features, blocks", [
    (4, 28, 28, 16),   # enc0, dec0 at widths 28/36/48/64
    (2, 28, 36, 16),   # enc1: the pooled 28 channels in, 36 out
    (2, 36, 36, 16),   # dec1
    (8, 16, 16, 16),   # enc0, dec0 at widths 16/32/64/128
    (4, 16, 32, 16),   # enc1 there
])
def test_a_block_through_the_kernel_is_xlas_block(
        fold, channels, features, blocks, rounded_operands):
    """Three convolutions, their batch norms, ReLUs and the residual as
    three calls of the kernel, on values that bfloat16 does not hold
    exactly: equal to the block XLA runs to the order of the sums."""
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (2, 3, 6, blocks, fold * channels))
    plain = rsunet.RSBlock(features, fold=fold)
    kernel = rsunet.RSBlock(features, fold=fold, kernel=True, interpret=True)
    params = jax.tree_util.tree_map(
        lambda leaf: leaf + 0.1 * jax.random.normal(
            jax.random.PRNGKey(leaf.size), leaf.shape),
        plain.init(jax.random.PRNGKey(1), x))
    want = np.asarray(plain.apply(params, x))
    got = np.asarray(kernel.apply(params, x))
    # conv2 and conv3 round an operand that differs in its last float32
    # bits: here and there the rounding falls the other way, one bfloat16
    # step of one operand; a tap or an edge missed moves every voxel
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 2 * BF16_STEP * scale
    assert np.abs(got - want).mean() <= 1e-5 * scale


@pytest.mark.parametrize("dtype, fold, blocks", [
    ("float32", 4, 16), ("float32", 8, 16), ("bfloat16", 4, 16),
    ("bfloat16", 8, 32)])
def test_the_embedding_packed_into_one_pass_is_xlas(dtype, fold, blocks,
                                                    rounded_operands):
    """1x5x5 on one channel: centre, halo and the five rows rotated into
    one lane tile; two positions of either neighbour, first and last row
    and block included."""
    embed = rsunet.XFoldConv(28, rsunet.EMBED_KERNEL, dtype=jnp.dtype(dtype),
                             fold=fold, interpret=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 7, blocks, fold))
    params = embed.init(jax.random.PRNGKey(1), x)
    want = np.asarray(embed.apply(params, x), np.float32)
    got = np.asarray(embed.apply(
        params, x, rsunet.Epilogue(relu=False)), np.float32)
    bound = 1e-5 if dtype == "float32" else BF16_STEP
    assert np.all(np.abs(got - want) <= bound * np.maximum(np.abs(want), 1))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_head_in_the_last_blocks_kernel_is_xlas_head(dtype,
                                                         rounded_operands):
    """``dec0`` and the 1x1x1 head that alone reads it, as one kernel:
    the head's result, the array between them never written."""
    fold, width = 4, 28
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 6, 16, fold * width))
    x = x.astype(dtype)
    kinds = dict(dtype=jnp.dtype(dtype), fold=fold)
    blocks = [rsunet.RSBlock(width, kernel=kernel, interpret=kernel, **kinds)
              for kernel in (False, True)]
    out = rsunet.XFoldConv(3, (1, 1, 1), name="out", **kinds)
    block_params = blocks[0].init(jax.random.PRNGKey(1), x)
    out_params = out.init(jax.random.PRNGKey(2), x)
    want = out.apply(out_params, blocks[0].apply(block_params, x))
    its = out_params["params"]
    head = rsunet.Head(*rsunet.fold_head(
        its["kernel"], its["bias"], fold, jnp.dtype(dtype)), "out")
    got = blocks[1].apply(block_params, x, head)
    assert got.shape == want.shape == (*x.shape[:-1], fold * 3)
    want, got = (np.asarray(a, np.float32) for a in (want, got))
    assert np.all(np.abs(got - want)
                  <= 4 * BF16_STEP * np.maximum(np.abs(want), 1))
    assert np.abs(got - want).mean() <= (
        1e-5 if dtype == "float32" else BF16_STEP / 4)


@pytest.mark.parametrize("output_patch_size", [None, (4, 8, 32)])
@pytest.mark.parametrize("widths, dtype, x_extent", [
    ("superhuman", "bfloat16", 128), ("deepem", "float32", 128)])
def test_the_whole_forward_with_the_kernel_is_the_forward_without(
        widths, dtype, x_extent, output_patch_size, rounded_operands):
    """Same parameter tree, same values, in the cells' compute dtypes:
    with the embedding, both folded levels' blocks and the head through
    the kernel, the prediction stays within what a rounding that falls the
    other way here and there moves it (a block at the wrong place, a tap
    or an edge missed reads tenths)."""
    plain, x = _model_and_input(widths, dtype, False, x_extent)
    kernel, _ = _model_and_input(widths, dtype, True, x_extent)
    params = _params(widths, x)
    want = np.asarray(jax.jit(lambda p, v: plain.apply(
        p, v, output_patch_size=output_patch_size))(params, x))
    got = np.asarray(jax.jit(lambda p, v: kernel.apply(
        p, v, output_patch_size=output_patch_size))(params, x))
    assert got.shape == want.shape
    assert 0.03 < float(want.std())  # a forward that says something
    assert np.abs(got - want).max() <= 0.05
    assert np.abs(got - want).mean() <= 2e-3


@pytest.mark.parametrize("widths, dtype, x_extent, interpret, count", [
    # x blocks 16 at level 0 (whole bfloat16 tiles), 32 at level 1
    # both folded levels' blocks, the embedding and the head
    ("superhuman", "bfloat16", 64, True, 14),
    # 8 and 8 x blocks: whole float32 tiles, half a tile of the operands,
    # which are bfloat16 whatever the activations are: no block takes it
    ("deepem", "float32", 64, True, 0),
    ("deepem", "bfloat16", 64, True, 0),
    ("deepem", "float32", 128, True, 14),
    ("deepem", "bfloat16", 128, True, 14),
    ("superhuman", "bfloat16", 64, False, 0),
])
def test_the_gauge_says_how_many_convolutions_took_the_kernel(
        widths, dtype, x_extent, interpret, count):
    """``forward/kernel_convolutions`` is what the rule says of the
    blocks' shapes: three a block that takes the kernel, and with level
    0's blocks the embedding and the head."""
    model, x = _model_and_input(widths, dtype, interpret, x_extent)
    params = jax.eval_shape(
        lambda: rsunet.RSUNet(width=WIDTHS[widths], down_factors=DOWN).init(
            jax.random.PRNGKey(0), x[:1]))
    folds = rsunet.level_folds(WIDTHS[widths], x_extent, DOWN)
    backend = "tpu" if interpret else jax.default_backend()
    extents = [(4, 16, x_extent // folds[0]),
               (4, 8, x_extent // 2 // folds[1])]
    said = sum(
        3 * rsunet.kernel_takes(folds[level], channels, WIDTHS[widths][level],
                                jnp.dtype(dtype), extents[level], backend)
        for level, channels in ((0, WIDTHS[widths][0]),
                                (1, WIDTHS[widths][0]),
                                (1, WIDTHS[widths][1]),
                                (0, WIDTHS[widths][0])))
    # the embedding goes with enc0, the head with dec0
    said += 2 * rsunet.kernel_takes(
        folds[0], WIDTHS[widths][0], WIDTHS[widths][0], jnp.dtype(dtype),
        extents[0], backend)
    telemetry.reset()
    try:
        jax.eval_shape(model.apply, params, x)
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.reset()
    assert gauges["forward/kernel_convolutions"] == said == count


@pytest.mark.parametrize("widths, dtype, x_extent", [
    ("superhuman", "bfloat16", 64), ("deepem", "float32", 128)])
def test_calls_that_agree_are_one_function_of_the_program(widths, dtype,
                                                          x_extent):
    """What a start pays for is a kernel traced and lowered: thirteen
    calls a forward, nine functions. ``enc0`` and ``dec0`` share
    ``conv1`` and ``conv2``, level 1's blocks ``conv2`` and ``conv3``
    (``enc1/conv1`` reads level 0's channels, ``dec0/conv3`` carries the
    head); the embedding is the ninth."""
    model, x = _model_and_input(widths, dtype, True, x_extent)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x[:1]))
    text = jax.jit(model.apply).lower(params, x).as_text()
    assert len(re.findall(r"func\.func private @folded_conv", text)) == 9
    assert len(re.findall(r"call @folded_conv", text)) == 13


def test_the_kernel_runs_under_shard_map_on_a_data_axis():
    """The multi-chip engine calls the same forward under ``shard_map``:
    there the kernel sees one shard's batch, and the result is the
    unsharded one."""
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()[:2]
    if len(devices) < 2:
        pytest.skip("needs two devices")
    model, x = _model_and_input("superhuman", "bfloat16", True)
    params = _params("superhuman", x)
    want = np.asarray(jax.jit(model.apply)(params, x))
    mesh = Mesh(np.array(devices), ("data",))
    sharded = jax.jit(jax.shard_map(
        model.apply, mesh=mesh, in_specs=(P(), P("data")),
        out_specs=P("data"), check_vma=False))
    assert np.array_equal(np.asarray(sharded(params, x)), want)


def test_the_marker_scope_names_the_kernel_a_convolution():
    """The lowered forward carries the marker scope inside each
    convolution's own module path, which is what lists the custom call in
    ``op_convolutions`` (tests/core/test_profiling.py)."""
    model, x = _model_and_input("superhuman", "bfloat16", True)
    params = _params("superhuman", x)
    text = jax.jit(model.apply).lower(params, x).as_text(debug_info=True)
    paths = set(profiling._LOWERED_PATH.findall(text))
    for block, conv, window in [("enc0", "conv1", "1x3x3"),
                                ("enc0", "conv3", "3x3x3"),
                                ("dec0", "conv2", "3x3x3")]:
        marked = [p for p in paths
                  if f"/{block}/{conv}/kernel_convolution_{window}/" in p]
        assert marked, (block, conv)
        assert profiling._kernel_convolutions(marked[0])[0][1] == window


_A_START = """
import sys
import jax
import jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.models import rsunet
model = rsunet.RSUNet(width=(28, 36, 48), down_factors=((1, 2, 2), (2, 2, 2)),
                      dtype=jnp.bfloat16, platform="tpu")
x = jnp.zeros((2, 4, 16, 64, 1))
params = jax.eval_shape(
    lambda: rsunet.RSUNet(width=(28, 36, 48), down_factors=(
        (1, 2, 2), (2, 2, 2))).init(jax.random.PRNGKey(0), x[:1]))
text = jax.jit(model.apply).trace(params, x).lower(
    lowering_platforms=("tpu",)).as_text()
counters = telemetry.snapshot()["counters"]
print(text.count("tpu_custom_call"),
      int(counters.get("compile_cache/lowered_builds", 0)),
      int(counters.get("compile_cache/lowered_hits", 0)),
      int("jax.experimental.pallas" in sys.modules))
"""


def test_a_start_that_finds_its_kernels_lowered_traces_none(tmp_path):
    """The program lowered for a TPU by two processes with one cache
    directory: the first traces and lowers its nine kernels and leaves
    them beside the executables, the second reads them back, holds the
    same nine Mosaic calls, and never imports Pallas (a second of a
    start, which is what ``setup_s`` was held against)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    said = []
    for _ in range(2):
        done = subprocess.run(
            [sys.executable, "-c", _A_START, str(tmp_path)], cwd=repo,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True,
            capture_output=True, timeout=300)
        assert done.returncode == 0, done.stderr[-2000:]
        said.append(done.stdout.split())
    assert said == [["9", "9", "0", "1"], ["9", "0", "9", "0"]]
