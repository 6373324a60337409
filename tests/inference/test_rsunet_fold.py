"""The x-folded levels of the RSUNet are an exact re-lowering: the folded
forward equals the same module held to F = 1, every level's fold follows
from the widths, the x extent and the pooling alone, the parameter tree is
what converted checkpoints were written against, and a fold of 1 is the
plain convolution."""
import functools
import os

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chunkflow_tpu.models import rsunet

WIDTHS = {"28-36-48-64": (28, 36, 48, 64), "16-32-64-128": (16, 32, 64, 128)}
DOWN = ((1, 2, 2), (2, 2, 2), (2, 2, 2))
# x extent -> (levels the extent allows, pooling): 30 and 20 halve once,
# 17 not at all in x
SHAPES = {
    32: (4, DOWN),
    30: (2, DOWN[:1]),
    20: (2, DOWN[:1]),
    17: (2, ((1, 2, 1),)),
}


@functools.lru_cache(maxsize=None)
def _params(widths, down):
    """Perturbed parameters of the levels ``down`` joins: they depend on
    neither the compute dtype nor the extent of the input."""
    model = rsunet.RSUNet(width=WIDTHS[widths][:len(down) + 1],
                          down_factors=down)
    return _perturbed(jax.jit(model.init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 16, 16, 1))))


def _perturbed(params, seed=1):
    """Every leaf away from its init: biases and affines are 0 and 1
    there, which would hide a wrong tiling. Kernels move by a tenth of
    their own spread, so the activations keep their scale."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        leaf + 0.1 * (leaf.std() if leaf.ndim > 1 else 1.0)
        * jax.random.normal(key, leaf.shape, leaf.dtype)
        for leaf, key in zip(leaves, keys)])


@pytest.mark.parametrize("x_extent", sorted(SHAPES, reverse=True))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_folded_forward_equals_the_unfolded_one(widths, dtype, x_extent,
                                                monkeypatch):
    levels, down = SHAPES[x_extent]
    width = WIDTHS[widths][:levels]
    folds = rsunet.level_folds(width, x_extent, down)
    model = rsunet.RSUNet(width=width, down_factors=down,
                          dtype=jnp.dtype(dtype))
    x = jax.random.uniform(jax.random.PRNGKey(0), (2, 4, 16, x_extent, 1))
    params = _params(widths, down)

    def forward():  # a new function each time: nothing cached across folds
        return np.asarray(jax.jit(lambda p, v: model.apply(p, v))(params, x))

    seen = []  # (level, fold) of every convolution that ran
    real = rsunet.XFoldConv.__call__

    def spy(self, x):
        block = self.path[0]  # embed, enc{i}, bridge, dec{i}, out
        level = (int(block[3:]) if block[:3] in ("enc", "dec")
                 else levels - 1 if block == "bridge" else 0)
        seen.append((level, self.fold))
        return real(self, x)

    # embed, enc0, dec0, out at level 0; enc{i} and dec{i} below; the bridge
    convs = [8] + [6] * (levels - 2) + [3]
    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(rsunet.XFoldConv, "__call__", spy)
        folded = forward()
        assert sorted(seen) == sorted(
            (level, folds[level]) for level in range(levels)
            for _ in range(convs[level]))
        assert folds[0] == rsunet.x_fold(width[0], x_extent)
        assert all(fold == 1 for fold in folds[2:])
        monkeypatch.setattr(rsunet, "x_fold", lambda width0, x: 1)
        del seen[:]
        plain = forward()
        assert {fold for _, fold in seen} == {1}
    assert folded.shape == plain.shape == x.shape[:-1] + (3,)
    assert 0.05 < plain.std()  # a forward that says something
    # float32: the summation order of one convolution; bfloat16: 2 ulp
    # of an output in [0.5, 1)
    bound = 1e-4 if dtype == "float32" else 2 * 2.0 ** -8
    assert np.abs(folded - plain).max() <= bound


@pytest.mark.parametrize("width0,x_extent,fold", [
    (28, 256, 4), (16, 256, 8), (36, 256, 2), (64, 256, 2), (65, 256, 1),
    (28, 32, 4), (16, 32, 8), (28, 30, 2), (16, 30, 2), (28, 20, 4),
    (16, 20, 4), (16, 24, 8), (28, 17, 1), (16, 17, 1), (128, 256, 1),
    (8, 256, 16), (1, 6, 2),
])
def test_fold_factor_rule(width0, x_extent, fold):
    assert rsunet.x_fold(width0, x_extent) == fold


@pytest.mark.parametrize("width,x_extent,down,folds", [
    # level 1 takes the fold the (1,2,2) pool hands it: 36 x 2, 32 x 4
    ((28, 36, 48, 64), 256, DOWN, [4, 2, 1, 1]),
    ((16, 32, 64, 128), 256, DOWN, [8, 4, 1, 1]),
    ((28, 36, 48, 64), 32, DOWN, [4, 2, 1, 1]),
    ((16, 32, 64, 128), 32, DOWN, [8, 4, 1, 1]),
    # 30 folds by 2 and hands down 1; 20 by 4 and hands down 2
    ((28, 36), 30, DOWN[:1], [2, 1]),
    ((16, 32), 30, DOWN[:1], [2, 1]),
    ((28, 36), 20, DOWN[:1], [4, 2]),
    ((16, 32), 20, DOWN[:1], [4, 2]),
    # an odd extent folds nowhere, whatever the pool
    ((28, 36), 17, ((1, 2, 1),), [1, 1]),
    ((16, 32), 17, ((1, 2, 1),), [1, 1]),
    # a pool that leaves x alone hands the whole fold down, as far as the
    # width below allows
    ((16, 32), 32, ((1, 2, 1),), [8, 4]),
    ((16, 16), 32, ((1, 2, 1),), [8, 8]),
    # a width over 64 never folds, nor does anything below level 1
    ((16, 65, 64), 256, DOWN[:2], [8, 1, 1]),
    ((16, 128), 256, DOWN[:1], [8, 1]),
    ((65, 16), 256, DOWN[:1], [1, 1]),
    ((8, 8, 8), 256, DOWN[:2], [16, 8, 1]),
    # windows of 4 straddle blocks of 2: nothing to hand down
    ((28, 36), 24, ((1, 2, 4),), [4, 1]),
    ((28, 36), 30, ((1, 2, 3),), [2, 1]),
])
def test_level_folds_rule(width, x_extent, down, folds):
    assert rsunet.level_folds(width, x_extent, down) == folds
    for fold, level_width, factor in zip(folds, width, (*down, (1, 1, 1))):
        assert fold * level_width <= rsunet.LANES or fold == 1
        assert x_extent % fold == 0
        x_extent //= factor[2]


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_parameter_tree_is_the_one_checkpoints_were_written_against(widths):
    """Paths, shapes and dtypes of ``model.init`` as they were before
    level 0 was folded (rsunet_param_tree.txt, written from that commit):
    ``models/converter.py`` pairs a checkpoint's tensors by these names."""
    model = rsunet.RSUNet(width=WIDTHS[widths])
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 32, 32, 1))))
    listing = [
        "%s %s %s %s" % (widths, "/".join(k.key for k in path),
                         "x".join(map(str, leaf.shape)), leaf.dtype)
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]]
    golden = os.path.join(os.path.dirname(__file__), "rsunet_param_tree.txt")
    with open(golden) as f:
        expected = [line.rstrip("\n") for line in f
                    if line.startswith(widths + " ")]
    assert listing == expected


@pytest.mark.parametrize("kernel_size", [(1, 5, 5), (1, 3, 3), (3, 3, 3),
                                         (1, 1, 1), (1, 3, 4)])
def test_a_fold_of_one_is_the_published_kernel(kernel_size):
    kernel = jax.random.normal(jax.random.PRNGKey(2), kernel_size + (5, 7))
    folded, x_pad = rsunet.fold_kernel(kernel, 1)
    kx = kernel_size[2]
    assert x_pad == ((kx - 1) // 2, kx // 2)
    np.testing.assert_array_equal(np.asarray(folded), np.asarray(kernel))
    # and the module is nn.Conv: same parameters, same values, same result
    x = jax.random.uniform(jax.random.PRNGKey(3), (2, 3, 6, 8, 5))
    native = nn.Conv(7, kernel_size, padding="SAME")
    params = _perturbed(native.init(jax.random.PRNGKey(0), x))
    ours = rsunet.XFoldConv(7, kernel_size)
    mine = ours.init(jax.random.PRNGKey(0), x)
    assert jax.tree_util.tree_map(jnp.shape, mine) == \
        jax.tree_util.tree_map(jnp.shape, params)
    np.testing.assert_array_equal(
        np.asarray(mine["params"]["kernel"]),
        np.asarray(native.init(jax.random.PRNGKey(0), x)["params"]["kernel"]))
    np.testing.assert_array_equal(np.asarray(ours.apply(params, x)),
                                  np.asarray(native.apply(params, x)))


@pytest.mark.parametrize("fold", [2, 4, 8])
@pytest.mark.parametrize("kernel_size", [(1, 5, 5), (3, 3, 3), (1, 1, 1)])
def test_folded_kernel_holds_published_weights_and_zeros(kernel_size, fold):
    """Every entry of the block-banded kernel is the published tap
    ``dx = fold*t + p_in - p_out`` or an exact zero: data movement only."""
    cin, cout = 3, 2
    kernel = np.asarray(jax.random.normal(
        jax.random.PRNGKey(4), kernel_size + (cin, cout)))
    folded, (lo, hi) = rsunet.fold_kernel(jnp.asarray(kernel), fold)
    folded = np.asarray(folded)
    h = (kernel_size[2] - 1) // 2
    assert lo == hi == -(-h // fold)
    assert folded.shape == kernel_size[:2] + (
        lo + hi + 1, fold * cin, fold * cout)
    blocks = folded.reshape(kernel_size[:2] + (-1, fold, cin, fold, cout))
    for t in range(-lo, hi + 1):
        for p_in in range(fold):
            for p_out in range(fold):
                dx = fold * t + p_in - p_out
                want = kernel[:, :, dx + h] if -h <= dx <= h else 0.0
                np.testing.assert_array_equal(
                    blocks[:, :, t + lo, p_in, :, p_out, :],
                    np.broadcast_to(want, blocks.shape[:2] + (cin, cout)))


# -- the decoder runs on the output patch's cone of dependence only ---------

# name -> (input patch, output patch): margins that cut level 0 alone, level
# 1 too, that are odd and no multiple of a fold, zero on one axis, zero on all
REGIONS = {
    "level0": ((8, 32, 32), (4, 16, 16)),
    "level1": ((8, 80, 80), (4, 32, 32)),
    "odd": ((8, 64, 64), (6, 46, 42)),        # margins 1, 9, 11
    "off-fold": ((8, 64, 64), (4, 34, 38)),   # margins 2, 15, 13
    "y-only": ((8, 64, 64), (8, 28, 64)),     # margins 0, 18, 0
    "x-only": ((8, 64, 64), (8, 64, 32)),     # margins 0, 0, 16
    "none": ((8, 32, 32), (8, 32, 32)),
}


def _forward_gauges(model, params, x, size):
    """The result with an output region and the ``forward/*`` gauges its
    trace set."""
    from chunkflow_tpu.core import telemetry

    telemetry.reset()
    try:
        out = model.apply(params, x, output_patch_size=size)
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.reset()
    return np.asarray(out), {k.split("/", 1)[1]: v for k, v in gauges.items()
                             if k.startswith("forward/")}


@pytest.mark.parametrize("region", sorted(REGIONS))
@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_output_region_equals_the_whole_forward_cropped(widths, region):
    pin, pout = REGIONS[region]
    model = rsunet.RSUNet(width=WIDTHS[widths])
    params = _params(widths, DOWN)
    x = jax.random.uniform(jax.random.PRNGKey(5), (2,) + pin + (1,))
    margin = [(i - o) // 2 for i, o in zip(pin, pout)]
    inner = (slice(None),) + tuple(
        slice(m, m + o) for m, o in zip(margin, pout))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply(params, x))[inner]
        got, gauges = _forward_gauges(model, params, x, pout)
    assert got.shape == want.shape == (2,) + pout + (3,)
    assert 0.05 < want.std()
    assert np.abs(got - want).max() <= 1e-6
    # and something was left out, wherever a margin is wider than the halo
    cut = [gauges[f"dec{i}_voxel_share"] for i in range(3)]
    assert all(0 < share <= 1 for share in cut) and cut[2] == 1.0
    if region == "none":
        assert cut == [1.0, 1.0, 1.0] and gauges["flops_share"] == 1.0
    else:
        assert cut[0] < 1 and gauges["flops_share"] < 1
        if region.startswith("level"):
            assert (cut[1] < 1) == (region == "level1")


@pytest.mark.parametrize("widths", sorted(WIDTHS))
def test_without_a_margin_the_forward_is_the_one_it_was(widths):
    """Every box is its whole array and no slice is emitted: the jaxpr
    with the whole patch as output region is the jaxpr without one."""
    model = rsunet.RSUNet(width=WIDTHS[widths], dtype=jnp.bfloat16)
    params = _params(widths, DOWN)
    x = jnp.zeros((2, 8, 32, 32, 1))
    plain = jax.make_jaxpr(lambda p, v: model.apply(p, v))(params, x)
    whole = jax.make_jaxpr(lambda p, v: model.apply(
        p, v, output_patch_size=(8, 32, 32)))(params, x)
    assert str(plain) == str(whole)
    cut = jax.make_jaxpr(lambda p, v: model.apply(
        p, v, output_patch_size=(4, 16, 16)))(params, x)
    assert str(cut) != str(plain)


@pytest.mark.parametrize("size", [(9, 32, 32), (8, 0, 32), (8, 32, 34)])
def test_an_output_patch_outside_the_patch_raises(size):
    model = rsunet.RSUNet()
    x = jnp.zeros((1, 8, 32, 32, 1))
    with pytest.raises(ValueError, match="no output patch"):
        jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), x,
                                          output_patch_size=size))


def _levels(patch):
    shapes = [patch]
    for factor in DOWN:
        shapes.append(tuple(n // f for n, f in zip(shapes[-1], factor)))
    return shapes


@pytest.mark.parametrize("patch,region,widths,folds,boxes", [
    # the production geometry, 20x256x256 -> 16x192x192 at F = 4 (PERF.md)
    ((20, 256, 256), ((2, 18), (32, 224), (32, 224)), "28-36-48-64",
     [4, 2, 1, 1], [
        ((0, 20), (28, 228), (28, 228)),
        ((0, 20), (10, 118), (10, 118)),
        ((0, 10), (0, 64), (0, 64)),   # 2 of 64 a side: under the halo
        ((0, 5), (0, 32), (0, 32))]),
    # the same at F = 8: the x box rounds out to whole blocks of 8
    ((20, 256, 256), ((2, 18), (32, 224), (32, 224)), "16-32-64-128",
     [8, 4, 1, 1], [
        ((0, 20), (28, 228), (24, 232)),
        ((0, 20), (10, 118), (8, 120)),
        ((0, 10), (0, 64), (0, 64)),
        ((0, 5), (0, 32), (0, 32))]),
    # a margin as wide as the halo cuts nothing: z here, and all of "none"
    ((8, 32, 32), ((2, 6), (8, 24), (8, 24)), "28-36-48-64", [4, 2, 1, 1], [
        ((0, 8), (4, 28), (4, 28)),
        ((0, 8), (0, 16), (0, 16)),
        ((0, 4), (0, 8), (0, 8)),
        ((0, 2), (0, 4), (0, 4))]),
    ((8, 32, 32), ((0, 8), (0, 32), (0, 32)), "28-36-48-64", [4, 2, 1, 1], [
        ((0, 8), (0, 32), (0, 32)),
        ((0, 8), (0, 16), (0, 16)),
        ((0, 4), (0, 8), (0, 8)),
        ((0, 2), (0, 4), (0, 4))]),
    # an odd region: grown by (2, 3, 3), then out to even rows and blocks
    ((8, 64, 64), ((1, 7), (9, 55), (11, 53)), "28-36-48-64", [4, 2, 1, 1], [
        ((0, 8), (6, 58), (4, 60)),
        ((0, 8), (0, 32), (0, 32)),
        ((0, 4), (0, 16), (0, 16)),
        ((0, 2), (0, 8), (0, 8))]),
])
def test_the_cone_of_dependence(patch, region, widths, folds, boxes):
    shapes = _levels(patch)
    assert rsunet.level_folds(WIDTHS[widths], patch[2], DOWN) == folds
    cone = rsunet.decoder_cone(shapes, region, DOWN, folds,
                               rsunet.BLOCK_HALO)
    assert [box for box, _ in cone] == boxes
    assert rsunet.BLOCK_HALO == (2, 3, 3)
    for i, ((box, want), shape) in enumerate(zip(cone, shapes)):
        for (lo, hi), (w_lo, w_hi), n in zip(box, want, shape):
            assert 0 <= lo <= w_lo < w_hi <= hi <= n
        if i < len(DOWN):  # the level below emits exactly the box
            assert cone[i + 1][1] == tuple(
                (lo // f, hi // f) for (lo, hi), f in zip(box, DOWN[i]))
            assert all(lo % f == 0 and hi % f == 0
                       for (lo, hi), f in zip(box, DOWN[i]))
    # every slice in x takes whole blocks of its level's fold
    for (box, want), fold in zip(cone, folds):
        assert all(edge % fold == 0 for edge in box[2] + want[2])


@pytest.mark.parametrize("in_fold,fold", [(1, 2), (1, 4), (2, 4), (2, 8),
                                          (4, 8)])
@pytest.mark.parametrize("factor", [(1, 2, 2), (2, 2, 2)])
def test_upsampling_of_a_folded_input_is_the_transposed_convolution(
        factor, in_fold, fold):
    """``XFoldUp`` takes the fold its input arrives in and emits the fold
    asked for: ``nn.ConvTranspose`` on the unfolded array, folded."""
    cin, features = 5, 7
    x = jax.random.uniform(jax.random.PRNGKey(6), (2, 3, 4, 16, cin))
    native = nn.ConvTranspose(features, kernel_size=factor, strides=factor)
    params = _perturbed(native.init(jax.random.PRNGKey(0), x))
    ours = rsunet.XFoldUp(features, factor=factor, fold=fold,
                          in_fold=in_fold)
    folded = rsunet.fold_x(x, in_fold)
    mine = ours.init(jax.random.PRNGKey(0), folded)
    assert jax.tree_util.tree_map(jnp.shape, mine) == \
        jax.tree_util.tree_map(jnp.shape, params)
    with jax.default_matmul_precision("highest"):
        want = rsunet.fold_x(native.apply(params, x), fold)
        got = ours.apply(params, folded)
    assert got.shape == want.shape == (
        2, 3 * factor[0], 8, 32 // fold, fold * features)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 1e-6


def _equations(jaxpr, found=None):
    """Primitive names of a jaxpr's equations, inner jaxprs included."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn.primitive.name)
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _equations(inner, found)
    return found


# factor -> the convolutions that emit its rows: one a z plane of the
# factor, each of the input dilated with zeros in y
EMITTED = {(1, 2, 2): 1, (2, 2, 2): 2}
# the part of a (3, 4, 16) input to up-sample, as the cone cuts it: none,
# y and x blocks, every axis, one side only
CUTS = {
    "whole": ((0, 3), (0, 4), (0, 16)),
    "yx": ((0, 3), (1, 3), (4, 12)),
    "zyx": ((1, 3), (1, 4), (8, 16)),
    "low-side": ((0, 2), (0, 3), (0, 8)),
}


@pytest.mark.parametrize("in_fold,fold", [(1, 2), (2, 4), (4, 8)])
@pytest.mark.parametrize("factor", sorted(EMITTED))
def test_upsampling_emits_the_convolutions_it_says(factor, in_fold, fold):
    """``XFoldUp.convolutions`` (the gauge ``forward/up{i}_convolutions``)
    against the jaxpr: that many convolutions, each dilated by the
    factor's y, stacked where there is more than one and not otherwise."""
    up = rsunet.XFoldUp(7, factor=factor, fold=fold, in_fold=in_fold)
    assert up.convolutions == EMITTED[factor]
    x = jnp.zeros((2, 3, 4, 16 // in_fold, in_fold * 5))
    params = jax.eval_shape(lambda: up.init(jax.random.PRNGKey(0), x))
    jaxpr = jax.make_jaxpr(up.apply)(params, x).jaxpr
    names = _equations(jaxpr)
    assert names.count("conv_general_dilated") == up.convolutions
    assert ("concatenate" in names) == (up.convolutions > 1)
    assert {eqn.params["lhs_dilation"] for eqn in jaxpr.eqns
            if eqn.primitive.name == "conv_general_dilated"} \
        == {(1, factor[1], 1)}


@pytest.mark.parametrize("cut", sorted(CUTS))
@pytest.mark.parametrize("factor", sorted(EMITTED))
def test_upsampling_cut_by_its_box_is_the_upsampling_of_the_slice(factor,
                                                                  cut):
    """``want`` inside ``held``: the values are those of the sliced input
    up-sampled, and the convolutions take the cut as (negative) padding,
    so no activation is sliced; with ``want == held`` the jaxpr is the
    one without a box."""
    in_fold, fold, cin = 2, 4, 5
    held = ((4, 7), (2, 6), (8, 24))   # a box of some level, not at 0
    want = tuple((lo + h0, hi + h0)
                 for (lo, hi), (h0, _) in zip(CUTS[cut], held))
    x = jax.random.uniform(jax.random.PRNGKey(8), (2, 3, 4, 16, cin))
    folded = rsunet.fold_x(x, in_fold)
    up = rsunet.XFoldUp(7, factor=factor, fold=fold, in_fold=in_fold)
    params = _perturbed(up.init(jax.random.PRNGKey(0), folded))
    (z0, z1), (y0, y1), (x0, x1) = CUTS[cut]
    with jax.default_matmul_precision("highest"):
        expected = up.apply(params, rsunet.fold_x(
            x[:, z0:z1, y0:y1, x0:x1], in_fold))
        got = up.apply(params, folded, want, held)
    assert got.shape == expected.shape == (
        2, (z1 - z0) * factor[0], (y1 - y0) * 2, (x1 - x0) * 2 // fold,
        fold * 7)
    assert np.abs(np.asarray(got) - np.asarray(expected)).max() <= 1e-6
    boxed = jax.make_jaxpr(
        lambda p, v: up.apply(p, v, want, held))(params, folded).jaxpr
    # the only slices take a z plane of the kernel
    assert all(eqn.invars[0].aval.shape[-1] == fold * 7
               for eqn in boxed.eqns if eqn.primitive.name == "slice")
    if cut == "whole":
        assert _equations(boxed) == _equations(
            jax.make_jaxpr(up.apply)(params, folded).jaxpr)


def _gauges_of_config(config: str):
    """The gauges the model's trace sets at the widths and the (rehearsal)
    geometry of a benchmark configuration, the configuration's model
    block and the x extent."""
    import json

    from chunkflow_tpu.core import telemetry

    with open(os.path.join(os.path.dirname(__file__), "..", "..",
                           "benchmarks", "configs", config + ".json")) as f:
        cfg = json.load(f)
    spec, geometry = cfg["model"], cfg["rehearse"]
    model = rsunet.RSUNet(
        in_channels=spec["in_channels"], out_channels=spec["out_channels"],
        width=tuple(spec["width"]),
        down_factors=tuple(map(tuple, spec["pooling"])),
        dtype=jnp.dtype(spec["compute_dtype"]))
    x = jnp.zeros((1, *geometry["patch"], spec["in_channels"]))
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), x))
    telemetry.reset()
    try:
        jax.eval_shape(lambda p, v: model.apply(
            p, v, output_patch_size=geometry.get("output_patch")), params, x)
        return telemetry.snapshot()["gauges"], spec, x.shape[3]
    finally:
        telemetry.reset()


@pytest.mark.parametrize("config", ["rsunet-superhuman", "rsunet-deepem",
                                    "rsunet-superhuman-prod"])
def test_every_decoder_level_says_how_its_upsampling_is_emitted(config):
    """``forward/up{i}_convolutions``, one gauge a decoder level, at the
    widths and the (rehearsal) geometry of each benchmark configuration:
    ``up0`` (1,2,2) is one dilated convolution, ``up1`` (2,2,2) two of
    them stacked in z, ``up2`` below the folded levels flax's own
    ConvTranspose."""
    gauges, spec, x_extent = _gauges_of_config(config)
    assert [gauges[f"forward/up{i}_convolutions"]
            for i in range(len(spec["width"]) - 1)] == [1, 2, 1]
    assert rsunet.level_folds(spec["width"], x_extent,
                              spec["pooling"])[2:] == [1, 1]


@pytest.mark.parametrize("config", ["rsunet-superhuman", "rsunet-deepem"])
def test_every_pool_says_whether_it_runs_on_the_folded_array(config):
    """``forward/pool{i}_folded``, one gauge a pool: ``pool0`` and
    ``pool1`` take their maximum on the x-folded array
    (:func:`rsunet.max_pool_folded`), ``pool2``, between two unfolded
    levels, is ``nn.max_pool``."""
    gauges, spec, _ = _gauges_of_config(config)
    assert [gauges[f"forward/pool{i}_folded"]
            for i in range(len(spec["width"]) - 1)] == [1, 1, 0]


# every (factor, fold) a configuration runs: (1,2,2) x 4 and x 8 (pool0),
# (2,2,2) x 2 and x 4 (pool1), fold == fx; (2,2,1) hands the fold down whole
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fold", [2, 4, 8])
@pytest.mark.parametrize("factor", [(1, 2, 2), (2, 2, 2), (2, 2, 1)])
def test_pool_of_a_folded_array_is_the_max_pool(factor, fold, dtype):
    """``max_pool_folded`` hands down the fold over the x factor: the
    values are ``nn.max_pool``'s of the unfolded array, bit for bit, in
    either compute dtype, where a window holds negative values only (the
    window starts from -inf and not from 0) and where it holds
    none."""
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 4, 6, 32, 5))
    x = x.at[0, :2, :2, :16].set(-1.0 - jnp.abs(x[0, :2, :2, :16]))
    x = x.at[1, 2:, 4:, 16:].set(jnp.abs(x[1, 2:, 4:, 16:]))
    x = x.astype(dtype)
    want = nn.max_pool(x, window_shape=factor, strides=factor)
    assert (np.asarray(want[0, 0, 0, :8], np.float32) <= -1.0).all()
    got = rsunet.max_pool_folded(rsunet.fold_x(x, fold), factor, fold)
    handed = fold // factor[2]
    assert got.dtype == dtype
    assert got.shape == rsunet.fold_x(want, handed).shape
    np.testing.assert_array_equal(
        np.asarray(rsunet.unfold_x(got, handed), np.float32),
        np.asarray(want, np.float32))


@pytest.mark.parametrize("factor,fold", [((1, 2, 2), 4), ((1, 2, 2), 8),
                                         ((2, 2, 2), 2), ((2, 2, 2), 4)])
def test_the_pool_takes_z_and_y_as_a_window_on_the_folded_array(factor, fold):
    """The form of ``max_pool_folded``: one ``reduce_window_max`` over z
    and y on the x-folded array, then the maximum inside the lanes. No
    array of rank 7 (``[b, z/fz, fz, y/fy, fy, x, c]`` and a ``max`` over
    the window axes put y in the lanes on the chip and cost seven
    full-size passes between ``enc{i}/conv3`` and ``enc{i+1}/conv1``:
    PERF.md, PR 43), no reduce and no transpose."""
    x = jnp.zeros((2, 4, 8, 16 // fold, fold * 5), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda v: rsunet.max_pool_folded(v, factor, fold))(x).jaxpr
    windows = [eqn for eqn in jaxpr.eqns
               if eqn.primitive.name == "reduce_window_max"]
    assert len(windows) == 1
    window, = windows
    assert window.invars[0] is jaxpr.invars[0]   # the folded array itself
    assert window.params["window_dimensions"] == (1, *factor[:2], 1, 1)
    assert window.params["window_strides"] == (1, *factor[:2], 1, 1)
    assert all(pad == (0, 0) for pad in window.params["padding"])
    assert {eqn.primitive.name for eqn in jaxpr.eqns} <= {
        "reduce_window_max", "slice", "max", "concatenate"}
    assert all(len(var.aval.shape) == 5
               for eqn in jaxpr.eqns for var in eqn.outvars)


@pytest.mark.parametrize("config", ["rsunet-superhuman", "rsunet-deepem",
                                    "rsunet-superhuman-prod"])
def test_the_modules_whole_patch_flops_are_the_benchmarks(config):
    """``forward/flops_share`` is over the count of
    ``benchmarks/flops/rsunet.py``: the module's own formula gives the
    same number for the whole patch of every configuration."""
    import importlib.util
    import json

    bench = os.path.join(os.path.dirname(__file__), "..", "..", "benchmarks")
    spec = importlib.util.spec_from_file_location(
        "bench_flops_rsunet", os.path.join(bench, "flops", "rsunet.py"))
    flops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flops)
    with open(os.path.join(bench, "configs", config + ".json")) as f:
        cfg = json.load(f)
    voxels = [int(np.prod(shape)) for shape in _levels(tuple(cfg["patch"]))]
    model = cfg["model"]
    assert [tuple(f) for f in model["pooling"]] == list(DOWN)
    assert rsunet.forward_flops(
        model["width"], model["in_channels"], model["out_channels"],
        voxels, voxels[:-1], voxels[0]) == flops.flops_per_patch(cfg)
