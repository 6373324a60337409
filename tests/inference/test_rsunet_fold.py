"""The x-folded full-resolution level of the RSUNet is an exact
re-lowering: the folded forward equals the same module held to F = 1, the
fold factor follows from width[0] and the x extent alone, the parameter
tree is what converted checkpoints were written against, and a fold of 1
is the plain convolution."""
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
    fold = rsunet.x_fold(width[0], x_extent)
    model = rsunet.RSUNet(width=width, down_factors=down,
                          dtype=jnp.dtype(dtype))
    x = jax.random.uniform(jax.random.PRNGKey(0), (2, 4, 16, x_extent, 1))
    params = _params(widths, down)

    def forward():  # a new function each time: nothing cached across folds
        return np.asarray(jax.jit(lambda p, v: model.apply(p, v))(params, x))

    seen = []
    real = rsunet.XFoldConv.__call__

    def spy(self, x):
        seen.append(self.fold)
        return real(self, x)

    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(rsunet.XFoldConv, "__call__", spy)
        folded = forward()
        assert max(seen) == fold
        # level 0: embed, enc0, dec0, out; everything below runs at 1
        assert seen.count(fold) == (8 if fold > 1 else len(seen))
        monkeypatch.setattr(rsunet, "x_fold", lambda width0, x: 1)
        del seen[:]
        plain = forward()
        assert set(seen) == {1}
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
