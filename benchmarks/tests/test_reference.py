"""The plain reference forward against models/rsunet.py, at both widths, at
a tiny patch on the CPU: the two implementations share no code, so their
agreement to float32 rounding says both compute the same network."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cfbench import catalog


@pytest.mark.parametrize("width", [(28, 36, 48, 64), (16, 32, 64, 128)])
def test_plain_forward_matches_the_programs_module(width):
    from chunkflow_tpu.models import rsunet, unet3d

    patch = (4, 16, 16)
    model = rsunet.RSUNet(in_channels=1, out_channels=3, width=width)
    logits = model.clone(final_activation="none")
    params = unet3d.init_params(model, patch, 1)
    # the seeded init leaves biases 0 and scales 1: perturb every leaf so
    # that a dropped bias or affine would show
    leaves, tree = jax.tree.flatten(params)
    rng = np.random.default_rng(0)
    leaves = [leaf + 0.03 * rng.standard_normal(leaf.shape).astype(np.float32)
              for leaf in leaves]
    params = jax.tree.unflatten(tree, leaves)
    x = jnp.asarray(rng.random((2,) + patch + (1,), dtype=np.float32))
    want = np.asarray(model.apply({"params": params}, x))
    reference = catalog.load_module("reference", "rsunet")
    got = np.asarray(reference.forward(params, x))
    assert got.shape == want.shape == (2,) + patch + (3,)
    assert 1e-3 < want.std() < 0.45      # not saturated
    # float32 on the CPU: rounding only
    assert np.abs(got - want).max() < 2e-5
    # and before the sigmoid, relative to the logits' scale
    want = np.asarray(logits.apply({"params": params}, x))
    got = np.asarray(reference.forward(params, x, final_activation="none"))
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()
