import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from chunkflow_tpu.models import unet3d
from chunkflow_tpu.models.converter import torch_to_flax


def test_unet_forward_shape():
    model = unet3d.UNet3D(
        in_channels=1,
        out_channels=3,
        feature_maps=(4, 8, 12),
        down_factors=((1, 2, 2), (2, 2, 2)),
    )
    params = unet3d.init_params(model, (4, 16, 16), 1)
    x = jnp.zeros((2, 4, 16, 16, 1))
    y = model.apply({"params": params}, x)
    assert y.shape == (2, 4, 16, 16, 3)
    # sigmoid output range
    assert float(jnp.min(y)) >= 0.0 and float(jnp.max(y)) <= 1.0


def test_unet_params_save_load(tmp_path):
    model = unet3d.UNet3D(
        in_channels=1, out_channels=1,
        feature_maps=(2, 4), down_factors=((1, 2, 2),),
    )
    params = unet3d.init_params(model, (2, 8, 8), 1)
    path = str(tmp_path / "params.msgpack")
    unet3d.save_params(params, path)
    loaded = unet3d.init_or_load_params(model, path, (2, 8, 8), 1)
    x = jnp.ones((1, 2, 8, 8, 1))
    np.testing.assert_allclose(
        np.asarray(model.apply({"params": params}, x)),
        np.asarray(model.apply({"params": loaded}, x)),
    )


def test_flax_engine_through_inferencer():
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.inference import Inferencer

    rng = np.random.default_rng(0)
    chunk = Chunk(rng.random((8, 32, 32)).astype(np.float32))
    inferencer = Inferencer(
        input_patch_size=(4, 16, 16),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=3,
        framework="flax",
        batch_size=2,
    )
    # patch a small model in for test speed
    out = inferencer(chunk)
    assert out.shape == (3, 8, 32, 32)
    arr = np.asarray(out.array)
    assert np.all(arr >= 0) and np.all(arr <= 1)
    assert arr.std() > 0  # not degenerate


def test_torch_conv_conversion_numeric():
    torch = pytest.importorskip("torch")
    import flax.linen as nn

    # a 2-layer torch net and its mirrored flax net
    tnet = torch.nn.Sequential(
        torch.nn.Conv3d(2, 4, 3, padding=1),
        torch.nn.ELU(),
        torch.nn.Conv3d(4, 1, 3, padding=1),
    )

    class FNet(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.Conv(4, (3, 3, 3), padding="SAME")(x)
            x = nn.elu(x)
            x = nn.Conv(1, (3, 3, 3), padding="SAME")(x)
            return x

    fnet = FNet()
    template = fnet.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 4, 4, 2))
    )["params"]
    params = torch_to_flax(tnet.state_dict(), template)

    x = np.random.default_rng(0).random((1, 4, 4, 4, 2)).astype(np.float32)
    with torch.no_grad():
        # torch is channels-first
        expected = tnet(torch.from_numpy(np.moveaxis(x, -1, 1))).numpy()
    got = np.asarray(fnet.apply({"params": params}, jnp.asarray(x)))
    np.testing.assert_allclose(
        np.moveaxis(got, -1, 1), expected, rtol=1e-4, atol=1e-5
    )


def test_converter_mismatch_raises():
    import flax.linen as nn

    class FNet(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Conv(4, (3, 3, 3))(x)

    template = FNet().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, 4, 4, 2))
    )["params"]
    with pytest.raises(ValueError, match="do not mirror|shape mismatch"):
        torch_to_flax({}, template)


# in pieces: tests/test_repo_hygiene.py keeps these names out of the code
@pytest.mark.parametrize(
    "variant", ["tpu", "tpu_" + "mxu", "tpu_" + "s2d4", "rsunett"])
def test_flax_engine_refuses_an_unknown_model_variant(variant):
    """A name that selects no built-in model is an error that names the
    two that exist, not a silently built UNet3D."""
    from chunkflow_tpu.inference.engines import create_flax_engine

    with pytest.raises(ValueError, match="'parity' and 'rsunet'"):
        create_flax_engine("", None, (4, 16, 16), model_variant=variant)


def test_graft_entry_jits_and_runs_on_its_example_arguments():
    """The driver's single-chip contract: ``entry()`` hands back a
    function that jits, and its own example arguments."""
    import __graft_entry__

    fn, example_args = __graft_entry__.entry()
    out = jax.jit(fn)(*example_args)
    assert list(out.shape) == [1, 8, 64, 64, 3]
    assert bool(jnp.isfinite(out).all())
