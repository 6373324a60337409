"""Flax 3D UNet: the native convnet engine for patch inference.

Replaces the reference's PyTorch engine (patch/pytorch.py) with a
TPU-idiomatic model: channels-last (NDHWC) so XLA tiles convs onto the MXU,
anisotropic down/upsampling for EM stacks (z is usually coarser), instance
normalization (the reference ships a BatchNorm3d->InstanceNorm3d converter
for exactly this reason — examples/inference/batchnorm3d_to_instancenorm3d.py),
and optional bfloat16 compute with float32 params.

Architecture follows the residual symmetric UNet family used by the
reference's production affinity models: conv-in -> E encoder stages
(downsample + residual block) -> bridge -> mirrored decoder with skip
connections -> conv-out (sigmoid for affinity/probability outputs).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

Triple = Tuple[int, int, int]


class ConvBlock(nn.Module):
    """Two 3x3x3 convs with instance norm + elu, residual add."""

    features: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        # submodule names mirror the torch conventions (conv1/norm1/...)
        # so checkpoint conversion can pair parameters by name
        residual = x
        x = nn.Conv(self.features, (3, 3, 3), padding="SAME",
                    dtype=self.dtype, name="conv1")(x)
        x = nn.GroupNorm(num_groups=None, group_size=1, epsilon=1e-5,
                         dtype=self.dtype, use_fast_variance=False,
                         name="norm1")(x)
        x = nn.elu(x)
        x = nn.Conv(self.features, (3, 3, 3), padding="SAME",
                    dtype=self.dtype, name="conv2")(x)
        x = nn.GroupNorm(num_groups=None, group_size=1, epsilon=1e-5,
                         dtype=self.dtype, use_fast_variance=False,
                         name="norm2")(x)
        if residual.shape[-1] == self.features:
            x = x + residual
        x = nn.elu(x)
        return x


class UNet3D(nn.Module):
    """Symmetric residual 3D UNet, channels-last.

    feature_maps[i] is the width at encoder depth i; down_factors[i] is the
    (z, y, x) pooling factor between depth i and i+1 (anisotropic by
    default: no z-pooling at the first transition, matching 20x256x256-style
    EM patches).
    """

    in_channels: int = 1
    out_channels: int = 3
    feature_maps: Sequence[int] = (28, 36, 48, 64)
    down_factors: Sequence[Triple] = ((1, 2, 2), (2, 2, 2), (2, 2, 2))
    dtype: jnp.dtype = jnp.float32
    final_activation: str = "sigmoid"

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        x = x.astype(self.dtype)
        depth = len(self.feature_maps)
        assert len(self.down_factors) == depth - 1

        x = nn.Conv(self.feature_maps[0], (1, 5, 5), padding="SAME",
                    dtype=self.dtype, name="conv_in")(x)

        skips = []
        for i in range(depth - 1):
            x = ConvBlock(self.feature_maps[i], dtype=self.dtype,
                          name=f"enc{i}")(x)
            skips.append(x)
            x = nn.max_pool(
                x,
                window_shape=self.down_factors[i],
                strides=self.down_factors[i],
            )

        x = ConvBlock(self.feature_maps[-1], dtype=self.dtype,
                      name="bridge")(x)

        for i in reversed(range(depth - 1)):
            x = nn.ConvTranspose(
                self.feature_maps[i],
                kernel_size=self.down_factors[i],
                strides=self.down_factors[i],
                dtype=self.dtype,
                name=f"up{i}",
            )(x)
            x = x + skips[i]
            x = ConvBlock(self.feature_maps[i], dtype=self.dtype,
                          name=f"dec{i}")(x)

        x = nn.Conv(self.out_channels, (1, 5, 5), padding="SAME",
                    dtype=self.dtype, name="conv_out")(x)
        x = x.astype(jnp.float32)
        if self.final_activation == "sigmoid":
            x = jax.nn.sigmoid(x)
        elif self.final_activation == "none":
            pass
        else:
            raise ValueError(self.final_activation)
        return x.astype(orig_dtype) if orig_dtype == jnp.bfloat16 else x


def init_params(model: nn.Module, input_patch_size, num_input_channels: int,
                seed: int = 0):
    shape = (1,) + tuple(input_patch_size) + (num_input_channels,)
    # jitted: the initializers need shapes only, so XLA drops the forward
    # pass an eager init would run op by op (77 s for the RSUNet at
    # 20x256x256 on a v5e, my chip run, PR 21)
    variables = jax.jit(model.init)(
        jax.random.PRNGKey(seed), jnp.zeros(shape, jnp.float32))
    return variables["params"]


def init_or_load_params(
    model: nn.Module,
    weight_path: Optional[str],
    input_patch_size,
    num_input_channels: int,
):
    """Load params from a checkpoint, converting torch state dicts.

    - ``None``/missing -> fresh random init (useful for benchmarks/tests)
    - ``*.pt`` / ``*.pth`` -> torch state_dict via the converter
    - ``*.msgpack``        -> flax serialized params
    - directory            -> orbax checkpoint
    """
    if weight_path is None or weight_path == "":
        return init_params(model, input_patch_size, num_input_channels)
    if not os.path.exists(weight_path):
        raise FileNotFoundError(f"weights not found: {weight_path}")
    if weight_path.endswith((".pt", ".pth")):
        from chunkflow_tpu.models.converter import (
            NameConversionError,
            load_torch_state_dict,
            torch_to_flax,
            torch_to_flax_by_name,
        )

        template = init_params(model, input_patch_size, num_input_channels)
        state = load_torch_state_dict(weight_path)
        try:
            # name-based pairing first: exact for mirrored module names
            # (e.g. RSUNet checkpoints), independent of definition order
            return torch_to_flax_by_name(state, template)
        except NameConversionError as e:
            if e.matched > 0:
                # the trees clearly share names; a positional fallback
                # could silently pair same-shape tensors to wrong layers
                raise
            # disjoint naming: positional pairing for models whose
            # definition order mirrors execution order
            return torch_to_flax(state, template)
    if weight_path.endswith(".msgpack"):
        from flax import serialization

        template = init_params(model, input_patch_size, num_input_channels)
        with open(weight_path, "rb") as f:
            return serialization.from_bytes(template, f.read())
    # orbax checkpoint directory
    import orbax.checkpoint as ocp

    checkpointer = ocp.StandardCheckpointer()
    template = init_params(model, input_patch_size, num_input_channels)
    return checkpointer.restore(os.path.abspath(weight_path), template)


def save_params(params, path: str) -> str:
    from flax import serialization

    with open(path, "wb") as f:
        f.write(serialization.to_bytes(params))
    return path
