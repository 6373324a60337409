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
import numpy as np

Triple = Tuple[int, int, int]


def _make_conv(conv_impl: str, features: int, kernel_size: Triple,
               dtype, name: str):
    """nn.Conv or its MXU-lowered twin — identical parameter trees, so
    ``conv_impl`` is a pure lowering choice (checkpoints interchange)."""
    if conv_impl == "mxu":
        return MxuConv(features, kernel_size, dtype=dtype, name=name)
    return nn.Conv(features, kernel_size, padding="SAME", dtype=dtype,
                   name=name)


class ConvBlock(nn.Module):
    """Two 3x3x3 convs with instance norm + elu, residual add."""

    features: int
    dtype: jnp.dtype = jnp.float32
    conv_impl: str = "native"

    @nn.compact
    def __call__(self, x):
        # submodule names mirror the torch conventions (conv1/norm1/...)
        # so checkpoint conversion can pair parameters by name
        residual = x
        x = _make_conv(self.conv_impl, self.features, (3, 3, 3),
                       self.dtype, "conv1")(x)
        x = nn.GroupNorm(num_groups=None, group_size=1, epsilon=1e-5,
                         dtype=self.dtype, use_fast_variance=False,
                         name="norm1")(x)
        x = nn.elu(x)
        x = _make_conv(self.conv_impl, self.features, (3, 3, 3),
                       self.dtype, "conv2")(x)
        x = nn.GroupNorm(num_groups=None, group_size=1, epsilon=1e-5,
                         dtype=self.dtype, use_fast_variance=False,
                         name="norm2")(x)
        if residual.shape[-1] == self.features:
            x = x + residual
        x = nn.elu(x)
        return x


def space_to_depth(x, factor: Triple):
    """[B, D, H, W, C] -> [B, D/fz, H/fy, W/fx, C*fz*fy*fx] (lossless)."""
    b, d, h, w, c = x.shape
    fz, fy, fx = factor
    x = x.reshape(b, d // fz, fz, h // fy, fy, w // fx, fx, c)
    x = x.transpose(0, 1, 3, 5, 2, 4, 6, 7)
    return x.reshape(b, d // fz, h // fy, w // fx, fz * fy * fx * c)


def depth_to_space(x, factor: Triple):
    """Inverse of :func:`space_to_depth`."""
    b, d, h, w, c = x.shape
    fz, fy, fx = factor
    cout = c // (fz * fy * fx)
    x = x.reshape(b, d, h, w, fz, fy, fx, cout)
    x = x.transpose(0, 1, 4, 2, 5, 3, 6, 7)
    return x.reshape(b, d * fz, h * fy, w * fx, cout)


class MxuConv(nn.Module):
    """Drop-in for ``nn.Conv(features, kernel_size, padding='SAME')`` with
    an identical parameter tree, lowered as z-decomposed 2D convolutions.

    XLA's native Conv3D lowering on TPU is expected to underuse the MXU
    at these channel counts (device number: not measured); a
    (kz, ky, kx) conv is mathematically the sum of
    kz z-shifted (ky, kx) 2D convs, and 2D convs with depth merged into
    batch hit the battle-tested conv2d path. Same FLOPs, same parameters
    (kernel [kz,ky,kx,Cin,F] + bias); partials are accumulated in float32
    (preferred_element_type) and rounded to the compute dtype once, so
    bf16 numerics track native Conv3D's single-rounding accumulation —
    asserted by tests/inference/test_mxu_conv.py; on-chip A/B: not
    measured."""

    features: int
    kernel_size: Triple
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        from jax import lax

        kz, ky, kx = self.kernel_size
        cin = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (kz, ky, kx, cin, self.features),
        )
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        x = x.astype(self.dtype)
        k = jnp.asarray(kernel, self.dtype)
        b, d, h, w, _ = x.shape
        if kz > 1:
            # flax SAME padding: lo=(k-1)//2, hi=k//2
            x = jnp.pad(x, ((0, 0), ((kz - 1) // 2, kz // 2),
                            (0, 0), (0, 0), (0, 0)))
        acc = None
        for dz in range(kz):
            xs = lax.slice_in_dim(x, dz, dz + d, axis=1)
            y = lax.conv_general_dilated(
                xs.reshape(b * d, h, w, cin),
                k[dz],
                window_strides=(1, 1),
                padding="SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32,
            )
            acc = y if acc is None else acc + y
        acc = acc.reshape(b, d, h, w, self.features)
        acc = acc + jnp.asarray(bias, jnp.float32)
        return acc.astype(self.dtype)


class MxuConvTranspose(nn.Module):
    """Drop-in for ``nn.ConvTranspose(features, k, strides=k)`` (the
    kernel==strides upsampling used by the decoder) with an identical
    parameter tree, lowered as one 1x1x1 GEMM + depth_to_space.

    With kernel == strides the transposed conv's output blocks never
    overlap: each input position emits an independent (fz, fy, fx, F)
    block — i.e. a pure channel matmul (MXU-native) followed by a lossless
    pixel shuffle, instead of XLA's general gradient-conv lowering."""

    features: int
    factor: Triple
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        fz, fy, fx = self.factor
        cin = x.shape[-1]
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (fz, fy, fx, cin, self.features),
        )
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        x = x.astype(self.dtype)
        # lax.conv_transpose places the spatially FLIPPED kernel in each
        # output block (verified with a one-hot probe), so flip to match
        # nn.ConvTranspose exactly — checkpoints must interchange
        k = jnp.asarray(kernel, self.dtype)[::-1, ::-1, ::-1]
        # [fz,fy,fx,Cin,F] -> [Cin, fz*fy*fx*F] with channel order
        # (i, j, k, f) — exactly what depth_to_space expects
        k2 = k.transpose(3, 0, 1, 2, 4).reshape(cin, fz * fy * fx * self.features)
        y = x @ k2
        y = depth_to_space(y, self.factor)
        return y + jnp.asarray(bias, self.dtype)


class UNet3D(nn.Module):
    """Symmetric residual 3D UNet, channels-last.

    feature_maps[i] is the width at encoder depth i; down_factors[i] is the
    (z, y, x) pooling factor between depth i and i+1 (anisotropic by
    default: no z-pooling at the first transition, matching 20x256x256-style
    EM patches).

    ``s2d_factor`` enables the TPU-optimized stem: the input is losslessly
    space-to-depth'd (e.g. (1, 2, 2) turns [D, H, W, C] into
    [D, H/2, W/2, 4C]) so the widest full-resolution stages run with 4x the
    channels at 1/4 the positions — same FLOPs and bandwidth for a given
    feature_maps, but far better MXU lane (128) utilization than the
    reference models' 28-36 channels; the output head is depth-to-space'd
    back to full resolution. EM convnets on GPUs never need this because
    warps don't care about channel counts; the systolic array does.
    """

    in_channels: int = 1
    out_channels: int = 3
    feature_maps: Sequence[int] = (28, 36, 48, 64)
    down_factors: Sequence[Triple] = ((1, 2, 2), (2, 2, 2), (2, 2, 2))
    dtype: jnp.dtype = jnp.float32
    final_activation: str = "sigmoid"
    s2d_factor: Optional[Triple] = None
    conv_impl: str = "native"  # "native" (XLA Conv3D) | "mxu" (2D/GEMM)

    @nn.compact
    def __call__(self, x):
        orig_dtype = x.dtype
        x = x.astype(self.dtype)
        depth = len(self.feature_maps)
        assert len(self.down_factors) == depth - 1
        assert self.conv_impl in ("native", "mxu"), self.conv_impl

        if self.s2d_factor is not None:
            x = space_to_depth(x, self.s2d_factor)

        x = _make_conv(self.conv_impl, self.feature_maps[0], (1, 5, 5),
                       self.dtype, "conv_in")(x)

        skips = []
        for i in range(depth - 1):
            x = ConvBlock(self.feature_maps[i], dtype=self.dtype,
                          conv_impl=self.conv_impl, name=f"enc{i}")(x)
            skips.append(x)
            x = nn.max_pool(
                x,
                window_shape=self.down_factors[i],
                strides=self.down_factors[i],
            )

        x = ConvBlock(self.feature_maps[-1], dtype=self.dtype,
                      conv_impl=self.conv_impl, name="bridge")(x)

        for i in reversed(range(depth - 1)):
            if self.conv_impl == "mxu":
                x = MxuConvTranspose(
                    self.feature_maps[i],
                    factor=self.down_factors[i],
                    dtype=self.dtype,
                    name=f"up{i}",
                )(x)
            else:
                x = nn.ConvTranspose(
                    self.feature_maps[i],
                    kernel_size=self.down_factors[i],
                    strides=self.down_factors[i],
                    dtype=self.dtype,
                    name=f"up{i}",
                )(x)
            x = x + skips[i]
            x = ConvBlock(self.feature_maps[i], dtype=self.dtype,
                          conv_impl=self.conv_impl, name=f"dec{i}")(x)

        if self.s2d_factor is None:
            x = _make_conv(self.conv_impl, self.out_channels, (1, 5, 5),
                           self.dtype, "conv_out")(x)
        else:
            fz, fy, fx = self.s2d_factor
            x = _make_conv(self.conv_impl,
                           self.out_channels * fz * fy * fx, (1, 5, 5),
                           self.dtype, "conv_out")(x)
            x = depth_to_space(x, self.s2d_factor)
        x = x.astype(jnp.float32)
        if self.final_activation == "sigmoid":
            x = jax.nn.sigmoid(x)
        elif self.final_activation == "none":
            pass
        else:
            raise ValueError(self.final_activation)
        return x.astype(orig_dtype) if orig_dtype == jnp.bfloat16 else x


def create_tpu_optimized_model(
    in_channels: int = 1,
    out_channels: int = 3,
    dtype=jnp.bfloat16,
    conv_impl: str = "native",
    s2d_factor: Triple = (1, 2, 2),
) -> "UNet3D":
    """The flagship affinity model tuned for the MXU.

    Space-to-depth stem with widths scaled by sqrt(prod(s2d_factor))
    relative to the reference-class model (28, 36, 48, 64): at the
    full-resolution level the per-voxel FLOPs are identical
    ((28*s)^2 / s^2 == 28^2) but convs run with wide channels, so the
    128-lane systolic array stays busy; compute in bfloat16 with float32
    params and output. The default (1, 2, 2) stem gives 56-128 channels;
    the aggressive (1, 4, 4) stem (battery A/B ``fwd_tpu_s2d4``) gives
    112-256 channels at 1/16 the positions — trading first-stage
    receptive-field granularity for near-saturated MXU lanes.

    ``conv_impl='mxu'`` additionally lowers every conv as z-decomposed 2D
    convs / GEMM upsampling (MxuConv / MxuConvTranspose) — identical
    parameters and numerics, different XLA lowering; on-chip A/B: not
    measured.
    """
    scale = int(round(float(np.prod(s2d_factor)) ** 0.5))
    return UNet3D(
        in_channels=in_channels,
        out_channels=out_channels,
        feature_maps=tuple(w * scale for w in (28, 36, 48, 64)),
        down_factors=((1, 2, 2), (2, 2, 2), (2, 2, 2)),
        dtype=dtype,
        s2d_factor=s2d_factor,
        conv_impl=conv_impl,
    )


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
