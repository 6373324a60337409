"""Patch inference engines: pure-jax batch-forward callables.

An engine is (params, apply) where ``apply(params, batch)`` maps a
``[B, Cin, *in_patch]`` float32 batch to ``[B, Cout, *out_patch]``; it must
be jax-traceable so the fused inference program can inline it. Engine
registry parity: reference _prepare_patch_inferencer (inferencer.py:206-241)
with frameworks identity/pytorch/universal; here the native framework is
``flax`` (pytorch checkpoints load through the weight converter in
chunkflow_tpu.models.converter), ``identity`` is the test oracle, and
``universal`` loads a user python file (reference patch/universal.py — the
engine contract explicitly designed for device-side masking, incl. TPU).
"""
from __future__ import annotations

import importlib.util
import inspect
import os
from typing import Callable, NamedTuple, Optional, Tuple

import jax.numpy as jnp


class Engine(NamedTuple):
    params: object
    apply: Callable  # (params, [B, Cin, *pin]) -> [B, Cout, *pout]
    num_input_channels: int
    num_output_channels: int
    # The stage protocol (parallel/pipeline.py, ISSUE 19): engines that
    # can be staged across a ``pipeline=N`` mesh declare their layer
    # stack as uniform-activation bodies plus a tail, with ``apply``
    # being their LITERAL composition (bitwise — the pipelined and
    # non-pipelined programs then run the same per-row expression).
    # ``None`` (the default) means the forward is opaque and a pipeline
    # mesh fails loudly instead of silently de-pipelining.
    stage_bodies: Optional[Tuple[Callable, ...]] = None
    stage_tail: Optional[Callable] = None


def create_identity_engine(
    input_patch_size,
    output_patch_size,
    num_output_channels: int = 1,
    num_input_channels: int = 1,
) -> Engine:
    """Crop-and-repeat oracle: output is the input's central crop, repeated
    across output channels. Identity through the whole blend path must
    reproduce the input exactly — the linchpin of inference testing
    (reference patch/identity.py)."""
    pin = tuple(input_patch_size)
    pout = tuple(output_patch_size)
    margin = tuple((i - o) // 2 for i, o in zip(pin, pout))

    # stage protocol (parallel/pipeline.py): one identity body (the
    # uniform-activation [B, ci, *pin] -> same shape/dtype rule) and the
    # crop/broadcast tail; ``apply`` is their literal composition, so
    # the pipelined program runs bitwise the same expression.
    def stage_body(params, x):
        return x

    def stage_tail(params, batch):
        sl = (slice(None), slice(0, 1)) + tuple(
            slice(m, m + o) for m, o in zip(margin, pout)
        )
        center = batch[sl]
        return jnp.broadcast_to(
            center,
            (batch.shape[0], num_output_channels) + pout,
        )

    def apply(params, batch):
        return stage_tail(params, stage_body(params, batch))

    return Engine(
        params=(),
        apply=apply,
        num_input_channels=num_input_channels,
        num_output_channels=num_output_channels,
        stage_bodies=(stage_body,),
        stage_tail=stage_tail,
    )


def create_flax_engine(
    model_path: str,
    weight_path: Optional[str],
    input_patch_size,
    num_input_channels: int = 1,
    num_output_channels: int = 3,
    dtype: str = "float32",
    model_variant: str = "parity",
    output_patch_size=None,
) -> Engine:
    """The native convnet engine: a Flax 3D UNet (or user model file).

    ``output_patch_size`` (default: the input patch) is the central part
    of the model's prediction that ``apply`` returns, ``[m, m + pout)``
    per axis with ``m = (pin - pout) // 2`` (reference patch/base.py: the
    network's valid core). A model whose ``__call__`` takes an
    ``output_patch_size`` keyword (models/rsunet.py) is handed the size and
    returns that part alone, computing only what it depends on; from any
    other model (``UNet3D``, a user's module) the whole prediction is
    taken and cropped here.

    ``model_path`` may be empty (use the built-in model), a python file
    exposing ``create_model(num_input_channels, num_output_channels)`` that
    returns a Flax module, or a reference-chunkflow pytorch ``model.py``
    (``InstantiatedModel`` / ``load_model`` contract, patch/pytorch.py:48-83)
    whose weights are converted by name into the Flax mirror selected by
    ``model_variant``. ``weight_path`` may be a ``.pt`` torch state dict
    (converted) or an orbax/msgpack flax checkpoint. ``model_variant``:
    'parity' is the reference-class UNet (models/unet3d.py); 'rsunet' the
    production RSUNet mirror (models/rsunet.py). Any other name raises.
    """
    from chunkflow_tpu.models import rsunet, unet3d

    pin = tuple(int(p) for p in input_patch_size)
    pout = pin if output_patch_size is None else tuple(
        int(p) for p in output_patch_size)
    if any((i - o) < 0 or (i - o) % 2 for i, o in zip(pin, pout)):
        raise ValueError(
            f"the flax engine crops the input patch {pin} centrally to the "
            f"output patch {pout}: the difference must be even and not "
            f"negative on every axis"
        )
    compute_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    module = None
    if model_path:
        module = _load_user_module(model_path, "chunkflow_user_model")

    if module is not None and hasattr(module, "create_model"):
        model = module.create_model(num_input_channels, num_output_channels)
    elif model_variant == "rsunet":
        model = rsunet.RSUNet(
            in_channels=num_input_channels,
            out_channels=num_output_channels,
            dtype=compute_dtype,
        )
    elif model_variant == "parity":
        model = unet3d.UNet3D(
            in_channels=num_input_channels,
            out_channels=num_output_channels,
            dtype=compute_dtype,
        )
    else:
        raise ValueError(
            f"unknown model_variant {model_variant!r}: the built-in models "
            f"are 'parity' and 'rsunet'"
        )

    if module is not None and not hasattr(module, "create_model"):
        # reference pytorch engine contract: migrate the torch weights
        from chunkflow_tpu.models.migrate import (
            flax_params_from_reference_model,
        )

        params = flax_params_from_reference_model(
            model_path, weight_path, model, input_patch_size,
            num_input_channels, module=module,
        )
    else:
        params = unet3d.init_or_load_params(
            model, weight_path, input_patch_size, num_input_channels
        )

    # the capability is the module's to declare, whoever built it
    crops_itself = "output_patch_size" in inspect.signature(
        type(model).__call__).parameters
    region = {"output_patch_size": pout} if crops_itself else {}

    def apply(params, batch):
        # batch: [B, C, z, y, x] float32 -> channels-last for TPU conv
        x = jnp.moveaxis(batch, 1, -1)
        y = model.apply({"params": params}, x, **region)
        out = jnp.moveaxis(y, -1, 1)
        # the central output patch of whatever extent came back. A slice
        # over a whole axis lowers to nothing: with output patch = input
        # patch the program is the one it was without the crop
        crop = (slice(None), slice(None)) + tuple(
            slice((n - o) // 2, (n - o) // 2 + o)
            for n, o in zip(out.shape[2:], pout))
        return out.astype(jnp.float32)[crop]

    return Engine(
        params=params,
        apply=apply,
        num_input_channels=num_input_channels,
        num_output_channels=num_output_channels,
    )


def create_universal_engine(
    model_path: str,
    weight_path: Optional[str],
    input_patch_size,
    output_patch_size,
    num_input_channels: int = 1,
    num_output_channels: int = 3,
) -> Engine:
    """User-supplied engine file exposing
    ``create_engine(weight_path, input_patch_size, output_patch_size,
    num_input_channels, num_output_channels) -> (params, apply)``."""
    module = _load_user_module(model_path, "chunkflow_universal_engine")
    params, apply = module.create_engine(
        weight_path,
        tuple(input_patch_size),
        tuple(output_patch_size),
        num_input_channels,
        num_output_channels,
    )
    return Engine(
        params=params,
        apply=apply,
        num_input_channels=num_input_channels,
        num_output_channels=num_output_channels,
    )


def _load_user_module(path: str, name: str):
    if not os.path.exists(path):
        raise FileNotFoundError(f"model file not found: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def create_engine(framework: str, **kwargs) -> Engine:
    if framework == "prebuilt":
        # pass an Engine object directly via the dedicated kwarg (reference
        # inferencer.py:209-211); programmatic use only — not on the CLI
        engine = kwargs.get("engine")
        if not isinstance(engine, Engine):
            raise TypeError(
                "framework='prebuilt' needs an Engine instance as engine="
            )
        return engine
    if framework == "identity":
        return create_identity_engine(
            kwargs["input_patch_size"],
            kwargs["output_patch_size"],
            num_output_channels=kwargs.get("num_output_channels", 1),
            num_input_channels=kwargs.get("num_input_channels", 1),
        )
    if framework in ("flax", "jax", "pytorch"):
        # pytorch checkpoints route through the same flax engine via the
        # state-dict converter; framework name kept for CLI parity
        return create_flax_engine(
            kwargs.get("model_path", ""),
            kwargs.get("weight_path"),
            kwargs["input_patch_size"],
            num_input_channels=kwargs.get("num_input_channels", 1),
            num_output_channels=kwargs.get("num_output_channels", 3),
            dtype=kwargs.get("dtype", "float32"),
            model_variant=kwargs.get("model_variant", "parity"),
            output_patch_size=kwargs.get("output_patch_size"),
        )
    if framework == "universal":
        return create_universal_engine(
            kwargs["model_path"],
            kwargs.get("weight_path"),
            kwargs["input_patch_size"],
            kwargs["output_patch_size"],
            num_input_channels=kwargs.get("num_input_channels", 1),
            num_output_channels=kwargs.get("num_output_channels", 3),
        )
    raise ValueError(f"unknown inference framework: {framework!r}")
