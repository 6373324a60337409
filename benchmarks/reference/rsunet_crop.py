"""Plain reference of a configuration that blends the central part of the
network's prediction: the whole forward of ``reference/rsunet.py`` over
the input patch (float32, ``highest``), then the central crop to the
configuration's ``output_patch``. It imports nothing of the program.

``make_rounded_forward`` is the same forward at a narrower precision (a
configuration without an ``output_patch`` is cropped to its whole
patch): with the operands of every convolution rounded, or with the
activations rounded too. It is the control behind every configuration's
tolerance, which has to come out as not correct, and, with bfloat16
operands, the reference of a configuration that states that precision
(``reference/rsunet_bf16_operands.py``).
"""
import importlib.util
import os

import jax
import jax.numpy as jnp

from cfbench import catalog


def central_crop(y, output_patch):
    """[N, z, y, x, C] -> [N, *output_patch, C], the same margin on both
    sides of every axis."""
    margins = [(full - out) // 2
               for full, out in zip(y.shape[1:4], output_patch)]
    if any(2 * m != full - out for m, full, out
           in zip(margins, y.shape[1:4], output_patch)):
        raise ValueError(f"cannot crop {y.shape[1:4]} centrally to "
                         f"{tuple(output_patch)}")
    window = tuple(slice(m, m + out)
                   for m, out in zip(margins, output_patch))
    return y[(slice(None), *window)]


def make_forward(config: dict):
    """The jitted reference forward of one configuration, cropped."""
    plain = catalog.load_module("reference", "rsunet")
    activation = config["model"].get("final_activation", "sigmoid")
    output_patch = tuple(config["output_patch"])
    return jax.jit(lambda params, x: central_crop(
        plain.forward(params, x, activation), output_patch))


def make_rounded_forward(config: dict, dtype, activations: bool = False):
    """The cropped reference forward with what every convolution and
    upsampling reads (activations and kernel) rounded to ``dtype`` and back,
    float32 accumulation as before: what a program whose convolutions read
    ``dtype`` gives, everything between them in float32. With
    ``activations`` every result that is kept (of a convolution, an
    upsampling, an affine, a block) and every parameter is rounded as
    well: what a program gives whose activations are ``dtype`` too. It
    runs on a copy of ``reference/rsunet.py`` of its own, so the plain
    reference is left as it is."""
    spec = importlib.util.spec_from_file_location(
        "cfbench_reference_rsunet_rounded",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "rsunet.py"))
    plain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plain)

    def rounded(a):
        return jnp.asarray(a, jnp.float32).astype(dtype).astype(jnp.float32)

    def reads_rounded(op):
        return lambda x, p: op(
            rounded(x), {"kernel": rounded(p["kernel"]), "bias": p["bias"]})

    def kept_rounded(op):
        return lambda x, p: rounded(op(x, jax.tree.map(rounded, p)))

    plain._conv = reads_rounded(plain._conv)
    plain._upsample = reads_rounded(plain._upsample)
    if activations:
        # _block finds _conv and _affine among the module's names when it
        # is called: the wrapped ones
        for name in ("_conv", "_upsample", "_affine", "_block"):
            setattr(plain, name, kept_rounded(getattr(plain, name)))
    activation = config["model"].get("final_activation", "sigmoid")
    # a configuration without an output patch blends the whole prediction
    output_patch = tuple(config.get("output_patch") or config["patch"])
    return jax.jit(lambda params, x: central_crop(
        plain.forward(params, x, activation), output_patch))
