"""Plain reference of a configuration that blends the central part of the
network's prediction: the whole forward of ``reference/rsunet.py`` over
the input patch (float32, ``highest``), then the central crop to the
configuration's ``output_patch``. It imports nothing of the program.

``make_rounded_forward`` is the control behind the configuration's
tolerance: the same forward with the operands of every convolution rounded
to a narrower type, which has to come out as not correct.
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


def make_rounded_forward(config: dict, dtype):
    """The cropped reference forward with what every convolution and
    upsampling reads (activations and kernel) rounded to ``dtype`` and back,
    float32 accumulation as before: what a program computing in ``dtype``
    would give. It runs on a copy of ``reference/rsunet.py`` of its own, so
    the plain reference is left as it is."""
    spec = importlib.util.spec_from_file_location(
        "cfbench_reference_rsunet_rounded",
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "rsunet.py"))
    plain = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(plain)
    conv, upsample = plain._conv, plain._upsample

    def rounded(a):
        return jnp.asarray(a, jnp.float32).astype(dtype).astype(jnp.float32)

    def reads_rounded(op):
        return lambda x, p: op(
            rounded(x), {"kernel": rounded(p["kernel"]), "bias": p["bias"]})

    plain._conv, plain._upsample = reads_rounded(conv), reads_rounded(upsample)
    activation = config["model"].get("final_activation", "sigmoid")
    output_patch = tuple(config["output_patch"])
    return jax.jit(lambda params, x: central_crop(
        plain.forward(params, x, activation), output_patch))
