"""Plain reference of a configuration that states XLA's default precision
on the TPU for a float32 network: every convolution reads its operands
(activations and kernel) rounded to bfloat16 and accumulates in float32;
everything between the convolutions is float32. The forward is
``reference/rsunet.py``'s with that rounding written out
(``rsunet_crop.make_rounded_forward``), so it computes the same on any
device, and imports nothing of the program.

Held to this reference, a program that lowers the activations to bfloat16
as well is told apart; held to the float32 ``highest`` reference it is
not, because the operands' rounding alone reads as much (PERF.md, PR 35).
"""
import jax.numpy as jnp

from cfbench import catalog


def make_forward(config: dict):
    """The jitted reference forward of one configuration."""
    crop = catalog.load_module("reference", "rsunet_crop")
    return crop.make_rounded_forward(config, jnp.bfloat16)
