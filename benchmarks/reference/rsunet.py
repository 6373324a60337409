"""Plain reference: the residual symmetric U-Net forward (Lee et al. 2017,
arXiv:1706.00120, Fig. 2) in straightforward ``jax.numpy`` and
``lax.conv_general_dilated``, float32 under
``jax.default_matmul_precision("highest")``.

It imports nothing of the program. It reads the parameter tree the
program's engine made from the seed (names ``embed``, ``enc{i}``,
``bridge``, ``up{i}``, ``dec{i}``, ``out``; blocks ``conv1/bn1`` ..
``conv3/bn3``) and takes widths and depth from the shapes in it, so one
file serves every width.

Departures from the paper, as the configuration files list under
``assumed``: the folded per-channel affine stands for batch norm at
inference; upsampling is a transposed convolution with kernel = stride =
the pooling factor; skips are summed.
"""
import jax
import jax.numpy as jnp
from jax import lax


def _conv(x, p):
    """'SAME' correlation, stride 1, NDHWC x DHWIO, plus bias."""
    y = lax.conv_general_dilated(
        x, jnp.asarray(p["kernel"], jnp.float32), window_strides=(1, 1, 1),
        padding="SAME", dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
    return y + jnp.asarray(p["bias"], jnp.float32)


def _affine(x, p):
    return x * jnp.asarray(p["scale"], jnp.float32) \
        + jnp.asarray(p["bias"], jnp.float32)


def _block(x, p):
    x = jax.nn.relu(_affine(_conv(x, p["conv1"]), p["bn1"]))
    residual = x
    x = jax.nn.relu(_affine(_conv(x, p["conv2"]), p["bn2"]))
    return jax.nn.relu(_affine(_conv(x, p["conv3"]), p["bn3"]) + residual)


def _max_pool(x, factor):
    n, d, h, w, c = x.shape
    fz, fy, fx = factor
    x = x.reshape(n, d // fz, fz, h // fy, fy, w // fx, fx, c)
    return x.max(axis=(2, 4, 6))


def _upsample(x, p):
    """Transposed convolution with kernel = stride: every input voxel
    writes one factor-sized brick, out[p*s + r] = x[p] . W[s-1-r]."""
    kernel = jnp.asarray(p["kernel"], jnp.float32)[::-1, ::-1, ::-1]
    fz, fy, fx, _, cout = kernel.shape
    n, d, h, w, _ = x.shape
    y = jnp.einsum("ndhwc,zyxco->ndzhywxo", x, kernel)
    y = y.reshape(n, d * fz, h * fy, w * fx, cout)
    return y + jnp.asarray(p["bias"], jnp.float32)


def forward(params, x, final_activation: str = "sigmoid"):
    """[N, z, y, x, Cin] float32 -> [N, z, y, x, Cout] float32."""
    levels = sum(1 for name in params if name.startswith("enc"))
    with jax.default_matmul_precision("highest"):
        x = _conv(x.astype(jnp.float32), params["embed"])
        skips = []
        for i in range(levels):
            x = _block(x, params[f"enc{i}"])
            skips.append(x)
            factor = params[f"up{i}"]["kernel"].shape[:3]
            x = _max_pool(x, factor)
        x = _block(x, params["bridge"])
        for i in reversed(range(levels)):
            x = _upsample(x, params[f"up{i}"]) + skips[i]
            x = _block(x, params[f"dec{i}"])
        x = _conv(x, params["out"])
        if final_activation == "sigmoid":
            x = jax.nn.sigmoid(x)
    return x


def make_forward(config: dict):
    """The jitted reference forward of one configuration."""
    activation = config["model"].get("final_activation", "sigmoid")
    return jax.jit(lambda params, x: forward(params, x, activation))
