"""Flax RSUNet: the production model family of reference chunkflow users.

The reference's production checkpoints are DeepEM/emvision "Residual
Symmetric U-Net" models (Lee et al. 2017; reference
examples/inference/universal_pytorch.py builds ``model='rsunet'`` with
width [16, 32, 64, 128]; the superhuman variant uses 28/36/48/64 with
anisotropic (1, 2, 2) first-level pooling).  This module is the Flax
mirror, built for migration: every submodule is named after the torch
attribute conventions of such models (``embed``, ``enc{i}``, ``bridge``,
``up{i}``, ``dec{i}``, ``out``; blocks use ``conv1/bn1/.../conv3/bn3``),
so ``models.converter.torch_to_flax_by_name`` can pair parameters BY NAME
— independent of torch module *definition order* — and fold BatchNorm
running statistics into the inference-affine ``bn*`` scale/bias.

Layout: channels-last NDHWC, so the channel count is the minor
dimension, which the chip pads to its 128 lanes.  At the full-resolution
level that is 28 (or 16) channels in 128 lanes: every level-0 array took
4.6x (8x) its bytes in HBM and every level-0 convolution filled 28 (16) of
the MXU's 128 columns (optimized HLO for a v5e, PERF.md, PR 24).  So level 0
runs *x-folded*: F adjacent x positions move into the channels
(``[B,z,y,X,C] -> [B,z,y,X/F,F*C]``, a row-major reshape) and each level-0
convolution runs on the folded array with a block-banded kernel built at
trace time from the published ``[kz,ky,kx,Cin,Cout]`` one
(:func:`fold_kernel`).  Same parameters, same products, same rounding
points; only exact zeros are added.  F is the largest power of two with
``F * width[0] <= 128`` that divides the x extent (:func:`x_fold`: 28 -> 4,
16 -> 8); at F = 1 the folded kernel is the published kernel and the call
is the plain convolution.  Level 1 runs folded too, by the fold the pool
above hands it (:func:`level_folds`: 36 channels x 2, 32 x 4): unfolded,
XLA put the batch in the lanes of every level-1 array at batch 6 and
copied each one in and out of that layout around every block (PERF.md,
PR 29).  A fold is inherited through a pool and never made by a reshape
that splits the lanes, so levels 2 and 3 run unfolded.  The transitions
are emitted folded as well (:class:`XFoldUp`, :func:`max_pool_folded`):
behind a plain reshape the pool alone took a quarter of the chip's time.
A pool takes its z and y maximum as one ``reduce_window`` on the folded
array, in the tiles ``enc{i}/conv3`` wrote (both axes lie above the
tiles: an elementwise maximum of rows), then the maximum of neighbouring
positions inside the lanes (gauge ``forward/pool{i}_folded``); as a
reshape and a ``max`` over the window axes XLA transposed y into the lanes
and back, seven full-size passes between an encoder block's last
convolution and the next level's first (PERF.md, PR 43).
An up-sampling emits its rows from a convolution of the input dilated
with zeros in y, one a z plane of its factor (``up0``: one, ``up1``: two,
stacked on the major axis; gauge ``forward/up{i}_convolutions``), with the
cone's cut below as the convolution's padding and, by XLA's own fusion,
the bias and the skip sum in its epilogue; stacked from one 1x1x1
convolution a row and reshaped, the interleave, the skip sum and the
copies between them were nine full-size passes around ``up0`` for an
array that is written once (PERF.md, PR 41).
On a TPU the folded levels' blocks do not run XLA's block-banded
convolution at all: of its three block taps in x the two outer ones hold
one position's weights each, and XLA issues a weight tile for each
(27 MXU passes a row for 3x3x3, three quarters of the products zeros).
Where :func:`kernel_takes` says so from the block's shapes, dtype and
the backend, an ``RSBlock`` is three calls of a kernel that builds the
x halo in VMEM (ops/pallas_conv.py: 18 passes), each with its batch
norm, ReLU and residual as the epilogue; the embedding in front of
``enc0`` and the head behind ``dec0`` ride in the same kernel, because
XLA's own convolutions beside a custom call pay a relayout (gauge
``forward/kernel_convolutions``; PERF.md, PR 47).
Norm is folded to a per-channel affine (no
batch statistics at inference), compute is optionally bfloat16 with
float32 params; the final activation is computed in the output's dtype.

The encoder and the bridge run on the whole patch; the decoder runs, at
each level, on the box the requested output region depends on
(:func:`decoder_cone`).  A deployment blends the central part of every
prediction only (20x256x256 in, 16x192x192 out upstream), and what a
decoder block computes further than its halo from that part nobody
reads: there ``dec0`` runs on 61% of its voxels and ``dec1`` on 71%
(gauges ``forward/dec{i}_voxel_share``, ``forward/flops_share``).  The
values kept are the whole forward's: the zeros a block pads at a cut
edge reach its halo only, which the next slice drops.  With no margin
every box is its whole array, no slice is emitted and the program is the
one it was.
"""
from __future__ import annotations

import contextlib
import functools
import math
from typing import NamedTuple, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from flax.linen.dtypes import promote_dtype
from jax import lax

from chunkflow_tpu.core import profiling
from chunkflow_tpu.ops import pallas_conv

Triple = Tuple[int, int, int]
Box = Tuple[Tuple[int, int], ...]  # (lo, hi) on z, y, x, in a level's voxels

LANES = pallas_conv.LANES  # of a TPU vreg, a VMEM tile and an MXU pass
EMBED_KERNEL = (1, 5, 5)
BLOCK_KERNELS = ((1, 3, 3), (3, 3, 3), (3, 3, 3))  # RSBlock's conv1-3
# how far a voxel of an RSBlock's result reads into its input, per axis
BLOCK_HALO = tuple(sum(k[a] // 2 for k in BLOCK_KERNELS) for a in range(3))


def x_fold(width0: int, x_extent: int) -> int:
    """The fold factor of the full-resolution level: the largest power of
    two F with ``F * width0 <= LANES`` and ``x_extent % F == 0``."""
    fold = 1
    while 2 * fold * width0 <= LANES and x_extent % (2 * fold) == 0:
        fold *= 2
    return fold


# levels 0 and 1 run folded: a level further down that inherits a fold (64 x 2
# at widths 16/32/64/128) read no faster folded on the chip (PERF.md, PR 29)
FOLDED_LEVELS = 2


def level_folds(width: Sequence[int], x_extent: int, down_factors) -> list:
    """The x-fold every level runs in. Level 0's is :func:`x_fold`; a level
    below takes what the pool above hands down (the fold above over the
    pool's x factor) as far as its own width and extent allow, and 1 where
    the pool's windows straddle the blocks."""
    folds = [x_fold(width[0], x_extent)]
    for level, factor in enumerate(down_factors, 1):
        x_extent //= factor[2]
        handed = 1 if folds[-1] % factor[2] else folds[-1] // factor[2]
        folds.append(min(x_fold(width[level], x_extent), handed)
                     if level < FOLDED_LEVELS else 1)
    return folds


def fold_x(x, fold: int):
    """[B,z,y,X,C] -> [B,z,y,X/fold,fold*C]: ``fold`` adjacent x positions
    side by side in the channels (position major, channel minor)."""
    *lead, xs, c = x.shape
    return x.reshape(*lead, xs // fold, fold * c)


def unfold_x(x, fold: int):
    """Inverse of :func:`fold_x`."""
    *lead, xs, c = x.shape
    return x.reshape(*lead, xs * fold, c // fold)


def fold_kernel(kernel, fold: int):
    """The kernel of the same 'SAME' convolution on an x-folded array.

    ``kernel`` is ``[kz,ky,kx,Cin,Cout]``. Returns ``[kz,ky,taps,
    fold*Cin,fold*Cout]`` and the zero blocks to pad in x, (lo, hi). Block
    tap t takes input position p_in of block b+t to output position p_out
    of block b with the published tap ``dx = fold*t + p_in - p_out`` where
    the kernel has one and with zero elsewhere, so a zero block beyond the
    edge is the zero padding of the positions in it. Pure data movement
    (pad, take, transpose): every entry is a published weight or 0. With
    ``fold == 1`` the taps are the published kernel's own."""
    kz, ky, kx, cin, cout = kernel.shape
    lo, hi = (kx - 1) // 2, kx // 2  # 'SAME' as flax pads it
    t_lo, t_hi = -(-lo // fold), -(-hi // fold)
    taps = t_lo + t_hi + 1
    t = np.arange(-t_lo, t_hi + 1)[:, None, None]
    p_in = np.arange(fold)[None, :, None]
    p_out = np.arange(fold)[None, None, :]
    k = fold * t + p_in - p_out + lo
    k = np.where((k >= 0) & (k < kx), k, kx)  # kx: the zero slot
    padded = jnp.pad(kernel, ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0)))
    folded = jnp.take(padded, k.reshape(-1), axis=2)
    folded = folded.reshape(kz, ky, taps, fold, fold, cin, cout)
    folded = folded.transpose(0, 1, 2, 3, 5, 4, 6)  # t, p_in, ci, p_out, co
    return folded.reshape(kz, ky, taps, fold * cin, fold * cout), (t_lo, t_hi)


def fold_head(kernel, bias, fold: int, dtype):
    """A 1x1x1 convolution's parameters (``[1,1,1,Cin,Cout]``, ``[Cout]``)
    as the folded array's: ``([F*Cin, F*Cout], [F*Cout])`` in the compute
    dtype, for the kernel of the block whose result the convolution alone
    reads."""
    folded, _ = fold_kernel(kernel.astype(dtype), fold)
    return folded[0, 0, 0], jnp.tile(bias.astype(dtype), fold)


def kernel_takes(fold: int, channels: int, features: int, dtype,
                 extents: Triple, backend: str) -> bool:
    """Whether an ``RSBlock`` whose input is ``[B, *extents, fold*channels]``
    (z, y and x blocks) runs its three convolutions as the kernel that
    builds the x halo in VMEM (ops/pallas_conv.py): a rule on what the
    code sees, with no option beside it.

    - a TPU ``backend`` (the platform the program is lowered for): the
      kernel is Mosaic's; anywhere else the block is XLA's as it was;
    - a fold of at least 2 (a neighbour's position lies in another block)
      and the folded lanes of input and result within one MXU tile: one
      centre pass and one halo pass a (kz, ky);
    - x blocks a multiple of the operands' sublane tile (16: they are
      bfloat16 whatever the activations are, two x blocks to a packed
      word), so that a plane's rows are matmul rows with no relayout (the
      production cone's ``dec0`` has 50, its ``dec1`` 54: XLA's);
    - the planes a call holds at once within what it may take of the
      core's VMEM (``pallas_conv.vmem_bytes``: 45 of 100 MiB at
      256x256 on the v5e; a 512x512 patch would ask for 165: XLA's);
    - bfloat16 or float32 activations: both levels of both published
      widths read faster through the kernel on the v5e, one
      ``engine.apply`` a program 64.0 -> 52.2 ms (28/36/48/64, batch 4),
      41.9 -> 36.3 (16/32/64/128, float32), 93.4 -> 80.3 (batch 6 with a
      16x192x192 output patch); with level 0's blocks alone and XLA's
      embedding 59.7 / 37.5 / 83.0 against 53.8 / 37.1 / 79.3 with both
      levels' (PERF.md, PR 47). The whole block or none of it: with ``conv1``
      left to XLA the first program read 61.9, a relayout either side of
      it."""
    _, ys, blocks = extents
    lanes = fold * max(channels, features)
    return (backend == "tpu" and fold >= 2 and lanes <= LANES
            and blocks % pallas_conv.OPERAND_SUBLANES == 0
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32))
            and pallas_conv.vmem_bytes(BLOCK_KERNELS[-1][:2], (ys, blocks),
                                       lanes, dtype)
            <= pallas_conv.vmem_limit_bytes())


class Head(NamedTuple):
    """A 1x1x1 convolution that reads a block's result and nothing else
    does (:func:`fold_head`), and its module's name below the model
    (``out``)."""
    kernel: jax.Array
    bias: jax.Array
    name: str


class Epilogue(NamedTuple):
    """What follows a convolution inside an ``RSBlock``, for the kernel
    that takes it along: the folded batch norm's terms (tiled over the
    fold, in the compute dtype), the residual or None, then the ReLU, then
    the :class:`Head` or None."""
    scale: Optional[jax.Array] = None
    shift: Optional[jax.Array] = None
    residual: Optional[jax.Array] = None
    head: Optional[Head] = None
    relu: bool = True


class XFoldConv(nn.Module):
    """``nn.Conv(features, kernel_size, padding='SAME')`` with the same
    parameter tree, on an array whose channels hold ``fold`` adjacent x
    positions. Rounds where nn.Conv does: the convolution's result in the
    compute dtype, then the bias.

    With an ``epilogue`` (``RSBlock``, where :func:`kernel_takes` says so)
    the convolution, the bias and the epilogue are one call of the kernel
    that builds the x halo in VMEM (ops/pallas_conv.py), and the result is
    the block's next array (``interpret``: the kernel interpreted, for
    tests)."""

    features: int
    kernel_size: Triple
    dtype: jnp.dtype = jnp.float32
    fold: int = 1
    interpret: bool = False

    @nn.compact
    def __call__(self, x, epilogue: Optional[Epilogue] = None):
        kz, ky, _ = self.kernel_size
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (*self.kernel_size, x.shape[-1] // self.fold, self.features),
        )
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        kernel, x_pad = fold_kernel(kernel, self.fold)
        if epilogue is not None:
            # the lanes of a neighbouring block that the x taps reach
            reach = self.kernel_size[2] // 2 * (x.shape[-1] // self.fold)
            centre, halo = pallas_conv.halo_kernels(kernel, reach)
            head = epilogue.head
            # this convolution's marker innermost: Mosaic names the call
            with profiling.kernel_convolution((1, 1, 1), head.name) \
                    if head else contextlib.nullcontext(), \
                    profiling.kernel_convolution(self.kernel_size):
                return pallas_conv.folded_conv(
                    x, centre, halo, jnp.tile(bias, self.fold),
                    epilogue.scale, epilogue.shift, epilogue.residual,
                    relu=epilogue.relu, head=head and head[:2],
                    channels=reach, interpret=self.interpret)
        y = lax.conv_general_dilated(
            x, kernel, window_strides=(1, 1, 1),
            padding=(((kz - 1) // 2, kz // 2), ((ky - 1) // 2, ky // 2),
                     x_pad),
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        )
        return y + jnp.tile(bias, self.fold)


class XFoldUp(nn.Module):
    """``nn.ConvTranspose(features, kernel_size=factor, strides=factor)``
    with the same parameter tree, on an array x-folded by ``in_fold``,
    whose result comes out x-folded by ``fold`` (a multiple of
    ``in_fold`` times the x factor) with no full-resolution array in
    between. With kernel == stride every input position emits its own
    (fz, fy, fx, features) block, so the input folded by ``fold // fx``
    needs a block-diagonal kernel and no tap in x: the x part of each
    block is already the folded channel order. Only y and z are
    interleaved, above the lanes:

    - y by the convolution itself: the input is dilated with zeros by fy
      and the kernel holds the fy blocks (output row ``fy*y + j`` is
      ``x[y] . k[j]``; every other product is an exact zero), so each row
      is written where it belongs, XLA fuses the bias and the skip sum
      that follows into the convolution's epilogue, and ``dec{i}/conv1``
      reads the result in the layout it was written in. Emitted as one
      1x1x1 convolution a row, stacked and reshaped, each left XLA's
      convolution layout through a copy of its own, the interleave was a
      pad-and-maximum fusion and two more copies, and the skip sum a
      pass of its own with a copy behind it: nine full-size passes around
      ``up0`` where one does (PERF.md, PR 41);
    - z, the major axis, by a stack of fz such convolutions
      (:attr:`convolutions`), one a plane of the factor.

    ``want`` inside ``held`` (the box of its level that the input holds):
    the part of the input to up-sample. The convolutions cut it by their
    padding, negative where rows are dropped, and no slice is emitted: a
    slice between ``dec{i+1}`` and a dilated convolution cost what the
    interleave saved."""

    features: int
    factor: Triple
    dtype: jnp.dtype = jnp.float32
    fold: int = 2
    in_fold: int = 1

    @property
    def convolutions(self) -> int:
        """How many convolutions emit the rows: one a z plane of the
        factor, and not one dilated in z as well. One ``engine.apply`` a
        program on the v5e, ms (PERF.md, PR 41; batch 4 / 4 / 6 at widths
        28-36-48-64, 16-32-64-128 and the first with a 16x192x192 output
        patch), ``up0`` (1,2,2) one dilated convolution in all and
        ``up1`` (2,2,2) as four 1x1x1 convolutions stacked in z and y
        69.46 / 44.23 / 97.82, as one convolution dilated in z and y
        72.22 / 42.88 / 95.58, as two dilated in y and stacked in z
        68.07 / 43.86 / 96.13: the only one of the three that loses in
        no program. Behind a convolution dilated in z XLA keeps the x
        tiles of the unfolded level below, 8 + 2 blocks wide where levels
        1 and 0 have 8 + 1, all the way up the decoder, and pads and
        copies both skips into them."""
        return self.factor[0]

    @nn.compact
    def __call__(self, x, want: Optional[Box] = None,
                 held: Optional[Box] = None):
        fz, fy, fx = self.factor
        cin = x.shape[-1] // self.in_fold
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (fz, fy, fx, cin, self.features))
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (self.features,))
        x, kernel, bias = promote_dtype(x, kernel, bias, dtype=self.dtype)
        g = self.fold // fx
        # nn.ConvTranspose puts the spatially flipped kernel in each block
        k = kernel[::-1, ::-1, ::-1].transpose(0, 1, 3, 2, 4)  # i,j,c,k,f
        same = np.eye(g, dtype=bool)[:, None, :, None, None]
        k = jnp.where(same, k[:, :, None, :, None], 0)  # i,j,g,c,g,k,f
        k = k.reshape(fz, fy, 1, g * cin, self.fold * self.features)
        # what the box drops of the input at either end of each axis
        (z0, z1), (y0, y1), (x0, x1) = [(0, 0)] * 3 if want is None \
            else [(lo - origin, end - hi)
                  for (lo, hi), (origin, end) in zip(want, held)]
        x = fold_x(x, g // self.in_fold)
        planes = [lax.conv_general_dilated(
            x, k[i:i + 1, ::-1],  # the convolution flips the taps back
            window_strides=(1, 1, 1),
            padding=((-z0, -z1), (fy - 1 - fy * y0, fy - 1 - fy * y1),
                     (-(x0 // g), -(x1 // g))),
            lhs_dilation=(1, fy, 1),
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))
            for i in range(fz)]
        y_ = planes[0]
        if fz > 1:
            b, z, *rest = y_.shape
            y_ = jnp.stack(planes, axis=2).reshape(b, z * fz, *rest)
        return y_ + jnp.tile(bias, self.fold)


def max_pool_folded(x, factor: Triple, fold: int):
    """``nn.max_pool(x, factor, strides=factor)`` of an array x-folded by
    ``fold`` (a multiple of the x factor): a maximum over the z and y
    windows and over neighbouring positions inside the lanes. The result
    is x-folded by ``fold // fx``.

    The z and y windows lie above the tiles, so their maximum is a
    ``reduce_window`` on the folded array: an elementwise maximum of fz*fy
    rows a tile, which XLA emits as one fusion that reads the result of
    ``enc{i}/conv3`` in the tiles it was written in and writes the same
    tiles. Taken as a reshape to ``[..., z/fz, fz, y/fy, fy, ...]`` and a
    ``max`` over the two window axes, XLA's layout for that reduce put y
    in the lanes: the block's result left the convolution's tiles, was
    transposed, reduced and transposed back for ``enc{i+1}/conv1``, seven
    full-size passes where one does (PERF.md, PR 43). This window is not
    the one PR 24 met (``nn.max_pool`` of the unfolded array: 28 channels
    in the lanes, the window over x in the sublanes)."""
    fz, fy, fx = factor
    c = x.shape[-1] // fold
    # from -inf, as nn.max_pool starts: the identity XLA knows a maximum by
    x = lax.reduce_window(x, np.array(-np.inf, x.dtype), lax.max,
                          window_dimensions=(1, fz, fy, 1, 1),
                          window_strides=(1, fz, fy, 1, 1), padding="VALID")
    lanes = [x[..., p * c:(p + 1) * c] for p in range(fold)]
    return jnp.concatenate(
        [functools.reduce(jnp.maximum, lanes[g * fx:(g + 1) * fx])
         for g in range(fold // fx)], axis=-1)


class Affine(nn.Module):
    """Per-channel scale + bias: an inference-time BatchNorm3d, with the
    running statistics folded in by the converter."""

    features: int
    dtype: jnp.dtype = jnp.float32
    fold: int = 1

    def setup(self):
        self.scale = self.param("scale", nn.initializers.ones,
                                (self.features,))
        self.bias = self.param("bias", nn.initializers.zeros,
                               (self.features,))

    def __call__(self, x):
        scale, bias = affine_terms(self)
        return x * scale + bias


def affine_terms(bn: Affine):
    """``(scale, bias)`` of an :class:`Affine` as it applies them: in its
    dtype, tiled over its fold."""
    return (jnp.tile(bn.scale.astype(bn.dtype), bn.fold),
            jnp.tile(bn.bias.astype(bn.dtype), bn.fold))


class RSBlock(nn.Module):
    """Residual block: conv1(1,3,3) -> conv2(3,3,3) -> conv3(3,3,3), each
    conv -> bn -> relu, with the residual taken after conv1 (the
    superhuman-RSUNet shape). ``fold``: the x-fold of its input.
    ``kernel``: its convolutions take the halo-in-VMEM kernel with their
    batch norm, ReLU and the residual as its epilogue
    (:func:`kernel_takes`; ``interpret``: interpreted, for tests)."""

    features: int
    dtype: jnp.dtype = jnp.float32
    fold: int = 1
    kernel: bool = False
    interpret: bool = False

    def setup(self):
        f, dt, fold = self.features, self.dtype, self.fold
        k1, k2, k3 = BLOCK_KERNELS
        conv = functools.partial(XFoldConv, dtype=dt, fold=fold,
                                 interpret=self.interpret)
        self.conv1 = conv(f, k1)
        self.bn1 = Affine(f, dtype=dt, fold=fold)
        self.conv2 = conv(f, k2)
        self.bn2 = Affine(f, dtype=dt, fold=fold)
        self.conv3 = conv(f, k3)
        self.bn3 = Affine(f, dtype=dt, fold=fold)

    def __call__(self, x, head: Optional[Head] = None):
        """``head`` (with ``kernel`` only): the 1x1x1 convolution that
        alone reads this block's result rides in ``conv3``'s kernel, and
        what is returned is the head's result."""
        if self.kernel:
            def after(bn, residual=None, head=None):
                return Epilogue(*affine_terms(bn), residual, head)
            residual = self.conv1(x, after(self.bn1))
            x = self.conv2(residual, after(self.bn2))
            return self.conv3(x, after(self.bn3, residual, head))
        x = nn.relu(self.bn1(self.conv1(x)))
        residual = x
        x = nn.relu(self.bn2(self.conv2(x)))
        x = nn.relu(self.bn3(self.conv3(x)) + residual)
        return x


def _voxels(box: Box) -> int:
    return math.prod(hi - lo for lo, hi in box)


def _whole(shape: Triple) -> Box:
    return tuple((0, n) for n in shape)


def decoder_cone(shapes: Sequence[Triple], region: Box, down_factors, folds,
                 halo: Triple):
    """What of each level (``shapes[i]``: its extent) the output ``region``
    of a patch depends on, walking the decoder from the head back.

    Returns one ``(box, want)`` a level, level 0 first, the bridge's last,
    in that level's own voxels: ``want`` is the part of the level's result
    that is read (by ``out`` at level 0, by ``up{i-1}`` below), ``box``
    the part of ``up{i} + skip`` its block has to run on, which is
    ``want`` grown by the block's ``halo``, clipped to the array and
    rounded out to what can be sliced with no relayout: whole x-fold
    blocks of this level, and whole windows of ``up{i}`` (kernel ==
    stride), so that the level below emits exactly the box. An axis is
    cut only where that drops more than the halo the cut costs (on the
    chip a level-2 box of 60 in 64 was slower than the whole level, and
    every rounding wider than this one slower still: PERF.md, PR 27).
    Where the region is the whole patch every box is its whole array;
    the bridge's box always is (the encoder's pools read everything)."""
    want = tuple((lo // s * s, -(-hi // s) * s)
                 for (lo, hi), s in zip(region, (1, 1, folds[0])))
    cone = []
    for i, factor in enumerate(down_factors):
        step = (factor[0], factor[1],
                math.lcm(folds[i], factor[2] * folds[i + 1]))
        box = tuple((max(lo - h, 0) // s * s, -(-min(hi + h, n) // s) * s)
                    for (lo, hi), h, n, s in zip(want, halo, shapes[i], step))
        box = tuple((lo, hi) if max(lo, n - hi) > h else (0, n)
                    for (lo, hi), h, n in zip(box, halo, shapes[i]))
        cone.append((box, want))
        want = tuple((lo // f, hi // f) for (lo, hi), f in zip(box, factor))
    cone.append((_whole(shapes[-1]), want))
    return cone


def _crop(x, want: Box, held: Box, fold: int = 1):
    """``x`` ([B,z,y,X/fold,fold*C]) holds the box ``held`` of its level:
    the part ``want`` of it. Where they are the same nothing is emitted."""
    if want == held:
        return x
    (z0, z1), (y0, y1), (x0, x1) = (
        (lo - origin, hi - origin)
        for (lo, hi), (origin, _) in zip(want, held))
    return x[:, z0:z1, y0:y1, x0 // fold:x1 // fold]


def forward_flops(width, in_channels: int, out_channels: int,
                  level_voxels, dec_voxels, out_voxels: int) -> int:
    """Operations of one patch forward from shapes: 2 x taps x Cin x Cout
    for every result voxel of every convolution, 2 x Cin x Cout for every
    voxel an upsampling emits (kernel == stride: one tap each).
    ``level_voxels[i]``: the voxels of level i (encoder and bridge run on
    all of them); ``dec_voxels[i]``: those ``up{i}`` emits and ``dec{i}``
    runs on; ``out_voxels``: those of the head."""
    taps = [math.prod(k) for k in BLOCK_KERNELS]

    def block(c_in, w, v):
        return 2 * v * (taps[0] * c_in * w + (taps[1] + taps[2]) * w * w)

    total = 2 * level_voxels[0] * math.prod(EMBED_KERNEL) \
        * in_channels * width[0]
    for i, v in enumerate(dec_voxels):
        total += block(width[max(i - 1, 0)], width[i], level_voxels[i])
        total += 2 * v * width[i + 1] * width[i]  # up{i}
        total += block(width[i], width[i], v)     # dec{i}
    total += block(width[-2], width[-1], level_voxels[-1])  # bridge
    return total + 2 * out_voxels * width[0] * out_channels


class RSUNet(nn.Module):
    """Residual symmetric U-Net, channels-last, anisotropic pooling.

    width[i] is the feature count at depth i; down_factors[i] the pooling
    between depths i and i+1 ((1, 2, 2) first — EM z is coarse).  Decoder
    upsampling is ConvTranspose with kernel == stride == the down factor,
    followed by skip-add and a residual block, mirroring the torch models.
    """

    in_channels: int = 1
    out_channels: int = 3
    width: Sequence[int] = (28, 36, 48, 64)
    down_factors: Sequence[Triple] = ((1, 2, 2), (2, 2, 2), (2, 2, 2))
    dtype: jnp.dtype = jnp.float32
    final_activation: str = "sigmoid"
    # what the program is lowered for, where that is not the process's
    # default backend (tools/aot_cost.py: a described chip from a CPU host)
    platform: Optional[str] = None
    interpret: bool = False  # tests: the rule as on a TPU, the kernel interpreted

    @nn.compact
    def __call__(self, x, output_patch_size=None):
        """``x``: [B, z, y, x, in_channels]. Returns the prediction over the
        central ``output_patch_size`` of it (default: all of it). The
        decoder runs on the region's cone of dependence only
        (:func:`decoder_cone`); every value returned is the one the whole
        forward has there."""
        depth = len(self.width)
        assert len(self.down_factors) == depth - 1
        dt = self.dtype
        # level i runs x-folded by folds[i]
        folds = level_folds(self.width, x.shape[-2], self.down_factors)
        fold = folds[0]
        profiling.trace_gauge("forward/x_fold", fold)
        for i in range(1, depth):
            profiling.trace_gauge(f"forward/x_fold_{i}", folds[i])
        shapes = [tuple(x.shape[1:4])]
        for factor in self.down_factors:
            shapes.append(tuple(n // f for n, f in zip(shapes[-1], factor)))
        size = shapes[0] if output_patch_size is None else tuple(
            int(o) for o in output_patch_size)
        if any(not 0 < o <= n for o, n in zip(size, shapes[0])):
            raise ValueError(
                f"no output patch {size} in a patch {shapes[0]}")
        region = tuple(((n - o) // 2, (n - o) // 2 + o)
                       for n, o in zip(shapes[0], size))
        cone = decoder_cone(shapes, region, self.down_factors, folds,
                            BLOCK_HALO)
        self._trace_cone_gauges(shapes, cone)

        kernels = []  # one an RSBlock: whether it took the kernel

        # while the parameters are initialised the forward is traced for
        # its shapes alone: no kernel to trace and lower (a second apiece
        # for the fourteen of a program) for XLA to drop
        backend = "none" if self.is_initializing() else self.platform or (
            "tpu" if self.interpret else jax.default_backend())

        def block(i, name, x, head: str = ""):
            """``head``: the name of the 1x1x1 convolution that alone
            reads the block's result; it rides in the block's kernel
            where there is one (the second result says so)."""
            kernels.append(i < FOLDED_LEVELS and kernel_takes(
                folds[i], x.shape[-1] // folds[i], self.width[i], dt,
                x.shape[1:4], backend))
            run = RSBlock(self.width[i], dtype=dt, fold=folds[i],
                          kernel=kernels[-1], interpret=self.interpret,
                          name=name)
            if head and kernels[-1]:
                its = self.variables["params"][head]
                return run(x, Head(*fold_head(
                    its["kernel"], its["bias"], folds[i], dt), head)), True
            return run(x), False

        # what this method emits itself goes under a name of its own, as a
        # flax module's ops go under the module's: the device trace is split
        # by these names (core/profiling.py ``op_parts``). Metadata only.
        orig_dtype = x.dtype
        with jax.named_scope("in"):
            x = fold_x(x.astype(dt), fold)
        embed = XFoldConv(self.width[0], EMBED_KERNEL, dtype=dt, fold=fold,
                          interpret=self.interpret, name="embed")
        if kernel_takes(
                fold, self.width[0], self.width[0], dt,
                (*x.shape[1:3], x.shape[3]), backend):
            # enc0 takes the kernel: what feeds it comes in plain tiles
            x = embed(x, Epilogue(relu=False))
            embedded = True
        else:
            x = embed(x)
            embedded = False
        skips = []
        for i in range(depth - 1):
            x, _ = block(i, f"enc{i}", x)
            skips.append(x)
            factor = self.down_factors[i]
            folded = folds[i] % factor[2] == 0
            profiling.trace_gauge(f"forward/pool{i}_folded", int(folded))
            with jax.named_scope(f"pool{i}"):
                if folded:  # by folds[i] // fx: level i+1's fold, or more
                    x = unfold_x(max_pool_folded(x, factor, folds[i]),
                                 folds[i] // factor[2] // folds[i + 1])
                else:  # one window's positions lie in two blocks
                    x = nn.max_pool(unfold_x(x, folds[i]),
                                    window_shape=factor, strides=factor)
        x, _ = block(depth - 1, "bridge", x)
        headed = False
        for i in reversed(range(depth - 1)):
            factor = self.down_factors[i]
            box, _ = cone[i]
            held, want = cone[i + 1]  # of level i+1's result
            if folds[i] % factor[2]:
                emitted = 1
                with jax.named_scope(f"crop{i + 1}"):
                    x = _crop(x, want, held, folds[i + 1])
                x = fold_x(nn.ConvTranspose(
                    self.width[i], kernel_size=factor, strides=factor,
                    dtype=dt, name=f"up{i}")(x), folds[i])
            else:  # the cut rides in the convolutions' padding
                up = XFoldUp(self.width[i], factor=factor, dtype=dt,
                             fold=folds[i], in_fold=folds[i + 1],
                             name=f"up{i}")
                emitted = up.convolutions
                x = up(x, want, held)
            profiling.trace_gauge(f"forward/up{i}_convolutions", emitted)
            with jax.named_scope(f"skip{i}"):
                x = x + _crop(skips[i], box, _whole(shapes[i]), folds[i])
            # the head reads dec0's result and nothing else does: where
            # dec0 takes the kernel it rides in conv3's, before the crop
            x, headed = block(i, f"dec{i}", x, "" if i else "out")
        profiling.trace_gauge("forward/kernel_convolutions",
                              len(BLOCK_KERNELS) * sum(kernels) + headed
                              + embedded)
        held, want = cone[0]
        with jax.named_scope("crop0"):
            x = _crop(x, want, held, fold)
        if not headed:
            x = XFoldConv(self.out_channels, (1, 1, 1), dtype=dt, fold=fold,
                          name="out")(x)
        # the activation in the output's dtype: what the chip computed all
        # along while head, sigmoid and cast were one fusion (XLA keeps
        # excess precision inside one), now that a copy lies between them
        with jax.named_scope("post"):
            x = _crop(unfold_x(x, fold), region, want).astype(orig_dtype)
            if self.final_activation == "sigmoid":
                x = nn.sigmoid(x)
        return x

    def _trace_cone_gauges(self, shapes, cone) -> None:
        """Says how much of the decoder the traced forward runs: a gauge a
        decoder level (voxels its block runs on over the level's; 1.0
        where nothing is cut) and the forward's FLOPs over those of the
        whole patch (docs/observability.md)."""
        level_voxels = [math.prod(shape) for shape in shapes]
        dec_voxels = [_voxels(box) for box, _ in cone[:-1]]
        for i, (cut, whole) in enumerate(zip(dec_voxels, level_voxels)):
            profiling.trace_gauge(f"forward/dec{i}_voxel_share", cut / whole)
        counts = (self.width, self.in_channels, self.out_channels,
                  level_voxels)
        profiling.trace_gauge(
            "forward/flops_share",
            forward_flops(*counts, dec_voxels, _voxels(cone[0][1]))
            / forward_flops(*counts, level_voxels[:-1], level_voxels[0]))
