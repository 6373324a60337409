"""Scatter-free overlap-add: parity-class dense accumulation ("fold").

The generic blend path (ops/blend.py) scatter-adds patch windows at
RUNTIME coordinates — XLA cannot prove the windows disjoint, so TPU
lowering serializes read-modify-write window traffic (measured round-2:
the stacked single-scatter variant cost ~20 s on a 64x512x512 parity
config whose raw forward is ~5 s). This module removes the scatter
entirely for the common case of a UNIFORM patch grid:

1. the chunk is padded (high side) so ``(extent - pin) % stride == 0``
   per axis — every start coordinate becomes a static Python int (the
   weight-mask reciprocal normalization keeps edge voxels exact, same
   trick the engine already uses for arbitrary chunk sizes);
2. patches are gathered with static ``lax.slice``s and run through the
   engine under ``lax.map`` (batched);
3. weighted predictions accumulate by PARITY CLASS: along axis i, patches
   whose grid index is congruent mod ``k_i = ceil(pout_i / stride_i)``
   never overlap, so each class lays out as a dense
   reshape/transpose/pad block added at a STATIC offset — prod(k_i)
   dense adds (8 for overlap < pout/2) replace every scatter.

Everything XLA sees is reshapes, transposes, pads, static-slice adds and
the conv forward — all fusable, nothing serialized.

Reference parity: this computes exactly the reference's bump-weighted
overlap-add + reciprocal mask (inferencer.py:294-333,:404-455) — the
identity oracle holds to float tolerance (tests/ops/test_fold_blend.py).

Selection: ``Inferencer(blend="fold")`` or ``CHUNKFLOW_BLEND=fold``;
gated to single-device programs and stacks below the same byte budget as
the stacked scatter path.
"""
from __future__ import annotations

import math
from typing import Tuple

import numpy as np

from chunkflow_tpu.core.contracts import Spec, contract

Triple = Tuple[int, int, int]


def fold_pad_shape(zyx: Triple, pin: Triple, stride: Triple) -> Triple:
    """Smallest per-axis extents >= zyx making the patch grid uniform
    (no edge snapping): ``(extent - pin) % stride == 0``."""
    out = []
    for length, p, s in zip(zyx, pin, stride):
        length = max(length, p)
        out.append(length + (-(length - p) % s))
    return tuple(out)


def fold_grid(zyx: Triple, pin: Triple, stride: Triple) -> Triple:
    """Patches per axis for a uniform (pre-padded) shape."""
    for length, p, s in zip(zyx, pin, stride):
        if (length - p) % s:
            raise ValueError(
                f"shape {zyx} is not uniform for patch {pin} stride "
                f"{stride}; pad with fold_pad_shape first"
            )
    return tuple(
        (length - p) // s + 1 for length, p, s in zip(zyx, pin, stride)
    )


def _class_counts(g: int, k: int) -> list:
    """Patches in each parity class c (0..k-1): indices c, c+k, ... < g."""
    return [len(range(c, g, k)) for c in range(k)]


@contract(
    stack=Spec("n", "co", "pz", "py", "px", dtype="float32"),
    _result=Spec("co", None, None, None),
)
def fold_accumulate(stack, grid: Triple, stride: Triple, pout: Triple,
                    offset: Triple, out_zyx: Triple):
    """Dense parity-class overlap-add.

    stack: [N, co, *pout] weighted patches in z-major grid order.
    Returns [co, *out_zyx]; patch p's window starts at
    ``offset + grid_index(p) * stride``.
    """
    import jax.numpy as jnp

    gz, gy, gx = grid
    n, co = stack.shape[0], stack.shape[1]
    # grid/stride are static trace-time ints, not tracers
    assert n == gz * gy * gx, (n, grid)  # graftlint: disable=GL003
    k = tuple(max(1, math.ceil(p / s)) for p, s in zip(pout, stride))
    tile = tuple(ki * si for ki, si in zip(k, stride))
    # headroom: a class's dense block may extend past the true output
    # extent by up to tile - pout per axis
    buf_zyx = tuple(
        max(
            out_zyx[i],
            max(
                offset[i] + c * stride[i]
                + _class_counts(grid[i], k[i])[c] * tile[i]
                for c in range(k[i])
            ),
        )
        for i in range(3)
    )
    stack = stack.reshape((gz, gy, gx, co) + tuple(pout))
    buf = jnp.zeros((co,) + buf_zyx, dtype=stack.dtype)
    for cz in range(k[0]):
        for cy in range(k[1]):
            for cx in range(k[2]):
                sub = stack[cz::k[0], cy::k[1], cx::k[2]]
                mz, my, mx = sub.shape[:3]
                if 0 in (mz, my, mx):
                    continue
                pad = [(0, 0)] * 4 + [
                    (0, tile[i] - pout[i]) for i in range(3)
                ]
                tiles = jnp.pad(sub, pad)
                dense = tiles.transpose(3, 0, 4, 1, 5, 2, 6).reshape(
                    co, mz * tile[0], my * tile[1], mx * tile[2]
                )
                z0 = offset[0] + cz * stride[0]
                y0 = offset[1] + cy * stride[1]
                x0 = offset[2] + cx * stride[2]
                buf = buf.at[
                    :,
                    z0:z0 + dense.shape[1],
                    y0:y0 + dense.shape[2],
                    x0:x0 + dense.shape[3],
                ].add(dense)
    return buf[:, : out_zyx[0], : out_zyx[1], : out_zyx[2]]


def build_fold_program(
    forward,
    num_input_channels: int,
    num_output_channels: int,
    input_patch_size: Triple,
    output_patch_size: Triple,
    stride: Triple,
    batch_size: int,
    bump: np.ndarray,
    zyx: Triple,
    out_dtype="float32",
):
    """jit program(chunk [ci, *zyx], params) -> [co, *zyx] normalized.

    ``zyx`` must be uniform (fold_pad_shape). All geometry is static:
    static-slice gather, lax.map batched forward, parity-class fold,
    reciprocal normalization.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chunkflow_tpu.ops.blend import normalize_blend

    ci = num_input_channels
    co = num_output_channels
    pin = tuple(input_patch_size)
    pout = tuple(output_patch_size)
    stride = tuple(stride)
    grid = fold_grid(zyx, pin, stride)
    margin = tuple((i - o) // 2 for i, o in zip(pin, pout))
    starts = [
        (z, y, x)
        for z in range(0, zyx[0] - pin[0] + 1, stride[0])
        for y in range(0, zyx[1] - pin[1] + 1, stride[1])
        for x in range(0, zyx[2] - pin[2] + 1, stride[2])
    ]
    n = len(starts)
    assert n == int(np.prod(grid))
    nb = -(-n // batch_size)
    n_pad = nb * batch_size - n
    bump = jnp.asarray(bump, jnp.float32)

    def program(chunk, params):
        # the four parts under the names every patch program uses
        # (core/profiling.py DEVICE_SCOPES; metadata only)
        with jax.named_scope("gather"):
            patches = jnp.stack([
                lax.slice(
                    chunk, (0,) + s,
                    (ci,) + tuple(a + b for a, b in zip(s, pin))
                )
                for s in starts
            ])
            if n_pad:
                patches = jnp.concatenate(
                    [patches, jnp.zeros((n_pad, ci) + pin, patches.dtype)]
                )
        with jax.named_scope("forward"):
            preds = lax.map(
                lambda xb: forward(params, xb),
                # split patch axis n -> (nb, batch)
                patches.reshape((nb, batch_size, ci) + pin),
            )
        with jax.named_scope("accumulate"):
            # merge (nb, batch) -> flat patch axis, drop padding
            preds = preds.reshape((nb * batch_size, co) + pout)[:n]
            weighted = preds.astype(jnp.float32) * bump[None, None]
            out = fold_accumulate(weighted, grid, stride, pout, margin, zyx)
            wstack = jnp.broadcast_to(bump[None, None], (n, 1) + pout)
            weight = fold_accumulate(
                wstack, grid, stride, pout, margin, zyx)[0]
        return normalize_blend(out, weight, out_dtype)

    # the chunk buffer is dead after the call (GL005): XLA may reuse it
    # for the accumulation/output instead of allocating per chunk —
    # callers must hand over a buffer they own (docs/performance.md)
    return jax.jit(program, donate_argnums=(0,))
