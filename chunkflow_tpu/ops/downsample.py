"""Downsampling pyramids — the tinybrain (C++) equivalent, on XLA.

Two pooling modes, matching the reference's use of tinybrain
(flow/downsample_upload.py:73-79):
- images / probability maps: average pooling where the chunk is, by
  numpy on the host and by one program for every level on the device
  (``average_pyramid``; the thumbnail of ``save-precomputed``);
- segmentations: mode pooling ("countless" semantics — the most frequent
  label in each 2x2x... block, implemented by exact bincount over the
  gathered block corners, vectorized in jnp for factor (1,2,2)/(2,2,2)).
"""
from __future__ import annotations

from typing import List

import numpy as np

from chunkflow_tpu.chunk.base import Chunk, LayerType
from chunkflow_tpu.core.cartesian import Cartesian, to_cartesian
from chunkflow_tpu.core.compile_cache import ProgramCache

# one averaging program a (chunk shape, dtype, factor, levels)
_PROGRAMS = ProgramCache(maxsize=16, label="thumbnail")


def _pool(xp, arr, factor):
    """Average pooling of ``arr`` (``[c, z, y, x]``, every extent a
    multiple of its factor) in ``xp``, numpy or ``jax.numpy``: the same
    arithmetic on either, an axis at a time. Integers of 8 and 16 bits
    are summed exactly, in the narrowest type that holds the sum, and
    divided with ties to even, so the host's and the device's result
    agree bit for bit; wider integers and floats go through a float32
    sum."""
    dtype = np.dtype(arr.dtype)
    n = int(np.prod(factor))
    exact = dtype.kind in "iu" and dtype.itemsize <= 2
    # static choices: a dtype and the caller's Python factor
    if not exact:
        wide = np.float32
    elif dtype.itemsize == 1 and n <= 256:  # graftlint: disable=GL003
        wide = np.uint16 if dtype.kind == "u" else np.int16
    else:
        wide = np.int32
    total = arr
    for axis, f in zip((1, 2, 3), factor):
        parts = [total[(slice(None),) * axis + (slice(k, None, f),)]
                 for k in range(f)]
        total = parts[0].astype(wide)
        for part in parts[1:]:
            total = total + part.astype(wide)
    if not exact:
        mean = total / np.float32(n)
        return (xp.round(mean) if dtype.kind in "iu" else mean).astype(dtype)
    # 2 * rest < 2 * n fits the sum's type: n does, and a sum has room
    quotient, rest = xp.divmod(total, xp.asarray(n, wide))
    twice = rest * xp.asarray(2, wide)
    up = (twice > n) | ((twice == n) & ((quotient & 1) == 1))
    return (quotient + up).astype(dtype)


def _average_levels(xp, arr, factor, num_mips: int):
    levels = []
    for _ in range(num_mips):
        trimmed = [n - n % f for n, f in zip(arr.shape[1:], factor)]
        arr = _pool(xp, arr[:, :trimmed[0], :trimmed[1], :trimmed[2]],
                    factor)
        levels.append(arr)
    return levels


def _build_program(factor, num_mips):
    import jax
    import jax.numpy as jnp

    def program(arr):
        with jax.named_scope("thumbnail"):
            return tuple(_average_levels(jnp, arr, factor, num_mips))

    # no donation: the caller keeps the chunk it gave
    return jax.jit(program)  # graftlint: disable=GL005


def average_pyramid(chunk: Chunk, factor=(1, 2, 2),
                    num_mips: int = 1) -> List[Chunk]:
    """``num_mips`` successive average poolings of ``chunk``, made where
    the chunk is (``chunk.is_on_device``): on the host by numpy, with
    nothing uploaded, or on the device by one jitted program a shape
    that returns every level, built through
    :class:`~chunkflow_tpu.core.compile_cache.ProgramCache` under the
    named scope ``thumbnail`` (core/profiling.py ``DEVICE_SCOPES``).
    Extents that the factor does not divide lose their remainder."""
    by = to_cartesian(factor)
    factor = tuple(int(f) for f in by)
    arr = chunk.array
    squeeze = arr.ndim == 3
    if chunk.is_on_device:
        if squeeze:
            arr = arr[None]
        key = ("thumbnail", tuple(arr.shape), str(arr.dtype), factor,
               num_mips)
        arrays = _PROGRAMS.get(
            key, lambda: _build_program(factor, num_mips))(arr)
    else:
        arr = np.asarray(arr)
        arrays = _average_levels(np, arr[None] if squeeze else arr, factor,
                                 num_mips)
    levels = []
    offset, size = chunk.voxel_offset, chunk.voxel_size
    for pooled in arrays:
        offset, size = offset // by, size * by
        levels.append(Chunk(
            pooled[0] if squeeze else pooled, voxel_offset=offset,
            voxel_size=size, layer_type=chunk.layer_type))
    return levels


def downsample_average(chunk: Chunk, factor=(1, 2, 2)) -> Chunk:
    return average_pyramid(chunk, factor, 1)[0]


def _stack_corners_numpy(arr: np.ndarray, factor) -> np.ndarray:
    """[n_corners, c, z', y', x'] corner samples of each pooling block."""
    corners = []
    for dz in range(factor.z):
        for dy in range(factor.y):
            for dx in range(factor.x):
                corners.append(
                    arr[:, dz :: factor.z, dy :: factor.y, dx :: factor.x]
                )
    return np.stack(corners, axis=0)


def mode_pool_numpy(arr: np.ndarray, factor) -> np.ndarray:
    """Reference host implementation: exact mode with ties going to the
    first corner (z-major corner order)."""
    stacked = _stack_corners_numpy(arr, factor)
    n = stacked.shape[0]
    counts = np.zeros(stacked.shape, dtype=np.int8)
    for i in range(n):
        for j in range(n):
            counts[i] += stacked[i] == stacked[j]
    winner = np.argmax(counts, axis=0)
    return np.take_along_axis(stacked, winner[None], axis=0)[0]


def mode_pool_device(arr, factor):
    """The same mode pooling as one fused XLA program (the tinybrain /
    countless replacement, SURVEY §2.9): all-pairs equality counting is
    pure elementwise compare+add, so the whole n²-corner vote fuses into
    device code — a 512³ uint32 segmentation pools in device time instead
    of 64 full-array numpy passes.

    Tie semantics match ``mode_pool_numpy`` exactly: argmax returns the
    first corner with the max count in z-major corner order.
    """
    import jax.numpy as jnp

    arr = jnp.asarray(arr)
    c = arr.shape[0]
    zp, yp, xp = (
        arr.shape[1] // factor.z,
        arr.shape[2] // factor.y,
        arr.shape[3] // factor.x,
    )
    # czyx -> block axes (c, z', fz, y', fy, x', fx)
    blocks = arr.reshape(c, zp, factor.z, yp, factor.y, xp, factor.x)
    # [n_corners, c, z', y', x'] in z-major corner order (dz, dy, dx)
    stacked = blocks.transpose(2, 4, 6, 0, 1, 3, 5).reshape(
        factor.z * factor.y * factor.x, c, zp, yp, xp
    )
    n = stacked.shape[0]
    counts = jnp.zeros(stacked.shape, dtype=jnp.int8)
    for j in range(n):  # unrolled compare+add chain; XLA fuses it
        counts = counts + (stacked == stacked[j][None]).astype(jnp.int8)
    winner = jnp.argmax(counts, axis=0)
    return jnp.take_along_axis(stacked, winner[None], axis=0)[0]


def downsample_mode(chunk: Chunk, factor=(1, 2, 2)) -> Chunk:
    """Mode (most-frequent-label) pooling for segmentations.

    Runs on device (XLA) for <=32-bit labels; 64-bit labels fall back to
    the numpy path unless jax x64 is enabled (jnp would silently truncate
    them). Ties: the first corner in z-major order wins, in both paths.
    """
    factor = to_cartesian(factor)
    arr = chunk.array
    squeeze = hasattr(arr, "ndim") and arr.ndim == 3
    host_in = not chunk.is_on_device
    if host_in:
        arr = np.asarray(arr)
    if squeeze:
        arr = arr[None]
    spatial = Cartesian.from_collection(arr.shape[1:])
    trimmed = (spatial // factor) * factor
    arr = arr[:, : trimmed.z, : trimmed.y, : trimmed.x]

    use_device = True
    if np.dtype(chunk.dtype).itemsize > 4:
        try:
            import jax

            use_device = bool(jax.config.jax_enable_x64)
        except Exception:
            use_device = False
    if use_device:
        pooled = mode_pool_device(arr, factor)
        if host_in:
            pooled = np.asarray(pooled)
    else:
        pooled = mode_pool_numpy(np.asarray(arr), factor)
    if squeeze:
        pooled = pooled[0]
    return Chunk(
        pooled,
        voxel_offset=chunk.voxel_offset // factor,
        voxel_size=chunk.voxel_size * factor,
        layer_type=chunk.layer_type,
    )


def downsample(chunk: Chunk, factor=(1, 2, 2)) -> Chunk:
    if chunk.is_segmentation:
        return downsample_mode(chunk, factor)
    return downsample_average(chunk, factor)


def pyramid(chunk: Chunk, factor=(1, 2, 2), num_mips: int = 3) -> List[Chunk]:
    """Successive downsamples: [mip+1, mip+2, ...]."""
    if not chunk.is_segmentation:
        return average_pyramid(chunk, factor, num_mips)
    levels = []
    current = chunk
    for _ in range(num_mips):
        current = downsample(current, factor)
        levels.append(current)
    return levels
