"""Multi-resolution masking (parity: reference flow/mask.py + chunk.maskout).

A mask chunk stored at a coarser mip multiplies a finer chunk: each mask
voxel covers an integer factor block (nearest neighbour), cut to the
chunk's box. The chunk is masked where it is, by what the code can see
(``chunk.is_on_device``):

- a **host** chunk is multiplied on the host, one slab of equal coarse
  planes at a time, by the coarse plane broadcast over its factor block.
  No full-size mask is made and nothing is uploaded;
- a **device** chunk is multiplied by one jitted program a shape, built
  through :class:`~chunkflow_tpu.core.compile_cache.ProgramCache` like
  the patch programs (so ``compile_cache/build`` and ``programs.json``
  see it) under the named scope ``mask`` (core/profiling.py
  ``DEVICE_SCOPES``). Only the coarse window goes up
  (``mask/h2d_bytes``); the result stays on the device.

A window that is all one returns the chunk as it is, one that is all zero
a zero chunk, with no multiply. ``mask/zeroed_voxels`` counts the voxels
of the chunk's box that lie under the mask's zeros.
"""
from __future__ import annotations

import numpy as np

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import telemetry
from chunkflow_tpu.core.cartesian import Cartesian
from chunkflow_tpu.core.compile_cache import ProgramCache

# one program a (chunk shape, dtype, coarse window, factor, phase)
_PROGRAMS = ProgramCache(maxsize=16, label="mask")


def upsample_factor(fine: Chunk, coarse: Chunk) -> Cartesian:
    factor = coarse.voxel_size / fine.voxel_size
    if any(f != int(f) or f < 1 for f in factor):
        raise ValueError(
            f"mask voxel size {coarse.voxel_size} must be an integer multiple "
            f"of chunk voxel size {fine.voxel_size}"
        )
    return factor.astype_int()


def coarse_window(chunk: Chunk, mask: Chunk, inverse: bool = False):
    """``(keep, factor, phase)``: the bool window of ``mask`` that covers
    the chunk's box (``True`` keeps a voxel), the integer factor a mask
    voxel covers, and how many fine voxels of the window's first coarse
    voxel lie in front of the chunk's start (a chunk need not start on a
    coarse voxel's edge)."""
    factor = upsample_factor(chunk, mask)
    # a coarse mask is small: on the host whatever it was handed as
    mask_arr = np.asarray(mask.array)
    if mask_arr.ndim == 4:
        mask_arr = mask_arr[0]
    # chunk start relative to the mask origin, in fine (chunk-res) voxels
    phys_delta = (
        chunk.voxel_offset * chunk.voxel_size - mask.voxel_offset * mask.voxel_size
    )
    fine_start = (phys_delta / chunk.voxel_size).floor()
    coarse_start = fine_start // factor
    phase = fine_start - coarse_start * factor
    shape = (phase + chunk.shape[-3:]).ceildiv(factor)
    keep = mask_arr[
        tuple(slice(s, s + n) for s, n in zip(coarse_start, shape))
    ] != 0
    if any(s < 0 for s in coarse_start) or keep.shape != tuple(shape):
        raise ValueError(
            f"mask {mask.bbox} at voxel size {mask.voxel_size} does not "
            f"cover chunk {chunk.bbox} at {chunk.voxel_size}"
        )
    if inverse:
        keep = ~keep
    return keep, tuple(int(f) for f in factor), tuple(int(p) for p in phase)


def _runs(length: int, factor: int, phase: int):
    """``(coarse index, fine start, fine stop)`` of each run of fine
    voxels along one axis that share a coarse voxel."""
    start = 0
    for index in range(-(-(phase + length) // factor)):
        stop = min((index + 1) * factor - phase, length)
        yield index, start, stop
        start = stop


def _fine_plane(keep_yx: np.ndarray, factor, phase, shape_yx) -> np.ndarray:
    """One coarse yx plane over its factor block, cut to the chunk."""
    plane = keep_yx
    for axis, f in enumerate(factor[1:]):
        if f > 1:
            plane = np.repeat(plane, f, axis=axis)
    return plane[phase[1]:phase[1] + shape_yx[0],
                 phase[2]:phase[2] + shape_yx[1]]


def _mask_host(arr: np.ndarray, keep, factor, phase) -> np.ndarray:
    """``arr`` times the window, a slab along z at a time: each multiply
    broadcasts one fine yx plane (a chunk's cross-section, not its
    volume) over the slab's slices and the channels. Neighbouring runs
    whose coarse planes are equal make one slab, so a mask that does not
    change along z costs one multiply."""
    out = np.empty_like(arr)
    shape = arr.shape[-3:]
    slabs = []      # [coarse index, fine start, fine stop]
    for index, z0, z1 in _runs(shape[0], factor[0], phase[0]):
        if slabs and np.array_equal(keep[index], keep[slabs[-1][0]]):
            slabs[-1][2] = z1
        else:
            slabs.append([index, z0, z1])
    for index, z0, z1 in slabs:
        plane = keep[index]
        slab, into = arr[..., z0:z1, :, :], out[..., z0:z1, :, :]
        if plane.all():
            into[...] = slab
        elif not plane.any():
            into[...] = 0
        else:
            np.multiply(slab, _fine_plane(plane, factor, phase, shape[1:])
                        .astype(arr.dtype), out=into)
    return out


def _build_program(factor, phase, shape):
    import jax
    import jax.numpy as jnp

    def program(arr, keep):
        with jax.named_scope("mask"):
            for axis, f in enumerate(factor):
                if f > 1:
                    keep = jnp.repeat(keep, f, axis=axis)
            keep = keep[tuple(slice(p, p + s) for p, s in zip(phase, shape))]
            return arr * keep.astype(arr.dtype)

    # no donation: `maskout` is a function of a chunk, not its consumer
    # (`Chunk.maskout`, `mask -i a -o b`: the caller keeps what it gave)
    return jax.jit(program)  # graftlint: disable=GL005


def _mask_device(arr, keep: np.ndarray, factor, phase):
    import jax

    key = ("mask", tuple(arr.shape), str(arr.dtype), keep.shape, factor,
           phase)
    program = _PROGRAMS.get(
        key, lambda: _build_program(factor, phase, tuple(arr.shape[-3:])))
    return program(arr, jax.device_put(keep))


def zeroed_voxels(keep: np.ndarray, factor, phase, shape) -> int:
    """Voxels of a ``shape`` box under the window's zeros."""
    lengths = [
        np.array([stop - start for _, start, stop in _runs(n, f, p)])
        for n, f, p in zip(shape, factor, phase)
    ]
    return int(np.einsum("zyx,z,y,x->", (~keep).astype(np.int64), *lengths))


def maskout(chunk: Chunk, mask: Chunk, inverse: bool = False) -> Chunk:
    """Multiply ``chunk`` by a (possibly coarser-resolution) binary mask,
    on the host or on the device: wherever the chunk is."""
    keep, factor, phase = coarse_window(chunk, mask, inverse)
    on_device = chunk.is_on_device
    with telemetry.span("mask/apply", voxels=int(np.prod(chunk.shape[-3:])),
                        device=int(on_device)):
        if keep.all():
            return chunk
        telemetry.inc("mask/zeroed_voxels", zeroed_voxels(
            keep, factor, phase, chunk.shape[-3:]))
        # what this mask sends to the device: the coarse window of a
        # device chunk, nothing of a host chunk
        telemetry.inc("mask/h2d_bytes", keep.nbytes if on_device else 0)
        if on_device:
            # also the all-zero window: a multiply by zero, in place of
            # an upload of the chunk's size in zeros
            return chunk._with_array(
                _mask_device(chunk.array, keep, factor, phase))
        arr = np.asarray(chunk.array)
        if not keep.any():
            # fresh zero pages: nothing is written until somebody reads
            return chunk._with_array(np.zeros(arr.shape, arr.dtype))
        return chunk._with_array(_mask_host(arr, keep, factor, phase))
