"""Affinity map chunk (parity: reference chunk/affinity_map/base.py)."""
from __future__ import annotations

import numpy as np

from chunkflow_tpu.chunk.base import Chunk, LayerType
from chunkflow_tpu.core.compile_cache import ProgramCache

# one quantizing program a (chunk shape, dtype, mode)
_PROGRAMS = ProgramCache(maxsize=16, label="thumbnail")


class AffinityMap(Chunk):

    """3-channel float 4D chunk of zyx boundary affinities."""

    @classmethod
    def from_chunk(cls, chunk: Chunk) -> "AffinityMap":
        # Chunk.__init__ copies all metadata when given a Chunk
        return cls(chunk)

    @classmethod
    def from_segmentation(
        cls,
        seg,
        inside: float = 1.0,
        boundary: float = 0.0,
        **kwargs,
    ) -> "AffinityMap":
        """Ground-truth affinity graph of a segmentation.

        Channel ``c`` at voxel (z, y, x) holds the edge to its neighbor
        one step NEGATIVE along axis ``c`` — the zyx convention shared by
        the native watershed (native/src/watershed.cpp) and the
        reference's affinity outputs. An edge scores ``inside`` iff both
        endpoints share the same nonzero label, else ``boundary``;
        label 0 is background and never connects. Leading-plane edges
        (no neighbor in range) score ``inside`` (self-edge). Used for
        training-target generation and as the analytic fixture behind
        the agglomeration quality harness and watershed bench.
        """
        if isinstance(seg, Chunk):
            kwargs.setdefault("voxel_offset", seg.voxel_offset)
            kwargs.setdefault("voxel_size", seg.voxel_size)
            seg = seg.array
        arr = np.asarray(seg)
        if arr.ndim != 3:
            raise ValueError(f"need a 3D (z, y, x) segmentation, got "
                             f"{arr.shape}")
        aff = np.full((3,) + arr.shape, np.float32(inside), np.float32)
        for c in range(3):
            sl_a = [slice(None)] * 3
            sl_b = [slice(None)] * 3
            sl_a[c] = slice(1, None)
            sl_b[c] = slice(0, -1)
            a, b = arr[tuple(sl_a)], arr[tuple(sl_b)]
            aff[(c, *sl_a)] = np.where(
                (a == b) & (a != 0), np.float32(inside), np.float32(boundary)
            )
        return cls(aff, **kwargs)

    def __init__(self, array, **kwargs):
        kwargs.setdefault("layer_type", LayerType.AFFINITY_MAP)
        super().__init__(array, **kwargs)
        if self.ndim != 4:
            raise ValueError("affinity maps are 4D (c, z, y, x)")

    def quantize(self, mode: str = "xy") -> Chunk:
        """Compress to a uint8 grayscale thumbnail chunk.

        ``xy``: mean of the y and x affinity channels; ``z``: z channel
        only; times 255, clipped and truncated. Made where the chunk is:
        a host chunk section by section in numpy (two buffers of a
        section's size, no copy of the chunk's), a device chunk by one
        cached program a shape under the scope ``thumbnail``.
        """
        if mode not in ("xy", "z"):
            raise ValueError(f"unknown quantize mode {mode!r}")
        if self.is_on_device:
            key = ("quantize", tuple(self.array.shape),
                   str(self.array.dtype), mode)
            gray = _PROGRAMS.get(
                key, lambda: _build_quantize(mode))(self.array)
        else:
            arr = np.asarray(self.array)
            gray = np.empty(arr.shape[1:], np.uint8)
            section = np.empty(arr.shape[2:], np.float32)
            for z in range(arr.shape[1]):
                if mode == "xy":
                    np.add(arr[1, z], arr[2, z], out=section,
                           dtype=np.float32)
                    section *= np.float32(0.5)
                else:
                    section[...] = arr[0, z]
                section *= np.float32(255.0)
                np.clip(section, 0, 255, out=section)
                gray[z] = section
        return Chunk(
            gray,
            voxel_offset=self.voxel_offset,
            voxel_size=self.voxel_size,
            layer_type=LayerType.IMAGE,
        )


def _build_quantize(mode: str):
    import jax
    import jax.numpy as jnp

    def program(arr):
        with jax.named_scope("thumbnail"):
            if mode == "xy":
                gray = (arr[1].astype(jnp.float32)
                        + arr[2].astype(jnp.float32)) * jnp.float32(0.5)
            else:
                gray = arr[0].astype(jnp.float32)
            return jnp.clip(gray * jnp.float32(255.0), 0, 255).astype(
                jnp.uint8)

    # no donation: the chunk is saved after its thumbnail is made
    return jax.jit(program)  # graftlint: disable=GL005
