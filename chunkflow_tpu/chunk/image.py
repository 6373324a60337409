"""Grayscale image chunk (parity: reference chunk/image/base.py).

Contrast normalization is the reference's with ``levels_path``: each
z-section through a lookup table built from the section's precomputed
histogram sidecar (ops/contrast.py). Without it, it is a vectorized
per-section percentile stretch of the chunk's own voxels (jnp-friendly,
any dtype): another operator, and many times the cost on a large chunk.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from chunkflow_tpu.chunk.base import Chunk, LayerType


class Image(Chunk):
    def __init__(self, array, **kwargs):
        kwargs.setdefault("layer_type", LayerType.IMAGE)
        super().__init__(array, **kwargs)

    @classmethod
    def from_chunk(cls, chunk: Chunk) -> "Image":
        return cls(
            chunk.array,
            voxel_offset=chunk.voxel_offset,
            voxel_size=chunk.voxel_size,
        )

    def inference(self, inferencer) -> Chunk:
        """Run patch-wise convnet inference over this image."""
        return inferencer(self)

    def normalize_shang(
        self,
        nominalmin=None,
        nominalmax=None,
        clipvalues: bool = False,
    ) -> "Image":
        """Slice-wise min/max normalization to a nominal range, Shang's
        method (reference chunk/image/adjust_grey.py:209-255)."""
        from chunkflow_tpu.chunk.adjust_grey import normalize_shang

        out = normalize_shang(
            np.asarray(self.array), nominalmin, nominalmax, clipvalues
        )
        return Image(
            out, voxel_offset=self.voxel_offset, voxel_size=self.voxel_size
        )

    def normalize_contrast(
        self,
        lower_clip_fraction: float = 0.01,
        upper_clip_fraction: float = 0.01,
        minval: int = 1,
        maxval: int = 255,
        per_section: bool = True,
        levels_path: Optional[str] = None,
    ) -> "Image":
        """Clip the darkest/brightest fractions and stretch the remainder
        to [minval, maxval].

        With ``levels_path``: the reference's histogram-lookup
        normalization (image/base.py:93-133) of a uint8 image, the
        fractions taken from each section's sidecar ``levels_path/<z>``
        (ops/contrast.py). Without: a percentile stretch of the chunk's
        own voxels, per z-section by default.
        """
        if levels_path is not None:
            from chunkflow_tpu.ops.contrast import (
                normalize_contrast_by_levels,
            )

            return normalize_contrast_by_levels(
                self, levels_path, lower_clip_fraction, upper_clip_fraction,
                minval, maxval)
        # stays on device when the payload is already HBM-resident
        if self.is_on_device:
            import jax.numpy as xp
        else:
            xp = np
        arr = xp.asarray(self.array).astype(xp.float32)
        lo_q = lower_clip_fraction * 100.0
        hi_q = 100.0 - upper_clip_fraction * 100.0
        # per z-section (and per channel for 4D): reduce over the trailing
        # (y, x) axes; otherwise over the whole array
        axes = (-2, -1) if per_section else tuple(range(-3, 0))
        lows = xp.percentile(arr, lo_q, axis=axes, keepdims=True)
        highs = xp.percentile(arr, hi_q, axis=axes, keepdims=True)
        scale = (maxval - minval) / xp.maximum(highs - lows, 1e-6)
        out = xp.clip((arr - lows) * scale + minval, minval, maxval)
        dtype = self.dtype if np.dtype(self.dtype).kind in "iu" else np.uint8
        return Image(
            out.astype(dtype),
            voxel_offset=self.voxel_offset,
            voxel_size=self.voxel_size,
        )
