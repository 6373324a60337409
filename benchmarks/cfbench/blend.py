"""The system's patch decomposition and bump-weighted overlap-add, in numpy
float64: the plain side of the comparison that decides ``correct``
(copied from chip_smoke.py, which ran against the chip in PR 21)."""
import itertools

import numpy as np


def bump_weights(patch):
    """The "wu" bump exp(-sum 1/(1-u^2)) on the open (-1, 1)^3 grid,
    conditioned affinely into [1, 1e6]. float64 throughout."""
    axes = [np.linspace(-1.0, 1.0, n + 2)[1:-1] for n in patch]
    zz, yy, xx = np.meshgrid(*axes, indexing="ij")
    with np.errstate(under="ignore"):
        bump = np.exp(-1.0 / (1.0 - zz ** 2) - 1.0 / (1.0 - yy ** 2)
                      - 1.0 / (1.0 - xx ** 2))
    return (bump - bump.min()) / (bump.max() - bump.min()) * (1e6 - 1.0) + 1.0


def patch_starts(extent: int, patch: int, stride: int) -> list:
    starts = list(range(0, extent - patch + 1, stride))
    if starts[-1] != extent - patch:
        starts.append(extent - patch)
    return starts


def covering_patches(shape, patch, overlap, box):
    """Start corners of the patches of the ``shape`` chunk that touch
    ``box`` = (start, stop) in chunk coordinates."""
    stride = [p - o for p, o in zip(patch, overlap)]
    per_axis = []
    for axis in range(3):
        per_axis.append([
            s for s in patch_starts(shape[axis], patch[axis], stride[axis])
            if s < box[1][axis] and s + patch[axis] > box[0][axis]])
    return list(itertools.product(*per_axis))


def blend_box(image_u8, patch, overlap, box, forward):
    """[C, *box] float64: the blended output inside ``box`` of the chunk
    ``image_u8`` (zyx uint8), from every patch that touches the box.
    ``forward(window)`` maps one [z, y, x] float32 patch to [C, z, y, x].
    """
    weights = bump_weights(patch)
    start, stop = box
    size = [b - a for a, b in zip(start, stop)]
    out = total = None
    corners = covering_patches(image_u8.shape, patch, overlap, box)
    for corner in corners:
        window = tuple(slice(c, c + p) for c, p in zip(corner, patch))
        pred = np.asarray(forward(
            image_u8[window].astype(np.float32) * np.float32(1.0 / 255)),
            np.float64)
        if out is None:
            out = np.zeros((pred.shape[0], *size), np.float64)
            total = np.zeros(size, np.float64)
        # the part of this patch inside the box, in both frames
        lo = [max(c, a) for c, a in zip(corner, start)]
        hi = [min(c + p, b) for c, p, b in zip(corner, patch, stop)]
        in_patch = tuple(slice(l - c, h - c)
                         for l, h, c in zip(lo, hi, corner))
        in_box = tuple(slice(l - a, h - a) for l, h, a in zip(lo, hi, start))
        out[(slice(None),) + in_box] += pred[(slice(None),) + in_patch] \
            * weights[in_patch]
        total[in_box] += weights[in_patch]
    return out / total, len(corners)
