"""The plain side of the comparison where the output patch is the central
part of the input patch: the decomposition of ``inference/patching.py``
and the bump-weighted overlap-add in numpy float64, with the bump over
the output patch and the input windows offset by the crop margin.

    out[c, v] = sum_p w(v - s_p - m) y_p[c, v - s_p - m]
                / sum_p w(v - s_p - m)

over the patches p whose output window ``[s_p + m, s_p + m + pout)``
holds v, with ``s_p`` the start of the input window on the grid of stride
``pout - overlap`` (the last one snapped to the chunk's end), ``m = (pin
- pout) // 2``, ``y_p`` the cropped prediction and ``w`` the bump on the
``pout`` grid.
"""
import itertools

import numpy as np

from cfbench import blend, catalog


def blend_box(image_u8, patch, output_patch, output_overlap, box, forward):
    """([C, *box] float64, patches used): the blended output inside
    ``box`` = (start, stop), in the coordinates of the chunk ``image_u8``
    (zyx uint8), from every patch whose output window touches the box.
    ``forward(window)`` maps one [*patch] float32 window to the cropped
    prediction [C, *output_patch]."""
    crop = [(p - o) // 2 for p, o in zip(patch, output_patch)]
    stride = [o - v for o, v in zip(output_patch, output_overlap)]
    weights = blend.bump_weights(output_patch)
    start, stop = box
    per_axis = []
    for axis in range(3):
        per_axis.append([
            s for s in blend.patch_starts(image_u8.shape[axis], patch[axis],
                                          stride[axis])
            if s + crop[axis] < stop[axis]
            and s + crop[axis] + output_patch[axis] > start[axis]])
    corners = list(itertools.product(*per_axis))
    out = total = None
    for corner in corners:
        window = tuple(slice(c, c + p) for c, p in zip(corner, patch))
        pred = np.asarray(forward(
            image_u8[window].astype(np.float32) * np.float32(1.0 / 255)),
            np.float64)
        if out is None:
            size = [b - a for a, b in zip(start, stop)]
            out = np.zeros((pred.shape[0], *size), np.float64)
            total = np.zeros(size, np.float64)
        first = [c + m for c, m in zip(corner, crop)]   # output window
        lo = [max(f, a) for f, a in zip(first, start)]
        hi = [min(f + o, b) for f, o, b in zip(first, output_patch, stop)]
        in_patch = tuple(slice(l - f, h - f)
                         for l, h, f in zip(lo, hi, first))
        in_box = tuple(slice(l - a, h - a) for l, h, a in zip(lo, hi, start))
        out[(slice(None),) + in_box] += pred[(slice(None),) + in_patch] \
            * weights[in_patch]
        total[in_box] += weights[in_patch]
    return out / total, len(corners)


def reference_output(ctx, image_u8, box, forward=None):
    """As :func:`cfbench.check.reference_output`, for a configuration
    with an ``output_patch``."""
    config = ctx.config
    reference = catalog.load_module("reference", config["reference"])
    params = ctx.engine_params()
    forward = forward or reference.make_forward(config)

    def one_patch(window):
        out = forward(params, window[None, ..., None])
        return np.moveaxis(np.asarray(out[0]), -1, 0)

    return blend_box(image_u8, tuple(config["patch"]),
                     tuple(config["output_patch"]),
                     tuple(config["overlap"]), box, one_patch)
