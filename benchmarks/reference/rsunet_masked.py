"""Plain reference of the masked production pipeline: upstream's
``cutout > mask > inference > crop > mask > save`` (seung-lab/chunkflow
``tests/flow/test_flow.py:test_inference_pipeline``) around the forward of
``reference/rsunet.py``. It imports nothing of the program: the masks are
numpy index arithmetic here (the program repeats and multiplies run by
run), the blend is the caller's (``cfbench/blend.py``, numpy float64).

**The deployment's equations.** A coarse mask ``m`` has voxels ``f =
(fz, fy, fx)`` times the image's. ``U_f(m)`` repeats each of its voxels
``f`` times along its axis (nearest neighbour, the reference's
``maskout``) and is cut to the chunk's box: the fine voxel at global
``p`` reads ``m[p // f]``. For a task with input chunk ``x`` (uint8, the
task's box grown by the margin), image mask ``m_in`` and output mask
``m_out``::

    x' = x * (U_f(m_in) != 0)
    y  = 0                                   if x' is all zero
    y  = crop_margin(Blend(F(patches(x' / 255)))) * (U_f(m_out) != 0)
                                             otherwise

``y`` is float32 over the task's box in every channel; ``F`` is the
forward of ``reference/rsunet.py`` and ``Blend`` the bump-weighted
overlap-add of ``cfbench/blend.py``, exactly the unmasked
configuration's. Where ``x'`` is all zero the forward does not run.
Every task, blank or not, commits all its blocks: a blank task's are
written as zeros, so that the volume is complete and a re-run's
``skip-task-by-blocks-in-volume`` finds them.
"""
import numpy as np

from cfbench import catalog


def make_forward(config: dict):
    """The forward is the unmasked configuration's."""
    return catalog.load_module("reference", "rsunet").make_forward(config)


def keep(mask: np.ndarray, factor, start, shape) -> np.ndarray:
    """``U_f(m) != 0`` over the fine box ``start .. start + shape``
    (global fine voxels; ``mask[0, 0, 0]`` is the coarse voxel at the
    origin): bool, ``shape``."""
    index = [(np.arange(a, a + n) // f) for a, n, f
             in zip(start, shape, factor)]
    return mask[np.ix_(*index)] != 0


def masked_input(image_u8: np.ndarray, mask_in, factor, start) -> np.ndarray:
    """``x'``: the input chunk (zyx uint8 at global ``start``) times the
    image mask."""
    return image_u8 * keep(mask_in, factor, start, image_u8.shape)


def output(image_u8, mask_in, mask_out, factor, start, box, blend):
    """``([C, *box] float64 or None, keep_out over the box, patches
    used)``: ``y`` inside ``box`` = (start, stop) in the input chunk's
    coordinates. ``blend(x', box)`` gives the unmasked configuration's
    blended output of a chunk inside a box and the patches it took.
    ``None`` stands for the blank task's zeros: no forward runs, so the
    channel count is not known here."""
    x = masked_input(image_u8, mask_in, factor, start)
    lo, hi = box
    kept = keep(mask_out, factor, [s + a for s, a in zip(start, lo)],
                [b - a for a, b in zip(lo, hi)])
    if not x.any():
        return None, kept, 0
    want, n_patches = blend(x, box)
    return np.asarray(want, np.float64) * kept, kept, n_patches
