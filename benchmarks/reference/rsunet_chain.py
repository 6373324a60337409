"""Plain reference of upstream's worker command without the masks
(seung-lab/chunkflow ``distributed/kubernetes/deploy.yml:30-37``)::

    load-precomputed > normalize-contrast > inference > crop-margin >
    save-precomputed (+ thumbnail, + log)

around the forward of ``reference/rsunet.py``. It imports nothing of the
program: the tables are Python integer arithmetic on the sidecars'
counts, the thumbnail is numpy integer arithmetic on a block of the
result, the blend is the caller's (``cfbench/blend.py``, numpy float64).

**normalize-contrast** (upstream ``chunk/image/base.py:93-133``, its
clamping-value search ``:30-62``, as SURVEY.md line 69 records them: each
z-section through a 256-entry lookup table built from the section's
*precomputed* histogram and two clip fractions). The sidecar of section
``z`` is ``<levels path>/<z>``, a JSON object whose ``levels`` is the
256 counts ``h`` of that section over the whole volume. With ``h[0] :=
0`` (pure black is no tissue and is left out of the shares), ``cdf[v] =
h[0] + .. + h[v]``, ``total = cdf[255]``, fractions ``l``, ``u`` and
range ``minval .. maxval``::

    lo = max{v : cdf[v] / total <= l}        (0 where no v qualifies)
    hi = max{v : cdf[v] / total <= 1 - u}    (0 where no v qualifies)
    T[v] = clip(round((v - lo) * (maxval - minval) / max(hi - lo, 1))
                + minval, minval, maxval)            v = 0 .. 255
    x'[z, y, x] = T_z[x[z, y, x]]

``cdf[v] / total`` and ``1 - u`` are float64 (a quotient of two integers
correctly rounded), ``round`` is to the nearest integer with ties to
even, computed here on integers; ``total = 0`` gives ``lo = hi = 0``.
What the record does not settle and was chosen (the configuration lists
each under ``assumed``): of several ``v`` with equal share the last one
is the clamping value (the search walks up and keeps the last value
within the fraction); ties round to even; the stretch is to ``maxval -
minval`` with ``minval`` added, so ``lo`` maps to ``minval`` and ``hi``
to ``maxval``, where upstream as remembered scales by ``maxval / (hi -
lo)`` and clips; ``hi = lo`` divides by 1 in place of an identity table;
``T[0]`` follows the formula (``minval``) like every other value.

**inference, crop-margin**: ``y = crop_margin(Blend(F(patches(x' /
255))))``, float32 over the task's box in every channel, exactly the
anchor configuration's (``reference/rsunet.py``, ``cfbench/blend.py``)
on the *normalized* chunk.

**The thumbnail** (upstream ``flow/save_precomputed.py:104-139``: the
result quantized to grey and downsampled by (1, 2, 2) into the sibling
``thumbnail`` layer). For a committed block ``y`` (``[C, z, y, x]``
float32, its y and x starts and extents multiples of ``2**levels`` in
the task's own frame, which is where the program's pooling grid is
anchored)::

    g0 = trunc(clip((y[1] + y[2]) / 2 * 255, 0, 255))      uint8, mode xy
    g{k}[z, j, i] = round_half_even((g{k-1}[z, 2j, 2i] + g{k-1}[z, 2j, 2i+1]
                    + g{k-1}[z, 2j+1, 2i] + g{k-1}[z, 2j+1, 2i+1]) / 4)

for ``k = 1 .. levels``; level ``k`` lies in the layer's mip ``k`` at the
block's box divided by ``2**k``. ``g0`` is computed here in float64, the
program's in float32: a product that float32 rounds up to a whole number
truncates one grey level higher, and a pooling of values at most one
apart is at most one apart, which is why the comparison allows one grey
level at every level and no more.

**The log**: one JSON file ``<volume>/log/<task box>.json`` a task,
written after the result's and the thumbnail's blocks are durable; the
task is acked after it.
"""
import numpy as np

from cfbench import catalog


def make_forward(config: dict):
    """The forward is the anchor configuration's."""
    return catalog.load_module("reference", "rsunet").make_forward(config)


def clamping_values(levels, lower: float, upper: float):
    """``(lo, hi)`` of one section's 256 counts."""
    counts = [int(c) for c in levels]
    if len(counts) != 256:
        raise ValueError(f"256 counts make a histogram, got {len(counts)}")
    counts[0] = 0
    total = sum(counts)
    if total == 0:
        return 0, 0
    lo = hi = 0
    running = 0
    for value, count in enumerate(counts):
        running += count
        share = running / total
        if share <= lower:
            lo = value
        if share <= 1.0 - upper:
            hi = value
    return lo, hi


def round_half_even(numerator: int, denominator: int) -> int:
    """``numerator / denominator`` to the nearest integer, ties to even,
    on integers (``denominator`` > 0)."""
    quotient, rest = divmod(numerator, denominator)
    if 2 * rest > denominator or (2 * rest == denominator and quotient % 2):
        quotient += 1
    return quotient


def lookup_table(levels, lower=0.01, upper=0.01, minval=1, maxval=255):
    """``T``: uint8[256]."""
    lo, hi = clamping_values(levels, lower, upper)
    span = max(hi - lo, 1)
    return np.array([
        min(max(round_half_even((v - lo) * (maxval - minval), span) + minval,
                minval), maxval)
        for v in range(256)], np.uint8)


def normalized(image_u8: np.ndarray, histograms, normalize: dict):
    """``x'``: the chunk (zyx uint8, its first section the volume's
    section 0) with section ``z`` through the table of
    ``histograms[z]``; ``normalize``: the configuration's block."""
    out = np.empty_like(image_u8)
    for z in range(image_u8.shape[0]):
        table = lookup_table(
            histograms[z], normalize["lower_clip_fraction"],
            normalize["upper_clip_fraction"], normalize["minval"],
            normalize["maxval"])
        out[z] = table[image_u8[z]]
    return out


def output(image_u8, histograms, normalize: dict, box, blend):
    """``([C, *box] float64, patches used)``: ``y`` inside ``box`` =
    (start, stop) in the input chunk's coordinates. ``blend(x', box)``
    gives the anchor configuration's blended output of a chunk inside a
    box and the patches it took."""
    return blend(normalized(image_u8, histograms, normalize), box)


def grey(block: np.ndarray) -> np.ndarray:
    """``g0`` of a ``[C, z, y, x]`` block: uint8 ``[z, y, x]``."""
    mean = (block[1].astype(np.float64) + block[2].astype(np.float64)) / 2
    return np.trunc(np.clip(mean * 255.0, 0.0, 255.0)).astype(np.uint8)


def pooled(level: np.ndarray) -> np.ndarray:
    """One (1, 2, 2) average of a uint8 ``[z, y, x]`` level, ties to
    even."""
    wide = level.astype(np.int64)
    total = (wide[:, 0::2, 0::2] + wide[:, 0::2, 1::2]
             + wide[:, 1::2, 0::2] + wide[:, 1::2, 1::2])
    quotient, rest = total >> 2, total & 3
    return (quotient + ((rest == 3) | ((rest == 2) & (quotient & 1 == 1)))
            ).astype(np.uint8)


def thumbnail_levels(block: np.ndarray, levels: int) -> list:
    """``[g1, .., g{levels}]`` of a committed ``[C, z, y, x]`` block."""
    out, current = [], grey(block)
    for _ in range(levels):
        current = pooled(current)
        out.append(current)
    return out
