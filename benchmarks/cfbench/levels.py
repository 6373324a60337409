"""The seeded input volume of :mod:`cfbench.volume` together with its
"levels": the histogram of every z-section over the whole volume, one
JSON file ``<image>/levels/0/<z>`` a section with 256 counts under
``levels``, as igneous's luminance-levels task leaves them for
``normalize-contrast --levels-path``. A dataset's levels are computed
once by a job of their own; here the slabs are counted as they are
written, so the volume is made once."""
import json
import os

import numpy as np

from cfbench import volume


def levels_path(image_path: str) -> str:
    """Where the sidecars of ``image_path``'s mip 0 lie."""
    return image_path.rstrip("/") + "/levels/0"


def section_histograms(slab: np.ndarray) -> np.ndarray:
    """int64[z, 256]: the counts of each z-section of a zyx uint8 slab."""
    return np.stack([np.bincount(section.ravel(), minlength=256)
                     for section in slab])


def write_volume_and_levels(path: str, seed: int, geometry: volume.Geometry,
                            threads: int = 4) -> np.ndarray:
    """:func:`cfbench.volume.write_volume`'s volume (the same info file,
    blocks and slabs) and the sidecars of every section; returns the
    histograms, int64[z, 256]."""
    from concurrent.futures import ThreadPoolExecutor

    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    size, width = geometry.size, geometry.task[2]
    if width % geometry.block[2]:
        raise ValueError(f"task width {width} is not a multiple of the "
                         f"block width {geometry.block[2]}")
    layer = PrecomputedVolume.create(
        path, volume_size=size, voxel_size=(1, 1, 1), num_channels=1,
        dtype="uint8", layer_type="image", block_size=geometry.block)
    root = path[len("file://"):]
    directory = os.path.join(root, layer.info["scales"][0]["key"])
    os.makedirs(directory, exist_ok=True)

    def one(index: int) -> np.ndarray:
        slab = volume.seeded_slab(seed, geometry, index)
        volume.write_blocks(directory, slab, index * width, geometry.block)
        return section_histograms(slab)

    with ThreadPoolExecutor(threads) as pool:
        histograms = sum(pool.map(one, range(-(-size[2] // width))))
    write_levels(levels_path(root), histograms)
    return histograms


def write_levels(directory: str, histograms: np.ndarray) -> None:
    os.makedirs(directory, exist_ok=True)
    for z, counts in enumerate(histograms):
        with open(os.path.join(directory, str(z)), "w") as f:
            json.dump({"levels": [int(c) for c in counts]}, f)


def read_levels(directory: str, sections: int) -> list:
    """The sidecars' counts as the files have them: what the plain
    reference builds its tables from."""
    out = []
    for z in range(sections):
        with open(os.path.join(directory, str(z))) as f:
            out.append(json.load(f)["levels"])
    return out
