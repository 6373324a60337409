"""The volume jobs' input: a seeded uint8 EM-like volume as precomputed on
local disk, cut into tasks that tile x, and the geometry of one task."""
import dataclasses
import os

import numpy as np


@dataclasses.dataclass
class Geometry:
    patch: tuple
    overlap: tuple
    margin: tuple
    block: tuple
    grid: tuple        # patches per task in z, y, x
    n_tasks: int

    @property
    def stride(self):
        return tuple(p - o for p, o in zip(self.patch, self.overlap))

    @property
    def chunk_in(self):
        """The task's input: its output box grown by the margin."""
        return tuple(p + (g - 1) * s for p, g, s
                     in zip(self.patch, self.grid, self.stride))

    @property
    def task(self):
        return tuple(c - 2 * m for c, m in zip(self.chunk_in, self.margin))

    @property
    def roi(self):
        t = self.task
        return (t[0], t[1], t[2] * self.n_tasks)

    @property
    def size(self):
        return tuple(r + 2 * m for r, m in zip(self.roi, self.margin))

    @property
    def patches_per_task(self) -> int:
        return int(np.prod(self.grid))

    @property
    def task_voxels(self) -> int:
        return int(np.prod(self.task))

    def task_start(self, index: int):
        m, t = self.margin, self.task
        return (m[0], m[1], m[2] + index * t[2])

    def check_box(self):
        """(start, stop) in input-chunk coordinates of the block the
        comparison reads: one stride wide from the middle of the first
        patch, so that 2x2x2 patches overlap inside it."""
        start = tuple(p // 2 for p in self.patch)
        return start, tuple(a + s for a, s in zip(start, self.stride))


def make_slab(rng, shape) -> np.ndarray:
    """zyx uint8, EM-like: 8-voxel blobs under pixel noise. Integer
    arithmetic on raw generator words, so a gigabyte takes seconds."""
    n = int(np.prod(shape))
    noise = rng.bit_generator.random_raw(-(-n // 8)).view(np.uint8)[:n]
    noise = noise.reshape(shape)
    noise >>= 2                                   # 0..63
    coarse = rng.integers(0, 64, [-(-s // 8) for s in shape],
                          dtype=np.uint8) * np.uint8(3)   # 0..189
    blobs = coarse.repeat(8, 2).repeat(8, 1).repeat(8, 0)
    noise += blobs[:shape[0], :shape[1], :shape[2]]
    return noise


def seeded_slab(seed: int, geometry: Geometry, index: int) -> np.ndarray:
    """Slab ``index`` of the volume: the x range of task ``index``'s
    output (the last, narrow one closes the margin). Each slab has a
    generator of its own keyed by (seed, index), so any slab can be made
    again without the others."""
    size, width = geometry.size, geometry.task[2]
    x0 = index * width
    x1 = min(x0 + width, size[2])
    return make_slab(np.random.default_rng([seed, index]),
                     (size[0], size[1], x1 - x0))


def seeded_task_input(seed: int, geometry: Geometry, index: int):
    """The expanded input chunk of task ``index``, made again from the
    seed: what the plain side of the comparison is given."""
    pair = [seeded_slab(seed, geometry, index),
            seeded_slab(seed, geometry, index + 1)]
    return np.concatenate(pair, axis=2)[:, :, :geometry.chunk_in[2]]


def write_blocks(directory: str, slab: np.ndarray, x0: int, block) -> None:
    """``slab`` (zyx uint8, at x offset ``x0``) as raw precomputed blocks
    under ``directory``: one file ``x0-x1_y0-y1_z0-z1`` a block, its bytes
    the block in C order of zyx (x fastest), edge blocks clamped to the
    slab. A plain writer: the input volume is somebody else's data, and
    the program's own writer takes ten times as long for it."""
    nz, ny, nx = slab.shape
    bz, by, bx = block
    for z in range(0, nz, bz):
        z1 = min(z + bz, nz)
        for y in range(0, ny, by):
            y1 = min(y + by, ny)
            rows = np.ascontiguousarray(slab[z:z1, y:y1])
            for x in range(0, nx, bx):
                x1 = min(x + bx, nx)
                name = f"{x0 + x}-{x0 + x1}_{y}-{y1}_{z}-{z1}"
                with open(os.path.join(directory, name), "wb") as f:
                    f.write(np.ascontiguousarray(rows[:, :, x:x1]).data)


def write_volume(path: str, seed: int, geometry: Geometry,
                 threads: int = 4) -> None:
    """The seeded input volume of ``geometry`` as precomputed at ``path``
    (file://...): the info file through the program, the blocks slab by
    slab by a few threads."""
    from concurrent.futures import ThreadPoolExecutor

    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    size, width = geometry.size, geometry.task[2]
    if width % geometry.block[2]:
        raise ValueError(f"task width {width} is not a multiple of the "
                         f"block width {geometry.block[2]}")
    volume = PrecomputedVolume.create(
        path, volume_size=size, voxel_size=(1, 1, 1), num_channels=1,
        dtype="uint8", layer_type="image", block_size=geometry.block)
    directory = os.path.join(path[len("file://"):],
                             volume.info["scales"][0]["key"])
    os.makedirs(directory, exist_ok=True)

    def one(index: int) -> None:
        write_blocks(directory, seeded_slab(seed, geometry, index),
                     index * width, geometry.block)

    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(one, range(-(-size[2] // width))))
