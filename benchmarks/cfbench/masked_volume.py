"""The masks of a masked volume job: two coarse uint8 layers (an image
mask in front of inference, an output mask behind it) over the seeded
volume of :mod:`cfbench.volume`, laid out by task index and not by the
seed, so that every run has the same tasks blank, cut and whole.

Both masks are zero over x from the middle of task ``k - 1`` to the
middle of task ``k + 1`` for every ``k`` of ``blank_tasks`` (to the
volume's end where ``k`` is the last task) and one elsewhere, over all of
z and y. Task ``k``'s expanded input box then lies wholly under the zeros
(a *blank* task: its masked input is all zero), the tasks beside it have
half their output zeroed (*edge*: the full forward runs), and the rest
meet no zero (*whole*).
"""
import dataclasses

import numpy as np

from cfbench import volume


@dataclasses.dataclass
class MaskLayout:
    geometry: volume.Geometry
    factor: tuple          # fine voxels a mask voxel covers, zyx
    blank_tasks: tuple

    def __post_init__(self):
        g = self.geometry
        if 2 * g.margin[2] > g.task[2]:
            raise ValueError("the margin is wider than half a task: a "
                             "blank task's input would leave the zeros")
        for x0, x1 in self.zero_ranges():
            if x0 % self.factor[2] or (x1 % self.factor[2]
                                       and x1 != g.size[2]):
                raise ValueError(
                    f"the masks' edge {x0}..{x1} is not on a mask voxel's "
                    f"edge (factor {self.factor})")
        for index in range(g.n_tasks):
            self.kind(index)

    def zero_ranges(self) -> list:
        """Global fine x ranges under the masks' zeros."""
        g = self.geometry
        margin, width = g.margin[2], g.task[2]
        out = []
        for k in self.blank_tasks:
            x0 = margin + (k - 1) * width + width // 2
            x1 = (g.size[2] if k == g.n_tasks - 1
                  else margin + (k + 1) * width + width // 2)
            out.append((x0, x1))
        return out

    def kind(self, index: int) -> str:
        """``blank``, ``edge`` or ``whole``."""
        g = self.geometry
        in0 = index * g.task[2]
        in1 = in0 + g.chunk_in[2]
        out0, out1 = in0 + g.margin[2], in1 - g.margin[2]
        ranges = self.zero_ranges()
        if any(x0 <= in0 and in1 <= x1 for x0, x1 in ranges):
            return "blank"
        if any(x0 < out1 and out0 < x1 for x0, x1 in ranges):
            return "edge"
        if any(x0 < in1 and in0 < x1 for x0, x1 in ranges):
            raise ValueError(f"task {index}: the masks cut its margin "
                             f"and not its output")
        return "whole"

    @property
    def coarse_size(self):
        return tuple(-(-s // f) for s, f
                     in zip(self.geometry.size, self.factor))

    def mask(self) -> np.ndarray:
        """The coarse mask, zyx uint8, its first voxel at the origin."""
        out = np.ones(self.coarse_size, np.uint8)
        fx = self.factor[2]
        for x0, x1 in self.zero_ranges():
            out[:, :, x0 // fx:-(-x1 // fx)] = 0
        return out

    def edge_box(self, index: int):
        """(start, stop) in the input chunk's coordinates of the block
        the comparison reads of an edge task: the anchor's block in z and
        y, one stride wide in x around the masks' edge."""
        g = self.geometry
        (z0, y0, _), (z1, y1, _) = g.check_box()
        in0 = index * g.task[2]
        edges = [x - in0 for pair in self.zero_ranges() for x in pair
                 if in0 + g.margin[2] < x < in0 + g.chunk_in[2] - g.margin[2]]
        half = g.stride[2] // 2
        return (z0, y0, edges[0] - half), (z1, y1, edges[0] + half)

    def write(self, path: str, block) -> None:
        """The mask as a precomputed uint8 layer at ``path``, through the
        program's own writer: voxels ``factor`` times the image's."""
        from chunkflow_tpu.chunk.base import Chunk
        from chunkflow_tpu.volume.precomputed import PrecomputedVolume

        PrecomputedVolume.from_chunk(
            Chunk(self.mask(), voxel_size=self.factor), path,
            block_size=tuple(block))
