"""The geometry of a volume job whose output patch is smaller than its
input patch: the network reads ``patch`` voxels and the central
``output_patch`` of its prediction is blended, with ``output_overlap``
between neighbouring output patches.

Patch p has output start ``s_p`` on the grid of stride ``output_patch -
output_overlap`` over the output frame, which lies ``crop`` = ``(patch -
output_patch) // 2`` inside the input chunk, so in the chunk's coordinates
it reads ``[s_p, s_p + patch)`` and writes ``[s_p + crop, s_p + crop +
output_patch)``. Input windows therefore overlap by ``patch - stride``,
which is what :class:`cfbench.volume.Geometry` calls ``overlap``: chunk,
task, slabs and volume follow from it unchanged.
"""
import dataclasses

from cfbench import volume


@dataclasses.dataclass
class CropGeometry(volume.Geometry):
    output_patch: tuple = ()
    output_overlap: tuple = ()

    @classmethod
    def of(cls, patch, output_patch, output_overlap, **rest):
        stride = [o - v for o, v in zip(output_patch, output_overlap)]
        g = cls(patch=tuple(patch),
                overlap=tuple(p - s for p, s in zip(patch, stride)),
                output_patch=tuple(output_patch),
                output_overlap=tuple(output_overlap), **rest)
        if any((p - o) % 2 or p < o for p, o in zip(patch, output_patch)):
            raise ValueError(f"patch {g.patch} and output patch "
                             f"{g.output_patch} differ by an odd or a "
                             f"negative number")
        if any(m < c for m, c in zip(g.margin, g.crop)):
            raise ValueError(f"the task's margin {g.margin} is smaller "
                             f"than the patch's own {g.crop}")
        start, stop = g.check_box()
        if any(a < m or b > c - m for a, b, m, c
               in zip(start, stop, g.margin, g.chunk_in)):
            raise ValueError(f"the comparison's block {start}..{stop} "
                             f"leaves the task's output")
        return g

    @property
    def crop(self):
        """What the network's prediction loses on each side."""
        return tuple((p - o) // 2
                     for p, o in zip(self.patch, self.output_patch))

    def check_box(self):
        """(start, stop) in input-chunk coordinates: one output stride
        wide from the middle of the first output patch, so that 2x2x2
        output patches overlap inside it."""
        start = tuple(c + o // 2 for c, o in zip(self.crop,
                                                 self.output_patch))
        return start, tuple(a + s for a, s in zip(start, self.stride))
