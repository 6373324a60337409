"""Operations one patch forward of the RSUNet needs at its
full-resolution level, from shapes alone: the ``embed``, ``enc0``, ``dec0``
and ``out`` terms of ``flops/rsunet.py``'s count (2 x taps x Cin x Cout
for every output voxel of every 'SAME' convolution), the whole patch's.
``up0`` emits level-0 voxels too and is left out: it is the model's glue,
not one of level 0's convolutions.
"""


def flops_per_patch(config: dict) -> int:
    model = config["model"]
    w = int(model["width"][0])
    voxels = 1
    for n in config["patch"]:
        voxels *= int(n)
    block = 9 * w * w + 27 * w * w + 27 * w * w   # conv1 (1,3,3), conv2, conv3
    return 2 * voxels * (
        25 * int(model["in_channels"]) * w        # embed (1,5,5)
        + block                                   # enc0: w -> w
        + block                                   # dec0: w -> w
        + w * int(model["out_channels"]))         # out (1,1,1)
