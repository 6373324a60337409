"""Operations one patch forward of the RSUNet needs, from shapes alone.

Counted: 2 x taps x Cin x Cout for every output voxel of every
convolution ('SAME', stride 1), and 2 x Cin x Cout for every output voxel
of an upsampling (kernel = stride, so each output voxel has one tap).
Not counted: bias, affine, relu, pooling, skip sums, sigmoid (under 1% of
the total), nor any padding row of a batch.
"""


def _prod(values) -> int:
    out = 1
    for v in values:
        out *= int(v)
    return out


def flops_per_patch(config: dict) -> int:
    model = config["model"]
    width = model["width"]
    factors = model["pooling"]
    cin, cout = model["in_channels"], model["out_channels"]
    voxels = [_prod(config["patch"])]
    for factor in factors:
        voxels.append(voxels[-1] // _prod(factor))

    def block(c_in, w, v):  # conv1 (1,3,3) c_in -> w; conv2, conv3 (3,3,3)
        return 2 * v * (9 * c_in * w + 27 * w * w + 27 * w * w)

    levels = len(width) - 1
    total = 2 * voxels[0] * 25 * cin * width[0]               # embed (1,5,5)
    for i in range(levels):
        total += block(width[max(i - 1, 0)], width[i], voxels[i])   # enc{i}
        total += 2 * voxels[i] * width[i + 1] * width[i]            # up{i}
        total += block(width[i], width[i], voxels[i])               # dec{i}
    total += block(width[-2], width[-1], voxels[-1])          # bridge
    total += 2 * voxels[0] * width[0] * cout                  # out (1,1,1)
    return total


def flops_per_voxel(config: dict) -> float:
    return flops_per_patch(config) / _prod(config["patch"])
