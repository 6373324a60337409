"""The FLOPs function against a count made by hand."""
import pytest

from cfbench import catalog

FACTORS = [[1, 2, 2], [2, 2, 2], [2, 2, 2]]


def by_hand(width, patch=(20, 256, 256)):
    """Every convolution written out: 2 x taps x Cin x Cout x voxels."""
    w0, w1, w2, w3 = width
    v0 = patch[0] * patch[1] * patch[2]
    v1, v2, v3 = v0 // 4, v0 // 32, v0 // 256
    total = 2 * 25 * 1 * w0 * v0                               # embed
    total += 2 * (9 * w0 * w0 + 54 * w0 * w0) * v0             # enc0
    total += 2 * (9 * w0 * w1 + 54 * w1 * w1) * v1             # enc1
    total += 2 * (9 * w1 * w2 + 54 * w2 * w2) * v2             # enc2
    total += 2 * (9 * w2 * w3 + 54 * w3 * w3) * v3             # bridge
    total += 2 * w3 * w2 * v2 + 2 * 63 * w2 * w2 * v2          # up2, dec2
    total += 2 * w2 * w1 * v1 + 2 * 63 * w1 * w1 * v1          # up1, dec1
    total += 2 * w1 * w0 * v0 + 2 * 63 * w0 * w0 * v0          # up0, dec0
    total += 2 * w0 * 3 * v0                                   # out
    return total


@pytest.mark.parametrize("width, mflop_per_voxel", [
    ((28, 36, 48, 64), 0.30), ((16, 32, 64, 128), 0.17)])
def test_flops_per_patch(width, mflop_per_voxel):
    flops = catalog.load_module("flops", "rsunet")
    config = {"patch": [20, 256, 256], "model": {
        "width": list(width), "pooling": FACTORS,
        "in_channels": 1, "out_channels": 3}}
    assert flops.flops_per_patch(config) == by_hand(width)
    assert flops.flops_per_voxel(config) / 1e6 == pytest.approx(
        mflop_per_voxel, abs=0.005)
