"""Bytes the blend around the model has to move for one patch, from shapes
alone: the floor of the HBM traffic, against which the time under the
program's scopes is laid.

Accumulating one prediction reads and writes the window of the float32
sums (one per output channel) and of the weight volume under the output
patch, and reads the prediction once. Not counted: the bump (one output
patch, read again for every patch and small beside the rest), the zeroing
of the accumulators once a task, any weighted copy of the prediction that
a lowering materializes, nor any padding row of a batch.
"""


def _prod(values) -> int:
    out = 1
    for v in values:
        out *= int(v)
    return out


def accumulate_bytes_per_patch(config: dict) -> int:
    voxels = _prod(config.get("output_patch") or config["patch"])
    channels = config["model"]["out_channels"]
    read_modify_write = 2 * (channels + 1) * voxels * 4
    prediction = channels * voxels * 4
    return read_modify_write + prediction
