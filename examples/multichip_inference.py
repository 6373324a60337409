"""Multi-chip and multi-host patch-parallel inference.

On a real TPU slice the mesh covers the local chips automatically; on a
laptop, emulate 8 chips with the virtual CPU mesh:

    JAX_PLATFORMS=cpu PYTHONPATH=. \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/multichip_inference.py

For a multi-HOST pod slice, call `multihost.initialize()` first (one
process per host); `Inferencer(sharding="patch")` then automatically
routes through global arrays — see docs/distributed.md.
"""
import numpy as np

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.inference import Inferencer
from chunkflow_tpu.parallel.distributed import make_mesh


def main():
    import jax

    mesh = make_mesh()
    print(f"mesh: {mesh.devices.size} x {jax.devices()[0].platform}")

    rng = np.random.default_rng(0)
    chunk = Chunk(rng.random((16, 64, 64)).astype(np.float32))

    # unified mesh engine (docs/multichip.md): patch-parallel — chunk
    # replicated, each chip forwards its share of patch batches, the
    # reference blend accumulation replays verbatim (bitwise identical
    # to the single-device path; CHUNKFLOW_MESH=auto does the same)
    sharded = Inferencer(
        input_patch_size=(8, 32, 32),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=3,
        framework="identity",
        batch_size=1,
        mesh=f"data={mesh.devices.size}" if mesh.devices.size > 1 else "1",
        crop_output_margin=False,
    )
    out = np.asarray(sharded(chunk).array)

    # bitwise parity with the single-device path (same weights)
    single = Inferencer(
        input_patch_size=(8, 32, 32),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=3,
        framework="identity",
        batch_size=1,
        crop_output_margin=False,
    )
    ref = np.asarray(single(chunk).array)
    diff = float(np.abs(out - ref).max())
    print(f"sharded vs single-device max-abs-diff: {diff:.2e}")
    assert np.array_equal(out, ref), "mesh output diverged bitwise"


if __name__ == "__main__":
    main()
