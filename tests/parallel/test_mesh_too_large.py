"""A mesh spec that needs more devices than the machine has is an error,
never a smaller run (ISSUE 21: on a one-chip machine ``--mesh data=4``
must not quietly run on one device). Spec parsing defers the count to
mesh construction, so the check is made where the chunk meets the
engine."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.inference import Inferencer


@pytest.mark.parametrize("spec", ["data=16", "y=4,x=4", "pipeline=16"])
def test_mesh_larger_than_the_machine_raises(spec):
    have = len(jax.local_devices())
    assert have < 16
    inferencer = Inferencer(
        input_patch_size=(4, 16, 16), output_patch_overlap=(2, 8, 8),
        num_output_channels=1, framework="identity", mesh=spec,
        crop_output_margin=False,
    )
    chunk = Chunk(np.ones((8, 64, 64), np.float32))
    with pytest.raises(ValueError,
                       match=f"needs 16 devices, only {have} available"):
        inferencer(chunk)


def test_mesh_error_reaches_the_command_line():
    from chunkflow_tpu.flow.cli import main

    with pytest.raises(ValueError, match="needs 64 devices"):
        main(["create-chunk", "--size", "8", "32", "32",
              "inference", "--framework", "identity",
              "--input-patch-size", "4", "16", "16", "--mesh", "data=64"],
             standalone_mode=False)
