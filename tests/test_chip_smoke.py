"""chip_smoke.py's contract off the chip (ISSUE 21): without a TPU it
fails and prints no result, and the reference it checks the chip against
is independent of the system yet agrees with the system's definitions."""
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, env=None):
    return subprocess.run(
        [sys.executable, SMOKE, *args], capture_output=True, text=True,
        env=dict(os.environ, **(env or {})), timeout=600, cwd=REPO)


def test_without_a_tpu_it_fails_and_prints_no_result():
    """An inherited JAX_PLATFORMS=cpu (which this sandbox sets) must not
    turn the chip check into a CPU run that passes."""
    proc = _run(env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "platform: cpu" in proc.stdout
    assert "needs a TPU" in proc.stderr.strip().splitlines()[-1]


def test_it_imports_nothing_from_tests():
    with open(SMOKE) as f:
        source = f.read()
    assert "import tests" not in source and "from tests" not in source


@pytest.mark.slow
def test_rehearsal_passes_and_never_prints_the_ok_line():
    proc = _run("--rehearse")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "platform: cpu" in proc.stdout
    assert '"ok"' not in proc.stdout
    assert '"claim": null' in proc.stdout
    assert proc.stdout.strip().splitlines()[-1].startswith(
        "rehearsal passed on platform: cpu")


def test_the_convolution_kernel_phase_rehearses(capsys):
    """The Mosaic smoke of the convolution kernel, at its tiny size with
    the kernel interpreted: the same block, rule and bound as on the chip,
    and the chip's shapes are ones the rule admits."""
    import jax.numpy as jnp

    from chunkflow_tpu.models import rsunet

    chip_smoke.phase_convolution_kernel(dict(chip_smoke.TINY, rehearse=True))
    assert "[interpret]: max-abs-diff" in capsys.readouterr().out
    z, y, x = chip_smoke.FULL["conv_patch"]
    assert chip_smoke.FULL["conv_batches"] == (4, 6)
    assert rsunet.kernel_takes(4, 28, 28, jnp.bfloat16, (z, y, x // 4), "tpu")


@pytest.mark.parametrize("patch", [(20, 256, 256), (8, 32, 32), (4, 16, 48)])
def test_reference_bump_is_the_systems_bump(patch):
    """The script writes the weighting out again in float64; it must be
    the function the system documents (inference/bump.py)."""
    from chunkflow_tpu.inference.bump import bump_map

    np.testing.assert_allclose(
        chip_smoke.bump_weights(patch), bump_map(patch), rtol=1e-6)


@pytest.mark.parametrize("extent, patch, stride", [
    (52, 20, 16), (640, 256, 192), (36, 20, 16), (300, 256, 192), (20, 20, 16),
])
def test_reference_patch_grid_is_the_systems_grid(extent, patch, stride):
    from chunkflow_tpu.inference.patching import starts_1d

    assert chip_smoke.patch_starts(extent, patch, stride) \
        == starts_1d(extent, patch, stride)
