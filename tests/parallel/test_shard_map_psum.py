"""The replication assumption the sharded programs rest on: they call
``jax.shard_map(..., check_vma=False)`` because the blend programs psum
explicitly, and a psum result is replicated by construction."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")


def _mesh(n):
    from jax.sharding import Mesh

    devices = jax.devices()
    if len(devices) < n:
        pytest.skip(f"needs {n} virtual devices (tests/conftest.py)")
    return Mesh(np.asarray(devices[:n]), ("data",))


def _run_psum_program(n):
    """Per-device partial sums merge over the mesh and return REPLICATED
    (out_specs P()) — exactly the shape the blend programs rely on."""
    from jax.sharding import PartitionSpec as P

    def device_fn(x):
        return jax.lax.psum(x.sum(), "data")

    program = jax.jit(jax.shard_map(
        device_fn, mesh=_mesh(n), in_specs=(P("data"),), out_specs=P(),
        check_vma=False,
    ))
    x = np.arange(4 * n, dtype=np.float32).reshape(n * 2, 2)
    np.testing.assert_allclose(float(program(x)), float(x.sum()))


@pytest.mark.parametrize("n", [2, 8])
def test_psum_replication_assumption_pinned(n):
    """With replication checking off, a psum-merged out_specs=P() result
    equals the full reduction on every device — on 2 AND 8 chips so a
    regrouping regression would show."""
    _run_psum_program(n)
