"""One process per chip (ISSUE 21). A TPU chip belongs to one process and
fleet workers are given no chip of their own: on a v5e the second
jax-using process died with "The TPU is already in use by process with
pid N". So the supervisor refuses, before it spawns anything, to run
more than one chip-using worker on a host that has TPU chips."""
import pytest

from chunkflow_tpu.parallel import fleet
from chunkflow_tpu.parallel.fleet import FleetSupervisor

ARGS = ["fetch-task-from-queue", "-q", "x", "delete-task-in-queue"]


@pytest.fixture()
def tpu_host(monkeypatch):
    monkeypatch.setattr(fleet, "host_tpu_chips",
                        lambda: ["/dev/vfio/0", "/dev/vfio/1"])


@pytest.mark.parametrize("platforms", ["tpu", "tpu,cpu", ""])
def test_refuses_second_chip_using_worker(tpu_host, monkeypatch, platforms):
    monkeypatch.setenv("JAX_PLATFORMS", platforms)
    with pytest.raises(ValueError, match="A chip belongs to one process"):
        FleetSupervisor("memory://chips", ARGS, max_workers=2)


def test_one_chip_using_worker_is_fine(tpu_host, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    FleetSupervisor("memory://chips", ARGS, min_workers=1, max_workers=1)


def test_cpu_workers_are_fine_on_a_tpu_host(tpu_host, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    FleetSupervisor("memory://chips", ARGS, max_workers=4,
                    worker_env={"JAX_PLATFORMS": "cpu"})


def test_hosts_without_chips_are_not_refused(monkeypatch):
    monkeypatch.setattr(fleet, "host_tpu_chips", lambda: [])
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    FleetSupervisor("memory://chips", ARGS, max_workers=4)


def test_host_tpu_chips_lists_device_nodes_that_exist():
    import os

    chips = fleet.host_tpu_chips()
    assert isinstance(chips, list)
    assert all(os.path.exists(path) for path in chips)
