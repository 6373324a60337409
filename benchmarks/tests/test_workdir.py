"""Where a run keeps its work directory (PR 35): the placement rule of
``cfbench/workdir.py``, the sweep of what killed runs left, and both
through ``run.py``."""
import json
import os
import subprocess
import sys

import pytest

from cfbench import workdir
from conftest import BENCH_DIR, bench, make_checkout
from test_rehearsal import run_cell

MEMORY = {"work_gb": 2}


@pytest.fixture
def shm(tmp_path, monkeypatch):
    """A directory that passes for the machine's tmpfs, with a settable
    amount of room."""
    root = tmp_path / "shm"
    root.mkdir()
    room = {"free": 100e9}
    monkeypatch.setattr(workdir, "is_tmpfs", lambda path: True)
    monkeypatch.setattr(workdir, "free_bytes", lambda path: room["free"])
    return str(root), room


def test_memory_where_there_is_room(shm, tmp_path):
    root, room = shm
    work, note = workdir.place(MEMORY, "a.cell", False, str(tmp_path), root)
    mine = workdir.root_of(str(tmp_path), root)
    assert work == os.path.join(mine, f"a.cell-{os.getpid()}")
    assert os.path.dirname(mine) == root
    assert os.path.basename(mine).startswith("cfbench-")
    assert "tmpfs" in note
    # another checkout on the same machine has a root of its own
    assert workdir.root_of(str(tmp_path / "other"), root) != mine
    room["free"] = 3.0e9      # exactly 1.5 x 2 GB: enough
    workdir.place(MEMORY, "a.cell", False, str(tmp_path), root)


@pytest.mark.parametrize("free, tmpfs, said", [
    (2.9e9, True, "wants 3.0 GB free"),
    (100e9, False, "is no tmpfs"),
])
def test_memory_without_room_is_no_result(shm, tmp_path, monkeypatch, free,
                                          tmpfs, said):
    root, room = shm
    room["free"] = free
    monkeypatch.setattr(workdir, "is_tmpfs", lambda path: tmpfs)
    with pytest.raises(SystemExit, match=said) as raised:
        workdir.place(MEMORY, "a.cell", False, str(tmp_path), root)
    assert "No result" in str(raised.value)


@pytest.mark.parametrize("traffic, rehearse", [
    ({}, False),            # a traffic file without the key: as before
    (MEMORY, True),         # a rehearsal never leaves the checkout
    ({}, True),
])
def test_the_checkout_otherwise(shm, tmp_path, traffic, rehearse):
    root, _ = shm
    work, note = workdir.place(traffic, "a.cell", rehearse, str(tmp_path),
                               root)
    assert work == os.path.join(str(tmp_path), f"a.cell-{os.getpid()}")
    assert "checkout" in note and not os.listdir(root)


def test_is_tmpfs_reads_the_mount_table(tmp_path):
    mounts = tmp_path / "mounts"
    mounts.write_text("none / 9p rw 0 0\nnone /dev/shm tmpfs rw 0 0\n"
                      "none /mnt/shm ext4 rw 0 0\n")
    assert workdir.is_tmpfs("/dev/shm", str(mounts))
    assert not workdir.is_tmpfs("/", str(mounts))
    assert not workdir.is_tmpfs("/mnt/shm", str(mounts))
    assert not workdir.is_tmpfs("/dev/shm", str(tmp_path / "absent"))


def test_free_bytes_is_capped_by_the_machines_memory(tmp_path):
    meminfo = tmp_path / "meminfo"
    meminfo.write_text("MemTotal: 47185920 kB\nMemAvailable: 1000 kB\n")
    assert workdir.free_bytes(str(tmp_path), str(meminfo)) == 1000 * 1024
    assert workdir.free_bytes(str(tmp_path), str(tmp_path / "absent")) > 0


def test_sweep_removes_what_this_checkouts_dead_runs_left(shm, tmp_path):
    root, _ = shm
    checkout, other = str(tmp_path / "a"), str(tmp_path / "b")
    mine = workdir.root_of(checkout, root)
    child = subprocess.Popen([sys.executable, "-c", "pass"])
    child.wait()            # a pid that is gone
    living = subprocess.Popen([sys.executable, "-c",
                               "import time; time.sleep(60)"])
    try:
        dead = f"a.cell-{child.pid}"
        live = f"b.cell-{living.pid}"
        rebooted = f"c.cell-{living.pid}"     # signed before this boot
        theirs = f"d.cell-{child.pid}"        # another checkout's
        unsigned = f"e.cell-{child.pid}"
        for name, signer in ((dead, checkout), (live, checkout),
                             (rebooted, checkout), (theirs, other)):
            workdir.create(os.path.join(mine, name, "out"), signer)
            os.rename(os.path.join(mine, name, "out", workdir.OWNER),
                      os.path.join(mine, name, workdir.OWNER))
        with open(os.path.join(mine, rebooted, workdir.OWNER), "a") as f:
            f.write("an older boot\n")
        for name in (unsigned, "not-a-run"):
            os.makedirs(os.path.join(mine, name, "out"))
        work, note = workdir.place(MEMORY, "c.cell", False, checkout, root)
        assert sorted(os.listdir(mine)) == sorted(
            [live, theirs, unsigned, "not-a-run"])
        assert dead in note and rebooted in note
        assert live not in note and theirs not in note
    finally:
        living.kill()
        living.wait()
    # nothing to sweep, no directory yet: no error
    assert workdir.sweep(os.path.join(root, "absent"), "x\ny\n") == []


def test_create_signs_and_remove_leaves_nothing(shm, tmp_path):
    root, _ = shm
    work, _ = workdir.place(MEMORY, "a.cell", False, str(tmp_path), root)
    workdir.create(work, str(tmp_path))
    with open(os.path.join(work, workdir.OWNER)) as f:
        signed = f.read()
    assert signed == workdir.owner(str(tmp_path))
    assert signed.split("\n")[0] == os.path.realpath(str(tmp_path))
    os.makedirs(os.path.join(work, "out"))
    # made anew: what an earlier run of the same pid left is gone
    workdir.create(work, str(tmp_path))
    assert os.listdir(work) == [workdir.OWNER]
    other = os.path.join(os.path.dirname(work), "b.cell-1")
    os.makedirs(other)
    workdir.remove(work)       # the root stays while another run's is there
    assert os.listdir(os.path.dirname(work)) == ["b.cell-1"]
    workdir.create(work, str(tmp_path))
    os.rmdir(other)
    workdir.remove(work)       # and goes with the last
    assert os.listdir(root) == []


def test_run_py_ends_with_no_result_where_memory_has_no_room(tmp_path):
    """Through the command: a cell whose traffic wants more memory than
    any machine has exits non-zero, prints no line, and says why before
    it looks for a chip."""
    b = bench()
    checkout = make_checkout(str(tmp_path / "checkout"), b)
    cell = b["workloads"][0]
    path = os.path.join(checkout, "benchmarks", "traffic",
                        cell["traffic"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    assert traffic["work_gb"] > 0
    traffic["work_gb"] = 1e9
    with open(path, "w") as f:
        json.dump(traffic, f)
    done = run_cell(checkout, cell["name"], 0, extra=())
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "another measurement" in done.stderr
    assert "No result" in done.stderr and "needs a TPU" not in done.stderr
    assert not os.path.exists(os.path.join(checkout, "benchmarks", ".work"))


def test_every_accepted_traffic_says_where_its_work_lies():
    for cell in bench()["workloads"]:
        with open(os.path.join(BENCH_DIR, "traffic",
                               cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert "work" not in traffic       # work_gb alone says it
        assert traffic["work_gb"] > 0 and traffic["work_why"]
        assert "lower bound" in traffic["work_why"]
