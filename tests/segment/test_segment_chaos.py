"""Chaos acceptance of the stitching job (ISSUE 20): a REAL worker
subprocess is SIGKILLed inside a merge task (the ``segment/merge``
chaos point fires after the inputs are read and before the table is
written — mid-merge by construction), its lease expires, and the
surviving worker replays the merge. The final segmentation must be
label-isomorphic to a fault-free monolithic labeling, with exactly one
ledger marker per tree node and per relabel chunk."""
import os
import subprocess
import sys
import time

import numpy as np

from chunkflow_tpu.ops import connected_components as cc
from chunkflow_tpu.parallel.lifecycle import FileLedger
from chunkflow_tpu.parallel.queues import open_queue
from chunkflow_tpu.segment import labels_isomorphic, open_store
from chunkflow_tpu.segment.driver import export_segmentation

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _worker_cmd(qdir, ledger, seg_dir, vis=3):
    return [
        sys.executable, "-m", "chunkflow_tpu.flow.cli",
        "fetch-task-from-queue", "-q", str(qdir), "-v", str(vis),
        "-r", "400", "--poll-interval", "0.05", "--max-retries", "3",
        "--ledger", str(ledger),
        "label-chunk", "-d", str(seg_dir),
        "merge-seg", "-d", str(seg_dir),
        "relabel", "-d", str(seg_dir),
        "delete-task-in-queue",
    ]


def test_sigkill_mid_merge_replays_to_isomorphic_result(tmp_path):
    rng = np.random.default_rng(11)
    arr = (rng.random((14, 12, 10)) > 0.6).astype(np.float32)
    input_npy = tmp_path / "input.npy"
    np.save(input_npy, arr)
    seg_dir = tmp_path / "job"
    qdir = tmp_path / "queue"
    ledger = tmp_path / "ledger"

    base_env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        PYTHONPATH=_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
    )
    base_env.pop("XLA_FLAGS", None)

    coordinator = subprocess.Popen(
        [sys.executable, "-m", "chunkflow_tpu.flow.cli", "segment-volume",
         "-i", str(input_npy), "-d", str(seg_dir), "-c", "6", "6", "6",
         "--connectivity", "26", "-q", str(qdir), "--ledger", str(ledger),
         "--timeout", "150"],
        env=base_env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    started = [coordinator]
    try:
        # let spec.json land before the workers open the store
        deadline = time.monotonic() + 30
        while not (seg_dir / "spec.json").exists():
            assert coordinator.poll() is None, coordinator.communicate()[0]
            assert time.monotonic() < deadline
            time.sleep(0.05)

        # worker A self-SIGKILLs inside its first merge: the task is
        # claimed, the faces/child tables are read, the output is not
        # yet written — true process death, nothing unwinds
        env_a = dict(base_env,
                     CHUNKFLOW_CHAOS="once=segment/merge:action=kill")
        proc_a = subprocess.Popen(
            _worker_cmd(qdir, ledger, seg_dir), env=env_a,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        started.append(proc_a)
        # alone on the queue until it dies, so the merge it dies in is
        # its own: beside a second worker it could be left without one
        rc_a = proc_a.wait(timeout=120)
        assert rc_a in (-9, 137), (rc_a, proc_a.communicate()[0][-2000:])
        # worker B: clean; drains everything A dropped once the lease
        # expires (visibility 3s -> janitored back to pending)
        proc_b = subprocess.Popen(
            _worker_cmd(qdir, ledger, seg_dir), env=base_env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )

        started.append(proc_b)

        out, _ = coordinator.communicate(timeout=180)
        assert coordinator.returncode == 0, out[-3000:]
        rc_b = proc_b.wait(timeout=60)
        assert rc_b == 0, proc_b.communicate()[0][-2000:]
    finally:
        for proc in started:
            if proc.poll() is None:
                proc.kill()

    store = open_store(str(seg_dir))
    seg = export_segmentation(store)
    mono = cc.label_binary(arr > 0.5, connectivity=26)
    assert labels_isomorphic(seg, mono)

    # exactly one ledger marker per tree node body + per relabel body
    plan = store.plan
    expected = {plan.node_body(n) for n in plan.make_tree().walk()}
    expected |= {plan.relabel_body(c) for c in plan.chunks}
    assert sorted(FileLedger(str(ledger)).keys()) == sorted(expected)

    # the queue drained clean: nothing pending, in flight or poisoned
    queue = open_queue(str(qdir))
    assert queue.stats()["pending"] == 0
    assert queue.stats()["inflight"] == 0
    assert queue.dead_letters() == []
