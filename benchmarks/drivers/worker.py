"""Driver of the ``worker`` kind: a closed loop, as a fleet worker is.

A seeded input volume on local disk, tasks that tile x on a ``file://``
queue, and the README worker chain run in this process through the
program's command line. A monitor thread keeps the queue a few tasks deep
until the window ends, and sees every commit from outside: the log file
``save-precomputed`` leaves beside the output volume when a task's blocks
are durable. A cleaner thread reads the block the comparison needs,
checks that a committed task has all its blocks, and unlinks them so that
a run never holds more than a few tasks of output on disk.

The traffic file's parameters: ``patch_grid`` (patches per task in z, y,
x), ``margin``, ``block``, ``tasks`` (distinct tasks in the volume),
``warmup_tasks``, ``queue_depth``, ``async_depth``, ``mesh`` (or null),
``trace`` (``start_after_s``, ``seconds``), ``env`` (name -> value, each
with its reason in ``env_why``), and ``rehearse`` (overrides for CPU
rehearsals).
"""
import os
import queue as queue_module
import threading
import time

import numpy as np

from cfbench import check, program, volume
from cfbench.run_record import RunRecord

POLL_S = 0.02
# The worker leaves after this many empty polls 0.1 s apart. More than one,
# so that a monitor thread held off the interpreter lock for a moment (the
# main thread traces and compiles during warm-up) does not end the run.
EMPTY_POLLS = 30


def _geometry(config: dict, traffic: dict) -> volume.Geometry:
    return volume.Geometry(
        patch=tuple(config["patch"]), overlap=tuple(config["overlap"]),
        margin=tuple(traffic["margin"]), block=tuple(traffic["block"]),
        grid=tuple(traffic["patch_grid"]), n_tasks=int(traffic["tasks"]))


class Monitor(threading.Thread):
    """Feeds the queue, times commits, runs the profiler window."""

    def __init__(self, ctx, geometry, work, out_dir, cleaner):
        super().__init__(name="bench-monitor", daemon=True)
        from chunkflow_tpu.core.bbox import BoundingBox
        from chunkflow_tpu.parallel.queues import open_queue

        self.ctx, self.geometry = ctx, geometry
        self.queue = open_queue(f"file://{work}/queue")
        self.pending_dir = os.path.join(work, "queue", "pending")
        self.log_dir = os.path.join(out_dir, "log")
        self.cleaner = cleaner
        self.bodies = [
            BoundingBox.from_delta(geometry.task_start(i),
                                   geometry.task).string
            for i in range(geometry.n_tasks)]
        self.index_of = {body: i for i, body in enumerate(self.bodies)}
        self.pushed = 0
        self.pushes = [0] * geometry.n_tasks     # per distinct task
        self.cursor = 0
        self.purged = 0
        self.commits = []            # (commit time, task index)
        self.seen = {}               # log file -> mtime_ns last seen
        self.window = None           # (start, end) on time.time()
        self.left_early = False
        self.stop = threading.Event()
        self.error = None
        self.depth = int(ctx.traffic["queue_depth"])
        self.warmup = int(ctx.traffic["warmup_tasks"])
        self.profiler = None

    def feed(self) -> None:
        """Keep ``depth`` tasks pending. Past the volume's last task the
        first comes round again: its output is written anew, and by then
        the tasks between have long flushed its input from the storage
        layer's 256 MB block cache. A task comes round only once its last
        turn is committed and its blocks are cleaned away: the file
        queue hands out pending tasks by name, not by age, so one can lie
        there for a whole round, and two turns of one task in flight would
        write, commit and clean the same blocks and log file at once."""
        while len(os.listdir(self.pending_dir)) < self.depth:
            for step in range(len(self.bodies)):
                index = (self.cursor + step) % len(self.bodies)
                if self.pushes[index] <= self.cleaner.cleaned[index]:
                    break
            else:
                return       # every task is in flight: none to offer yet
            self.queue.send_messages([self.bodies[index]])
            self.pushes[index] += 1
            self.cursor = index + 1
            self.pushed += 1

    def scan_commits(self) -> None:
        try:
            names = os.listdir(self.log_dir)
        except FileNotFoundError:
            return
        for name in names:
            index = self.index_of.get(name[:-len(".json")])
            if index is None:
                continue
            stat = os.stat(os.path.join(self.log_dir, name))
            # an empty file is one being written: its time is not final
            if not stat.st_size or self.seen.get(name) == stat.st_mtime_ns:
                continue
            self.seen[name] = stat.st_mtime_ns
            self.commits.append((stat.st_mtime, index))
            self.cleaner.inbox.put(index)

    def purge_pending(self) -> None:
        """Tasks never fetched are taken back through the queue's own
        API, so the worker only drains what it already holds."""
        while os.listdir(self.pending_dir):
            claimed = self.queue.receive()
            if claimed is None:
                break
            self.queue.delete(claimed[0])
            self.purged += 1

    def run(self) -> None:
        try:
            seconds = float(self.ctx.seconds)
            while not self.stop.is_set():
                self.scan_commits()
                if self.window is None and len(self.commits) >= self.warmup:
                    start = sorted(self.commits)[self.warmup - 1][0]
                    self.window = (start, start + seconds)
                    if self.ctx.trace:
                        self.profiler = self.ctx.start_profiler_thread(
                            self.window, self.ctx.traffic["trace"])
                if self.window and time.time() >= self.window[1]:
                    break
                self.feed()
                time.sleep(POLL_S)
            self.left_early = self.stop.is_set()
            self.purge_pending()
            # the worker drains what it holds; keep timing its commits
            while not self.stop.is_set():
                self.scan_commits()
                time.sleep(POLL_S)
            self.scan_commits()
        except BaseException as exc:   # surfaced by the driver
            self.error = exc


class Cleaner(threading.Thread):
    """Reads the comparison's block, checks blocks, unlinks them."""

    def __init__(self, geometry, out_path, out_dir, warmup):
        super().__init__(name="bench-cleaner", daemon=True)
        self.geometry, self.out_path, self.out_dir = \
            geometry, out_path, out_dir
        self.warmup = warmup       # commits to let pass before the check
        self.check_task = None     # index of the first steady task
        self.inbox = queue_module.Queue()
        self.check_block = None
        self.incomplete = []
        self.cleaned = [0] * geometry.n_tasks    # turns done, per task
        self.error = None

    def run(self) -> None:
        from chunkflow_tpu.core.bbox import BoundingBox
        from chunkflow_tpu.volume.precomputed import PrecomputedVolume

        try:
            g = self.geometry
            vol = None
            arrivals = 0
            while True:
                index = self.inbox.get()
                if index is None:
                    return
                arrivals += 1
                if arrivals == self.warmup + 1:
                    self.check_task = index
                if vol is None:
                    vol = PrecomputedVolume(self.out_path)
                box = BoundingBox.from_delta(g.task_start(index), g.task)
                names = vol.block_names(box)
                paths = [os.path.join(self.out_dir, n) for n in names]
                if not all(os.path.exists(p) for p in paths):
                    self.incomplete.append(index)
                if arrivals == self.warmup + 1:
                    start, stop = g.check_box()
                    origin = [a - m + t for a, m, t in zip(
                        start, g.margin, g.task_start(index))]
                    size = [b - a for a, b in zip(start, stop)]
                    cut = vol.cutout(BoundingBox.from_delta(origin, size),
                                     fill_missing=False)
                    array = np.asarray(cut.array)
                    self.check_block = array.reshape(
                        (-1,) + array.shape[-3:]).copy()
                for path in paths:
                    try:
                        os.unlink(path)
                    except FileNotFoundError:
                        pass
                self.cleaned[index] += 1
        except BaseException as exc:
            self.error = exc


def run(ctx) -> RunRecord:
    config, traffic = ctx.config, ctx.traffic
    g = _geometry(config, traffic)
    work = ctx.work
    warmup = int(traffic["warmup_tasks"])

    t_begin = time.time()
    volume.write_volume(f"file://{work}/image", ctx.seed, g)
    t_volume = time.time()
    out_path, out_dir = f"file://{work}/out", os.path.join(work, "out")
    program.chunkflow(
        "create-info", "-v", out_path, "--volume-size", *g.roi,
        "--voxel-offset", *g.margin,
        "--num-channels", config["model"]["out_channels"],
        "--dtype", "float32", "--block-size", *g.block)
    os.makedirs(os.path.join(work, "queue", "pending"), exist_ok=True)

    cleaner = Cleaner(g, out_path, out_dir, warmup)
    monitor = Monitor(ctx, g, work, out_dir, cleaner)
    monitor.feed()            # the queue is never empty when polled
    cleaner.start()
    monitor.start()

    inference = ctx.resolve_args(config["args"]["inference"])
    if traffic.get("mesh"):
        inference += ["--mesh", traffic["mesh"]]
    head = ["--metrics-dir", ctx.metrics_dir] if ctx.trace else []
    try:
        program.chunkflow(
            *head,
            "fetch-task-from-queue", "-q", f"file://{work}/queue",
            "--retry-times", EMPTY_POLLS, "--poll-interval", 0.1,
            "load-precomputed", "-v", f"file://{work}/image",
            "--expand-margin-size", *g.margin,
            "inference", *inference,
            "--input-patch-size", *g.patch,
            "--output-patch-overlap", *g.overlap,
            "--num-output-channels", config["model"]["out_channels"],
            "--batch-size", config["batch"],
            "--async-depth", traffic["async_depth"],
            "crop-margin",
            "save-precomputed", "-v", out_path,
            "delete-task-in-queue",
        )
    finally:
        monitor.stop.set()
        monitor.join()
        if monitor.profiler is not None:
            monitor.profiler.join()
        cleaner.inbox.put(None)
        cleaner.join()
    for thread in (monitor, cleaner):
        if thread.error is not None:
            raise thread.error
    if monitor.left_early and monitor.window is not None:
        raise SystemExit(
            "benchmarks: the worker left before the window ended: it found "
            "the queue empty, so this run measured the feeder. No result.")
    if monitor.window is None:
        raise SystemExit(
            f"benchmarks: the worker left after {len(monitor.commits)} "
            f"commit(s), before the {warmup} warm-up task(s) were done")

    record = RunRecord(cell=ctx.cell, config=config, traffic=traffic,
                       device=ctx.device, window=monitor.window)
    start, end = monitor.window
    steady = sorted(t for t, _ in monitor.commits if start < t <= end)
    left = sum(len(os.listdir(os.path.join(work, "queue", sub)))
               for sub in ("pending", "claimed", "dead"))
    record.attempted = monitor.pushed - monitor.purged
    first = min(t for t, _ in monitor.commits)
    record.notes.append(
        f"set-up: {t_begin - ctx.t0:.1f} s imports and device, "
        f"{t_volume - t_begin:.1f} s input volume ({g.n_tasks} tasks), "
        f"{first - t_volume:.1f} s to the first commit, "
        f"{start - first:.1f} s to the window")
    record.failed = (record.attempted - len(monitor.commits)
                     + len(cleaner.incomplete))
    if left:
        record.notes.append(f"{left} task(s) left in the queue")
    if monitor.pushed > g.n_tasks:
        record.notes.append(
            f"the volume's {g.n_tasks} tasks came round again "
            f"({monitor.pushed} pushed)")
    record.client = {
        "setup_s": start - ctx.t0,
        "steady_commit_times": steady,
        "window_start": start,
        "task_voxels": g.task_voxels,
        "steady_tasks": len(steady),
    }
    if steady:
        record.notes.append(
            "steady commit intervals, ms: " + " ".join(
                f"{(b - a) * 1000:.0f}"
                for a, b in zip([start] + steady, steady)))
        record.client["patches_per_s"] = \
            len(steady) * g.patches_per_task / (steady[-1] - start)

    # the comparison that decides `correct`, outside the window
    if cleaner.check_block is None:
        record.notes.append("the comparison's task was never committed")
        return record
    ctx.memory_peaks()     # before the reference's programs load
    want, n_patches = check.reference_output(
        ctx, volume.seeded_task_input(ctx.seed, g, cleaner.check_task),
        g.check_box())
    check.judge(
        record, cleaner.check_block, want,
        f"task {cleaner.check_task} block {g.check_box()} vs {n_patches} "
        f"reference patches",
        {"every fetched task committed": record.failed == 0,
         "queue empty": left == 0})
    return record
