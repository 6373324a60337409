"""Driver of the ``worker_chain`` kind: the closed loop of the ``worker``
kind under upstream's whole worker command (``deploy.yml:30-37`` without
the masks): ``normalize-contrast --levels-path`` in front of inference,
``save-precomputed --create-thumbnail --upload-log`` behind it.

Queue, monitor and feeder are those of ``drivers/worker.py``, loaded as
they are; the cleaner is its cleaner, which also asks for a committed
task's thumbnail blocks at every level, keeps the first steady task's for
the comparison and unlinks them with the result's. (The logs stay: the
monitor reads commits from them, and a task's is overwritten when the
task comes round again.) What differs is the set-up (the sections'
histogram sidecars beside the input volume, :mod:`cfbench.levels`; the
sibling ``thumbnail`` layer beside the output volume, as ``setup-env``
creates it), the chain, and the comparison that decides ``correct``:
against ``reference/rsunet_chain.py``, the ``worker`` kind's block of the
first steady task (the result is of the *normalized* image), that task's
thumbnail at every level against the reference's integer arithmetic on
the mip-0 block the timed path committed, and every committed task's
thumbnail blocks and log being there.

The traffic file's parameters are the ``worker`` kind's and
``thumbnail``: ``mip`` (the layer's last scale; levels 1..mip are
written) and ``block`` (the layer's block size).

A program whose ``normalize-contrast`` has no ``--levels-path`` or whose
volumes have no thumbnail layer cannot run this configuration: the run
ends at once with no result.
"""
import dataclasses
import os
import time

import numpy as np

from cfbench import catalog, check, levels, program, volume
from cfbench.run_record import RunRecord

worker = catalog.load_module("drivers", "worker")


def runs_the_chain() -> bool:
    """Whether the program has the two operators as the chain needs
    them."""
    from chunkflow_tpu.flow.cli import main
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    options = {p.name for p in main.commands["normalize-contrast"].params}
    return "levels_path" in options and hasattr(PrecomputedVolume,
                                                "thumbnail_layer")


@dataclasses.dataclass
class Thumbnail:
    """The thumbnail layer of a task's geometry: ``mip`` levels of
    (1, 2, 2), each task's box divided by its level's factor."""
    geometry: volume.Geometry
    mip: int
    block: tuple

    def __post_init__(self):
        cell = 2 ** self.mip
        if any(n % cell for n in self.geometry.task[1:]):
            raise ValueError(f"the task {self.geometry.task} is not a "
                             f"multiple of 2**{self.mip} in y and x")

    def create(self, out_path: str):
        """``<out>/thumbnail`` by the call ``setup-env`` makes
        (flow/setup_env.py): uint8, one channel, raw, a scale a mip."""
        from chunkflow_tpu.volume.precomputed import PrecomputedVolume

        g = self.geometry
        return PrecomputedVolume.create(
            out_path + "/thumbnail", volume_size=g.roi, voxel_size=(1, 1, 1),
            voxel_offset=g.margin, num_channels=1, dtype="uint8",
            layer_type="image", block_size=self.block,
            num_mips=self.mip + 1, encoding="raw")

    def check_box(self):
        """(start, stop) in the task's own frame of the mip-0 block whose
        thumbnail is compared: the first block's sections, three cells of
        the last level wide from the second cell in y and x."""
        cell = 2 ** self.mip
        depth = min(self.geometry.block[0], self.geometry.task[0])
        return (0, cell, cell), (depth, 4 * cell, 4 * cell)

    def level_box(self, index: int, level: int, local=None):
        """The box at ``level`` of task ``index``'s thumbnail, or of the
        part of it under ``local`` = (start, stop) in the task's frame:
        the program's pooling grid starts at the task's own corner."""
        from chunkflow_tpu.core.bbox import BoundingBox

        g = self.geometry
        by = (1, 2 ** level, 2 ** level)
        start, stop = local or ((0, 0, 0), g.task)
        corner = [a // f for a, f in zip(g.task_start(index), by)]
        return BoundingBox(
            [c + a // f for c, a, f in zip(corner, start, by)],
            [c + b // f for c, b, f in zip(corner, stop, by)])


class Cleaner(worker.Cleaner):
    """The ``worker`` kind's cleaner over two layers."""

    def __init__(self, geometry, thumbnail, out_path, out_dir, warmup):
        super().__init__(geometry, out_path, out_dir, warmup)
        self.thumbnail = thumbnail
        self.thumbnail_source = None   # [C, z, y, x] of the result
        self.thumbnail_levels = None   # [z, y, x] uint8 a level
        self.without_thumbnail = []

    @staticmethod
    def read(vol, box, mip=0):
        try:
            cut = vol.cutout(box, mip=mip, fill_missing=False)
        except FileNotFoundError:
            return None
        array = np.asarray(cut.array)
        return array.reshape((-1,) + array.shape[-3:]).copy()

    def run(self) -> None:
        from chunkflow_tpu.core.bbox import BoundingBox
        from chunkflow_tpu.volume.precomputed import PrecomputedVolume

        try:
            g, thumb = self.geometry, self.thumbnail
            thumb_dir = os.path.join(self.out_dir, "thumbnail")
            vol = layer = None
            files = {}      # task index -> (result's, thumbnail's) paths
            arrivals = 0
            while True:
                index = self.inbox.get()
                if index is None:
                    return
                arrivals += 1
                if vol is None:
                    vol = PrecomputedVolume(self.out_path)
                    layer = PrecomputedVolume(self.out_path + "/thumbnail")
                start = g.task_start(index)
                if index not in files:
                    # a task's names, made once: it comes round again,
                    # and this thread shares the interpreter's lock with
                    # the thread that dispatches
                    box = BoundingBox.from_delta(start, g.task)
                    files[index] = (
                        [os.path.join(self.out_dir, n)
                         for n in vol.block_names(box)],
                        [os.path.join(thumb_dir, n)
                         for level in range(1, thumb.mip + 1)
                         for n in layer.block_names(
                             thumb.level_box(index, level), level)])
                paths, small = files[index]
                if not all(os.path.exists(p) for p in paths):
                    self.incomplete.append(index)
                elif not all(os.path.exists(p) for p in small):
                    self.incomplete.append(index)
                    self.without_thumbnail.append(index)
                if arrivals == self.warmup + 1:
                    self.check_task = index
                    lo, hi = g.check_box()
                    self.check_block = self.read(vol, BoundingBox(
                        [a - m + t for a, m, t in zip(lo, g.margin, start)],
                        [b - m + t for b, m, t in zip(hi, g.margin, start)]))
                    local = thumb.check_box()
                    self.thumbnail_source = self.read(vol, BoundingBox(
                        [t + a for t, a in zip(start, local[0])],
                        [t + b for t, b in zip(start, local[1])]))
                    self.thumbnail_levels = [
                        self.read(layer, thumb.level_box(index, level, local),
                                  mip=level)
                        for level in range(1, thumb.mip + 1)]
                for path in paths + small:
                    try:
                        os.unlink(path)
                    except FileNotFoundError:
                        pass
                self.cleaned[index] += 1
        except BaseException as exc:
            self.error = exc


def compare(ctx, record, g, cleaner, histograms, also: dict) -> None:
    """``record.correct``: the result's block under the configuration's
    two bounds, the thumbnail's levels under its one."""
    config = ctx.config
    reference = catalog.load_module("reference", config["reference"])
    forward = reference.make_forward(config)
    limit = config["thumbnail_limits"]["thumbnail_max_abs"]
    index = cleaner.check_task
    got = cleaner.thumbnail_levels
    if cleaner.thumbnail_source is None or any(g_ is None for g_ in got):
        record.notes.append(f"not correct: task {index}'s thumbnail, or "
                            f"the block under it, is not there")
        gap = 255.0
    else:
        want = reference.thumbnail_levels(cleaner.thumbnail_source, len(got))
        gap = max(
            float(np.abs(a[0].astype(np.int64) - b.astype(np.int64)).max())
            if a[0].shape == b.shape else 255.0 for a, b in zip(got, want))
        also["the thumbnail is not constant"] = float(got[0].std()) > 0.5
    record.checks["thumbnail_max_abs"] = {"value": gap, "limit": limit}
    want, n_patches = reference.output(
        volume.seeded_task_input(ctx.seed, g, index), histograms,
        config["normalize"], g.check_box(),
        lambda image, box: check.reference_output(ctx, image, box,
                                                  forward=forward))
    check.judge(
        record, cleaner.check_block, want,
        f"task {index} block {g.check_box()} vs {n_patches} reference "
        f"patches of the normalized chunk; its thumbnail under "
        f"{cleaner.thumbnail.check_box()} at {len(got)} levels", also)


def run(ctx) -> RunRecord:
    config, traffic = ctx.config, ctx.traffic
    g = worker._geometry(config, traffic)
    thumbnail = Thumbnail(g, int(traffic["thumbnail"]["mip"]),
                          tuple(traffic["thumbnail"]["block"]))
    work = ctx.work
    warmup = int(traffic["warmup_tasks"])

    t_begin = time.time()
    if not runs_the_chain():
        raise SystemExit(
            "benchmarks: this program's normalize-contrast has no "
            "--levels-path, or its volumes no thumbnail layer: it cannot "
            "run upstream's worker command as this configuration states "
            "it. No result.")
    image_path = f"file://{work}/image"
    levels.write_volume_and_levels(image_path, ctx.seed, g)
    t_volume = time.time()
    out_path, out_dir = f"file://{work}/out", os.path.join(work, "out")
    program.chunkflow(
        "create-info", "-v", out_path, "--volume-size", *g.roi,
        "--voxel-offset", *g.margin,
        "--num-channels", config["model"]["out_channels"],
        "--dtype", "float32", "--block-size", *g.block)
    thumbnail.create(out_path)
    os.makedirs(os.path.join(work, "queue", "pending"), exist_ok=True)

    cleaner = Cleaner(g, thumbnail, out_path, out_dir, warmup)
    monitor = worker.Monitor(ctx, g, work, out_dir, cleaner)
    monitor.feed()            # the queue is never empty when polled
    cleaner.start()
    monitor.start()

    inference = ctx.resolve_args(config["args"]["inference"])
    normalize = config["normalize"]
    head = ["--metrics-dir", ctx.metrics_dir] if ctx.trace else []
    try:
        program.chunkflow(
            *head,
            "fetch-task-from-queue", "-q", f"file://{work}/queue",
            "--retry-times", worker.EMPTY_POLLS, "--poll-interval", 0.1,
            "load-precomputed", "-v", image_path,
            "--expand-margin-size", *g.margin,
            "normalize-contrast",
            "--levels-path", levels.levels_path(image_path),
            "--lower-clip-fraction", normalize["lower_clip_fraction"],
            "--upper-clip-fraction", normalize["upper_clip_fraction"],
            "--minval", normalize["minval"], "--maxval", normalize["maxval"],
            "inference", *inference,
            "--input-patch-size", *g.patch,
            "--output-patch-overlap", *g.overlap,
            "--num-output-channels", config["model"]["out_channels"],
            "--batch-size", config["batch"],
            "--async-depth", traffic["async_depth"],
            "crop-margin",
            "save-precomputed", "-v", out_path,
            "--create-thumbnail", "--upload-log",
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
        f"{t_volume - t_begin:.1f} s input volume and levels "
        f"({g.n_tasks} tasks, {g.size[0]} sections), "
        f"{first - t_volume:.1f} s to the first commit, "
        f"{start - first:.1f} s to the window")
    record.failed = (record.attempted - len(monitor.commits)
                     + len(cleaner.incomplete))
    if cleaner.incomplete:
        record.notes.append(
            f"committed with blocks missing: task(s) "
            f"{sorted(set(cleaner.incomplete))}, of which in the thumbnail "
            f"alone: {sorted(set(cleaner.without_thumbnail))}")
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
        record.notes.append("the comparison's task was never committed, "
                            "or its blocks are not there")
        return record
    ctx.memory_peaks()     # before the reference's programs load
    histograms = levels.read_levels(
        levels.levels_path(image_path)[len("file://"):], g.size[0])
    compare(ctx, record, g, cleaner, histograms,
            {"every fetched task committed": record.failed == 0,
             "queue empty": left == 0})
    return record
