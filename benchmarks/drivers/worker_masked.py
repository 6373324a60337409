"""Driver of the ``worker_masked`` kind: the closed loop of the ``worker``
kind under upstream's production masks, over a volume with blank regions.

Queue, monitor and feeder are those of ``drivers/worker.py``, loaded as
they are; the cleaner is its cleaner with three blocks to keep in place
of one. What differs is the chain, ``load-precomputed > mask > inference
> crop-margin > mask > save-precomputed`` with an image mask and an
output mask as coarse precomputed layers (:mod:`cfbench.masked_volume`),
and the comparison that decides ``correct``: against
``reference/rsunet_masked.py``, a block of the first steady *whole* task
(the ``worker`` kind's block), a block of the first steady *edge* task
that straddles the masks' edge (the unmasked side under the same bounds,
the masked side exactly zero) and a block of the first steady *blank*
task (exactly zero, and there: every task commits all its blocks).

The traffic file's parameters are the ``worker`` kind's and ``masks``:
``factor`` (fine voxels a mask voxel covers), ``block`` (the mask
layers' block size) and ``blank_tasks``.

The deployment's guarantee needs a program that stores a written block
of zeros. One that does not (tensorstore's default) cannot run this
configuration: the run ends at once with no result.
"""
import os
import time

import numpy as np

from cfbench import catalog, check, masked_volume, program, volume
from cfbench.run_record import RunRecord

worker = catalog.load_module("drivers", "worker")

KINDS = ("whole", "edge", "blank")
ABSENT = 3.0e38       # what a block that is not there reads as: finite JSON


def largest(block) -> float:
    """The largest magnitude in a block that has to be exactly zero."""
    if block is None:
        return ABSENT
    return float(np.nan_to_num(np.abs(block).max(), nan=ABSENT,
                               posinf=ABSENT))


def stores_zero_blocks(work: str) -> bool:
    """Whether a block of zeros written through the program is a block
    afterwards: what a blank task's commit rests on."""
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    probe = PrecomputedVolume.from_chunk(
        Chunk(np.zeros((4, 4, 4), np.float32)), f"file://{work}/probe",
        block_size=(4, 4, 4))
    return probe.has_all_blocks(probe.bounds())


class Cleaner(worker.Cleaner):
    """The ``worker`` kind's cleaner, keeping one block of the first
    steady task of each kind."""

    def __init__(self, geometry, layout, out_path, out_dir, warmup):
        super().__init__(geometry, out_path, out_dir, warmup)
        self.layout = layout
        self.check_tasks = {}      # kind -> index of its first steady task
        self.check_blocks = {}     # kind -> [C, z, y, x], or None if absent

    def box_of(self, kind: str, index: int):
        return (self.layout.edge_box(index) if kind == "edge"
                else self.geometry.check_box())

    def read_block(self, vol, kind: str, index: int):
        from chunkflow_tpu.core.bbox import BoundingBox

        g = self.geometry
        start, stop = self.box_of(kind, index)
        origin = [a - m + t for a, m, t in zip(
            start, g.margin, g.task_start(index))]
        size = [b - a for a, b in zip(start, stop)]
        try:
            cut = vol.cutout(BoundingBox.from_delta(origin, size),
                             fill_missing=False)
        except FileNotFoundError:
            return None
        array = np.asarray(cut.array)
        return array.reshape((-1,) + array.shape[-3:]).copy()

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
                if vol is None:
                    vol = PrecomputedVolume(self.out_path)
                box = BoundingBox.from_delta(g.task_start(index), g.task)
                paths = [os.path.join(self.out_dir, n)
                         for n in vol.block_names(box)]
                if not all(os.path.exists(p) for p in paths):
                    self.incomplete.append(index)
                kind = self.layout.kind(index)
                if arrivals > self.warmup and kind not in self.check_tasks:
                    self.check_tasks[kind] = index
                    self.check_blocks[kind] = self.read_block(
                        vol, kind, index)
                for path in paths:
                    try:
                        os.unlink(path)
                    except FileNotFoundError:
                        pass
                self.cleaned[index] += 1
        except BaseException as exc:
            self.error = exc


def compare(ctx, record, g, layout, cleaner, also: dict) -> None:
    """``record.correct`` from the three blocks. The differences of the
    whole task's block and of the edge task's unmasked side go to one
    judge under the configuration's bounds; what has to be exactly zero
    is held to a limit of 0 beside them."""
    reference = catalog.load_module("reference", ctx.config["reference"])
    forward = reference.make_forward(ctx.config)    # compiled once
    mask = layout.mask()
    got, want, what = [], [], []
    for kind in ("whole", "edge"):
        index = cleaner.check_tasks[kind]
        box = cleaner.box_of(kind, index)
        start = (0, 0, index * g.task[2])       # of the input chunk
        if cleaner.check_blocks[kind] is None:
            record.notes.append(f"not correct: blocks of the {kind} task "
                                f"{index} are not there")
            return
        block = np.asarray(cleaner.check_blocks[kind], np.float64)
        plain, kept, n_patches = reference.output(
            volume.seeded_task_input(ctx.seed, g, index), mask, mask,
            layout.factor, start, box,
            lambda image, where: check.reference_output(
                ctx, image, where, forward=forward))
        if plain is None or block.shape[1:] != kept.shape:
            record.notes.append(f"not correct: the {kind} task {index} "
                                f"is blank to the reference, or its block "
                                f"has another shape")
            return
        got.append(block[:, kept])
        want.append(plain[:, kept])
        what.append(f"{kind} task {index} block {box} vs {n_patches} "
                    f"reference patches")
        if kind == "edge":
            share = 1.0 - kept.mean()
            also["the edge block straddles the masks' edge"] = \
                0.25 < share < 0.75
            record.checks["masked_max_abs"] = {
                "value": largest(block[:, ~kept]), "limit": 0.0}
    blank = cleaner.check_blocks["blank"]
    also["the blank task's block is there"] = blank is not None
    record.checks["blank_max_abs"] = {"value": largest(blank), "limit": 0.0}
    what.append(f"blank task {cleaner.check_tasks['blank']} block "
                f"{g.check_box()} vs zeros")
    check.judge(record, np.concatenate(got, axis=1),
                np.concatenate(want, axis=1), "; ".join(what), also)


def run(ctx) -> RunRecord:
    config, traffic = ctx.config, ctx.traffic
    g = worker._geometry(config, traffic)
    masks = traffic["masks"]
    layout = masked_volume.MaskLayout(
        g, tuple(masks["factor"]), tuple(masks["blank_tasks"]))
    work = ctx.work
    warmup = int(traffic["warmup_tasks"])

    t_begin = time.time()
    if not stores_zero_blocks(work):
        raise SystemExit(
            "benchmarks: this program does not store a written block of "
            "zeros, so a blank task commits nothing and the "
            "configuration's guarantee (every task commits all its "
            "blocks) cannot hold. No result.")
    volume.write_volume(f"file://{work}/image", ctx.seed, g)
    mask_paths = [f"file://{work}/{name}"
                  for name in ("image-mask", "output-mask")]
    for path in mask_paths:
        layout.write(path, masks["block"])
    t_volume = time.time()
    out_path, out_dir = f"file://{work}/out", os.path.join(work, "out")
    program.chunkflow(
        "create-info", "-v", out_path, "--volume-size", *g.roi,
        "--voxel-offset", *g.margin,
        "--num-channels", config["model"]["out_channels"],
        "--dtype", "float32", "--block-size", *g.block)
    os.makedirs(os.path.join(work, "queue", "pending"), exist_ok=True)

    cleaner = Cleaner(g, layout, out_path, out_dir, warmup)
    monitor = worker.Monitor(ctx, g, work, out_dir, cleaner)
    monitor.feed()            # the queue is never empty when polled
    cleaner.start()
    monitor.start()

    inference = ctx.resolve_args(config["args"]["inference"])
    head = ["--metrics-dir", ctx.metrics_dir] if ctx.trace else []
    try:
        program.chunkflow(
            *head,
            "fetch-task-from-queue", "-q", f"file://{work}/queue",
            "--retry-times", worker.EMPTY_POLLS, "--poll-interval", 0.1,
            "load-precomputed", "-v", f"file://{work}/image",
            "--expand-margin-size", *g.margin,
            "mask", "-v", mask_paths[0],
            "inference", *inference,
            "--input-patch-size", *g.patch,
            "--output-patch-overlap", *g.overlap,
            "--num-output-channels", config["model"]["out_channels"],
            "--batch-size", config["batch"],
            "--async-depth", traffic["async_depth"],
            "crop-margin",
            "mask", "-v", mask_paths[1],
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
    kinds = [layout.kind(i) for t, i in monitor.commits if start < t <= end]
    left = sum(len(os.listdir(os.path.join(work, "queue", sub)))
               for sub in ("pending", "claimed", "dead"))
    record.attempted = monitor.pushed - monitor.purged
    first = min(t for t, _ in monitor.commits)
    record.notes.append(
        f"set-up: {t_begin - ctx.t0:.1f} s imports and device, "
        f"{t_volume - t_begin:.1f} s input volume and masks "
        f"({g.n_tasks} tasks), {first - t_volume:.1f} s to the first "
        f"commit, {start - first:.1f} s to the window")
    record.failed = (record.attempted - len(monitor.commits)
                     + len(cleaner.incomplete))
    if cleaner.incomplete:
        record.notes.append(f"committed with blocks missing: task(s) "
                            f"{sorted(set(cleaner.incomplete))}")
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
            "steady tasks: " + ", ".join(
                f"{kinds.count(kind)} {kind}" for kind in KINDS))
        record.notes.append(
            "steady commit intervals, ms: " + " ".join(
                f"{(b - a) * 1000:.0f}"
                for a, b in zip([start] + steady, steady)))
        # the patches whose forward ran: a blank task has none
        record.client["patches_per_s"] = \
            (len(steady) - kinds.count("blank")) * g.patches_per_task \
            / (steady[-1] - start)

    # the comparison that decides `correct`, outside the window
    missing = [kind for kind in KINDS if kind not in cleaner.check_tasks]
    if missing:
        record.notes.append(f"the comparison's {' and '.join(missing)} "
                            f"task was never committed")
        return record
    ctx.memory_peaks()     # before the reference's programs load
    compare(ctx, record, g, layout, cleaner,
            {"every fetched task committed": record.failed == 0,
             "queue empty": left == 0})
    return record
