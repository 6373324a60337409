"""Driver of the ``worker_crop`` kind: the closed loop of the ``worker``
kind for a deployment whose output patch is the central part of its input
patch (``--output-patch-size`` smaller than ``--input-patch-size``).

Queue, monitor and cleaner are those of ``drivers/worker.py``, loaded as
they are. What differs is what that driver builds from one ``patch`` and
one ``overlap``: the geometry (:mod:`cfbench.crop_volume`, from the
configuration's ``patch``, ``output_patch`` and ``overlap``, the last
between *output* patches), the ``inference`` command line, and the
comparison that decides ``correct`` (:mod:`cfbench.crop_blend`'s reference
under :func:`cfbench.check.judge`).

The traffic file's parameters are the ``worker`` kind's. ``margin`` is
what ``load-precomputed`` expands a task by and ``crop-margin`` takes
off again: the patch's own margin plus the deployment's crop margin.
"""
import os
import time

import jax

from cfbench import catalog, check, crop_blend, crop_volume, program, volume
from cfbench.run_record import RunRecord

worker = catalog.load_module("drivers", "worker")


def _geometry(config: dict, traffic: dict) -> crop_volume.CropGeometry:
    return crop_volume.CropGeometry.of(
        config["patch"], config["output_patch"], config["overlap"],
        margin=tuple(traffic["margin"]), block=tuple(traffic["block"]),
        grid=tuple(traffic["patch_grid"]), n_tasks=int(traffic["tasks"]))


def run(ctx) -> RunRecord:
    config, traffic = ctx.config, ctx.traffic
    g = _geometry(config, traffic)
    work = ctx.work
    warmup = int(traffic["warmup_tasks"])
    channels = config["model"]["out_channels"]

    t_begin = time.time()
    volume.write_volume(f"file://{work}/image", ctx.seed, g)
    t_volume = time.time()
    out_path, out_dir = f"file://{work}/out", os.path.join(work, "out")
    program.chunkflow(
        "create-info", "-v", out_path, "--volume-size", *g.roi,
        "--voxel-offset", *g.margin, "--num-channels", channels,
        "--dtype", "float32", "--block-size", *g.block)
    os.makedirs(os.path.join(work, "queue", "pending"), exist_ok=True)

    cleaner = worker.Cleaner(g, out_path, out_dir, warmup)
    monitor = worker.Monitor(ctx, g, work, out_dir, cleaner)
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
            "--retry-times", worker.EMPTY_POLLS, "--poll-interval", 0.1,
            "load-precomputed", "-v", f"file://{work}/image",
            "--expand-margin-size", *g.margin,
            "inference", *inference,
            "--input-patch-size", *g.patch,
            "--output-patch-size", *g.output_patch,
            "--output-patch-overlap", *g.output_overlap,
            "--patch-num", *g.grid,
            "--num-output-channels", channels,
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
    chips = int(ctx.cell["chips"])
    record.client = {
        "setup_s": start - ctx.t0,
        "steady_commit_times": steady,
        "window_start": start,
        "task_voxels": g.task_voxels,
        "steady_tasks": len(steady),
        "hbm_bytes_limit": [
            int((device.memory_stats() or {}).get("bytes_limit", 0))
            for device in jax.devices()[:chips]],
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
    box = g.check_box()
    ctx.memory_peaks()     # before the reference's programs load
    want, n_patches = crop_blend.reference_output(
        ctx, volume.seeded_task_input(ctx.seed, g, cleaner.check_task), box)
    check.judge(
        record, cleaner.check_block, want,
        f"task {cleaner.check_task} block {box} vs {n_patches} cropped "
        f"reference patches",
        {"every fetched task committed": record.failed == 0,
         "queue empty": left == 0})
    return record
