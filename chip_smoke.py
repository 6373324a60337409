#!/usr/bin/env python3
"""chip_smoke.py: does the volume worker still start on the chip?

Drives the system's main path once, in this ONE process, through the
command line a user types (``chunkflow_tpu.flow.cli.main``), at the full
width of the RSUNet (28, 36, 48, 64), and checks what comes out:

* kernels    both Pallas kernels compiled by Mosaic at the production
             patch, bitwise against their XLA legs; the convolution
             kernel (ops/pallas_conv.py) as one ``RSBlock`` of level 0 at
             the anchor's and the production batch against XLA's block
* host       ``native.build()`` from source on this machine
* volume     a seeded uint8 volume as precomputed, ``generate-tasks``
             into a ``file://`` queue, then the README worker chain over
             three float32 tasks and one bfloat16 task; one task against
             a plain per-patch float32 ``model.apply`` blended in numpy
* identity   the same chain with ``--framework identity`` reproduces its
             input
* mesh       a mesh spec that needs more devices than there are is an
             error; with >= 4 devices, ``data=4`` and ``y=2,x=2`` equal
             one device bit for bit with work and memory on every chip
* serve      the ``serve`` command on an ephemeral port: packed answers
             equal per-chunk answers bit for bit, then a drain on SIGINT
* segment    ``connected-components`` on one thresholded affinity channel

Every check is fatal: an exception ends the run with its traceback and a
non-zero exit. Without a TPU (``jax.devices()[0].platform != "tpu"``,
an inherited ``JAX_PLATFORMS=cpu`` included) or with a ``device_kind``
the peaks table does not know, it exits non-zero and prints no result.
On success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Timings printed here are smoke timings — one cold run, compiles
included. They are not benchmark numbers and go under no metric name.

``--rehearse`` runs the same phases at a tiny size on four virtual CPU
devices with the Pallas kernels in interpret mode. It prints
``platform: cpu`` and never the OK line.
"""
from __future__ import annotations

import argparse
import base64
import contextlib
import glob
import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# Bounds on max-abs-diff. Outputs are sigmoid affinities in (0, 1) with a
# standard deviation near 0.05 over a task, so a misplaced or unblended
# patch shows as ~0.1. Measured on a TPU v5 lite (my chip run, PR 21),
# jax 0.9.0:
#   float32 task vs the "highest"-precision reference   2.43e-3
#     (XLA's default TPU conv precision rounds operands to bfloat16)
#   bfloat16 task vs the float32 task                   2.82e-3
#   identity chain vs its input                         2.98e-7
F32_VS_REFERENCE_BOUND = 1e-2
BF16_VS_F32_BOUND = 1.5e-2
IDENTITY_BOUND = 2e-6

FULL = dict(patch=(20, 256, 256), overlap=(4, 64, 64), margin=(2, 32, 32),
            block=(16, 64, 64), serve_many=(36, 448, 256), serve_batch=2,
            conv_batches=(4, 6), conv_patch=(20, 256, 256))
TINY = dict(patch=(8, 32, 32), overlap=(2, 8, 8), margin=(1, 4, 4),
            block=(6, 24, 24), serve_many=(14, 56, 32), serve_batch=2,
            conv_batches=(2,), conv_patch=(3, 8, 64))
# one RSBlock through the convolution kernel against XLA's, bfloat16,
# activations of order one: a few steps of the result's rounding (the
# kernel rounds its epilogue once where XLA's fusions may round more)
BOUND_CONV_BLOCK = 0.125

SUMMARY: dict = {"phases": {}}


# ---------------------------------------------------------------------------
# plumbing
# ---------------------------------------------------------------------------
def chunkflow(*args) -> None:
    """One ``chunkflow`` command line through the CLI entry point, in this
    process. Nothing is caught."""
    from chunkflow_tpu.flow.cli import main

    argv = [str(a) for a in args]
    print("$ chunkflow " + " ".join(argv), flush=True)
    main(argv, standalone_mode=False)


@contextlib.contextmanager
def phase(name: str):
    print(f"\n== {name} ==", flush=True)
    t0 = time.perf_counter()
    record = SUMMARY["phases"].setdefault(name, {})
    yield record
    record["smoke_seconds"] = round(time.perf_counter() - t0, 1)
    print(f"== {name}: ok, {record['smoke_seconds']} s (smoke timing) ==",
          flush=True)


def check(condition, message: str) -> None:
    if not condition:
        raise AssertionError(message)


def read_programs(metrics_dir: str) -> list:
    """The run's ``programs.json`` entries (core/profiling.py), printed
    with their cold compile seconds."""
    paths = glob.glob(os.path.join(metrics_dir, "programs*.json"))
    check(paths, f"no programs.json under {metrics_dir}")
    programs = []
    for path in paths:
        with open(path) as f:
            programs += json.load(f)["programs"]
    for entry in programs:
        print(f"  program {entry['family']} [{entry['label']}]: cold compile "
              f"{entry['compile_s']} s, {entry['calls']} call(s) "
              f"(smoke timing)")
    return programs


def read_counters(metrics_dir: str) -> dict:
    """Final counter values from the run's telemetry JSONL."""
    counters: dict = {}
    for path in glob.glob(os.path.join(metrics_dir, "*.jsonl")):
        with open(path) as f:
            for line in f:
                event = json.loads(line)
                if event.get("kind") == "snapshot":
                    counters.update(event.get("counters") or {})
                    for name, value in (event.get("gauges") or {}).items():
                        counters[name] = value
    return counters


# ---------------------------------------------------------------------------
# the reference: plain per-patch float32 model.apply + numpy overlap-add
# ---------------------------------------------------------------------------
def bump_weights(patch):
    """The system's patch weighting as bump.py documents it: the "wu"
    bump exp(-sum 1/(1-u^2)) on the open (-1, 1)^3 grid, conditioned
    affinely into [1, 1e6]. float64 throughout."""
    import numpy as np

    axes = [np.linspace(-1.0, 1.0, n + 2)[1:-1] for n in patch]
    zz, yy, xx = np.meshgrid(*axes, indexing="ij")
    with np.errstate(under="ignore"):
        bump = np.exp(-1.0 / (1.0 - zz ** 2) - 1.0 / (1.0 - yy ** 2)
                      - 1.0 / (1.0 - xx ** 2))
    return (bump - bump.min()) / (bump.max() - bump.min()) * (1e6 - 1.0) + 1.0


def patch_starts(extent: int, patch: int, stride: int) -> list:
    starts = list(range(0, extent - patch + 1, stride))
    if starts[-1] != extent - patch:
        starts.append(extent - patch)
    return starts


def reference_affinities(image_u8, patch, overlap):
    """[3, z, y, x] float64: every patch through the float32 RSUNet under
    jax.default_matmul_precision("highest"), blended by bump-weighted
    overlap-add in numpy. Same seed-0 weights the flax engine makes."""
    import jax
    import numpy as np

    from chunkflow_tpu.models import rsunet, unet3d

    model = rsunet.RSUNet(in_channels=1, out_channels=3)
    params = unet3d.init_params(model, patch, 1)
    with jax.default_matmul_precision("highest"):
        forward = jax.jit(lambda p, x: model.apply({"params": p}, x))
        image = image_u8.astype(np.float32) * np.float32(1.0 / 255)
        weights = bump_weights(patch)
        out = np.zeros((3,) + image.shape, np.float64)
        total = np.zeros(image.shape, np.float64)
        stride = [p - o for p, o in zip(patch, overlap)]
        grid = [patch_starts(image.shape[i], patch[i], stride[i])
                for i in range(3)]
        for z in grid[0]:
            for y in grid[1]:
                for x in grid[2]:
                    window = (slice(z, z + patch[0]), slice(y, y + patch[1]),
                              slice(x, x + patch[2]))
                    pred = forward(params, image[window][None, ..., None])
                    pred = np.moveaxis(np.asarray(pred[0]), -1, 0)
                    out[(slice(None),) + window] += pred * weights
                    total[window] += weights
    print(f"reference: {len(grid[0])}x{len(grid[1])}x{len(grid[2])} patches "
          f"of {patch}")
    return out / total


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_kernels(cfg) -> None:
    """Both Pallas kernels through their selection seams, compiled (chip)
    or interpreted (rehearsal), bitwise against the XLA legs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chunkflow_tpu.ops import blend, pallas_gather

    patch, rehearse = cfg["patch"], cfg["rehearse"]
    zyx = tuple(p + s for p, s in zip(patch, (16, 192, 384)))
    if rehearse:
        zyx = tuple(p + s for p, s in zip(patch, (4, 24, 140)))
    co = 3
    rng = np.random.default_rng(0)
    # aligned, unaligned in both minor dims, flush at the far corner, and
    # a batch-padding row; rows overlap so the RMW order matters
    starts = np.array(
        [[0, 0, 0], [3, 5, 7],
         [zyx[0] - patch[0], zyx[1] - patch[1], zyx[2] - patch[2]],
         [2, 9, 130]], np.int32)
    preds = rng.random((4, co) + patch, dtype=np.float32)
    valid = np.array([1, 1, 1, 0], np.float32)
    kernel_mode = "interpret" if rehearse else "on"

    def mosaic(fn, *args):
        """Proof that ``on``/``pallas`` reached Mosaic and neither the
        interpreter nor the XLA leg."""
        if rehearse:
            return
        text = fn.lower(*args).as_text()
        check("tpu_custom_call" in text,
              "compiled kernel mode lowered without a Mosaic custom call")

    def accumulate(mode):
        os.environ["CHUNKFLOW_PALLAS"] = mode
        from chunkflow_tpu.inference.bump import bump_map

        step, _, pad_y, pad_x = blend.make_accumulate(patch, bump_map(patch))
        shape = (zyx[0], zyx[1] + pad_y, zyx[2] + pad_x)

        @jax.jit
        def run(preds, valid, starts):
            out = jnp.zeros((co,) + shape, jnp.float32)
            weight = jnp.zeros(shape, jnp.float32)
            out, weight = step(out, weight, preds, valid, starts)
            out, weight = step(out, weight, preds * 0.5, valid, starts)
            return (out[:, :, :zyx[1], :zyx[2]],
                    weight[:, :zyx[1], :zyx[2]])

        if mode != "off":
            mosaic(run, preds, valid, starts)
        return [np.asarray(a) for a in run(preds, valid, starts)]

    want = accumulate("off")
    got = accumulate(kernel_mode)
    os.environ.pop("CHUNKFLOW_PALLAS")
    check(all(np.array_equal(a, b) for a, b in zip(got, want)),
          "fused_accumulate_patches differs from the XLA scatter leg")
    print(f"fused_accumulate_patches {patch} float32 [{kernel_mode}]: "
          f"bitwise equal to the XLA scatter leg")

    for dtype in (np.float32, np.uint8):
        if dtype == np.uint8:
            chunk = rng.integers(0, 256, (1,) + zyx, dtype=np.uint8)
        else:
            chunk = rng.random((1,) + zyx, dtype=np.float32)

        def gather(mode):
            os.environ["CHUNKFLOW_GATHER"] = mode
            prepare, take = pallas_gather.make_gather(1, patch)
            run = jax.jit(lambda c, s: take(prepare(c), s))
            if mode != "device":
                mosaic(run, chunk, starts)
            return np.asarray(run(chunk, starts))

        want = gather("device")
        got = gather("interpret" if rehearse else "pallas")
        os.environ.pop("CHUNKFLOW_GATHER")
        check(np.array_equal(got, want),
              f"gather_patches ({np.dtype(dtype).name}) differs from the "
              f"XLA gather leg")
        print(f"gather_patches {patch} {np.dtype(dtype).name} "
              f"[{kernel_mode}]: bitwise equal to the XLA gather leg")


def phase_convolution_kernel(cfg) -> None:
    """The convolution kernel that builds the x halo in VMEM, compiled
    (chip) or interpreted (rehearsal): one level-0 ``RSBlock`` of the
    RSUNet's full width (28 channels x-folded by 4, bfloat16) at the
    anchor's and the production batch, against the same block through
    XLA on the same parameters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from chunkflow_tpu.models import rsunet

    rehearse = cfg["rehearse"]
    width, fold = 28, 4
    for batch in cfg["conv_batches"]:
        z, y, x = cfg["conv_patch"]
        shape = (batch, z, y, x // fold, fold * width)
        check(rsunet.kernel_takes(fold, width, width, jnp.bfloat16,
                                  shape[1:4], "tpu"),
              f"the rule declines the level-0 block at {shape}")
        blocks = {kernel: rsunet.RSBlock(
            width, dtype=jnp.bfloat16, fold=fold, kernel=kernel,
            interpret=rehearse) for kernel in (False, True)}
        data = jax.random.normal(
            jax.random.PRNGKey(batch), shape, jnp.float32).astype(jnp.bfloat16)
        params = jax.jit(blocks[False].init)(
            jax.random.PRNGKey(0), data[:1, :2, :4])
        run = {kernel: jax.jit(block.apply)
               for kernel, block in blocks.items()}
        if not rehearse:
            text = run[True].lower(params, data).as_text()
            check(text.count("tpu_custom_call") >= 3,
                  "the block's convolutions lowered without Mosaic calls")
        want, got = (np.asarray(run[kernel](params, data), np.float32)
                     for kernel in (False, True))
        diff = float(np.abs(got - want).max())
        check(np.isfinite(got).all() and diff <= BOUND_CONV_BLOCK,
              f"RSBlock through the kernel differs from XLA's by {diff}")
        print(f"folded_conv RSBlock {shape} bfloat16 "
              f"[{'interpret' if rehearse else 'on'}]: max-abs-diff "
              f"{diff:.4g} from XLA's block (values to "
              f"{float(np.abs(want).max()):.3g})")


def phase_host_build() -> None:
    from chunkflow_tpu import native

    path = native.build()
    check(path == native.lib_path() and os.path.exists(path),
          f"native.build() left no library at {native.lib_path()}")
    check(native.available(), "native library built but does not load")
    print(f"native library built from source: {os.path.relpath(path, HERE)}")


def make_volume(cfg, work: str, n_tasks: int):
    """A seeded uint8 EM-like volume as precomputed on local disk, and
    the output ROI's geometry. Tasks tile x."""
    import numpy as np

    patch, overlap, margin = cfg["patch"], cfg["overlap"], cfg["margin"]
    stride = [p - o for p, o in zip(patch, overlap)]
    chunk_in = [p + 2 * s for p, s in zip(patch, stride)]  # 3x3x3 patches
    task = [c - 2 * m for c, m in zip(chunk_in, margin)]
    roi = [task[0], task[1], task[2] * n_tasks]
    size = [r + 2 * m for r, m in zip(roi, margin)]
    rng = np.random.default_rng(2021)
    coarse = rng.random([-(-s // 8) for s in size], dtype=np.float32)
    image = coarse.repeat(8, 0).repeat(8, 1).repeat(8, 2)
    image = image[:size[0], :size[1], :size[2]]
    image = 0.7 * image + 0.3 * rng.random(size, dtype=np.float32)
    image = (image * 255).astype(np.uint8)
    npy = os.path.join(work, "image.npy")
    np.save(npy, image)
    chunkflow(
        "load-npy", "-f", npy,
        "create-info", "-v", f"file://{work}/image", "-i", "chunk",
        "--block-size", *cfg["block"],
        "save-precomputed", "-v", f"file://{work}/image", "--no-upload-log",
    )
    geometry = dict(chunk_in=chunk_in, task=task, roi=roi, margin=margin,
                    size=size)
    print(f"volume {size} uint8; {n_tasks} tasks of {task} out / "
          f"{chunk_in} in")
    return image, geometry


def task_box(geometry, index: int):
    from chunkflow_tpu.core.bbox import BoundingBox

    m, t = geometry["margin"], geometry["task"]
    return BoundingBox.from_delta((m[0], m[1], m[2] + index * t[2]), t)


def push_tasks(geometry, queue: str, first: int, last: int) -> None:
    m, t, r = geometry["margin"], geometry["task"], geometry["roi"]
    chunkflow(
        "generate-tasks", "--chunk-size", *t,
        "--roi-start", *m,
        "--roi-stop", *(a + b for a, b in zip(m, r)),
        "--task-index-start", first, "--task-index-stop", last,
        "--queue-name", queue,
    )


def run_worker(cfg, work, geometry, name, inference, num_channels=3):
    """The README worker chain over whatever the queue holds; returns
    (output volume path, metrics dir, seconds)."""
    out = f"file://{work}/{name}"
    metrics = os.path.join(work, f"metrics-{name}")
    # the output volume is the ROI, so its blocks align with the tasks
    chunkflow(
        "create-info", "-v", out, "--volume-size", *geometry["roi"],
        "--voxel-offset", *geometry["margin"],
        "--num-channels", num_channels, "--dtype", "float32",
        "--block-size", *cfg["block"],
    )
    t0 = time.perf_counter()
    chunkflow(
        "--metrics-dir", metrics,
        "fetch-task-from-queue", "-q", f"file://{work}/queue",
        "--retry-times", 1, "--poll-interval", 0.1,
        "load-precomputed", "-v", f"file://{work}/image",
        "--expand-margin-size", *geometry["margin"],
        "inference", *inference,
        "--input-patch-size", *cfg["patch"],
        "--output-patch-overlap", *cfg["overlap"],
        "--num-output-channels", num_channels, "--async-depth", 2,
        "crop-margin",
        "save-precomputed", "-v", out,
        "delete-task-in-queue",
    )
    seconds = time.perf_counter() - t0
    for sub in ("pending", "claimed", "dead"):
        left = os.listdir(os.path.join(work, "queue", sub))
        check(not left, f"queue/{sub} not empty after worker {name}: {left}")
    return out, metrics, seconds


def read_task(volume: str, geometry, index: int):
    import numpy as np

    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    vol = PrecomputedVolume(volume)
    box = task_box(geometry, index)
    check(vol.has_all_blocks(box),
          f"{volume}: blocks of task {index} ({box.string}) not all written")
    array = np.asarray(vol.cutout(box, fill_missing=False).array)
    return array.reshape((-1,) + array.shape[-3:])  # zyx or czyx -> czyx


def task_seconds(volume: str) -> dict:
    """Seconds per operator per task, from the log ``save-precomputed``
    leaves beside the volume. With --async-depth 2 the inference timer
    runs from dispatch to materialize and overlaps the next task."""
    seconds = {}
    for path in sorted(glob.glob(
            os.path.join(volume[len("file://"):], "log", "*.json"))):
        with open(path) as f:
            log = json.load(f)
        seconds[log["bbox"]] = {k: round(v, 2)
                                for k, v in log["timer"].items()}
        print(f"  task {log['bbox']} on {log['compute_device']}: "
              f"{seconds[log['bbox']]} s (smoke timing)")
    return seconds


def report_programs(record, metrics: str, builds_expected: int):
    """Record the run's programs and check the build count; returns
    (programs, compile-cache hits)."""
    programs = read_programs(metrics)
    counters = read_counters(metrics)
    builds = counters.get("compile_cache/builds")
    hits = counters.get("compile_cache/hits", 0)
    record["programs"] = [
        {"family": e["family"], "compile_s": e["compile_s"],
         "calls": e["calls"]} for e in programs]
    check(builds == builds_expected and len(programs) == builds_expected,
          f"expected {builds_expected} inferencer program build(s), "
          f"counters say {builds}, programs.json lists {len(programs)}")
    return programs, hits


def phase_volume(cfg, work, image, geometry, diffs) -> str:
    import numpy as np

    queue = f"file://{work}/queue"
    rsunet = ["--framework", "flax", "--model-variant", "rsunet"]

    with phase("volume: 3 float32 tasks") as record:
        push_tasks(geometry, queue, 0, 3)
        f32, metrics, seconds = run_worker(
            cfg, work, geometry, "aff-f32", rsunet + ["--dtype", "float32"])
        (program,), hits = report_programs(record, metrics, 1)
        # tasks 2-3 hit the program task 1 built: no second compile
        check(program["calls"] == 3 and hits == 2,
              f"3 tasks should be 1 build + 2 hits, got calls="
              f"{program['calls']} hits={hits}")
        print(f"  3 tasks in {seconds:.1f} s, engine build and compile "
              f"included (smoke timing)")
        record["task_seconds"] = task_seconds(f32)
        outputs = [read_task(f32, geometry, i) for i in range(3)]
        for i, out in enumerate(outputs):
            check(out.shape == (3,) + tuple(geometry["task"])
                  and np.isfinite(out).all() and out.std() > 1e-3,
                  f"float32 task {i}: shape {out.shape}, "
                  f"finite={np.isfinite(out).all()}, std={out.std()}")

    with phase("volume: float32 task 0 against the reference") as record:
        m = geometry["margin"]
        box = task_box(geometry, 0)
        chunk_in = image[:, :, :geometry["chunk_in"][2]]
        check(chunk_in.shape == tuple(geometry["chunk_in"]), "bad geometry")
        reference = reference_affinities(
            chunk_in, cfg["patch"], cfg["overlap"])
        reference = reference[:, m[0]:-m[0], m[1]:-m[1], m[2]:-m[2]]
        diff = float(np.abs(outputs[0] - reference).max())
        diffs["f32_task_vs_highest_reference"] = diff
        print(f"  max-abs-diff float32 task {box.string} vs reference: "
              f"{diff:.3e} (bound {F32_VS_REFERENCE_BOUND:g})")
        check(diff <= F32_VS_REFERENCE_BOUND,
              f"float32 task differs from the reference by {diff:.3e}")

    with phase("volume: 1 bfloat16 task") as record:
        push_tasks(geometry, queue, 0, 1)
        bf16, metrics, seconds = run_worker(
            cfg, work, geometry, "aff-bf16",
            rsunet + ["--dtype", "bfloat16"])
        report_programs(record, metrics, 1)  # one build per dtype
        record["task_seconds"] = task_seconds(bf16)
        out = read_task(bf16, geometry, 0)
        check(np.isfinite(out).all(), "bfloat16 task has non-finite values")
        diff = float(np.abs(out - outputs[0]).max())
        diffs["bf16_task_vs_f32_task"] = diff
        diffs["bf16_task_vs_highest_reference"] = float(
            np.abs(out - reference).max())
        print(f"  max-abs-diff bfloat16 vs float32 task: {diff:.3e} "
              f"(bound {BF16_VS_F32_BOUND:g}); vs reference: "
              f"{diffs['bf16_task_vs_highest_reference']:.3e}")
        check(diff <= BF16_VS_F32_BOUND,
              f"bfloat16 task differs from float32 by {diff:.3e}")

    with phase("identity: the chain reproduces its input"):
        push_tasks(geometry, queue, 1, 2)
        ident, _, _ = run_worker(
            cfg, work, geometry, "identity", ["--framework", "identity"],
            num_channels=1)
        out = read_task(ident, geometry, 1)
        box = task_box(geometry, 1)
        sl = tuple(slice(a, b) for a, b in zip(box.start, box.stop))
        want = image[sl].astype(np.float32) * np.float32(1.0 / 255)
        diff = float(np.abs(out[0] - want).max())
        diffs["identity_chain_vs_input"] = diff
        print(f"  max-abs-diff identity chain vs input: {diff:.3e} "
              f"(bound {IDENTITY_BOUND:g})")
        check(diff <= IDENTITY_BOUND,
              f"identity chain differs from its input by {diff:.3e}")
    return f32


def phase_mesh(cfg, work, geometry, f32_volume, n_devices: int) -> None:
    import jax
    import numpy as np

    with phase("mesh: a spec larger than the machine is an error"):
        too_many = max(4, 2 * n_devices)
        try:
            chunkflow(
                "create-chunk", "--size", *cfg["patch"],
                "inference", "--framework", "identity",
                "--input-patch-size", *cfg["patch"],
                "--mesh", f"data={too_many}",
            )
        except ValueError as exc:
            check(f"needs {too_many} devices" in str(exc), str(exc))
            print(f"  --mesh data={too_many} on {n_devices} device(s): "
                  f"{exc}")
        else:
            raise AssertionError(
                f"--mesh data={too_many} ran on {n_devices} device(s)")

    if n_devices < 4:
        print(f"\nmesh: saw {n_devices} device(s), fewer than 4 — the "
              f"data=4 / y=2,x=2 phase did not run")
        SUMMARY["phases"]["mesh: 4 chips"] = {"ran": False,
                                              "devices": n_devices}
        return
    single = read_task(f32_volume, geometry, 0)
    for spec in ("data=4", "y=2,x=2"):
        with phase(f"mesh: --mesh {spec}") as record:
            push_tasks(geometry, f"file://{work}/queue", 0, 1)
            out, metrics, _ = run_worker(
                cfg, work, geometry, f"aff-mesh-{spec.replace(',', '-')}",
                ["--framework", "flax", "--model-variant", "rsunet",
                 "--dtype", "float32", "--mesh", spec])
            read_programs(metrics)
            check(np.array_equal(read_task(out, geometry, 0), single),
                  f"--mesh {spec} output differs from one device")
            counters = read_counters(metrics)
            voxels = [counters.get(f"shard/chip/{i}/voxels", 0)
                      for i in range(4)]
            # the CPU backend of a rehearsal reports no memory statistics
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                     for d in jax.devices()[:4]]
            record["chip_voxels"] = voxels
            print(f"  bitwise equal to one device; voxels per chip "
                  f"{voxels}; peak HBM bytes per chip {peaks}")
            check(all(v > 0 for v in voxels)
                  and (cfg["rehearse"] or all(peaks)),
                  f"--mesh {spec}: not every chip did work / held memory")


def post_infer(port: int, array):
    import numpy as np

    body = json.dumps({
        "shape": list(array.shape), "dtype": array.dtype.name,
        "data_b64": base64.b64encode(
            np.ascontiguousarray(array).tobytes()).decode(),
        "deadline_s": 900.0,
    }).encode()
    request = urllib.request.Request(
        f"http://127.0.0.1:{port}/infer", data=body, method="POST")
    with urllib.request.urlopen(request, timeout=900) as response:
        check(response.status == 200, f"POST /infer -> {response.status}")
        payload = json.loads(response.read())
    return np.frombuffer(
        base64.b64decode(payload["data_b64"]), dtype=payload["dtype"]
    ).reshape(payload["shape"])


def phase_serve(cfg, work, diffs) -> None:
    """``serve`` runs in the main thread, as it does for a user, and is
    ended the way a user ends it: SIGINT. A client thread posts the
    requests — first with the packer, then with CHUNKFLOW_SERVE=0 (the
    per-chunk path) — and both answers to every request must be equal
    bit for bit."""
    import numpy as np

    metrics = os.path.join(work, "metrics-serve")
    rng = np.random.default_rng(7)
    requests = [
        rng.integers(0, 256, shape, dtype=np.uint8)
        for shape in (cfg["patch"], cfg["serve_many"],
                      cfg["patch"], cfg["serve_many"])
    ]
    client: dict = {}

    def drive():
        try:
            deadline = time.time() + 600
            port = None
            while port is None and time.time() < deadline:
                for path in glob.glob(
                        os.path.join(metrics, "endpoint-*.json")):
                    with open(path) as f:
                        port = json.load(f).get("serving_port")
                time.sleep(0.2)
            check(port, "serve never published its port")
            # packed: all four in flight at once, so device batches mix
            # patches of different requests
            packed = [None] * len(requests)

            def one(i):
                try:
                    packed[i] = post_infer(port, requests[i])
                except BaseException as exc:
                    packed[i] = exc

            t0 = time.perf_counter()
            threads = [threading.Thread(target=one, args=(i,), daemon=True)
                       for i in range(len(requests))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            for answer in packed:
                if isinstance(answer, BaseException):
                    raise answer
            client["packed_s"] = time.perf_counter() - t0
            os.environ["CHUNKFLOW_SERVE"] = "0"
            client["per_chunk"] = [post_infer(port, a) for a in requests]
            client["packed"] = packed
        except BaseException as exc:
            client["error"] = exc
        finally:
            os.environ.pop("CHUNKFLOW_SERVE", None)
            signal.raise_signal(signal.SIGINT)

    thread = threading.Thread(target=drive, daemon=True)
    thread.start()
    chunkflow(
        "--metrics-dir", metrics,
        "serve", "--port", 0, "--host", "127.0.0.1",
        "--input-patch-size", *cfg["patch"],
        "--output-patch-overlap", *cfg["overlap"],
        "--num-output-channels", 3, "--framework", "flax",
        "--batch-size", cfg["serve_batch"], "--serve-workers", 4,
        "--default-deadline-s", 900,
    )
    thread.join(timeout=60)
    if "error" in client:
        raise client["error"]
    check("packed" in client, "the serving client did not finish")
    for i, (a, b) in enumerate(zip(client["packed"], client["per_chunk"])):
        check(a.shape == (3,) + requests[i].shape and np.isfinite(a).all(),
              f"request {i}: shape {a.shape}")
        check(np.array_equal(a, b),
              f"request {i}: packed answer differs from the per-chunk "
              f"answer by {np.abs(a - b).max():.3e}")
    diffs["serve_packed_vs_per_chunk"] = 0.0
    # serve returned, so it drained; its counters say how
    n = 2 * len(requests)
    counters = read_counters(metrics)
    served = {name: counters.get(f"serving/{name}", 0) for name in (
        "requests", "completed", "rejected_admission", "rejected_memory",
        "deadline_missed", "errors")}
    check(served == dict.fromkeys(served, 0) | {"requests": n,
                                                "completed": n},
          f"unclean drain: {served}")
    print(f"  {n} requests of {[list(r.shape) for r in requests[:2]]} "
          f"answered 200; packed == per-chunk bit for bit; "
          f"{counters.get('serving/batches', 0):g} packed batches, "
          f"{counters.get('serving/fallbacks', 0):g} per-chunk fallbacks; "
          f"4 packed requests in {client['packed_s']:.1f} s including "
          f"compiles (smoke timing)")
    read_programs(metrics)


def phase_segment(work, geometry, f32_volume) -> None:
    import numpy as np
    from scipy import ndimage

    from chunkflow_tpu import native

    check(native.available(), "native library does not load")
    affinity = read_task(f32_volume, geometry, 2)[0]
    threshold = float(np.median(affinity))
    src, dst = os.path.join(work, "aff0.npy"), os.path.join(work, "seg.npy")
    np.save(src, affinity)
    chunkflow(
        "load-npy", "-f", src,
        "connected-components", "--threshold", threshold,
        "--connectivity", 26,
        "save-npy", "-f", dst,
    )
    labels = np.load(dst)
    labels = labels.reshape(affinity.shape)
    want, count = ndimage.label(affinity > threshold,
                                structure=np.ones((3, 3, 3), bool))
    check(np.array_equal(labels > 0, want > 0),
          "segmentation foreground differs from the thresholded affinity")
    check(int(labels.max()) == count
          and len(np.unique(labels[want == 1])) == 1,
          f"native labeling found {int(labels.max())} components, "
          f"scipy {count}")
    print(f"  {count} components at threshold {threshold:.4f}, as scipy "
          f"labels them")


# ---------------------------------------------------------------------------
def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse", action="store_true",
        help="tiny size on 4 virtual CPU devices, Pallas interpreted; "
             "prints platform: cpu and never the chip's OK line")
    args = parser.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()
        # CPU entries stay out of <checkout>/.jax_cache, which the chip
        # tool copies to the chip with the tree
        os.environ.setdefault(
            "JAX_COMPILATION_CACHE_DIR",
            os.path.join(tempfile.gettempdir(), "chip-smoke-rehearsal-cache"))
    cfg = dict(TINY if args.rehearse else FULL, rehearse=args.rehearse)

    import jax
    import jaxlib

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"platform: {device['platform']}")
    print(f"device_kind: {device['kind']}")
    print(f"device count: {device['count']}")
    if not args.rehearse and device["platform"] != "tpu":
        print(
            f"chip_smoke.py needs a TPU: jax.devices()[0].platform is "
            f"{device['platform']!r} (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}). No result.",
            file=sys.stderr)
        return 2
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
        check(args.rehearse, "libtpu is not importable")
    print(f"jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu_version}")

    from chunkflow_tpu.core import compile_cache, profiling

    # a device_kind the peaks table does not know is an error here, not
    # a default there
    peaks = profiling.device_peaks(device["kind"])
    print(f"peaks row: {peaks['source']}")
    cache_dir = compile_cache.enable_persistent_cache()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    check(cache_dir == (placed or os.path.join(HERE, ".jax_cache"))
          and jax.config.jax_compilation_cache_dir == cache_dir,
          f"compile cache in effect is {cache_dir!r}; "
          f"JAX_COMPILATION_CACHE_DIR={placed!r}")
    cache_files = len(glob.glob(os.path.join(cache_dir, "*")))
    print(f"compile cache directory: {cache_dir} "
          f"({'JAX_COMPILATION_CACHE_DIR' if placed else 'checkout default'}"
          f"), {cache_files} file(s) at start")
    SUMMARY.update(device=device, jax=jax.__version__,
                   jaxlib=jaxlib.__version__, libtpu=libtpu_version,
                   compile_cache_dir=cache_dir,
                   compile_cache_files_at_start=cache_files,
                   rehearsal=args.rehearse)

    diffs: dict = {}
    work = tempfile.mkdtemp(prefix="chip-smoke-")
    t_start = time.perf_counter()
    try:
        with phase("kernels: Pallas, compiled, bitwise vs XLA"):
            phase_kernels(cfg)
            phase_convolution_kernel(cfg)
        with phase("host: native.build() from source"):
            phase_host_build()
        with phase("volume: seeded image as precomputed"):
            image, geometry = make_volume(cfg, work, n_tasks=3)
        f32_volume = phase_volume(cfg, work, image, geometry, diffs)
        phase_mesh(cfg, work, geometry, f32_volume, len(devices))
        with phase("serve: POST /infer, packed vs per-chunk, drain"):
            phase_serve(cfg, work, diffs)
        with phase("segment: connected-components on an affinity channel"):
            phase_segment(work, geometry, f32_volume)
    finally:
        # the serve requests breach the default latency SLO, which
        # starts a bounded profiler capture under the metrics dir
        profiling.wait_for_captures(60)
        shutil.rmtree(work, ignore_errors=True)

    hbm = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in devices]
    cache_files_end = len(glob.glob(os.path.join(cache_dir, "*")))
    print(f"\npeak HBM bytes in use per device: {hbm}")
    print(f"compile cache: {cache_files_end} file(s) at end "
          f"({cache_files_end - cache_files} added by this run)")
    print("max-abs-diffs: " + json.dumps(diffs))
    SUMMARY.update(
        peak_hbm_bytes_per_device=hbm,
        compile_cache_files_at_end=cache_files_end,
        max_abs_diff=diffs,
        total_smoke_seconds=round(time.perf_counter() - t_start, 1),
        note="smoke timings: one cold run, compiles included; not "
             "benchmark numbers",
        claim=None,
    )
    text = json.dumps(SUMMARY, indent=1)
    print(text)
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    with open(os.path.join(HERE, "chiprun_out", "chip_smoke.json"), "w") as f:
        f.write(text + "\n")
    if args.rehearse:
        print("rehearsal passed on platform: cpu — this is not a chip "
              "result")
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
