"""The chained-command CLI: a pipeline is a shell command.

Parity target: reference flow/flow.py (62 chained click commands) +
lib/flow.py (chained group machinery). Each subcommand returns a stage
callable; the group's result callback wires them into one lazy generator
chain (see runtime.py) and drains it.

Example:
    chunkflow create-chunk --size 64 512 512 \
        inference --framework identity --input-patch-size 20 256 256 \
        save-h5 --file-name /tmp/out.h5
"""
from __future__ import annotations

import sys

import click
import numpy as np

from chunkflow_tpu.chunk import Chunk, Image, Segmentation
from chunkflow_tpu.chunk.base import LayerType
from chunkflow_tpu.core.bbox import BoundingBox, BoundingBoxes
from chunkflow_tpu.core.cartesian import to_cartesian
from chunkflow_tpu.flow.runtime import (
    DEFAULT_CHUNK_NAME,
    PipelineState,
    generator,
    operator,
    process_stream,
    write_operator,
)

state = PipelineState()


def cartesian_option(*names, default=None, required=False, help=""):
    return click.option(
        *names, type=int, nargs=3, default=default, required=required, help=help
    )


def _h5_task_path(prefix: str, bbox) -> str:
    """Complete a non-.h5 prefix as <prefix><bbox>.h5 (reference naming)."""
    return f"{prefix}{bbox.string}.h5"


def _touch_marker(prefix, bbox, suffix):
    """Touch <prefix><bbox><suffix> as a skip/resume marker (never under
    --dry-run: a dry preview must not fabricate resume state)."""
    import os
    from pathlib import Path

    if state.dry_run:
        return
    fname = f"{prefix}{bbox.string}{suffix}"
    if not os.path.exists(fname):
        Path(fname).touch()


def name_option(default):
    """--name: the operator's key in the task log timer (reference parity:
    every operator command takes --name so repeated operators — e.g. an
    input mask and an output mask — get distinct timer entries in
    log-summary; task-source generators keep fixed names)."""
    return click.option(
        "--name", "op_name", type=str, default=default,
        help="operator name key in the task log timer",
    )


@click.group(chain=True)
@click.option("--mip", type=int, default=0, help="storage hierarchy level")
@click.option("--dry-run/--real-run", default=False)
@click.option("--verbose", "-v", count=True)
@click.option("--profile-dir", type=str, default=None,
              help="capture a jax profiler trace of the run's first "
                   "--profile-tasks tasks here (bounded, not the whole "
                   "run; summarize with tools/analyze_trace.py or view "
                   "with tensorboard/xprof). CHUNKFLOW_TELEMETRY=0 "
                   "disables all profiling")
@click.option("--profile-tasks", type=int, default=None,
              help="tasks covered by the --profile-dir window "
                   "(CHUNKFLOW_PROFILE_TASKS, default 4; <=0 traces "
                   "the whole run — the pre-PR 8 behavior)")
@click.option("--metrics-dir", type=str, default=None,
              help="append structured telemetry JSONL (spans, stall "
                   "attribution, cache counters) here; aggregate with "
                   "log-summary --metrics-dir (docs/observability.md). "
                   "CHUNKFLOW_TELEMETRY=0 disables all telemetry")
@click.option("--metrics-port", type=int, default=None,
              help="serve live /metrics (Prometheus text) + /healthz "
                   "from this worker for the run's duration (0 binds an "
                   "ephemeral port; CHUNKFLOW_METRICS_PORT is the env "
                   "equivalent). CHUNKFLOW_TELEMETRY=0 creates no "
                   "listener (docs/observability.md \"Fleet view\")")
@click.option("--slo-config", type=str, default=None,
              help="TOML file overriding the SLO objectives / burn-rate "
                   "rules (top level = the [tool.chunkflow.slo] table; "
                   "docs/observability.md \"SLO view\"). Defaults + any "
                   "pyproject [tool.chunkflow.slo] apply without it; "
                   "CHUNKFLOW_SLO=0 disables the evaluator, "
                   "CHUNKFLOW_TELEMETRY=0 the whole plane")
def main(mip, dry_run, verbose, profile_dir, profile_tasks, metrics_dir,
         metrics_port, slo_config):
    """chunkflow-tpu: compose chunk operators into a pipeline.

    \b
    Adaptive scheduler env vars (docs/performance.md):
      CHUNKFLOW_SCHED=static    kill switch: compose the static prefetch/
                                pipeline/async-write stages exactly as
                                before (bit-identical); default: adaptive
      CHUNKFLOW_SCHED_MEM_GB    host-memory watermark bounding adaptive
                                depth growth (default 4)
      CHUNKFLOW_SCHED_INTERVAL  tasks between depth-controller ticks
                                (default 4)

    \b
    Multi-chip mesh (docs/multichip.md):
      CHUNKFLOW_MESH            unified sharded engine spec for every
                                inference/serving dispatch: 1 (kill
                                switch, single-device reference path —
                                default), auto, data=N (patch-parallel),
                                y=A or y=A,x=B (chunk sharded in slabs);
                                every mesh shape is bit-identical to the
                                single-device path. `inference --mesh`
                                overrides per command.

    \b
    Fault tolerance (docs/fault_tolerance.md):
      fetch-task-from-queue --max-retries/--lease-renew/--ledger runs
      the worker supervised (contained retries, dead-letter, resume);
      CHUNKFLOW_CHAOS injects seeded stage kills for drill runs
      (testing/chaos.py; action=kill for true SIGKILL process death).

    \b
    Fleet supervision (docs/fault_tolerance.md "Running a fleet"):
      fleet-run spawns/monitors/scales/evicts worker processes from
      live telemetry; CHUNKFLOW_FLEET=0 pins a static fleet size and
      bypasses the scaling controller (liveness replacement stays).

    \b
    Device performance plane (docs/observability.md "Device program
    view"): every compiled program's compile time + XLA cost analysis
    lands in program/* counters and --metrics-dir/programs.json;
    --profile-dir captures the first --profile-tasks tasks; anomaly
    captures (retrace watchdog, sustained dominant stall) write
    bounded profile-* trace dirs under --metrics-dir, summarized by
    log-summary / tools/analyze_trace.py; POST /profile?seconds=N on
    the metrics port profiles a live worker on demand.
    CHUNKFLOW_TELEMETRY=0 disables the entire plane.

    \b
    SLO plane (docs/observability.md "SLO view"): with --metrics-dir
    (or --slo-config) a time-series sampler records counter rates /
    gauges / latency quantiles (CHUNKFLOW_TS_INTERVAL, default 10 s;
    CHUNKFLOW_TS_POINTS ring size) and the burn-rate evaluator fires
    alert events against the configured objectives; GET /alerts on the
    metrics port shows live burn/budget state, log-summary --slo
    reconstructs the same from JSONL; CHUNKFLOW_SLO=0 disables just
    the evaluator.
    """
    from chunkflow_tpu.core import telemetry

    state.mip = mip
    state.dry_run = dry_run
    state.verbose = verbose
    # one CLI invocation = one telemetry run: drop metrics (and any open
    # sink) left by a previous invocation in this process (tests,
    # notebooks drive several per process)
    telemetry.reset()
    if metrics_dir:
        # configure BEFORE any stage runs so operator construction
        # (engine load, program cache) is visible in the stream too
        telemetry.configure(metrics_dir)
    if metrics_dir or slo_config:
        # the SLO plane (docs/observability.md "SLO view"): a bounded
        # time-series sampler over the registry plus burn-rate
        # evaluation against the configured objectives; both are
        # no-ops (no threads, no files) under CHUNKFLOW_TELEMETRY=0
        from chunkflow_tpu.core import slo

        telemetry.start_timeseries()
        slo.start_slo(slo_config)
    from chunkflow_tpu.parallel.restapi import (
        exporter_port_from_env,
        start_metrics_exporter,
    )

    port = metrics_port if metrics_port is not None \
        else exporter_port_from_env()
    state.metrics_server = (
        start_metrics_exporter(port) if port is not None else None
    )
    if state.metrics_server is not None:
        from chunkflow_tpu.parallel.restapi import (
            bound_port,
            write_endpoint_file,
        )

        bound = bound_port(state.metrics_server)
        if metrics_dir:
            # publish the actually-bound port so a supervisor that
            # spawned us with --metrics-port 0 (ephemeral; no port
            # collisions between workers on one host) can find us
            write_endpoint_file(metrics_dir, metrics_port=bound)
        if verbose or port == 0:
            # a requested port 0 MUST be reported — nothing else tells
            # the operator where the listener landed
            host = state.metrics_server.server_address[0]
            print(f"metrics exporter: http://{host}:{bound}/metrics")


def _print_run_telemetry(verbose: int) -> None:
    """End-of-run observability report: the span/counter summary table,
    ProgramCache builds vs. hits, and persistent-XLA-cache status.
    Everything here reads process-global state, so it covers every
    Inferencer/cache the pipeline created."""
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.core.compile_cache import persistent_cache_dir

    if not telemetry.enabled():
        return
    table = telemetry.summary_table()
    if verbose and table:
        print(table)
    if verbose:
        snap = telemetry.snapshot()
        builds = snap["counters"].get("compile_cache/builds", 0)
        hits = snap["counters"].get("compile_cache/hits", 0)
        retraces = snap["counters"].get("compile_cache/retrace_warnings", 0)
        if builds or hits:
            line = (
                f"program cache: {builds:g} build(s), {hits:g} hit(s)"
            )
            if retraces:
                line += f", {retraces:g} RETRACE WARNING(S)"
            print(line)
        cache_dir = persistent_cache_dir()
        print(
            f"persistent XLA cache: "
            f"{cache_dir if cache_dir else 'disabled'}"
        )
    if telemetry.configured_path():
        telemetry.flush()
        if verbose:
            print(f"telemetry events: {telemetry.configured_path()}")


@main.result_callback()
def run_pipeline(stages, mip, dry_run, verbose, profile_dir, profile_tasks,
                 metrics_dir, metrics_port, slo_config):
    window = None
    if profile_dir:
        # windowed capture (core/profiling.py): the trace covers the
        # first --profile-tasks tasks, not the whole run — a petabyte
        # job's profile should not be a petabyte of trace
        from chunkflow_tpu.core import profiling

        window = profiling.start_task_window(profile_dir,
                                             tasks=profile_tasks)
        if window is None:
            print(
                "profiler window not started (telemetry disabled or "
                "another profiler session active)", file=sys.stderr,
            )
    try:
        count = process_stream(stages, verbose=verbose)
    finally:
        if window is not None:
            window.close()
        _print_run_telemetry(verbose)
        # the exporter's lifetime is the run's: a supervisor scraping a
        # finished worker should see connection-refused, not stale data
        server = getattr(state, "metrics_server", None)
        if server is not None:
            server.shutdown()
            server.server_close()
            state.metrics_server = None
    if verbose:
        print(f"pipeline drained {count} task(s)")


# ---------------------------------------------------------------------------
# task sources
# ---------------------------------------------------------------------------
@main.command("generate-tasks")
@click.option("--volume-path", "-v", type=str, default=None,
              help="derive default roi bounds from this volume's metadata "
                   "at --mip (reference cartesian_coordinate.py:567-580)")
@click.option("--mip", "-m", type=int, default=None,
              help="scale level for --volume-path metadata "
                   "(default: the group-level --mip)")
@cartesian_option("--chunk-size", "-c", required=True, help="task chunk size")
@cartesian_option("--overlap", default=(0, 0, 0), help="chunk overlap")
@cartesian_option("--roi-start", "-s", default=None)
@cartesian_option("--roi-stop", "-r", default=None)
@cartesian_option("--roi-size", "-z", default=None,
                  help="alternative to --roi-stop: start + size")
@click.option("--bounding-box", "-b", type=str, default=None,
              help="roi as a canonical zs-ze_ys-ye_xs-xe string")
@cartesian_option("--grid-size", "-g", default=None)
@cartesian_option("--aligned-block-size", "-a", default=None,
                  help="snap chunk starts/stops to storage block multiples "
                       "(write-conflict avoidance)")
@click.option("--bounded/--no-bounded", default=False,
              help="shift trailing chunks back inside the roi instead of "
                   "spilling past it")
@click.option("--task-file", "--file-path", "-f", type=str, default=None,
              help="write tasks to .txt/.npy instead of streaming")
@click.option("--queue-name", "-q", type=str, default=None, help="push tasks to a queue (file://dir or sqs://name)")
@click.option("--task-index-start", "-i", type=int, default=None)
@click.option("--task-index-stop", "-p", type=int, default=None)
@click.option("--disbatch/--no-disbatch", default=False,
              help="select the single task at $DISBATCH_REPEAT_INDEX "
              "(disBatch cluster protocol, reference flow/flow.py:151-156)")
def generate_tasks_cmd(volume_path, mip, chunk_size, overlap, roi_start,
                       roi_stop, roi_size, bounding_box, grid_size,
                       aligned_block_size, bounded, task_file, queue_name,
                       task_index_start, task_index_stop, disbatch):
    """Fan the seed task into a grid of bbox tasks."""
    import os

    start, stop, size = roi_start, roi_stop, roi_size
    block = aligned_block_size
    block_anchor = None
    if stop is not None and size is not None:
        raise click.UsageError("give --roi-stop OR --roi-size, not both")
    if bounding_box is not None:
        if start is not None or stop is not None or size is not None:
            raise click.UsageError(
                "--bounding-box replaces --roi-start/--roi-stop/--roi-size"
            )
        box = BoundingBox.from_string(bounding_box)
        start, stop = tuple(box.start), tuple(box.stop)
    if volume_path is not None:
        # reference behavior: unspecified roi bounds come from the dataset
        from chunkflow_tpu.volume.precomputed import PrecomputedVolume

        vol = PrecomputedVolume(volume_path)
        vmip = mip if mip is not None else state.mip
        bounds = vol.bounds(vmip)
        derived = start is None and stop is None and size is None
        if start is None:
            start = tuple(bounds.start)
        if stop is None and size is None:
            stop = tuple(bounds.stop)
        # auto-align to storage blocks only when the bounds themselves came
        # from the volume; an explicit roi must not be silently expanded
        # (pass -a to opt in)
        if block is None and derived:
            block = tuple(vol.block_size(vmip))
        if block is not None:
            # the volume's block grid anchors at its voxel_offset
            block_anchor = tuple(vol.voxel_offset(vmip))
    if start is None:
        start = (0, 0, 0)

    @generator
    def stage(task):
        bboxes = BoundingBoxes.from_manual_setup(
            chunk_size=chunk_size,
            overlap=overlap,
            roi_start=start,
            roi_stop=stop,
            roi_size=size,
            grid_size=grid_size,
            aligned_block_size=block,
            block_offset=block_anchor,
            bounded=bounded,
        )
        boxes = list(bboxes)
        if task_index_start is not None or task_index_stop is not None:
            boxes = boxes[task_index_start:task_index_stop]
        elif disbatch:
            if "DISBATCH_REPEAT_INDEX" not in os.environ:
                raise click.UsageError(
                    "--disbatch needs $DISBATCH_REPEAT_INDEX in the "
                    "environment (set by the disBatch launcher)"
                )
            idx = int(os.environ["DISBATCH_REPEAT_INDEX"])
            if idx >= len(boxes):
                raise click.UsageError(
                    f"DISBATCH_REPEAT_INDEX={idx} exceeds the "
                    f"{len(boxes)}-task grid"
                )
            boxes = [boxes[idx]]
        if task_file is not None:
            BoundingBoxes(boxes).to_file(task_file)
            print(f"wrote {len(boxes)} tasks to {task_file}")
            return
        if queue_name is not None:
            from chunkflow_tpu.parallel.queues import open_queue

            queue = open_queue(queue_name)
            queue.send_messages([b.string for b in boxes])
            print(f"pushed {len(boxes)} tasks to {queue_name}")
            return
        from chunkflow_tpu.flow.runtime import new_task

        for bbox in boxes:
            t = new_task()
            t["bbox"] = bbox
            yield t

    return stage()


@main.command("setup-env")
@cartesian_option("--volume-start", required=True)
@cartesian_option("--volume-stop", default=None)
@cartesian_option("--volume-size", "-s", default=None)
@click.option("--volume-path", "--layer-path", "-l", type=str, required=True)
@click.option("--visibility-timeout", type=int, default=None,
              help="visibility timeout for the task queue being seeded")
@click.option("--max-ram-size", "-r", type=float, default=15.0,
              help="RAM budget in GB; half goes to the output buffer")
@cartesian_option("--output-patch-size", "-z", required=True)
@cartesian_option("--input-patch-size", default=None)
@cartesian_option("--output-patch-overlap", default=None)
@cartesian_option("--crop-chunk-margin", default=None)
@click.option("--channel-num", "-c", type=int, default=3)
@click.option("--dtype", type=click.Choice(["uint8", "float16", "float32"]),
              default="float32")
@click.option("--mip", "env_mip", type=int, default=0)
@click.option("--thumbnail-mip", type=int, default=6)
@click.option("--max-mip", type=int, default=5)
@click.option("--thumbnail/--no-thumbnail", default=True)
@click.option("--encoding", type=str, default="raw")
@cartesian_option("--voxel-size", default=(40, 4, 4))
@click.option("--overwrite-info/--no-overwrite-info", default=False)
@click.option("--queue-name", "-q", type=str, default=None,
              help="also push the task grid to this queue")
def setup_env_cmd(
    volume_start, volume_stop, volume_size, volume_path, visibility_timeout,
    max_ram_size, output_patch_size, input_patch_size, output_patch_overlap,
    crop_chunk_margin, channel_num, dtype, env_mip, thumbnail_mip, max_mip,
    thumbnail, encoding, voxel_size, overwrite_info, queue_name,
):
    """Plan chunk/block geometry, create volume infos, emit the task grid
    (reference flow/setup_env.py:99-209)."""
    from chunkflow_tpu.flow.setup_env import setup_environment

    def none_if_unset(tp):
        # click returns None for unset nargs=3 options; an explicit all-zero
        # tuple (e.g. --output-patch-overlap 0 0 0) is a real value
        return tuple(tp) if tp is not None else None

    @generator
    def stage(task):
        plan = setup_environment(
            dry_run=state.dry_run,
            volume_start=tuple(volume_start),
            volume_stop=none_if_unset(volume_stop),
            volume_size=none_if_unset(volume_size),
            volume_path=volume_path,
            max_ram_size=max_ram_size,
            output_patch_size=tuple(output_patch_size),
            input_patch_size=none_if_unset(input_patch_size),
            channel_num=channel_num,
            dtype=dtype,
            output_patch_overlap=none_if_unset(output_patch_overlap),
            crop_chunk_margin=none_if_unset(crop_chunk_margin),
            mip=env_mip,
            thumbnail_mip=thumbnail_mip,
            max_mip=max_mip,
            thumbnail=thumbnail,
            encoding=encoding,
            voxel_size=tuple(voxel_size),
            overwrite_info=overwrite_info,
        )
        if queue_name is not None and not state.dry_run:
            from chunkflow_tpu.parallel.queues import open_queue

            queue = open_queue(
                queue_name,
                **({"visibility_timeout": visibility_timeout}
                   if visibility_timeout is not None else {}),
            )
            queue.send_messages([b.string for b in plan.bboxes])
            print(f"pushed {len(plan.bboxes)} tasks to {queue_name}")
            return
        from chunkflow_tpu.flow.runtime import new_task

        for bbox in plan.bboxes:
            t = new_task()
            t["bbox"] = bbox
            yield t

    return stage()


@main.command("fetch-task-from-file")
@click.option("--task-file", "--file-path", "-f", type=str, required=True,
              help=".txt/.npy task list from generate-tasks")
@click.option("--job-index", type=int, default=None,
              help="index into the task list; defaults to $SLURM_ARRAY_TASK_ID")
@click.option("--granularity", "-g", type=int, default=1,
              help="number of consecutive tasks per job")
@click.option("--disbatch/--no-disbatch", default=False,
              help="take the job index from $DISBATCH_REPEAT_INDEX instead "
              "of $SLURM_ARRAY_TASK_ID (reference flow/flow.py:151-156)")
def fetch_task_from_file_cmd(task_file, job_index, granularity, disbatch):
    """Static sharding: take this job's slice of a task-list file
    (reference flow/flow.py:554-581; SLURM array + disBatch protocols)."""
    import os

    @generator
    def stage(task):
        from chunkflow_tpu.flow.runtime import new_task

        index = job_index
        if index is None and disbatch:
            if "DISBATCH_REPEAT_INDEX" not in os.environ:
                raise click.UsageError(
                    "--disbatch needs $DISBATCH_REPEAT_INDEX in the "
                    "environment (set by the disBatch launcher)"
                )
            index = int(os.environ["DISBATCH_REPEAT_INDEX"])
        if index is None:
            index = int(os.environ.get("SLURM_ARRAY_TASK_ID", 0))
        boxes = list(BoundingBoxes.from_file(task_file))
        start = index * granularity
        if start >= len(boxes):
            if disbatch:
                # a disBatch index addresses exactly one task; out of range
                # is a dropped shard (the reference asserts the same,
                # flow/flow.py:154)
                raise click.UsageError(
                    f"DISBATCH_REPEAT_INDEX={index} x granularity "
                    f"{granularity} exceeds the {len(boxes)}-task file"
                )
            # ragged tail of an over-provisioned SLURM array: a valid no-op
            print(f"job index {index}: no tasks in the {len(boxes)}-task "
                  "file; exiting cleanly")
        for bbox in boxes[start:start + granularity]:
            t = new_task()
            t["bbox"] = bbox
            yield t

    return stage()


@main.command("debug")
@name_option("debug")
def debug_cmd(op_name, ):
    """Drop into a debugger with the flowing task bound to ``task``."""

    @operator
    def stage(task):
        breakpoint()  # noqa: T100
        return task

    return stage(_name=op_name)


@main.command("prefetch")
@click.option(
    "--depth", "-d", type=int, default=2,
    help="how many tasks to stage ahead of the consumer",
)
@click.option(
    "--to-device/--no-to-device", default=False,
    help="also start the async H2D transfer of staged chunks",
)
def prefetch_cmd(depth, to_device):
    """Pipeline upstream stages in a background thread.

    Place after the load operators so the next task's host IO overlaps the
    current task's device compute (no reference analog — the reference's
    sequential loop is its acknowledged hot spot, SURVEY §3.2)."""
    from chunkflow_tpu.flow.runtime import prefetch_stage

    return prefetch_stage(depth=depth, to_device=to_device)


@main.command("fetch-task-from-queue")
@click.option("--queue-name", "-q", type=str, required=True)
@click.option("--visibility-timeout", "-v", type=int, default=1800)
@click.option("--retry-times", "-r", type=int, default=30,
              help="empty-queue polls before giving up (reference "
                   "sqs_queue.py:115-130). Keep this MODERATE for "
                   "fleet workers: the pipeline flushes its buffered "
                   "tail when this generator finishes, so a worker that "
                   "polls an empty queue for long holds its last "
                   "async-depth tasks claimed-but-unacked the whole "
                   "time (docs/fault_tolerance.md \"Running a fleet\")")
@click.option("--poll-interval", type=float, default=None,
              help="seconds between empty-queue polls (default: the "
                   "backend's own cadence). retry-times * poll-interval "
                   "is how long an idle worker lingers before flushing "
                   "its buffered tail and exiting — the drain-session "
                   "knob fleet workers tune down")
@click.option("--num", type=int, default=-1, help="max tasks to process (-1: drain)")
@click.option("--max-retries", type=int, default=None,
              help="supervised mode (docs/fault_tolerance.md): a task "
                   "failure no longer kills the worker — it retries with "
                   "exponential backoff up to this many failed attempts, "
                   "then moves to the dead-letter store with its failure "
                   "reason (inspect via `chunkflow dead-letter`)")
@click.option("--lease-renew", type=float, default=0.0,
              help="lease heartbeat interval in seconds: renew the "
                   "claimed task's visibility while it is in compute so "
                   "a slow chunk is not double-claimed (0: off; "
                   "visibility-timeout/3 is a good value)")
@click.option("--ledger", type=str, default=None,
              help="durable completion ledger (memory://name or a "
                   "directory): committed tasks are skipped idempotently "
                   "on requeue/replay, so an interrupted run resumes "
                   "from where it died")
@click.option("--backoff-base", type=float, default=0.5,
              help="first-retry backoff ceiling in seconds (doubles per "
                   "attempt, full jitter, capped at --backoff-cap)")
@click.option("--backoff-cap", type=float, default=60.0)
def fetch_task_cmd(queue_name, visibility_timeout, retry_times,
                   poll_interval, num, max_retries, lease_renew, ledger,
                   backoff_base, backoff_cap):
    """Pull bbox tasks from a queue; ack via delete-task-in-queue.

    With --max-retries / --lease-renew / --ledger the fetch runs under
    the task lifecycle supervisor (parallel/lifecycle.py): contained
    per-task retries, dead-letter for poison tasks, lease heartbeats,
    idempotent resume, and graceful SIGTERM/SIGINT preemption (the
    in-flight task is nacked back to the queue immediately).

    When the jax runtime spans processes (one inference program over a
    multi-host mesh), the task stream must be single-sourced: only the
    coordinator touches the queue, broadcasting each bbox to every peer
    (parallel/multihost.broadcast_string); peers yield mirror tasks that
    run the compute collectives but skip writes and acks
    (runtime.is_mirror_task). The reference's workers never share a
    runtime, so its loop (sqs_queue.py:115-130) has no such mode."""
    supervised = (
        max_retries is not None or lease_renew > 0 or ledger is not None
    )
    # --num is a PER-RUN cap, shared across chain rebuilds: a contained
    # task failure rebuilds the stage chain (runtime.process_stream),
    # which re-enters this generator — a budget local to one generator
    # instance would reset on every rebuild, letting a worker grind a
    # persistently-failing task until its receive count burns the whole
    # retry budget instead of handing it to another worker
    budget = {"left": num}

    def consume_budget() -> bool:
        """Count one claimed task; True when the run's budget is spent."""
        if budget["left"] < 0:
            return False  # -1: drain
        budget["left"] -= 1
        return budget["left"] <= 0

    @generator
    def stage(task):
        from chunkflow_tpu.flow.runtime import new_task
        from chunkflow_tpu.parallel.queues import open_queue

        try:
            import jax

            crosshost = jax.process_count() > 1
        except Exception:
            crosshost = False

        if crosshost:
            from chunkflow_tpu.parallel import multihost

            if not multihost.is_coordinator():
                # mirror loop: receive bboxes until the stop sentinel;
                # compute collectives run, writes/acks are skipped
                # (runtime.is_mirror_task)
                while True:
                    body = multihost.broadcast_string(None)
                    if body is None:
                        break
                    t = new_task()
                    t["bbox"] = BoundingBox.from_string(body)
                    t["replica_mirror"] = True
                    yield t
                return

        queue = open_queue(queue_name, visibility_timeout=visibility_timeout)
        queue.max_empty_retries = retry_times
        if poll_interval is not None:
            queue.retry_sleep = max(0.01, poll_interval)

        if supervised and not crosshost:
            from chunkflow_tpu.parallel import lifecycle

            if budget["left"] == 0:
                return  # rebuild after the last budgeted task: done
            supervisor = lifecycle.LifecycleSupervisor(
                queue,
                ledger=lifecycle.open_ledger(ledger) if ledger else None,
                max_retries=3 if max_retries is None else max_retries,
                lease_renew=lease_renew,
                backoff_base=backoff_base,
                backoff_cap=backoff_cap,
            )
            for lc in supervisor.tasks(num=-1):
                t = new_task()
                try:
                    # a malformed body is the canonical poison task:
                    # charge it (permanent → dead-letter), don't tear
                    # down the other in-flight tasks' budgets
                    t["bbox"] = BoundingBox.from_string(lc.body)
                except BaseException as exc:
                    lifecycle.tag_culprit(exc, lc)
                    raise
                t["queue"] = queue
                t["task_handle"] = lc.handle
                t["task_body"] = lc.body
                t["lifecycle"] = lc
                t["trace_id"] = lc.trace_id
                lc.task = t
                yield t
                if consume_budget():
                    return
            return
        if supervised and crosshost:
            print(
                "fetch-task-from-queue: lifecycle supervision does not "
                "compose with multi-host broadcast mode yet; running "
                "unsupervised", file=sys.stderr,
            )

        if budget["left"] == 0:
            return
        try:
            for handle, body in queue:
                if crosshost:
                    multihost.broadcast_string(body)
                t = new_task()
                t["bbox"] = BoundingBox.from_string(body)
                t["queue"] = queue
                t["task_handle"] = handle
                t["task_body"] = body
                t["trace_id"] = queue.trace_id(handle)
                yield t
                if consume_budget():
                    break
        finally:
            # sentinel on EVERY exit path — normal drain, --num cap,
            # downstream exception, generator close. A coordinator that
            # dies without broadcasting it would leave every peer blocked
            # forever inside the collective waiting for the next task.
            if crosshost:
                multihost.broadcast_string(None)

    return stage()


@main.command("delete-task-in-queue")
@name_option("delete-task-in-queue")
def delete_task_cmd(op_name, ):
    """Ack the current task: delete it from its queue (commit point)."""

    @operator
    def stage(task):
        from chunkflow_tpu.flow.runtime import drain_pending_writes

        lc = task.get("lifecycle")
        if lc is not None and not state.dry_run:
            # supervised task: the lifecycle commit is the ack — drain
            # writes, mark the completion ledger, delete from the queue,
            # stop the lease heartbeat (parallel/lifecycle.py)
            lc.commit(task)
            return task
        # the ack commits the task: every async write must be durable
        # first (--async-write saves attach futures to the task)
        drain_pending_writes(task)
        queue = task.get("queue")
        if queue is not None and not state.dry_run:
            queue.delete(task["task_handle"])
        return task

    return stage(_name=op_name)


@main.command("dead-letter")
@click.option("--queue-name", "-q", type=str, required=True)
@click.option("--requeue/--inspect", default=False,
              help="--requeue moves every dead-letter entry back to "
                   "pending with a fresh retry budget; default is a "
                   "read-only listing")
def dead_letter_cmd(queue_name, requeue):
    """Inspect or requeue a queue's dead-letter entries.

    Poison tasks land here after --max-retries failed attempts (or a
    permanent-class error), carrying their failure reason and delivery
    count — the operator triages, fixes the cause, and requeues
    (docs/fault_tolerance.md)."""

    @generator
    def stage(task):
        from chunkflow_tpu.parallel.queues import open_queue

        queue = open_queue(queue_name)
        entries = queue.dead_letters()
        if not entries:
            print(f"dead-letter store of {queue_name} is empty")
        else:
            print(f"{len(entries)} dead-letter task(s) in {queue_name}:")
            for entry in entries:
                trace = entry.get("trace_id")
                print(
                    f"  {entry.get('body', '')}  "
                    f"receives={entry.get('receives', 0)}  "
                    + (f"trace={trace}  " if trace else "")
                    + f"reason={entry.get('reason', '')}"
                )
        if requeue and not state.dry_run:
            n = queue.requeue_dead()
            print(f"requeued {n} task(s)")
        return
        yield  # pragma: no cover

    return stage()


@main.command("fleet-status")
@click.option("--queue-name", "-q", type=str, required=True)
@click.option("--workers", "-w", type=str, default=None,
              help="comma-separated worker /metrics endpoints "
                   "(host:port or full URLs) to sample live")
@click.option("--timeout", type=float, default=1.0,
              help="per-worker scrape timeout in seconds")
@click.option("--fleet-state", type=str, default=None,
              help="a fleet-run state file: its workers are sampled "
                   "too, and unreachable/dead ones report last-seen "
                   "time and exit code instead of a bare 'unreachable' "
                   "(default: fleet-state.json next to --metrics-dir)")
def fleet_status_cmd(queue_name, workers, timeout, fleet_state):
    """Live fleet dashboard: queue depth, in-flight leases, receive and
    dead-letter counts, plus each reachable worker's /healthz identity
    and a few headline /metrics samples — the same signal surface the
    fleet supervisor polls (docs/observability.md "Fleet view"). With a
    fleet-run state file (--fleet-state), supervisor-owned workers are
    included automatically and dead ones keep their post-mortem."""

    @generator
    def stage(task):
        import json
        import os
        import time as _time

        from chunkflow_tpu.core import telemetry
        from chunkflow_tpu.parallel.queues import open_queue
        from chunkflow_tpu.parallel.restapi import (
            achieved_mvox_s,
            scrape_worker,
        )

        queue = open_queue(queue_name)
        stats = queue.stats()

        def show(value):
            return "?" if value is None else f"{value:g}"

        print(
            f"queue {queue.describe()}: "
            f"pending={show(stats.get('pending'))} "
            f"in-flight={show(stats.get('inflight'))} "
            f"dead={show(stats.get('dead'))} "
            f"receives={show(stats.get('receives'))}"
        )
        if stats.get("dead"):
            print(
                "  -> dead-letter tasks pending triage: inspect with "
                f"`chunkflow dead-letter -q {queue_name}`"
            )

        # supervisor-owned workers from the fleet-run state file: the
        # post-mortem source for anything a live scrape cannot answer
        state_path = fleet_state
        if state_path is None and telemetry.configured_path():
            candidate = os.path.join(
                os.path.dirname(telemetry.configured_path()),
                "fleet-state.json")
            if os.path.exists(candidate):
                state_path = candidate
        records = {}
        if state_path:
            try:
                with open(state_path) as f:
                    fleet = json.load(f)
                for rec in fleet.get("workers", []):
                    if rec.get("endpoint"):
                        records[rec["endpoint"]] = rec
                print(
                    f"fleet {state_path}: target={fleet.get('target')} "
                    f"{'static' if fleet.get('static') else 'elastic'} "
                    f"[{fleet.get('min_workers')}..{fleet.get('max_workers')}]"
                )
            except (OSError, ValueError) as exc:
                print(f"fleet-state {state_path}: unreadable ({exc})",
                      file=sys.stderr)

        def age(t):
            return "never" if not t else f"{_time.time() - t:.1f}s ago"

        endpoints = [e.strip() for e in (workers or "").split(",")
                     if e.strip()]
        endpoints += [e for e in records if e not in endpoints]
        for endpoint in endpoints:
            rec = records.get(endpoint) or {}
            label = f" [{rec['worker']}]" if rec.get("worker") else ""
            if rec.get("state") == "exited":
                # supervisor-owned and already reaped: report the exit
                # code and last-seen time — no point scraping a corpse
                code = rec.get("exit_code")
                note = f"exit code {code}"
                if isinstance(code, int) and code < 0:
                    note += f" (signal {-code})"
                print(f"worker {endpoint}{label}: exited, {note}, "
                      f"last seen {age(rec.get('last_seen'))}")
                continue
            sample = scrape_worker(endpoint, timeout=timeout)
            if sample["error"] is not None:
                line = (f"worker {sample['endpoint']}{label}: "
                        f"unreachable ({sample['error']})")
                if rec:
                    line += (f", state={rec.get('state', '?')}, "
                             f"last seen {age(rec.get('last_seen'))}")
                print(line)
                continue
            health = sample["healthz"] or {}
            metrics = sample["metrics"] or {}
            committed = metrics.get("chunkflow_tasks_committed_total", 0)
            retried = metrics.get("chunkflow_tasks_retried_total", 0)
            dominant = metrics.get("chunkflow_stall_dominant_share")
            line = (
                f"worker {sample['endpoint']}{label}: "
                f"{health.get('worker', '?')} "
                f"leases={health.get('inflight_leases', '?')} "
                f"committed={committed:g} retried={retried:g}"
            )
            if dominant is not None:
                line += f" dominant-stall-share={dominant:.0%}"
            mvox = achieved_mvox_s(metrics)
            if mvox is not None:
                line += f" achieved={mvox:.2f} Mvox/s"
            if sample.get("slo_firing"):
                # out-of-spec workers lead with their firing objectives
                # (chunkflow_slo_*_firing gauges; docs/observability.md
                # "SLO view" — full detail on the worker's /alerts)
                line += (" SLO-FIRING: "
                         + ",".join(sample["slo_firing"]))
            print(line)
            serving = sample.get("serving")
            if serving:
                # the SERVING block: request-path health next to the
                # batch-path stats (docs/serving.md)
                def ms(value):
                    return ("?" if value is None
                            else f"{value * 1e3:.1f}ms")

                print(
                    f"  serving: in-flight={serving['inflight']:g} "
                    f"requests={serving['requests']:g} "
                    f"completed={serving['completed']:g} "
                    f"p50={ms(serving['p50_s'])} "
                    f"p99={ms(serving['p99_s'])} "
                    f"rejects={serving['rejects']:g} "
                    f"deadline-misses={serving['deadline_missed']:g}"
                )
        return
        yield  # pragma: no cover

    return stage()


@main.command("fleet-run")
@click.option("--queue-name", "-q", type=str, required=True)
@click.option("--worker-args", "-w", "worker_args_str", type=str,
              required=True,
              help="quoted pipeline stages each worker runs after its "
                   "supervised fetch stage, ending in "
                   "delete-task-in-queue — e.g. \"load-h5 -f in/ "
                   "inference ... save-h5 --file-name out/ "
                   "delete-task-in-queue\"")
@click.option("--min-workers", type=int, default=1)
@click.option("--max-workers", type=int, default=4)
@click.option("--interval", type=float, default=2.0,
              help="decision-tick interval in seconds")
@click.option("--scale-up-backlog", type=float, default=4.0,
              help="pending tasks per active worker above which a "
                   "compute-bound fleet grows by one worker per tick")
@click.option("--idle-ticks", type=int, default=3,
              help="consecutive idle ticks (pending=in-flight=0) "
                   "before draining back to --min-workers")
@click.option("--probe-misses", type=int, default=3,
              help="consecutive failed /healthz probes before a worker "
                   "is quarantined (SIGKILL + lease force-nack)")
@click.option("--term-grace", type=float, default=10.0,
              help="seconds a SIGTERM'd worker gets to nack and flush "
                   "before SIGKILL")
@click.option("--mem-watermark-gb", type=float, default=2.0,
              help="host MemAvailable floor: scale-up is held when one "
                   "more worker would dip below it")
@click.option("--drill-rate", type=float, default=0.0,
              help="spot-preemption drill: per-tick probability of "
                   "reclaiming a random live worker through the "
                   "SIGTERM path (prove preemption recovery "
                   "continuously; 0 disables)")
@click.option("--seed", type=int, default=None,
              help="seed for the drill/eviction rng (reproducible "
                   "drill runs)")
@click.option("--max-runtime", type=float, default=86400.0)
@click.option("--state-file", type=str, default=None,
              help="fleet-state JSON for fleet-status (default: "
                   "fleet-state.json under --metrics-dir)")
@click.option("--visibility-timeout", "-v", type=int, default=300)
@click.option("--retry-times", "-r", type=int, default=10,
              help="per-session empty-poll budget (drain sessions: an "
                   "idle worker flushes and exits; the supervisor "
                   "respawns while it owes the target size)")
@click.option("--poll-interval", type=float, default=1.0)
@click.option("--max-retries", type=int, default=10,
              help="failed-delivery budget per task. memory/file "
                   "queues hand preemption nacks back without charging "
                   "it; on SQS every delivery counts (ApproximateReceive"
                   "Count cannot be decremented), so size generously "
                   "for a drill-heavy fleet")
@click.option("--lease-renew", type=float, default=None,
              help="lease heartbeat interval (default: "
                   "visibility-timeout / 3)")
@click.option("--ledger", type=str, default=None,
              help="completion ledger passed to every worker "
                   "(REQUIRED for exactly-once effects under kills; "
                   "strongly recommended)")
def fleet_run_cmd(queue_name, worker_args_str, min_workers, max_workers,
                  interval, scale_up_backlog, idle_ticks, probe_misses,
                  term_grace, mem_watermark_gb, drill_rate, seed,
                  max_runtime, state_file, visibility_timeout,
                  retry_times, poll_interval, max_retries, lease_renew,
                  ledger):
    """Run an elastic, preemption-native worker fleet over a queue.

    Spawns supervised fetch-task-from-queue workers as subprocesses,
    scales them from live telemetry (queue depth, dominant stall,
    dead-letter rate) between --min-workers and --max-workers under a
    host-memory watermark, quarantines workers that stop answering
    /healthz (their leases are force-nacked so the fleet picks the work
    up immediately), drains gracefully on scale-down, and optionally
    runs spot-preemption drills. CHUNKFLOW_FLEET=0 pins a static size
    and bypasses the controller (docs/fault_tolerance.md "Running a
    fleet")."""
    import shlex

    @generator
    def stage(task):
        import os

        from chunkflow_tpu.core import telemetry
        from chunkflow_tpu.parallel.fleet import FleetSupervisor

        renew = (visibility_timeout / 3.0
                 if lease_renew is None else lease_renew)
        worker_args = [
            "fetch-task-from-queue", "-q", queue_name,
            "-v", str(visibility_timeout), "-r", str(retry_times),
            "--poll-interval", str(poll_interval),
            "--max-retries", str(max_retries),
            "--lease-renew", str(renew),
        ]
        if ledger:
            worker_args += ["--ledger", ledger]
        worker_args += shlex.split(worker_args_str)
        metrics_dir = (
            os.path.dirname(telemetry.configured_path())
            if telemetry.configured_path() else None
        )
        supervisor = FleetSupervisor(
            queue_name, worker_args,
            min_workers=min_workers, max_workers=max_workers,
            interval=interval, scale_up_backlog=scale_up_backlog,
            idle_ticks=idle_ticks, probe_misses=probe_misses,
            term_grace=term_grace, mem_watermark_gb=mem_watermark_gb,
            drill_rate=drill_rate, seed=seed, metrics_dir=metrics_dir,
            state_path=state_file,
            visibility_timeout=visibility_timeout,
        )
        summary = supervisor.run(max_runtime=max_runtime)
        print(
            f"fleet drained: {summary['spawned']} worker session(s), "
            f"{summary['scale_ups']:g} scale-up(s), "
            f"{summary['scale_downs']:g} scale-down(s), "
            f"{summary['evictions']:g} eviction(s), "
            f"{summary['worker_deaths']:g} unexpected death(s), "
            f"{summary['drill_preemptions']:g} drill preemption(s)"
            + (" [static]" if summary["static"] else "")
        )
        if supervisor.state_path:
            print(f"fleet state: {supervisor.state_path}")
        return
        yield  # pragma: no cover

    return stage()


@main.command("serve")
@click.option("--port", type=int, default=0,
              help="HTTP listener port; 0 (default) binds an ephemeral "
                   "port and prints it — multiple servers on one host "
                   "never collide")
@click.option("--host", type=str, default="0.0.0.0")
@cartesian_option("--input-patch-size", "-p", "-s", default=None,
                  help="required unless --spool (external workers own "
                       "the model there)")
@cartesian_option("--output-patch-size", "-z", default=None)
@cartesian_option("--output-patch-overlap", default=(0, 0, 0))
@click.option("--num-output-channels", "-c", type=int, default=3)
@click.option("--num-input-channels", type=int, default=1)
@click.option(
    "--framework", "-f",
    type=click.Choice(["identity", "flax", "jax", "pytorch", "universal"]),
    default="flax",
)
@click.option("--model-path", "-m", type=str, default="")
@click.option("--weight-path", "-w", type=str, default=None)
@click.option("--batch-size", "-b", type=int, default=4)
@click.option("--output-dtype",
              type=click.Choice(["float32", "bfloat16", "uint8"]),
              default="float32")
@click.option("--crop-output-margin/--no-crop-output-margin", default=True)
@cartesian_option("--shape-bucket", default=None,
                  help="bucket request shapes so ragged traffic shares "
                       "compiled programs (strongly recommended for "
                       "mixed-size serving)")
@click.option("--serve-workers", type=int, default=2,
              help="in-process lifecycle worker threads claiming "
                   "requests (local mode)")
@click.option("--max-inflight", type=int, default=8,
              help="admission control: concurrent requests past this "
                   "are rejected 429, not queued to death")
@click.option("--default-deadline-s", type=float, default=30.0,
              help="per-request deadline when the request does not "
                   "carry one; a missed deadline is a clean 504 + "
                   "serving/deadline_missed, never worker death")
@click.option("--max-retries", type=int, default=2,
              help="lifecycle retry budget per request (transient "
                   "compute failures retry with backoff; past the "
                   "budget the request dead-letters and fails cleanly)")
@click.option("--max-wait-ms", type=float, default=2.0,
              help="how long a partial device batch waits for more "
                   "cross-request patches before dispatching underfull "
                   "(the latency/occupancy knob, docs/serving.md)")
@click.option("--spool", type=str, default=None,
              help="spool-mode serving: requests land in <dir>/in + a "
                   "file queue and EXTERNAL supervised workers complete "
                   "them (preemptible, fleet-scalable); this process "
                   "serves HTTP only")
@click.option("--visibility-timeout", "-v", type=int, default=30,
              help="request lease timeout: a worker (thread or "
                   "process) that dies mid-request loses the lease and "
                   "the request is redelivered")
@click.option("--max-runtime", type=float, default=None,
              help="exit after this many seconds (tests/drills); "
                   "default: run until SIGTERM/SIGINT")
def serve_cmd(port, host, input_patch_size, output_patch_size,
              output_patch_overlap, num_output_channels,
              num_input_channels, framework, model_path, weight_path,
              batch_size, output_dtype, crop_output_margin, shape_bucket,
              serve_workers, max_inflight, default_deadline_s,
              max_retries, max_wait_ms, spool, visibility_timeout,
              max_runtime):
    """Serve ``POST /infer`` requests with continuous cross-request
    patch batching (docs/serving.md).

    Each request is a TASK: leased, retried on transient failures,
    committed exactly once through a completion ledger
    (docs/fault_tolerance.md), and its patches share fixed device
    batches with every other in-flight request's
    (chunkflow_tpu/serve/packer.py). Admission control and per-request
    deadlines shed overload as clean 429/504 responses; backpressure is
    the adaptive scheduler's host-memory watermark
    (CHUNKFLOW_SCHED_MEM_GB). ``/metrics``, ``/healthz`` and
    ``/profile`` ride the same listener. CHUNKFLOW_SERVE=0 disables the
    packer (requests run the per-chunk path, bit-identically)."""

    @generator
    def stage(task):
        import os
        import time as _time

        from chunkflow_tpu.core import telemetry
        from chunkflow_tpu.parallel.restapi import (
            bound_port,
            write_endpoint_file,
        )
        from chunkflow_tpu.serve.frontend import (
            AdmissionController,
            LocalBackend,
            ServingService,
            SpoolBackend,
            start_serving,
        )

        if spool is None:
            if input_patch_size is None or not any(input_patch_size):
                raise click.UsageError(
                    "serve needs --input-patch-size (or --spool for "
                    "external-worker mode)")
            from chunkflow_tpu.inference import Inferencer

            inferencer = Inferencer(
                input_patch_size=input_patch_size,
                output_patch_size=(
                    output_patch_size
                    if output_patch_size and any(output_patch_size)
                    else None),
                output_patch_overlap=output_patch_overlap,
                num_output_channels=num_output_channels,
                num_input_channels=num_input_channels,
                framework=framework,
                model_path=model_path,
                weight_path=weight_path,
                batch_size=batch_size,
                output_dtype=output_dtype,
                crop_output_margin=crop_output_margin,
                shape_bucket=shape_bucket,
                dry_run=state.dry_run,
            )
            backend = LocalBackend(
                inferencer, workers=serve_workers, max_retries=max_retries,
                max_wait_ms=max_wait_ms,
                visibility_timeout=visibility_timeout,
            )
        else:
            backend = SpoolBackend(
                spool, visibility_timeout=visibility_timeout)
        admission = AdmissionController(max_inflight=max_inflight)
        service = ServingService(
            backend, admission=admission,
            default_deadline_s=default_deadline_s,
        )
        server = start_serving(service, host=host, port=port)
        actual = bound_port(server)
        # port 0 is the default: ALWAYS report where we landed, and
        # publish it next to the telemetry stream for supervisors
        print(f"serving: http://{host}:{actual}/infer "
              f"(mode={'spool' if spool else 'local'})", flush=True)
        if telemetry.configured_path():
            write_endpoint_file(
                os.path.dirname(telemetry.configured_path()),
                serving_port=actual)
        deadline = (
            _time.time() + max_runtime if max_runtime is not None
            else None)
        try:
            while deadline is None or _time.time() < deadline:
                _time.sleep(0.2)
        except (KeyboardInterrupt, SystemExit):
            print("serve: draining on preemption signal", flush=True)
        finally:
            # graceful drain: stop admitting, finish in-flight, then
            # close the listener — rejected requests saw clean 429s
            admission.drain()
            server.shutdown()
            server.server_close()
            backend.close()
            stats = service.serving_stats()
            print(
                f"serve drained: {stats['requests']:g} request(s), "
                f"{stats['completed']:g} completed, "
                f"{stats['rejected_admission'] + stats['rejected_memory']:g}"
                f" rejected, {stats['deadline_missed']:g} deadline "
                f"miss(es), {stats['errors']:g} error(s)")
        return
        yield  # pragma: no cover

    return stage()


# ---------------------------------------------------------------------------
# chunk creation / I/O
# ---------------------------------------------------------------------------
@main.command("create-chunk")
@name_option("create-chunk")
@cartesian_option("--size", "-s", default=(64, 64, 64))
@click.option("--dtype", type=str, default="uint8")
@click.option("--pattern", type=click.Choice(["sin", "random", "zero"]), default="sin")
@cartesian_option("--voxel-offset", "-t", default=(0, 0, 0))
@cartesian_option("--voxel-size", default=(1, 1, 1))
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def create_chunk_cmd(op_name, size, dtype, pattern, voxel_offset, voxel_size, output_chunk_name):
    """Create a synthetic chunk (sin/random/zero pattern)."""

    @operator
    def stage(task):
        task[output_chunk_name] = Chunk.create(
            size=size,
            dtype=np.dtype(dtype),
            pattern=pattern,
            voxel_offset=voxel_offset,
            voxel_size=voxel_size,
        )
        return task

    return stage(_name=op_name)


@main.command("load-h5")
@name_option("load-h5")
@click.option("--file-name", "-f", type=str, required=True,
              help=".h5 path, or a prefix completed as <prefix><bbox>.h5")
@click.option("--dataset-path", "-d", type=str, default="main")
@click.option("--dtype", "-e", type=str, default=None)
@click.option("--layer-type", "-l",
              type=click.Choice(["image", "segmentation"]), default=None)
@cartesian_option("--voxel-offset", "-v", default=None)
@cartesian_option("--voxel-size", "-x", default=None)
@click.option("--channels", "-c", type=str, default=None,
              help="comma-separated channel indices to keep")
@cartesian_option("--cutout-start", "-t", default=None)
@cartesian_option("--cutout-stop", "-p", default=None)
@cartesian_option("--cutout-size", "-s", default=None)
@click.option("--set-bbox/--no-set-bbox", default=False,
              help="publish the loaded chunk's bbox as the task bbox")
@click.option("--remove-empty/--do-not-remove", default=False,
              help="delete the file when the loaded chunk is all zero")
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def load_h5_cmd(op_name, file_name, dataset_path, dtype, layer_type,
                voxel_offset, voxel_size, channels, cutout_start,
                cutout_stop, cutout_size, set_bbox, remove_empty,
                output_chunk_name):
    """Read an HDF5 chunk (reference flow.py:976-1066 surface)."""
    import os

    if cutout_start is not None:
        if cutout_stop is not None:
            cutout = BoundingBox(cutout_start, cutout_stop)
        elif cutout_size is not None:
            cutout = BoundingBox.from_delta(cutout_start, cutout_size)
        else:
            raise click.UsageError(
                "--cutout-start needs --cutout-stop or --cutout-size"
            )
    else:
        cutout = None

    @operator
    def stage(task):
        # an explicit cutout beats the task bbox (reference :1022-1033)
        bbox = cutout if cutout is not None else task.get("bbox")
        path = file_name
        if not path.endswith(".h5") and bbox is not None:
            path = _h5_task_path(path, bbox)
        chunk = Chunk.from_h5(
            path,
            dataset_path=dataset_path,
            voxel_offset=voxel_offset,
            voxel_size=voxel_size,
            bbox=bbox,
            dtype=np.dtype(dtype) if dtype else None,
            channels=channels,
        )
        if layer_type is not None:
            chunk.layer_type = LayerType(layer_type)
        if (remove_empty and not state.dry_run
                and not np.any(np.asarray(chunk.array))):
            print(f"remove empty {path}")
            os.remove(path)
        task[output_chunk_name] = chunk
        if set_bbox:
            task["bbox"] = chunk.bbox
        return task

    return stage(_name=op_name)


@main.command("save-h5")
@name_option("save-h5")
@click.option("--file-name", "-f", type=str, default=None,
              help=".h5 path, or a prefix completed as <prefix><bbox>.h5")
@click.option("--file-name-prefix", type=str, default=None,
              help="write one file per task: <prefix><bbox-string>.h5")
@cartesian_option("--chunk-size", "-s", default=None,
                  help="HDF5 dataset chunking (compression block shape)")
@click.option("--compression", "-c",
              type=click.Choice(["gzip", "lzf", "szip"]), default="gzip")
@click.option("--with-offset/--without-offset", default=True,
              help="write the voxel_offset sidecar dataset")
@cartesian_option("--voxel-size", "-v", default=None,
                  help="override the chunk's voxel size on write")
@click.option("--dtype", "-d", type=str, default=None,
              help="convert before writing")
@click.option("--input-chunk-name", "--input-name", "-i", type=str,
              default=DEFAULT_CHUNK_NAME)
def save_h5_cmd(op_name, file_name, file_name_prefix, chunk_size, compression,
                with_offset, voxel_size, dtype, input_chunk_name):
    if (file_name is None) == (file_name_prefix is None):
        raise click.UsageError(
            "save-h5 needs exactly one of --file-name / --file-name-prefix"
        )

    @write_operator
    def stage(task):
        chunk = task[input_chunk_name]
        if dtype is not None:
            chunk = chunk.astype(np.dtype(dtype))
        if voxel_size is not None:
            chunk = chunk.with_voxel_size(voxel_size)
        if file_name_prefix is not None:
            path = _h5_task_path(file_name_prefix, task.get("bbox") or chunk.bbox)
        elif not file_name.endswith(".h5"):
            # reference behavior: a non-.h5 --file-name is a prefix
            path = _h5_task_path(file_name, task.get("bbox") or chunk.bbox)
        else:
            path = file_name
        chunk.to_h5(
            path, compression=compression, chunk_size=chunk_size,
            with_offset=with_offset,
        )
        return task

    return stage(_name=op_name)


@main.command("load-tif")
@name_option("load-tif")
@click.option("--file-name", "-f", type=str, required=True)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
@cartesian_option("--voxel-offset", "-v", default=(0, 0, 0))
@cartesian_option("--voxel-size", "-s", default=None)
@click.option("--layer-type", "-l",
              type=click.Choice(["image", "segmentation"]), default=None)
@click.option("--dtype", "-d", type=str, default=None)
def load_tif_cmd(op_name, file_name, output_chunk_name, voxel_offset,
                 voxel_size, layer_type, dtype):
    @operator
    def stage(task):
        chunk = Chunk.from_tif(
            file_name,
            voxel_offset=voxel_offset,
            voxel_size=voxel_size,
            dtype=np.dtype(dtype) if dtype else None,
        )
        if layer_type is not None:
            chunk.layer_type = LayerType(layer_type)
        task[output_chunk_name] = chunk
        return task

    return stage(_name=op_name)


@main.command("save-tif")
@name_option("save-tif")
@click.option("--file-name", "-f", type=str, required=True)
@click.option("--dtype", "-d", type=str, default=None,
              help="convert before writing")
@click.option("--compression", type=str, default="zlib",
              help="tifffile compression codec")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
def save_tif_cmd(op_name, file_name, dtype, compression, input_chunk_name):
    @write_operator
    def stage(task):
        chunk = task[input_chunk_name]
        if dtype is not None:
            chunk = chunk.astype(np.dtype(dtype))
        chunk.to_tif(file_name, compression=compression)
        return task

    return stage(_name=op_name)


# ---------------------------------------------------------------------------
# precomputed volumes
# ---------------------------------------------------------------------------
@main.command("create-info")
@name_option("create-info")
@click.option("--volume-path", "-v", type=str, required=True)
@cartesian_option("--volume-size", "-s", default=None)
@cartesian_option("--voxel-size", default=(1, 1, 1))
@cartesian_option("--voxel-offset", default=(0, 0, 0))
@click.option("--num-channels", "--channel-num", "-c", type=int, default=1)
@click.option("--dtype", "--data-type", type=str, default="uint8")
@click.option("--encoding", "-e", type=str, default="raw",
              help="block encoding written to the info file")
@click.option("--input-chunk-name", "-i", type=str, default=None,
              help="derive size/offset/dtype/voxel-size defaults from this "
                   "chunk in the task (reference flow.py:459-519)")
@click.option("--layer-type", type=click.Choice(["image", "segmentation"]), default="image")
@cartesian_option("--block-size", default=(64, 64, 64))
@click.option("--max-mip", type=int, default=0)
@cartesian_option("--factor", default=(1, 2, 2))
def create_info_cmd(op_name, volume_path, volume_size, voxel_size, voxel_offset,
                    num_channels, dtype, encoding, layer_type, block_size,
                    max_mip, factor, input_chunk_name):
    """Create a precomputed volume info file (with mip pyramid)."""
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    @operator
    def stage(task):
        size, vsize, voffset, dt, nchan = (
            volume_size, voxel_size, voxel_offset, dtype, num_channels
        )
        if input_chunk_name is not None:
            # the chunk supplies DEFAULTS; explicit options always win
            chunk = task[input_chunk_name]
            if size is None:
                size = tuple(chunk.shape[-3:])
            if tuple(voffset) == (0, 0, 0):
                voffset = tuple(chunk.voxel_offset)
            if tuple(vsize) == (1, 1, 1):
                vsize = tuple(chunk.voxel_size)
            if dt == "uint8":
                dt = str(np.dtype(chunk.dtype))
            if nchan == 1:
                nchan = chunk.nchannels
        if size is None:
            raise click.UsageError(
                "create-info needs --volume-size or --input-chunk-name"
            )
        PrecomputedVolume.create(
            volume_path,
            volume_size=size,
            voxel_size=vsize,
            voxel_offset=voffset,
            num_channels=nchan,
            dtype=dt,
            layer_type=layer_type,
            encoding=encoding,
            block_size=block_size,
            num_mips=max_mip + 1,
            downsample_factor=factor,
        )
        return task

    return stage(_name=op_name)


@main.command("load-precomputed")
@name_option("load-precomputed")
@click.option("--volume-path", "-v", type=str, required=True)
@click.option("--mip", type=int, default=None, help="defaults to global --mip")
@cartesian_option("--expand-margin-size", "-e", default=(0, 0, 0))
@cartesian_option("--chunk-start", "-s", default=None,
                  help="cut this explicit box instead of the task bbox")
@cartesian_option("--chunk-size", "-z", default=None,
                  help="with --chunk-start: the box extent")
@click.option("--fill-missing/--no-fill-missing", default=True)
@click.option("--blackout-sections/--no-blackout-sections", default=False,
              help="zero z-sections listed in the volume's blackout_section_ids.json")
@click.option("--validate-mip", type=int, default=None,
              help="cross-check the cutout against a re-download at this coarser mip")
@click.option("--validate-tolerance", type=float, default=0.01,
              help="max relative mean |pooled - coarse| before the task fails "
              "(the reference asserts exact equality; >0 tolerates pyramid "
              "rounding)")
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def load_precomputed_cmd(op_name, volume_path, mip, expand_margin_size,
                         chunk_start, chunk_size, fill_missing,
                         blackout_sections, validate_mip, validate_tolerance,
                         output_chunk_name):
    """Cut out the task bbox (plus margins) from a precomputed volume.

    Reference parity: LoadPrecomputedOperator incl. bad-section blackout
    (load_precomputed.py:99-113), cross-mip re-download validation
    (load_precomputed.py:115-182), and explicit --chunk-start/--chunk-size
    boxes (flow.py:1185-1191)."""
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    vol = PrecomputedVolume(volume_path)
    use_explicit = chunk_start is not None or chunk_size is not None

    def explicit_bbox(mip):
        # reference semantics (flow.py:1234-1243): a missing start/size
        # defaults from the volume's bounds at this mip
        bounds = vol.bounds(mip)
        start = chunk_start if chunk_start is not None else tuple(bounds.start)
        size = (
            chunk_size if chunk_size is not None
            else tuple(bounds.stop - to_cartesian(start))
        )
        return BoundingBox.from_delta(start, size)

    @operator
    def stage(task):
        the_mip_ = mip if mip is not None else state.mip
        # the task's own bbox wins (reference flow.py:1228-1232); the
        # explicit box is the no-task-grid fallback
        bbox = (
            task["bbox"] if task.get("bbox") is not None
            else explicit_bbox(the_mip_) if use_explicit
            else None
        )
        if bbox is None:
            raise click.UsageError(
                "no task bbox: run after generate-tasks/fetch-task, or "
                "give --chunk-start/--chunk-size"
            )
        if expand_margin_size and any(expand_margin_size):
            bbox = bbox.adjust(expand_margin_size)
        the_mip = the_mip_
        chunk = vol.cutout(bbox, mip=the_mip, fill_missing=fill_missing)
        # validate the RAW cutout; blackout intentionally zeroes data and
        # must not trigger mismatch warnings
        if validate_mip is not None and not state.dry_run:
            _validate_cutout(
                vol, chunk, the_mip, validate_mip, validate_tolerance
            )
        if blackout_sections:
            sidecar = vol.read_json("blackout_section_ids.json") or {}
            z0 = int(chunk.voxel_offset.z)
            nz = chunk.shape[-3]
            for z in sidecar.get("section_ids", ()):
                if z0 <= z < z0 + nz:
                    chunk[..., z - z0, :, :] = 0
        task[output_chunk_name] = chunk
        return task

    return stage(_name=op_name)


def _validate_cutout(vol, chunk, mip, validate_mip, tolerance=0.01):
    """Mean-pool the cutout to ``validate_mip`` and compare with a direct
    coarse-mip read of the same window; fail the task on mismatch.

    The reference asserts exact equality after pooling
    (load_precomputed.py:115-182); a small default tolerance absorbs
    pyramid rounding while still catching the corrupted / partially-black
    cutouts this check exists for."""
    from chunkflow_tpu.core.bbox import BoundingBox
    from chunkflow_tpu.ops.downsample import downsample_average

    if not (mip < validate_mip < vol.num_mips):
        raise ValueError(
            f"--validate-mip {validate_mip} must be coarser than the load "
            f"mip {mip} and exist in the volume ({vol.num_mips} mips)"
        )
    factor = tuple(
        int(c // f)
        for c, f in zip(vol.voxel_size(validate_mip), vol.voxel_size(mip))
    )
    # crop to a window whose offset AND extent are factor-aligned, so the
    # pooled grid coincides exactly with the coarse mip's voxel grid
    offset = tuple(int(o) for o in chunk.voxel_offset)
    skip = tuple((-o) % f for o, f in zip(offset, factor))
    spatial = chunk.shape[-3:]
    aligned = tuple(
        (s - k) - (s - k) % f for s, k, f in zip(spatial, skip, factor)
    )
    if any(a < f for a, f in zip(aligned, factor)):
        return  # window too small to compare
    sub = chunk.cutout(BoundingBox(
        tuple(o + k for o, k in zip(offset, skip)),
        tuple(o + k + a for o, k, a in zip(offset, skip, aligned)),
    ))
    pooled = downsample_average(sub, factor=factor)
    ref = vol.cutout(pooled.bbox, mip=validate_mip, fill_missing=True)
    a = np.asarray(pooled.array, dtype=np.float64)
    b = np.asarray(ref.array, dtype=np.float64)
    err = float(np.abs(a - b).mean())
    scale = max(float(np.abs(b).mean()), 1e-6)
    if err / scale > tolerance:
        import logging

        msg = (
            f"cross-mip validation mismatch (mip {mip} vs {validate_mip}): "
            f"mean|diff|={err:.4f} vs mean|ref|={scale:.4f} "
            f"(relative {err / scale:.4f} > tolerance {tolerance})"
        )
        logging.warning(msg)
        raise ValueError(msg)


@main.command("save-precomputed")
@name_option("save-precomputed")
@click.option("--volume-path", "-v", type=str, required=True)
@click.option("--mip", type=int, default=None)
@click.option("--upload-log/--no-upload-log", default=True)
@click.option("--create-thumbnail/--no-create-thumbnail", default=False)
@click.option("--intensity-threshold", type=float, default=None,
              help="skip the write when the chunk's max intensity is below "
                   "this (reference flow.py:2286-2309: don't waste storage "
                   "on near-empty chunks)")
@click.option("--parallel", type=int, default=1,
              help="accepted for reference compatibility; tensorstore "
                   "already writes blocks concurrently")
@click.option("--async-write/--sync-write", default=False,
              help="don't block on the storage commit: the write future "
                   "rides the task and is drained before the task ack "
                   "(delete-task-in-queue / mark-complete / pipeline "
                   "end), so ack-after-durable-write still holds while "
                   "the next task's compute overlaps this task's upload")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
def save_precomputed_cmd(op_name, volume_path, mip, upload_log, create_thumbnail,
                         intensity_threshold, parallel, async_write,
                         input_chunk_name):
    """Write the chunk to a precomputed volume (+ thumbnail pyramid in
    the sibling ``thumbnail`` layer, + timing log sidecar)."""
    import contextvars
    import json
    from concurrent.futures import ThreadPoolExecutor

    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    vol = PrecomputedVolume(volume_path)
    # an output volume without the layer is found now, not a task in
    thumbnails = vol.thumbnail_layer() if create_thumbnail else None
    thumbnail_maker = ThreadPoolExecutor(
        1, thread_name_prefix="thumbnail") if create_thumbnail else None

    def make_thumbnail(chunk, base):
        """The chunk (at mip ``base``) as grey, pooled by (1, 2, 2) level
        after level up to the thumbnail layer's last mip. Made where the
        chunk is: nothing of it goes to the device."""
        from chunkflow_tpu.chunk import AffinityMap
        from chunkflow_tpu.ops.downsample import average_pyramid

        on_device = int(chunk.is_on_device)
        telemetry.inc("thumbnail/h2d_bytes", 0)
        if chunk.ndim == 4:
            with telemetry.span("thumbnail/quantize", device=on_device):
                chunk = AffinityMap(
                    chunk.array,
                    voxel_offset=chunk.voxel_offset,
                    voxel_size=chunk.voxel_size,
                ).quantize(mode="xy")
        n_levels = thumbnails.num_mips - 1 - base
        with telemetry.span("thumbnail/downsample", levels=n_levels,
                            device=on_device):
            return average_pyramid(chunk, (1, 2, 2), n_levels)

    def write_thumbnail(levels, base, writes):
        """The levels into the mips above ``base`` of the thumbnail layer,
        each at the chunk's box divided by its level's factor (reference
        save_precomputed.py:104-139)."""
        with telemetry.span("thumbnail/write") as sp:
            blocks = nbytes = 0
            for level, down in enumerate(levels, start=base + 1):
                # a level is up to a thousand blocks of a few KB: one
                # driver write, not a future and a cache copy a block
                writes.append(thumbnails.save(
                    down, mip=level, wait=not async_write,
                    per_block=False))
                blocks += thumbnails.block_count(down.bbox, level)
                nbytes += int(down.array.nbytes)
            sp.annotate(blocks=blocks, bytes=nbytes)
        telemetry.inc("thumbnail/blocks_written", blocks)

    @write_operator
    def stage(task):
        chunk = task[input_chunk_name]
        if state.dry_run:
            return task
        thr = intensity_threshold
        if thr is not None and thr < 1.0 and np.dtype(chunk.dtype) == np.uint8:
            # thresholds are tuned for [0,1] float probabilities; with
            # --output-dtype uint8 the data arrives 0-255, so an
            # unscaled threshold would never trigger the skip. Exactly
            # 1.0 is treated as an absolute threshold (skip only
            # all-zero uint8 chunks), not rescaled to 255.
            thr = thr * 255.0
            print(f"intensity threshold rescaled to {thr} for uint8 chunk")
        if (thr is not None
                # reduce on device when HBM-resident: only the scalar
                # crosses D2H (np.asarray would pull the whole chunk)
                and float(chunk.array.max()) < thr):
            print(f"skip save: max intensity below {thr}")
            return task
        # the result, then its thumbnail, then the log: each is waited
        # for here, or rides the task to the barrier in front of the ack
        # (--async-write), where they are drained in this order
        base = mip if mip is not None else state.mip
        making = None
        if create_thumbnail and not chunk.is_on_device:
            # a host chunk's thumbnail is made beside its write, on a
            # thread of its own under the task's context: numpy and the
            # store's copy of the chunk both leave the interpreter's
            # lock, and in series the two outlast a task's device time
            making = thumbnail_maker.submit(
                contextvars.copy_context().run, make_thumbnail, chunk, base)
        writes = [vol.save(chunk, mip=base, wait=not async_write)]
        if create_thumbnail:
            levels = (making.result() if making is not None
                      else make_thumbnail(chunk, base))
            write_thumbnail(levels, base, writes)
        writes = [w for w in writes if w is not None]
        if upload_log:
            log = _LogWrite(vol, f"log/{chunk.bbox.string}.json", json.dumps({
                "timer": task["log"]["timer"],
                "compute_device": task["log"].get("compute_device", ""),
                "bbox": chunk.bbox.string,
            }).encode(), after=writes)
            if async_write:
                writes.append(log)
            else:
                log.result()
        if writes:
            task.setdefault("pending_writes", []).extend(writes)
        return task

    return stage(_name=op_name)


class _LogWrite:
    """The task's timing log, ``<volume>/log/<bbox>.json`` (reference
    save_precomputed.py:141-150), as a write of the task: made once the
    writes before it are durable, so a log beside a volume says that the
    task's blocks are there. Made at ``result()``: at once after waited
    writes, else by the barrier in front of the ack (--async-write)."""

    def __init__(self, vol, name: str, data: bytes, after: list):
        self._vol, self._name, self._data = vol, name, data
        self._after = list(after)

    def result(self):
        if self._data is None:
            return None
        for write in self._after:
            write.result()
        from chunkflow_tpu.core import telemetry

        with telemetry.span("storage/log_write", bytes=len(self._data)):
            self._vol.kv.write_bytes(self._name, self._data)
        self._data = None
        return None


@main.command("log-summary")
@click.option("--log-dir", "-l", type=str, default=None,
              help="legacy per-task JSON logs (save-precomputed sidecars)")
@click.option("--metrics-dir", "summary_metrics_dir", type=str, default=None,
              help="telemetry JSONL dir (--metrics-dir of a previous run): "
                   "per-phase stall breakdown, ring occupancy, cache "
                   "builds/hits")
@click.option("--fleet/--no-fleet", default=False,
              help="merge multi-worker JSONL by worker identity: "
                   "per-worker dominant stall, retries, ledger skips, "
                   "cache hit rates (docs/observability.md \"Fleet view\")")
@click.option("--trace-id", type=str, default=None,
              help="with --fleet: also print this task's merged "
                   "cross-worker timeline (submit → claim(s) → retries → "
                   "commit/dead-letter)")
@click.option("--slo/--no-slo", "slo_view", default=False,
              help="print the SLO block: alert timeline with burn-rate/"
                   "budget attributes, per-objective fleet state, and "
                   "sparkline timelines fleet-merged from the JSONL "
                   "timeseries events (docs/observability.md \"SLO "
                   "view\") — reconstructable after every worker died")
@click.option("--export-trace", "export_trace", type=str, default=None,
              metavar="OUT.JSON",
              help="convert the merged telemetry JSONL into a Chrome/"
                   "Perfetto trace-event file: workers as processes, "
                   "spans as slices, gauges/counters as counter tracks, "
                   "cross-worker task hops as trace_id flow arrows — "
                   "load it at ui.perfetto.dev (docs/observability.md "
                   "\"Timeline view\")")
@cartesian_option("--output-size", default=None)
def log_summary_cmd(log_dir, summary_metrics_dir, fleet, trace_id,
                    slo_view, export_trace, output_size):
    """Aggregate per-task timing logs and/or telemetry JSONL into a
    throughput + stall-attribution report."""
    from chunkflow_tpu.flow.log_summary import (
        print_fleet_summary,
        print_slo_summary,
        print_summary,
        print_telemetry_summary,
    )

    if log_dir is None and summary_metrics_dir is None:
        raise click.UsageError(
            "log-summary needs --log-dir and/or --metrics-dir"
        )
    if (fleet or trace_id or slo_view or export_trace) \
            and summary_metrics_dir is None:
        raise click.UsageError(
            "log-summary --fleet/--trace-id/--slo/--export-trace needs "
            "--metrics-dir"
        )

    @generator
    def stage(task):
        if log_dir is not None:
            print_summary(
                log_dir,
                output_size=output_size if output_size and any(output_size)
                else None,
            )
        if summary_metrics_dir is not None:
            if fleet or trace_id:
                print_fleet_summary(summary_metrics_dir, trace_id=trace_id)
            elif not slo_view and not export_trace:
                print_telemetry_summary(summary_metrics_dir)
            if slo_view:
                print_slo_summary(summary_metrics_dir)
            if export_trace:
                try:
                    from tools.trace_export import export_metrics_dir
                except ImportError:
                    raise click.UsageError(
                        "--export-trace needs the repo's tools/ package "
                        "on sys.path (run from the repository root)"
                    )
                stats = export_metrics_dir(summary_metrics_dir,
                                           export_trace)
                print(
                    f"exported {stats['trace_events']} trace event(s) "
                    f"({stats['workers']} worker process(es), "
                    f"{stats['flow_pairs']} cross-worker flow(s)) to "
                    f"{export_trace}"
                )
                for problem in stats["problems"]:
                    print(f"trace validation: {problem}")
        return
        yield  # pragma: no cover

    return stage()


# ---------------------------------------------------------------------------
# annotations / misc I/O
# ---------------------------------------------------------------------------
@main.command("load-synapses")
@name_option("load-synapses")
@click.option("--file-name", "--file-path", "-f", type=str, required=True,
              help=".json/.h5 file, or a directory with --suffix")
@click.option("--suffix", "-s", type=str, default=".h5",
              help="with a directory --file-path: load <dir>/<bbox><suffix>")
@cartesian_option("--resolution", default=None,
                  help="override the synapses' voxel size (nm)")
@click.option("--output-name", "-o", type=str, default="synapses")
def load_synapses_cmd(op_name, file_name, suffix, resolution, output_name):
    import os

    from chunkflow_tpu.annotations.synapses import Synapses

    @operator
    def stage(task):
        path = file_name
        if os.path.isdir(path):
            if task.get("bbox") is None:
                raise click.UsageError(
                    "directory --file-path needs a task bbox"
                )
            path = os.path.join(path, f"{task['bbox'].string}{suffix}")
        synapses = Synapses.from_file(path)
        if resolution is not None:
            synapses.resolution = to_cartesian(resolution)
        if task.get("bbox") is not None:
            synapses = synapses.filter_by_bbox(task["bbox"])
        task[output_name] = synapses
        return task

    return stage(_name=op_name)


@main.command("save-synapses")
@name_option("save-synapses")
@click.option("--file-name", "--file-path", "-f", type=str, required=True)
@click.option("--input-name", "-i", type=str, default="synapses")
def save_synapses_cmd(op_name, file_name, input_name):
    @write_operator
    def stage(task):
        task[input_name].to_file(file_name)
        return task

    return stage(_name=op_name)


@main.command("save-points")
@name_option("save-points")
@click.option("--file-name", "--file-path", "-f", type=str, required=True, help=".h5 or .npy")
@click.option("--input-name", "-i", type=str, default="points")
def save_points_cmd(op_name, file_name, input_name):
    from chunkflow_tpu.annotations.point_cloud import PointCloud

    @write_operator
    def stage(task):
        points = task[input_name]
        if not isinstance(points, PointCloud):
            points = PointCloud(np.asarray(points))
        if file_name.endswith(".npy"):
            points.to_npy(file_name)
        else:
            points.to_h5(file_name)
        return task

    return stage(_name=op_name)


@main.command("load-skeleton")
@name_option("load-skeleton")
@click.option("--file-name", "--path", "-f", type=str, required=True, help=".swc file")
@cartesian_option("--voxel-offset", "--offset", default=None,
                  help="shift node coordinates by this voxel offset")
@cartesian_option("--voxel-size", default=None,
                  help="scale voxel-offset shifts into nm (default 1nm)")
@click.option("--output-name", "-o", type=str, default="skeleton")
def load_skeleton_cmd(op_name, file_name, voxel_offset, voxel_size,
                      output_name):
    from chunkflow_tpu.annotations.skeleton import Skeleton

    @operator
    def stage(task):
        skel = Skeleton.from_swc(file_name)
        if voxel_offset is not None:
            vs = np.asarray(voxel_size if voxel_size is not None else (1, 1, 1))
            skel.nodes += np.asarray(voxel_offset) * vs
        task[output_name] = skel
        return task

    return stage(_name=op_name)


@main.command("save-swc")
@name_option("save-swc")
@click.option("--file-name", "--output-prefix", "-f", type=str, required=True,
              help=".swc path, or a prefix completed per skeleton id")
@click.option("--input-name", "-i", type=str, default="skeleton")
def save_swc_cmd(op_name, file_name, input_name):
    @write_operator
    def stage(task):
        value = task[input_name]
        if isinstance(value, dict):
            # skeletonize output: {obj_id: Skeleton} -> one file per id
            if file_name.endswith(".swc") and len(value) > 1:
                raise click.UsageError(
                    "multiple skeletons need a prefix (non-.swc "
                    "--output-prefix), not a single .swc path"
                )
            for obj_id, skel in value.items():
                path = (
                    file_name if file_name.endswith(".swc")
                    else f"{file_name}{obj_id}.swc"
                )
                skel.to_swc(path)
        else:
            path = file_name if file_name.endswith(".swc") else f"{file_name}.swc"
            value.to_swc(path)
        return task

    return stage(_name=op_name)


@main.command("load-npy")
@name_option("load-npy")
@click.option("--file-name", "--file-path", "-f", type=str, required=True)
@cartesian_option("--voxel-offset", default=(0, 0, 0))
@cartesian_option("--voxel-size", "--resolution", default=None)
@click.option("--output-chunk-name", "--output-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def load_npy_cmd(op_name, file_name, voxel_offset, voxel_size,
                 output_chunk_name):
    @operator
    def stage(task):
        chunk = Chunk.from_npy(file_name, voxel_offset=voxel_offset)
        if voxel_size is not None:
            chunk = chunk.with_voxel_size(voxel_size)
        task[output_chunk_name] = chunk
        return task

    return stage(_name=op_name)


@main.command("save-npy")
@name_option("save-npy")
@click.option("--file-name", "-f", type=str, required=True)
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
def save_npy_cmd(op_name, file_name, input_chunk_name):
    @write_operator
    def stage(task):
        task[input_chunk_name].to_npy(file_name)
        return task

    return stage(_name=op_name)


@main.command("load-json")
@name_option("load-json")
@click.option("--file-name", "--file-path", "-f", type=str, required=True)
@click.option("--output-name", "-o", type=str, default="json")
def load_json_cmd(op_name, file_name, output_name):
    import json as _json

    @operator
    def stage(task):
        with open(file_name) as f:
            task[output_name] = _json.load(f)
        return task

    return stage(_name=op_name)


@main.command("load-zarr")
@name_option("load-zarr")
@click.option("--store-path", "--store", "--path", "-p", type=str, required=True)
@click.option("--driver", type=click.Choice(["zarr", "zarr3", "n5"]),
              default="zarr", help="tensorstore driver")
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
@cartesian_option("--voxel-offset", default=(0, 0, 0))
@cartesian_option("--voxel-size", default=None)
@cartesian_option("--chunk-start", default=None,
                  help="explicit cutout start (overrides the task bbox)")
@cartesian_option("--chunk-size", default=None)
def load_zarr_cmd(op_name, store_path, driver, output_chunk_name,
                  voxel_offset, voxel_size, chunk_start, chunk_size):
    """Load a zyx zarr array (tensorstore zarr driver)."""
    import tensorstore as ts

    if (chunk_start is None) != (chunk_size is None):
        raise click.UsageError(
            "--chunk-start and --chunk-size must be given together"
        )

    @operator
    def stage(task):
        store = ts.open(
            {"driver": driver, "kvstore": {"driver": "file", "path": store_path}}
        ).result()
        explicit = (
            BoundingBox.from_delta(chunk_start, chunk_size)
            if chunk_start is not None else None
        )
        if explicit is not None or task.get("bbox") is not None:
            bbox = explicit if explicit is not None else task["bbox"]
            arr = store[bbox.slices].read().result()
            chunk = Chunk(arr, voxel_offset=bbox.start)
        else:
            chunk = Chunk(store.read().result(), voxel_offset=voxel_offset)
        if voxel_size is not None:
            chunk = chunk.with_voxel_size(voxel_size)
        task[output_chunk_name] = chunk
        return task

    return stage(_name=op_name)


@main.command("save-zarr")
@name_option("save-zarr")
@click.option("--store-path", "--store", "-p", type=str, required=True)
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@cartesian_option("--volume-size", "--shape", default=None, help="create store of this size first")
@cartesian_option("--chunk-size", default=None,
                  help="zarr store chunk shape on create")
@click.option("--dtype", type=str, default=None, help="convert before writing")
@cartesian_option("--resolution", default=None,
                  help="voxel size recorded on the chunk before writing")
@click.option("--mip", type=int, default=None,
              help="accepted for reference compatibility")
@click.option("--order", type=str, default=None,
              help="accepted for reference compatibility (always zyx/C)")
def save_zarr_cmd(op_name, store_path, input_chunk_name, volume_size,
                  chunk_size, dtype, resolution, mip, order):
    """Write the chunk into a zyx zarr array at its voxel offset."""
    import tensorstore as ts

    @write_operator
    def stage(task):
        chunk = task[input_chunk_name]
        if dtype is not None:
            chunk = chunk.astype(np.dtype(dtype))
        if resolution is not None:
            chunk = chunk.with_voxel_size(resolution)
        arr = np.asarray(chunk.array)
        spec = {
            "driver": "zarr",
            "kvstore": {"driver": "file", "path": store_path},
        }
        try:
            # existing store: open as-is (its domain must cover the bbox)
            store = ts.open(spec).result()
        except Exception:
            # create; without an explicit volume size the store must still
            # cover this chunk's GLOBAL bbox — a chunk at a nonzero
            # voxel_offset writes at bbox slices, so shape=arr.shape alone
            # would be out of bounds
            size = (
                tuple(volume_size)
                if volume_size and any(volume_size)
                else tuple(int(s) for s in chunk.bbox.stop)
            )
            # open=True tolerates a concurrent worker winning the create race
            if chunk_size is not None:
                spec = dict(spec)
                spec["metadata"] = {"chunks": list(chunk_size)}
            store = ts.open(
                spec,
                create=True,
                open=True,
                dtype=arr.dtype.name,
                shape=size,
            ).result()
        store[chunk.bbox.slices] = arr
        return task

    return stage(_name=op_name)


@main.command("create-bbox")
@name_option("create-bbox")
@cartesian_option("--start", "-s", required=True)
@cartesian_option("--stop", "-e", default=None)
@cartesian_option("--size", default=None)
def create_bbox_cmd(op_name, start, stop, size):
    """Set the task bbox explicitly (single-task pipelines)."""

    @operator
    def stage(task):
        if stop and any(stop):
            task["bbox"] = BoundingBox(start, stop)
        elif size and any(size):
            task["bbox"] = BoundingBox.from_delta(start, size)
        else:
            raise click.UsageError("need --stop or --size")
        return task

    return stage(_name=op_name)


@main.command("cleanup")
@name_option("cleanup")
@click.option("--dir", "-d", "directory", type=str, required=True)
@click.option("--mode", "-m",
              type=click.Choice(["exist", "empty", "not-empty"]),
              default="exist",
              help="remove only files meeting this condition "
                   "(reference flow.py:424-455)")
@click.option("--suffix", type=str, default=".h5")
def cleanup_cmd(op_name, directory, mode, suffix):
    """Remove per-task intermediate files for the task bbox."""
    import os

    def removable(path):
        if not os.path.exists(path):
            return False
        if mode == "empty":
            return os.path.getsize(path) == 0
        if mode == "not-empty":
            return os.path.getsize(path) > 0
        return True

    @operator
    def stage(task):
        if task.get("bbox") is not None:
            paths = [os.path.join(directory, f"{task['bbox'].string}{suffix}")]
        else:
            # bare seed task: sweep the whole directory (reference
            # flow.py:424-455 iterates every matching file)
            paths = [
                os.path.join(directory, f)
                for f in os.listdir(directory)
                if (not suffix or f.endswith(suffix))
                and os.path.isfile(os.path.join(directory, f))
            ]
        for path in paths:
            if removable(path) and not state.dry_run:
                os.remove(path)
        return task

    return stage(_name=op_name)


# ---------------------------------------------------------------------------
# flow control
# ---------------------------------------------------------------------------
@main.command("skip-all-zero")
@name_option("skip-all-zero")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--prefix", "-p", type=str, default=None,
              help="touch <prefix><bbox><suffix> as a completion marker "
                   "when skipping (reference flow.py:294-326)")
@click.option("--suffix", "-s", type=str, default="")
@click.option("--adjust-size", "-a", type=int, default=None,
              help="grow/shrink the marker bbox to match result filenames")
@click.option("--chunk-bbox/--task-bbox", default=True,
              help="name the marker after the chunk bbox or the task bbox")
def skip_all_zero_cmd(op_name, input_chunk_name, prefix, suffix, adjust_size,
                      chunk_bbox):
    """Drop the task if the chunk is entirely zero."""

    @operator
    def stage(task):
        if task[input_chunk_name].all_zero():
            if prefix is not None:
                bbox = (
                    task[input_chunk_name].bbox if chunk_bbox
                    else task.get("bbox")
                )
                if bbox is not None:
                    if adjust_size is not None:
                        bbox = bbox.adjust(adjust_size)
                    _touch_marker(prefix, bbox, suffix)
            return None
        return task

    return stage(_name=op_name)


@main.command("skip-none")
@name_option("skip-none")
@click.option("--input-chunk-name", "--input-name", "-i", type=str,
              default=DEFAULT_CHUNK_NAME)
@click.option("--prefix", "-p", type=str, default=None,
              help="touch <prefix><bbox><suffix> as a marker when skipping")
@click.option("--suffix", "-s", type=str, default="")
def skip_none_cmd(op_name, input_chunk_name, prefix, suffix):
    @operator
    def stage(task):
        if task.get(input_chunk_name) is None:
            if prefix is not None and task.get("bbox") is not None:
                _touch_marker(prefix, task["bbox"], suffix)
            return None
        return task

    return stage(_name=op_name)


@main.command("skip-task-by-file")
@name_option("skip-task-by-file")
@click.option("--prefix", "-p", type=str, required=True, help="marker path prefix")
@click.option("--suffix", "-s", type=str, default=".h5")
@click.option("--mode", "-m",
              type=click.Choice(["missing", "empty", "exist"]),
              default="exist",
              help="skip when the file is missing / missing-or-empty / "
                   "exists (reference flow.py:211-246)")
@click.option("--adjust-size", "-a", type=int, default=None,
              help="grow/shrink the bbox used in the file name")
def skip_task_by_file_cmd(op_name, prefix, suffix, mode, adjust_size):
    """Skip tasks by the state of their marker/output file (resume)."""
    import os

    @operator
    def stage(task):
        bbox = task["bbox"]
        if adjust_size is not None:
            bbox = bbox.adjust(adjust_size)
        path = f"{prefix}{bbox.string}{suffix}"
        if mode == "exist":
            skip = os.path.exists(path)
        elif mode == "missing":
            skip = not os.path.exists(path)
        else:  # empty
            skip = not os.path.exists(path) or os.path.getsize(path) == 0
        return None if skip else task

    return stage(_name=op_name)


@main.command("skip-task-by-blocks-in-volume")
@name_option("skip-task-by-blocks-in-volume")
@click.option("--volume-path", "-v", type=str, required=True)
@click.option("--mip", type=int, default=None)
def skip_task_by_blocks_cmd(op_name, volume_path, mip):
    """Skip tasks whose output blocks all exist in the volume (resume)."""
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    vol = PrecomputedVolume(volume_path)

    @operator
    def stage(task):
        if vol.has_all_blocks(
            task["bbox"], mip=mip if mip is not None else state.mip
        ):
            return None
        return task

    return stage(_name=op_name)


@main.command("mark-complete")
@name_option("mark-complete")
@click.option("--prefix", "-p", type=str, required=True)
@click.option("--suffix", "-s", type=str, default=".done")
def mark_complete_cmd(op_name, prefix, suffix):
    """Touch a completion marker file for the task bbox."""
    import os

    @write_operator
    def stage(task):
        from chunkflow_tpu.flow.runtime import drain_pending_writes

        # the marker claims completion: async writes must be durable first
        drain_pending_writes(task)
        if not state.dry_run:
            os.makedirs(os.path.dirname(prefix) or ".", exist_ok=True)
            with open(f"{prefix}{task['bbox'].string}{suffix}", "w"):
                pass
        return task

    return stage(_name=op_name)


@main.command("adjust-bbox")
@name_option("adjust-bbox")
@cartesian_option("--corner-offset", required=True, help="grow(+)/shrink(-) both corners")
def adjust_bbox_cmd(op_name, corner_offset):
    @operator
    def stage(task):
        task["bbox"] = task["bbox"].adjust(corner_offset)
        return task

    return stage(_name=op_name)


@main.command("delete-var")
@name_option("delete-var")
@click.option("--var-names", "-v", type=str, required=True, help="comma-separated task keys")
def delete_var_cmd(op_name, var_names):
    """Release chunks mid-pipeline to bound memory."""

    @operator
    def stage(task):
        for name in var_names.split(","):
            task.pop(name.strip(), None)
        return task

    return stage(_name=op_name)


@main.command("copy-var")
@name_option("copy-var")
@click.option("--from-name", "-f", type=str, required=True)
@click.option("--to-name", "-t", type=str, required=True)
def copy_var_cmd(op_name, from_name, to_name):
    @operator
    def stage(task):
        task[to_name] = task[from_name]
        return task

    return stage(_name=op_name)


# ---------------------------------------------------------------------------
# compute
# ---------------------------------------------------------------------------
@main.command("inference")
@name_option("inference")
@cartesian_option("--input-patch-size", "-p", "-s", required=True)
@cartesian_option("--output-patch-size", "-z", default=None)
@cartesian_option("--output-patch-overlap", "-v", default=(0, 0, 0))
@cartesian_option(
    "--output-crop-margin", default=None,
    help="explicit output crop margin (reference semantics); default: "
         "(input-output)//2 patch margin when cropping is on",
)
@cartesian_option(
    "--patch-num", "-n", default=None,
    help="expected patch grid in z,y,x; errors if the chunk's derived "
         "grid differs (reference aligned-mode contract)",
)
@click.option("--num-output-channels", "-c", type=int, default=3)
@click.option("--num-input-channels", type=int, default=1)
@click.option(
    "--framework", "-f",
    type=click.Choice(["identity", "flax", "jax", "pytorch", "universal"]),
    default="flax",
)
@click.option("--model-path", "--convnet-model", "-m", type=str, default="",
              help="flax factory module or reference pytorch model.py "
                   "(--convnet-model is the reference spelling)")
@click.option("--weight-path", "--convnet-weight-path", "-w", type=str,
              default=None, help=".pt/.msgpack/orbax weights")
@click.option("--batch-size", "-b", type=int, default=1)
@click.option("--bump", type=click.Choice(["wu", "zung"]), default="wu",
              help="bump function type (only wu is implemented, matching "
                   "the reference)")
@click.option("--augment/--no-augment", default=False, help="8x test-time augmentation")
@click.option("--crop-output-margin/--no-crop-output-margin", default=True)
@click.option("--mask-myelin-threshold", "-y", type=float, default=None)
@click.option("--dtype", "-d", type=click.Choice(["float32", "bfloat16", "float16"]),
              default="float32",
              help="compute dtype; float16 is accepted for reference "
                   "compatibility and mapped to bfloat16 (the TPU half type)")
@click.option("--output-dtype",
              type=click.Choice(["float32", "bfloat16", "uint8"]),
              default="float32",
              help="result dtype leaving the device; bfloat16 halves D2H "
                   "bytes, uint8 quantizes on device exactly like the "
                   "reference's save-time conversion (blend accumulation "
                   "stays float32 either way)")
@click.option(
    "--model-variant",
    type=click.Choice(["parity", "rsunet"]),
    default="parity",
    help="parity: reference-class UNet (torch-convertible); rsunet: the "
         "production RSUNet mirror, x-folded at full resolution",
)
@click.option(
    "--sharding",
    type=click.Choice(["none", "patch", "spatial", "spatial2d"]),
    default="none",
    help="legacy multi-chip layout names over all local devices; now "
         "aliases for the unified mesh engine (patch -> data=N, "
         "spatial -> y=N, spatial2d -> near-square y,x). Prefer --mesh "
         "/ CHUNKFLOW_MESH (docs/multichip.md)",
)
@click.option(
    "--mesh", "mesh_spec", type=str, default=None,
    help="unified multi-chip mesh spec (docs/multichip.md): 1 (single "
         "device), auto, data=N (patch-parallel over N chips), y=A or "
         "y=A,x=B (chunk sharded in slabs with halo exchange), "
         "pipeline=N (layer-parallel stages over engines declaring the "
         "stage protocol). Every shape produces output bit-identical "
         "to the single-device path. Overrides CHUNKFLOW_MESH; does "
         "not compose with the legacy --sharding names",
)
@cartesian_option(
    "--shape-bucket", default=None,
    help="pad chunk shapes up to multiples of this zyx quantum so ragged "
         "edge chunks reuse one compiled program (trade-off: the net sees "
         "edge-replicated padding past the true edge)",
)
@click.option(
    "--blend", type=click.Choice(["auto", "scatter", "fold"]),
    default="auto",
    help="overlap-add strategy: scatter (runtime-coordinate scatter-add "
         "or pallas kernel), fold (static parity-class dense adds; pads "
         "the chunk to a uniform patch grid — scatter-free, "
         "XLA-friendliest), auto (CHUNKFLOW_BLEND env or scatter)",
)
@click.option(
    "--async-depth", type=int, default=1,
    help="pipeline up to N tasks through the device: task i+1's fused "
         "program runs while task i's result rides D2H (jax dispatch is "
         "async). 1 = synchronous (reference behavior). Per-op timers "
         "then measure dispatch-to-materialize wall time, which overlaps "
         "across tasks. Under the adaptive scheduler (default; "
         "CHUNKFLOW_SCHED=static disables) this is the INITIAL depth — "
         "the controller may widen it up to the memory watermark "
         "(CHUNKFLOW_SCHED_MEM_GB)",
)
@click.option(
    "--prefetch-depth", type=int, default=2,
    help="adaptive scheduler only (with --async-depth > 1): initial "
         "number of upstream tasks pulled ahead in the scheduler's load "
         "thread, so load-operator IO overlaps device compute without a "
         "separate 'prefetch' command; widened by the controller when "
         "load/stage stalls dominate. CHUNKFLOW_SCHED=static ignores "
         "this — compose the 'prefetch' command instead",
)
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def inference_cmd(op_name, input_patch_size, output_patch_size,
                  output_patch_overlap, output_crop_margin, patch_num,
                  num_output_channels, num_input_channels, framework,
                  model_path, weight_path, batch_size, bump, augment,
                  crop_output_margin, mask_myelin_threshold, dtype,
                  output_dtype, model_variant, sharding, mesh_spec,
                  shape_bucket, blend, async_depth, prefetch_depth,
                  input_chunk_name, output_chunk_name):
    """Patch-wise convnet inference with bump-weighted overlap blending."""
    from chunkflow_tpu.inference import Inferencer

    if dtype == "float16":
        dtype = "bfloat16"
    if bump != "wu":
        # same capability as the reference (zung is accepted by its CLI and
        # unimplemented, pytorch.py:34-35) but fail cleanly at parse level
        raise click.UsageError(
            f"bump '{bump}' is not implemented; only 'wu' is (matching the "
            "reference)"
        )
    # click yields None when these nargs=3 options are unset, so zeros
    # stay meaningful: --output-crop-margin 0 0 0 means "do not crop"
    # (reference semantics), which a truthiness check would misread
    explicit_crop = output_crop_margin
    expected_patch_num = tuple(patch_num) if patch_num is not None else None

    # one Inferencer (and its compiled program cache) shared across tasks
    inferencer = Inferencer(
        input_patch_size=input_patch_size,
        output_patch_size=output_patch_size if output_patch_size and any(output_patch_size) else None,
        output_patch_overlap=output_patch_overlap,
        num_output_channels=num_output_channels,
        num_input_channels=num_input_channels,
        framework=framework,
        model_path=model_path,
        weight_path=weight_path,
        batch_size=batch_size,
        augment=augment,
        bump=bump,
        # explicit margin crops below instead of the derived patch margin
        crop_output_margin=crop_output_margin and explicit_crop is None,
        mask_myelin_threshold=mask_myelin_threshold,
        dtype=dtype,
        output_dtype=output_dtype,
        model_variant=model_variant,
        sharding=sharding,
        mesh=mesh_spec,
        shape_bucket=shape_bucket,
        blend=blend,
        dry_run=state.dry_run,
    )

    def check_grid(chunk):
        if expected_patch_num is not None:
            got = inferencer.patch_grid_shape(chunk.shape)
            if got != expected_patch_num:
                raise click.UsageError(
                    f"--patch-num {expected_patch_num} but chunk "
                    f"{tuple(chunk.shape)} decomposes into {got} patches"
                )

    if async_depth <= 1:
        @operator
        def stage(task):
            chunk = task[input_chunk_name]
            check_grid(chunk)
            out = inferencer(chunk)
            if explicit_crop is not None:
                out = out.crop_margin(explicit_crop)
            task[output_chunk_name] = out
            task["log"]["compute_device"] = inferencer.compute_device
            return task

        return stage(_name=op_name)

    # pipelined: the double-buffered executor threads the task dicts
    # through a staging ring + async dispatch so task i+1 stages H2D
    # while task i computes and task i-1's result rides D2H. Default is
    # the adaptive scheduler (flow/scheduler.py): upstream load IO runs
    # --prefetch-depth tasks ahead, drain + host materialization move to
    # a worker pool, and all depths widen under telemetry-driven control.
    # CHUNKFLOW_SCHED=static pins the PR 2 composition bit-identically.
    from chunkflow_tpu.flow.scheduler import scheduler_mode

    if scheduler_mode() == "static":
        from chunkflow_tpu.flow.pipeline import pipelined_inference_stage

        return pipelined_inference_stage(
            inferencer,
            depth=async_depth,
            input_name=input_chunk_name,
            output_name=output_chunk_name,
            op_name=op_name,
            crop=explicit_crop,
            check=check_grid,
        )
    from chunkflow_tpu.flow.scheduler import scheduled_inference_stage

    return scheduled_inference_stage(
        inferencer,
        depth=async_depth,
        prefetch_depth=prefetch_depth,
        input_name=input_chunk_name,
        output_name=output_chunk_name,
        op_name=op_name,
        crop=explicit_crop,
        check=check_grid,
    )


@main.command("crop-margin")
@name_option("crop-margin")
@cartesian_option("--margin-size", "-m", default=None)
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def crop_margin_cmd(op_name, margin_size, input_chunk_name, output_chunk_name):
    @operator
    def stage(task):
        chunk = task[input_chunk_name]
        if margin_size and any(margin_size):
            cropped = chunk.crop_margin(margin_size)
        elif task.get("bbox") is not None:
            cropped = chunk.cutout(task["bbox"])
        else:
            raise click.UsageError("need --margin-size or a task bbox")
        task[output_chunk_name] = cropped
        return task

    return stage(_name=op_name)


@main.command("threshold")
@name_option("threshold")
@click.option("--threshold", "-t", type=float, default=0.5)
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def threshold_cmd(op_name, threshold, input_chunk_name, output_chunk_name):
    @operator
    def stage(task):
        task[output_chunk_name] = task[input_chunk_name].threshold(threshold)
        return task

    return stage(_name=op_name)


@main.command("connected-components")
@name_option("connected-components")
@click.option("--threshold", "-t", type=float, default=0.5)
@click.option("--connectivity", "-c", type=click.Choice(["6", "18", "26"]), default="26")
@click.option("--device/--host", default=False,
              help="label on the accelerator (iterative propagation) instead "
              "of host union-find; NOTE device labels are non-consecutive "
              "uint32 (linear-index seeds) — chain a renumber when dense "
              "ids are required (the host path is already consecutive)")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def connected_components_cmd(op_name, threshold, connectivity, device, input_chunk_name, output_chunk_name):
    @operator
    def stage(task):
        task[output_chunk_name] = task[input_chunk_name].connected_component(
            threshold=threshold, connectivity=int(connectivity), device=device
        )
        return task

    return stage(_name=op_name)


@main.command("channel-voting")
@name_option("channel-voting")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def channel_voting_cmd(op_name, input_chunk_name, output_chunk_name):
    @operator
    def stage(task):
        task[output_chunk_name] = task[input_chunk_name].channel_voting()
        return task

    return stage(_name=op_name)


@main.command("normalize-contrast")
@name_option("normalize-contrast")
@click.option("--lower-clip-fraction", "-l", type=float, default=0.01)
@click.option("--upper-clip-fraction", "-u", type=float, default=0.01)
@click.option("--minval", type=int, default=1,
              help="minimum intensity of the transformed chunk")
@click.option("--maxval", type=int, default=255,
              help="maximum intensity of the transformed chunk")
@click.option("--per-section/--whole", default=True,
              help="normalize each z-section independently or the whole chunk")
@click.option("--levels-path", type=str, default=None,
              help="directory of the sections' histogram sidecars "
                   "(<image>/levels/<mip>, one JSON file a z with 256 "
                   "'levels'): each section of a uint8 image goes through "
                   "a lookup table built from its file (reference "
                   "image/base.py:93-133); a missing file is an error. "
                   "Without it: a percentile stretch of the chunk itself")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def normalize_contrast_cmd(op_name, lower_clip_fraction, upper_clip_fraction,
                           minval, maxval, per_section, levels_path,
                           input_chunk_name, output_chunk_name):
    @operator
    def stage(task):
        img = task[input_chunk_name]
        if not isinstance(img, Image):
            img = Image(img.array, voxel_offset=img.voxel_offset, voxel_size=img.voxel_size)
        task[output_chunk_name] = img.normalize_contrast(
            lower_clip_fraction=lower_clip_fraction,
            upper_clip_fraction=upper_clip_fraction,
            minval=minval,
            maxval=maxval,
            per_section=per_section,
            levels_path=levels_path,
        )
        return task

    return stage(_name=op_name)


@main.command("normalize-intensity")
@name_option("normalize-intensity")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def normalize_intensity_cmd(op_name, input_chunk_name, output_chunk_name):
    """uint8 grey image -> float32 in (-1, 1): x/127.5 - 1
    (reference flow/flow.py:1650-1668)."""

    @operator
    def stage(task):
        chunk = task[input_chunk_name]
        assert np.issubdtype(np.dtype(chunk.dtype), np.uint8), (
            "normalize-intensity expects a uint8 image chunk"
        )
        out = chunk.astype(np.float32)
        out = out / 127.5 - 1.0
        task[output_chunk_name] = out
        return task

    return stage(_name=op_name)


@main.command("normalize-section-shang")
@name_option("normalize-section-shang")
@click.option("--nominalmin", type=float, default=None,
              help="targeted minimum of the transformed chunk")
@click.option("--nominalmax", type=float, default=None,
              help="targeted maximum of the transformed chunk")
@click.option("--clipvalues", type=bool, default=False,
              help="clip transformed values to the target range")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def normalize_section_shang_cmd(op_name, 
    nominalmin, nominalmax, clipvalues, input_chunk_name, output_chunk_name
):
    """Slice-wise min/max normalization, Shang's method
    (reference flow/flow.py:1713-1748)."""

    @operator
    def stage(task):
        img = task[input_chunk_name]
        if not isinstance(img, Image):
            img = Image.from_chunk(img)
        task[output_chunk_name] = img.normalize_shang(
            nominalmin=nominalmin, nominalmax=nominalmax, clipvalues=clipvalues
        )
        return task

    return stage(_name=op_name)


@main.command("mask")
@name_option("mask")
@click.option("--volume-path", "-v", type=str, required=True,
              help="mask volume (its voxel size may be any integer multiple of the chunk's)")
@click.option("--mip", type=int, default=0, help="scale index within the mask volume")
@click.option("--inverse/--no-inverse", default=False)
@click.option("--fill-missing/--no-fill-missing", default=True)
@click.option("--input-chunk-name", "--input-names", "-i", type=str,
              default=DEFAULT_CHUNK_NAME,
              help="comma-separated chunk names: one mask cutout is "
                   "applied to every listed chunk (reference semantics)")
@click.option("--output-chunk-name", "--output-names", "-o", type=str,
              default=None, help="defaults to the input names")
def mask_cmd(op_name, volume_path, mip, inverse, fill_missing, input_chunk_name, output_chunk_name):
    """Multiply the chunk(s) by a (usually coarser-resolution) mask volume."""
    import math

    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.core.bbox import BoundingBox
    from chunkflow_tpu.core.cartesian import Cartesian
    from chunkflow_tpu.ops.mask import maskout
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    vol = PrecomputedVolume(volume_path)

    in_names = [n.strip() for n in input_chunk_name.split(",") if n.strip()]
    out_names = (
        [n.strip() for n in output_chunk_name.split(",") if n.strip()]
        if output_chunk_name else in_names
    )
    if len(in_names) != len(out_names):
        raise click.UsageError("input/output name counts must match")

    @operator
    def stage(task):
        first = task[in_names[0]]
        factor = vol.voxel_size(mip) / first.voxel_size
        start = Cartesian(
            *(int(math.floor(s / f)) for s, f in zip(first.bbox.start, factor))
        )
        stop = Cartesian(
            *(int(math.ceil(e / f)) for e, f in zip(first.bbox.stop, factor))
        )
        coarse_box = BoundingBox(start, stop)
        with telemetry.span(
                "mask/cutout",
                blocks=len(vol.block_names(coarse_box, mip))) as sp:
            mask_chunk = vol.cutout(
                coarse_box, mip=mip, fill_missing=fill_missing
            )
            sp.annotate(bytes=int(mask_chunk.array.nbytes))
        # one mask cutout masks every listed chunk (reference flow
        # applies MaskOperator to a chunk list); each is masked where it
        # is, host or device (ops/mask.py), and a cutout that is all
        # zero zeroes it with no multiply and nothing uploaded
        for in_name, out_name in zip(in_names, out_names):
            task[out_name] = maskout(
                task[in_name], mask_chunk, inverse=inverse
            )
        return task

    return stage(_name=op_name)


@main.command("multiply")
@name_option("multiply")
@click.option("--input-names", "-i", type=str, default=DEFAULT_CHUNK_NAME,
              help="comma-separated chunk names")
@click.option("--multiplier-name", "-m", type=str, default=None,
              help="multiply every input by this chunk (reference "
                   "semantics); without it, exactly two input names "
                   "multiply together")
@click.option("--output-names", "--output-chunk-name", "-o", type=str,
              default=None, help="defaults to the input names")
def multiply_cmd(op_name, input_names, multiplier_name, output_names):
    in_names = [n.strip() for n in input_names.split(",") if n.strip()]
    outs = (
        [n.strip() for n in output_names.split(",") if n.strip()]
        if output_names else None
    )
    # fail at pipeline assembly, before any task has done real work
    if multiplier_name is not None:
        outs = outs if outs is not None else in_names
        if len(outs) != len(in_names):
            raise click.UsageError("input/output name counts must match")
    else:
        if len(in_names) != 2:
            raise click.UsageError(
                "without --multiplier-name, give exactly two "
                "--input-names to multiply together"
            )
        outs = outs if outs is not None else [DEFAULT_CHUNK_NAME]
        if len(outs) != 1:
            raise click.UsageError("two-input multiply writes one output name")

    @operator
    def stage(task):
        if multiplier_name is not None:
            for in_name, out_name in zip(in_names, outs):
                task[out_name] = task[in_name] * task[multiplier_name]
        else:
            task[outs[0]] = task[in_names[0]] * task[in_names[1]]
        return task

    return stage(_name=op_name)


@main.command("mask-out-objects")
@name_option("mask-out-objects")
@click.option("--dust-size-threshold", "-d", type=int, default=0)
@click.option("--selected-obj-ids", "-s", type=str, default=None, help="comma-separated keep list")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def mask_out_objects_cmd(op_name, dust_size_threshold, selected_obj_ids,
                         input_chunk_name, output_chunk_name):
    @operator
    def stage(task):
        seg = task[input_chunk_name]
        if not isinstance(seg, Segmentation):
            seg = Segmentation.from_chunk(seg)
        if dust_size_threshold:
            seg = seg.mask_fragments(dust_size_threshold)
        if selected_obj_ids:
            ids = [int(x) for x in selected_obj_ids.split(",")]
            seg = seg.mask_except(ids)
        task[output_chunk_name] = seg
        return task

    return stage(_name=op_name)


@main.command("quantize")
@name_option("quantize")
@click.option("--mode", type=click.Choice(["xy", "z"]), default="xy")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def quantize_cmd(op_name, mode, input_chunk_name, output_chunk_name):
    """Compress an affinity map into a uint8 thumbnail image."""
    from chunkflow_tpu.chunk import AffinityMap

    @operator
    def stage(task):
        chunk = task[input_chunk_name]
        aff = AffinityMap(
            chunk.array,
            voxel_offset=chunk.voxel_offset,
            voxel_size=chunk.voxel_size,
        )
        task[output_chunk_name] = aff.quantize(mode=mode)
        return task

    return stage(_name=op_name)


@main.command("downsample")
@name_option("downsample")
@cartesian_option("--factor", "-f", default=(1, 2, 2))
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def downsample_cmd(op_name, factor, input_chunk_name, output_chunk_name):
    from chunkflow_tpu.ops.downsample import downsample

    @operator
    def stage(task):
        task[output_chunk_name] = downsample(task[input_chunk_name], factor)
        return task

    return stage(_name=op_name)


@main.command("downsample-upload")
@name_option("downsample-upload")
@click.option("--volume-path", "-v", type=str, required=True)
@cartesian_option("--factor", "-f", default=(1, 2, 2))
@click.option("--chunk-mip", type=int, default=None,
              help="mip level of the incoming chunk (default: the "
                   "group-level --mip); pyramid levels count from here")
@click.option("--start-mip", type=int, default=None,
              help="first level written (default: chunk mip + 1)")
@click.option("--stop-mip", type=int, default=None, help="exclusive; defaults to volume num_mips")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
def downsample_upload_cmd(op_name, volume_path, factor, chunk_mip, start_mip,
                          stop_mip, input_chunk_name):
    """Build a mip pyramid of the chunk and upload every level."""
    from chunkflow_tpu.ops.downsample import downsample
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    vol = PrecomputedVolume(volume_path)

    @operator
    def stage(task):
        base = chunk_mip if chunk_mip is not None else state.mip
        first = start_mip if start_mip is not None else base + 1
        if first <= base:
            # reference downsample_upload.py asserts start_mip > chunk_mip
            raise click.UsageError(
                f"--start-mip ({first}) must be above the chunk mip ({base})"
            )
        stop = stop_mip if stop_mip is not None else vol.num_mips
        current = task[input_chunk_name]
        for level in range(base + 1, stop):
            current = downsample(current, factor)
            if level >= first and not state.dry_run:
                vol.save(current, mip=level)
        return task

    return stage(_name=op_name)


@main.command("gaussian-filter")
@name_option("gaussian-filter")
@click.option("--sigma", "-s", type=float, default=1.0)
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def gaussian_filter_cmd(op_name, sigma, input_chunk_name, output_chunk_name):
    @operator
    def stage(task):
        task[output_chunk_name] = task[input_chunk_name].gaussian_filter_2d(sigma)
        return task

    return stage(_name=op_name)


@main.command("plugin")
@click.option("--name", "-n", "--file", "-f", type=str, required=True)
@click.option("--input-names", "-i", type=str, default=DEFAULT_CHUNK_NAME, help="comma-separated task keys")
@click.option("--output-names", "-o", type=str, default=DEFAULT_CHUNK_NAME, help="comma-separated task keys")
@click.option("--args", "-a", type=str, default=None, help="k=v;k2=(1,2) plugin args")
def plugin_cmd(name, input_names, output_names, args):
    """Run a user plugin file: execute(*inputs, **args).

    Bundled plugins are listed in chunkflow_tpu/plugins/. Note: the
    bundled czann_inference plugin is a documented stub (it needs the
    optional czmodel runtime, like the reference's own 2-line czann
    plugin); use the 'universal' inference engine for extracted models.
    """
    from chunkflow_tpu.flow.plugin import load_plugin, str_to_dict, wrap_outputs

    execute = load_plugin(name)
    kwargs = str_to_dict(args)

    @operator
    def stage(task):
        inputs = [task[k.strip()] for k in input_names.split(",") if k.strip()]
        outputs = execute(*inputs, **kwargs)
        wrapped = wrap_outputs(outputs, inputs)
        out_keys = [k.strip() for k in output_names.split(",") if k.strip()]
        for key, value in zip(out_keys, wrapped):
            task[key] = value
        return task

    return stage(_name=f"plugin-{name}")


@main.command("save-pngs")
@name_option("save-pngs")
@click.option("--output-path", "-o", type=str, required=True)
@click.option("--dtype", type=str, default=None, help="convert before export")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
def save_pngs_cmd(op_name, output_path, dtype, input_chunk_name):
    from chunkflow_tpu.volume.io_png import save_pngs

    @write_operator
    def stage(task):
        chunk = task[input_chunk_name]
        if dtype is not None:
            chunk = chunk.astype(np.dtype(dtype))
        save_pngs(chunk, output_path)
        return task

    return stage(_name=op_name)


@main.command("load-png")
@name_option("load-png")
@click.option("--path", "-p", type=str, required=True, help="directory of z-section pngs")
@cartesian_option("--voxel-offset", "-t", default=(0, 0, 0))
@cartesian_option("--voxel-size", "-x", default=None)
@cartesian_option("--cutout-offset", "-c", default=(0, 0, 0),
                  help="with --chunk-size: explicit cutout window start")
@cartesian_option("--chunk-size", "-s", default=None,
                  help="explicit cutout window size (overrides task bbox)")
@click.option("--digit-num", "-d", type=int, default=None,
              help="accepted for reference compatibility (section index "
                   "digits are parsed from the filenames)")
@click.option("--dtype", type=str, default=None)
@click.option("--output-chunk-name", "-o", type=str, default=DEFAULT_CHUNK_NAME)
def load_png_cmd(op_name, path, voxel_offset, voxel_size, cutout_offset,
                 chunk_size, digit_num, dtype, output_chunk_name):
    from chunkflow_tpu.volume.io_png import load_pngs

    @operator
    def stage(task):
        import numpy as _np

        if chunk_size is not None:
            bbox = BoundingBox.from_delta(cutout_offset, chunk_size)
        else:
            bbox = task.get("bbox")
        chunk = load_pngs(
            path,
            bbox=bbox,
            voxel_offset=voxel_offset,
            dtype=_np.dtype(dtype) if dtype else None,
        )
        if voxel_size is not None:
            chunk = chunk.with_voxel_size(voxel_size)
        task[output_chunk_name] = chunk
        return task

    return stage(_name=op_name)


@main.command("mesh")
@name_option("mesh")
@click.option("--output-path", "-o", type=str, required=True)
@click.option("--output-format", "-t", type=click.Choice(["precomputed", "obj", "ply"]), default="precomputed")
@click.option("--ids", type=str, default=None, help="comma-separated object ids (default: all)")
@click.option("--skip-ids", type=str, default=None)
@click.option("--manifest/--no-manifest", default=False)
@click.option("--simplification-error", "--max-simplification-error",
              type=float, default=0.0,
              help="max geometric error in nm for vertex-clustering simplification (0 = off)")
@click.option("--simplification-factor", type=int, default=None,
              help="accepted for reference compatibility; the error bound "
                   "above drives vertex-clustering instead of a target "
                   "face-count factor")
@click.option("--mip", type=int, default=None,
              help="accepted for reference compatibility (chunks carry "
                   "their own voxel size)")
@cartesian_option("--voxel-size", default=None,
                  help="override the chunk's voxel size (nm) for meshing")
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
def mesh_cmd(op_name, output_path, output_format, ids, skip_ids, manifest,
             simplification_error, simplification_factor, mip, voxel_size,
             input_chunk_name):
    """Mesh every object of a segmentation chunk (surface nets)."""
    from chunkflow_tpu.flow.mesh import MeshOperator

    op = MeshOperator(
        output_path,
        output_format=output_format,
        ids=[int(x) for x in ids.split(",")] if ids else None,
        skip_ids=tuple(int(x) for x in skip_ids.split(",")) if skip_ids else (),
        manifest=manifest,
        simplification_error_nm=simplification_error,
    )

    @operator
    def stage(task):
        chunk = task[input_chunk_name]
        if voxel_size is not None:
            chunk = chunk.with_voxel_size(voxel_size)
        count = op(chunk)
        if state.verbose:
            print(f"meshed {count} objects")
        return task

    return stage(_name=op_name)


@main.command("mesh-manifest")
@click.option("--mesh-dir", "--volume-path", "-d", "-v", type=str, required=True)
@click.option("--prefix", "-p", type=str, default=None,
              help="only aggregate object ids starting with this prefix "
                   "(reference mesh_manifest.py prefix sharding: run one "
                   "job per prefix to parallelize)")
@click.option("--digits", type=int, default=None,
              help="accepted for reference compatibility (number of "
                   "prefix digits used when sharding manifest jobs)")
def mesh_manifest_cmd(mesh_dir, prefix, digits):
    """Aggregate per-chunk mesh fragments into object manifests."""
    from chunkflow_tpu.flow.mesh import write_manifests

    @generator
    def stage(task):
        count = write_manifests(mesh_dir, id_prefix=prefix)
        print(f"wrote {count} mesh manifests")
        return
        yield  # pragma: no cover

    return stage()


@main.command("download-mesh")
@name_option("download-mesh")
@click.option("--mesh-dir", "--volume-path", "-v", type=str, required=True,
              help="directory holding mesh fragments + manifests")
@click.option("--ids", "-i", type=str, default=None,
              help="comma-separated object ids, or a text file of them")
@click.option("--input-chunk-name", "--input", type=str, default=None,
              help="rank objects by voxel count from this segmentation chunk")
@click.option("--start-rank", "-s", type=int, default=0)
@click.option("--stop-rank", "-p", type=int, default=None)
@click.option("--out-pre", "-o", type=str, default="./")
@click.option("--output-format", "--out-format", "-f",
              type=click.Choice(["ply", "obj"]), default="ply")
def download_mesh_cmd(op_name, mesh_dir, ids, input_chunk_name, start_rank, stop_rank,
                      out_pre, output_format):
    """Fuse an object's mesh fragments and write ply/obj files
    (reference flow/flow.py:2160-2210)."""
    import os

    from chunkflow_tpu.flow.mesh import download_mesh, to_obj, to_ply

    @operator
    def stage(task):
        if input_chunk_name is not None:
            seg = np.asarray(task[input_chunk_name].array)
            unique, count = np.unique(seg, return_counts=True)
            fg = unique != 0
            unique, count = unique[fg], count[fg]
            order = np.argsort(count)[::-1]
            obj_ids = unique[order][start_rank:stop_rank].tolist()
        else:
            import re

            text = ids
            if text is not None and os.path.isfile(text):
                with open(text) as f:
                    text = f.read()
            if text is None:
                raise click.UsageError("need --ids or --input-chunk-name")
            obj_ids = [int(x) for x in re.split(r"[\s,]+", text) if x]
        for obj_id in obj_ids:
            fused = download_mesh(mesh_dir, int(obj_id))
            if fused is None:
                print(f"object {obj_id}: no mesh manifest found")
                continue
            vertices, faces = fused
            out = f"{out_pre}{obj_id}.{output_format}"
            text_mesh = (
                to_ply(vertices, faces)
                if output_format == "ply"
                else to_obj(vertices, faces)
            )
            with open(out, "w") as f:
                f.write(text_mesh)
            print(f"wrote {out} ({vertices.shape[0]} vertices)")
        return task

    return stage(_name=op_name)


@main.command("aggregate-skeleton-fragments")
@click.option("--fragments-path", "--input-name", "-f", type=str, required=True)
@click.option("--prefix", "-p", type=str, default=None,
              help="only aggregate fragment files starting with this id "
                   "prefix (parallel sharding, as in the reference)")
@click.option("--output-path", "-o", type=str, default=None)
def aggregate_skeleton_fragments_cmd(fragments_path, prefix, output_path):
    """Merge per-chunk skeleton fragments into whole skeletons
    (reference flow/flow.py:623-649)."""
    from chunkflow_tpu.plugins.aggregate_skeleton_fragments import execute

    @generator
    def stage(task):
        execute(fragments_path, output_path, id_prefix=prefix)
        return
        yield  # pragma: no cover

    return stage()


@main.command("save-nrrd")
@name_option("save-nrrd")
@click.option("--file-name", "-f", type=str, required=True)
@click.option("--input-chunk-name", "-i", type=str, default=DEFAULT_CHUNK_NAME)
def save_nrrd_cmd(op_name, file_name, input_chunk_name):
    """Save the chunk as an NRRD file (reference flow/flow.py:853)."""
    from chunkflow_tpu.volume.io_nrrd import save_nrrd

    @write_operator
    def stage(task):
        chunk = task[input_chunk_name]
        save_nrrd(
            file_name,
            np.asarray(chunk.array),
            voxel_size=tuple(chunk.voxel_size),
            voxel_offset=tuple(chunk.voxel_offset),
        )
        return task

    return stage(_name=op_name)


@main.command("view")
@name_option("view")
@click.option("--image-chunk-name", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--segmentation-chunk-name", type=str, default=None)
@click.option("--screenshot", type=str, default=None,
              help="save a middle-section png instead of opening a window")
def view_cmd(op_name, image_chunk_name, segmentation_chunk_name, screenshot):
    """Quick-look viewer: middle z-section via matplotlib
    (reference flow/view.py microviewer equivalent)."""

    @operator
    def stage(task):
        import matplotlib

        if screenshot:
            matplotlib.use("Agg")
        import matplotlib.pyplot as plt

        chunk = task[image_chunk_name]
        arr = np.asarray(chunk.array)
        if arr.ndim == 4:
            arr = arr[0]
        mid = arr[arr.shape[0] // 2]
        ncols = 2 if segmentation_chunk_name else 1
        fig, axes = plt.subplots(1, ncols, squeeze=False)
        axes[0][0].imshow(mid, cmap="gray")
        axes[0][0].set_title(image_chunk_name)
        if segmentation_chunk_name:
            seg = np.asarray(task[segmentation_chunk_name].array)
            if seg.ndim == 4:
                seg = seg[0]
            axes[0][1].imshow(seg[seg.shape[0] // 2] % 251, cmap="tab20")
            axes[0][1].set_title(segmentation_chunk_name)
        if screenshot:
            fig.savefig(screenshot, dpi=120)
            print(f"wrote {screenshot}")
        else:  # pragma: no cover - interactive
            plt.show()
        plt.close(fig)
        return task

    return stage(_name=op_name)


@main.command("neuroglancer")
@name_option("neuroglancer")
@click.option("--chunk-names", "--inputs", "-c", type=str, default=DEFAULT_CHUNK_NAME,
              help="comma-separated chunk names to serve as layers")
@click.option("--port", "-p", type=int, default=0)
@click.option("--voxel-size", type=int, nargs=3, default=None)
def neuroglancer_cmd(op_name, chunk_names, port, voxel_size):
    """Serve chunks in an in-process neuroglancer viewer
    (reference flow/neuroglancer.py; requires the neuroglancer package)."""

    @operator
    def stage(task):
        try:
            import neuroglancer  # noqa: F401
        except ImportError as e:
            raise click.ClickException(
                "the neuroglancer package is not installed in this "
                "environment; install it to use this operator"
            ) from e
        from chunkflow_tpu.flow.viewers import serve_neuroglancer

        serve_neuroglancer(
            {
                name: task[name]
                for name in chunk_names.split(",")
                if name in task
            },
            port=port,
            voxel_size=voxel_size,
        )
        return task

    return stage(_name=op_name)


@main.command("napari")
@name_option("napari")
@click.option("--chunk-names", "--inputs", "-c", type=str, default=DEFAULT_CHUNK_NAME)
@cartesian_option("--voxel-size", default=None, help="accepted for reference compatibility (chunks carry their own)")
def napari_cmd(op_name, chunk_names, voxel_size):
    """Open chunks in napari (requires the napari package)."""

    @operator
    def stage(task):
        try:
            import napari
        except ImportError as e:
            raise click.ClickException(
                "the napari package is not installed in this environment"
            ) from e
        from chunkflow_tpu.flow.viewers import add_napari_layers

        viewer = napari.Viewer()
        add_napari_layers(
            viewer,
            {
                name: task[name]
                for name in chunk_names.split(",")
                if name in task
            },
        )
        napari.run()  # pragma: no cover - interactive
        return task

    return stage(_name=op_name)


@main.command("evaluate-segmentation")
@name_option("evaluate-segmentation")
@click.option("--segmentation-chunk-name", "-s", type=str, default=DEFAULT_CHUNK_NAME)
@click.option("--groundtruth-chunk-name", "-g", type=str, required=True)
@click.option("--output", "-o", type=str, default=None,
              help="append per-task scores to this JSON-lines file")
def evaluate_segmentation_cmd(op_name, segmentation_chunk_name,
                              groundtruth_chunk_name, output):
    import json

    @operator
    def stage(task):
        seg = task[segmentation_chunk_name]
        if not isinstance(seg, Segmentation):
            seg = Segmentation.from_chunk(seg)
        scores = seg.evaluate(task[groundtruth_chunk_name])
        print("segmentation evaluation:", scores)
        task["evaluation"] = scores
        if output:
            record = dict(scores)
            if task.get("bbox") is not None:
                record["bbox"] = task["bbox"].string
            with open(output, "a") as f:
                f.write(json.dumps(record) + "\n")
        return task

    return stage(_name=op_name)


# ---------------------------------------------------------------------------
# whole-volume segmentation plane (chunkflow_tpu/segment/,
# docs/segmentation.md)
# ---------------------------------------------------------------------------
def _segment_stage_cmd(kind: str, seg_dir: str, op_name: str):
    """One worker stage of the stitching job: execute queue bodies of
    ``kind`` against the job directory's store, pass every other task
    through untouched (so one worker pipeline chains all three stages
    and handles whatever the tree source emits)."""
    from chunkflow_tpu.segment.driver import open_store
    from chunkflow_tpu.segment.plan import SegmentPlan
    from chunkflow_tpu.segment.stages import execute_body

    cache = {}

    @operator
    def stage(task):
        body = task.get("task_body")
        if body is None:
            return task
        parsed = SegmentPlan.parse_body(body)
        if parsed is None or parsed[0] != kind:
            return task
        if "store" not in cache:  # one store per worker process
            cache["store"] = open_store(seg_dir)
        execute_body(cache["store"], body)
        return task

    return stage(_name=op_name)


@main.command("label-chunk")
@name_option("label-chunk")
@click.option("--seg-dir", "-d", type=str, required=True,
              help="segmentation job directory (init-ed by segment-volume)")
def label_chunk_cmd(op_name, seg_dir):
    """Map stage 1 of the stitching job: handle ``seg-label_<bbox>``
    queue tasks (label one chunk into the global id space + write its
    boundary face sidecars)."""
    return _segment_stage_cmd("label", seg_dir, op_name)


@main.command("merge-seg")
@name_option("merge-seg")
@click.option("--seg-dir", "-d", type=str, required=True,
              help="segmentation job directory (init-ed by segment-volume)")
def merge_seg_cmd(op_name, seg_dir):
    """Reduce stage of the stitching job: handle ``seg-merge_<bbox>``
    queue tasks (one tree node's cross-chunk equivalence merge)."""
    return _segment_stage_cmd("merge", seg_dir, op_name)


@main.command("relabel")
@name_option("relabel")
@click.option("--seg-dir", "-d", type=str, required=True,
              help="segmentation job directory (init-ed by segment-volume)")
def relabel_cmd(op_name, seg_dir):
    """Map stage 2 of the stitching job: handle ``seg-relabel_<bbox>``
    queue tasks (apply the global remap to one chunk, mesh if
    configured)."""
    return _segment_stage_cmd("relabel", seg_dir, op_name)


@main.command("segment-volume")
@click.option("--input-npy", "-i", type=str, required=True,
              help="source volume (.npy): probability map, binary mask "
                   "or multi-valued ids")
@click.option("--seg-dir", "-d", type=str, required=True,
              help="job directory: spec.json + KV label volume + "
                   "face/merge/remap sidecars")
@cartesian_option("--chunk-size", "-c", required=True,
                  help="grid chunk size (zyx)")
@click.option("--threshold", "-t", type=float, default=0.5)
@click.option("--connectivity", type=click.Choice(["6", "18", "26"]),
              default="26")
@click.option("--multivalue/--binary", default=False,
              help="treat the input as multi-valued ids (equal-value "
                   "connectivity) instead of thresholded/binary")
@click.option("--device/--host", default=False,
              help="label chunks on the accelerator "
                   "(ops/connected_components.label_binary_device)")
@click.option("--workers", "-w", type=int, default=4,
              help="local mode: labeling/relabel thread fan-out")
@click.option("--mesh-output", type=str, default=None,
              help="also mesh the merged labels into this directory "
                   "(fragments carry global ids: no chunk-seam splits)")
@click.option("--queue-name", "-q", type=str, default=None,
              help="coordinator mode: pump the task tree into this queue "
                   "instead of executing locally (requires --ledger)")
@click.option("--ledger", type=str, default=None,
              help="coordinator mode: completion ledger the workers "
                   "commit to (children's commits unlock parent merges)")
@click.option("--timeout", type=float, default=None,
              help="coordinator mode: give up after this many seconds")
def segment_volume_cmd(input_npy, seg_dir, chunk_size, threshold,
                       connectivity, multivalue, device, workers,
                       mesh_output, queue_name, ledger, timeout):
    """Whole-volume segmentation with exact cross-chunk stitching.

    Local mode (default): label every chunk, merge bottom-up over the
    spatial task tree, relabel — all in this process. Coordinator mode
    (--queue-name + --ledger): enqueue the same work as queue tasks for
    ``fetch-task-from-queue`` workers chaining ``label-chunk``,
    ``merge-seg`` and ``relabel`` stages, and wait for the ledger.
    """
    from chunkflow_tpu.parallel.lifecycle import open_ledger
    from chunkflow_tpu.parallel.queues import open_queue
    from chunkflow_tpu.segment.driver import (
        init_store,
        run_coordinator,
        run_local,
    )

    @generator
    def stage(task):
        store = init_store(
            seg_dir,
            input_npy,
            chunk_size,
            threshold=threshold,
            connectivity=int(connectivity),
            multivalue=multivalue,
            device=device,
            mesh_dir=mesh_output,
        )
        if queue_name is not None:
            if ledger is None:
                raise click.UsageError(
                    "coordinator mode needs --ledger: children's ledger "
                    "commits are what unlock the parent merges"
                )
            summary = run_coordinator(
                store,
                open_queue(queue_name),
                open_ledger(ledger),
                timeout=timeout,
            )
            print(
                f"segment-volume: coordinated {summary['tree_tasks']} "
                f"tree task(s) + {summary['relabel_tasks']} relabel "
                f"task(s) over {len(store.plan.chunks)} chunk(s)"
            )
        else:
            summary = run_local(store, workers=workers)
            print(
                f"segment-volume: {summary['chunks']} chunk(s) labeled, "
                f"{summary['merge_nodes']} merge node(s), relabeled in "
                f"place under {seg_dir}"
            )
        if mesh_output is not None:
            from chunkflow_tpu.flow.mesh import write_manifests

            write_manifests(mesh_output)
        return
        yield  # pragma: no cover

    return stage()


if __name__ == "__main__":
    main()
