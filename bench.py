"""Headline benchmark: 3D affinity patch-inference throughput per chip.

Metric (reference-canonical, flow/log_summary.py): Mvoxel/s of output
produced by the fused patch-inference engine on a 64x512x512 chunk with the
production-style patch config (input 20x256x256, overlap 4x64x64, 3
affinity channels).

Baseline: the only measured GPU datapoint in the reference repo — its
committed production logs (tests/data/log/*.json): aff-inference on a
108x2048x2048 chunk in ~273 s on a TITAN X (Pascal) = 1.66 Mvoxel/s.
``vs_baseline`` is measured_Mvoxel_per_s / 1.66.

``python bench.py`` measures in this one process, on the TPU jax finds,
and prints ONE JSON line naming the device it ran on. With no TPU it
prints no result and exits non-zero: a number from another backend is
not this metric. Configs run headline-first; a config that fails is
named in the row's ``failed`` list with its traceback on stderr.

``python bench.py <gate>`` runs one of the CPU micro-benchmarks (see
``main``); those force the host backend themselves.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time
import traceback

import numpy as np

BASELINE_MVOX_S = 1.66  # TITAN X (Pascal), reference tests/data/log fixtures


CHUNK_SIZE = (64, 512, 512)
INPUT_PATCH = (20, 256, 256)
OUTPUT_OVERLAP = (4, 64, 64)
NUM_OUT = 3

_HERE = os.path.dirname(os.path.abspath(__file__))

# Headline-first: the row reports the best SUCCESSFUL config. All use the
# per-batch scatter blend unless stated; pallas stays last.
CONFIGS = [
    # production pipeline + uint8 EM input riding the narrow H2D path:
    # scatter-free fold blend + pipelined D2H + on-device uint8
    # quantization (exactly the reference's save-time conversion,
    # save_precomputed.py:90-92) — quarter the transfer bytes both ways
    {"model_variant": "tpu", "dtype": "bfloat16", "batch_size": 4,
     "pallas": "0", "stream": 5, "output_dtype": "uint8", "blend": "fold",
     "input_dtype": "uint8"},
    # the flagship program alone
    {"model_variant": "tpu", "dtype": "bfloat16", "batch_size": 4,
     "pallas": "0"},
    # production pipeline without the uint8 input leg
    {"model_variant": "tpu", "dtype": "bfloat16", "batch_size": 4,
     "pallas": "0", "stream": 5, "output_dtype": "uint8", "blend": "fold"},
    # the aggressive (1,4,4) space-to-depth stem: ~half the HBM traffic
    # of the flagship at the same per-voxel FLOPs (docs/performance.md) —
    # the predicted winner if the forward pass is bandwidth-bound
    {"model_variant": "tpu_s2d4", "dtype": "bfloat16", "batch_size": 4,
     "pallas": "0", "stream": 5, "output_dtype": "uint8", "blend": "fold"},
    # fold + pipeline, bfloat16 results (half the D2H bytes)
    {"model_variant": "tpu", "dtype": "bfloat16", "batch_size": 4,
     "pallas": "0", "stream": 5, "output_dtype": "bfloat16",
     "blend": "fold"},
    # pipeline over the scatter blend (fold's A/B partner)
    {"model_variant": "tpu", "dtype": "bfloat16", "batch_size": 4,
     "pallas": "0", "stream": 5, "output_dtype": "bfloat16"},
    # reference-class parity model, float32
    {"model_variant": "parity", "dtype": "float32", "batch_size": 2,
     "pallas": "0"},
    # the fused pallas accumulate kernel
    {"model_variant": "tpu", "dtype": "bfloat16", "batch_size": 4,
     "pallas": "1"},
]


# external override preserved across configs: a cfg's env tweaks apply to
# that config only, then the user's environment value is restored
_ORIG_STACKED = os.environ.get("CHUNKFLOW_BLEND_STACKED")


def run_config(cfg: dict) -> dict:
    os.environ["CHUNKFLOW_PALLAS"] = cfg.get("pallas", "0")
    if "stacked" in cfg:  # opt-in single-trailing-scatter accumulation
        os.environ["CHUNKFLOW_BLEND_STACKED"] = str(cfg["stacked"])
    elif _ORIG_STACKED is not None:
        os.environ["CHUNKFLOW_BLEND_STACKED"] = _ORIG_STACKED
    else:
        os.environ.pop("CHUNKFLOW_BLEND_STACKED", None)
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.inference import Inferencer
    from chunkflow_tpu.ops.pallas_blend import pallas_mode

    # single source of truth for whether the kernel will actually run
    effective = pallas_mode()
    wants = cfg.get("pallas", "0").lower() not in ("0", "off", "false")
    if wants and effective == "off":
        # non-TPU backend: this config would silently run the XLA path
        # and misattribute its numbers to the pallas kernel
        raise RuntimeError("pallas requested but unavailable on this backend")
    if wants:
        _check_pallas_oracle()

    chunk_size = tuple(cfg.get("chunk_size", CHUNK_SIZE))
    rng = np.random.default_rng(0)

    def make_chunk():
        # input_dtype=uint8 mirrors production EM imagery and rides the
        # narrow H2D path (device-side normalize, 1/4 the transfer bytes)
        if cfg.get("input_dtype") == "uint8":
            return Chunk(rng.integers(
                0, 256, chunk_size, dtype=np.uint8))
        return Chunk(rng.random(chunk_size, dtype=np.float32))

    chunk = make_chunk()

    inferencer = Inferencer(
        input_patch_size=INPUT_PATCH,
        output_patch_overlap=tuple(cfg.get("overlap", OUTPUT_OVERLAP)),
        num_output_channels=NUM_OUT,
        framework="flax",
        batch_size=cfg["batch_size"],
        dtype=cfg["dtype"],
        output_dtype=cfg.get("output_dtype", "float32"),
        model_variant=cfg["model_variant"],
        blend=cfg.get("blend", "auto"),
        augment=bool(cfg.get("tta")),
        crop_output_margin=False,
    )

    if cfg.get("blend") == "fold":
        # same misattribution guard as the pallas check above: if the
        # stack budget gates fold off at this shape, the config would
        # silently measure the scatter fallback under a "fold" label
        run = inferencer._run_shape(chunk_size)
        if not inferencer._use_fold(run):
            raise RuntimeError(
                f"fold requested but gated off at shape {run} "
                f"(CHUNKFLOW_BLEND_STACK_MAX_GB too small)"
            )

    # warmup: trace + compile + first run; sanity-check the output
    t0 = time.perf_counter()
    out = inferencer(chunk)
    warmup_s = time.perf_counter() - t0
    arr = np.asarray(out.array)
    assert np.isfinite(arr).all(), "non-finite benchmark output"
    assert arr.std() > 0, "degenerate benchmark output"

    n_stream = int(cfg.get("stream", 0))
    if n_stream:
        chunks = [make_chunk() for _ in range(n_stream)]
        start = time.perf_counter()
        outs = list(inferencer.stream(iter(chunks)))
        total = time.perf_counter() - start
        assert len(outs) == n_stream
        mvox_s = n_stream * float(np.prod(chunk_size)) / total / 1e6
        return {"mvox_s": mvox_s, "warmup_s": round(warmup_s, 1),
                "steady_s": round(total / n_stream, 3),
                "pipelined_chunks": n_stream,
                # retrace accounting in the BENCH record: builds should
                # equal the program-geometry count (1 here), hits the
                # remaining dispatches — a builds>1 row IS the retrace bug
                "cache_builds": inferencer._programs.builds,
                "cache_hits": inferencer._programs.hits}

    times = []
    for _ in range(int(cfg.get("iters", 3))):
        start = time.perf_counter()
        out = inferencer(chunk)
        np.asarray(out.array)  # force host sync
        times.append(time.perf_counter() - start)
    mvox_s = float(np.prod(chunk_size)) / min(times) / 1e6
    return {"mvox_s": mvox_s, "warmup_s": round(warmup_s, 1),
            "steady_s": round(min(times), 3),
            "cache_builds": inferencer._programs.builds,
            "cache_hits": inferencer._programs.hits}


def run_pipeline_overlap(
    n_chunks: int = 6,
    chunk_size=(64, 256, 256),
    input_patch=(16, 64, 64),
    overlap=(4, 16, 16),
    ring: int = 2,
) -> dict:
    """Serial vs double-buffered wall time over N synthetic chunks.

    CPU-safe by construction (identity engine, smoke geometry). The
    synthetic workload models the production chunk loop: per
    chunk a host IO phase (simulated load, calibrated to the measured
    device time so the phases are balanced — the regime the double
    buffer exists for) followed by the fused inference program. The
    serial loop pays io + compute per chunk; the pipelined executor
    (flow/pipeline.py) overlaps chunk k+1's IO/staging with chunk k's
    compute, so ideal speedup approaches 2x; the gate in
    tests/test_bench.py asserts >= 1.2x.
    """
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.flow.pipeline import pipeline_chunks
    from chunkflow_tpu.inference import Inferencer

    # per-benchmark telemetry JSONL (stall attribution of the measured
    # run itself); CHUNKFLOW_TELEMETRY=0 keeps this a no-op
    telemetry.configure(_bench_metrics_dir())

    inferencer = Inferencer(
        input_patch_size=input_patch,
        output_patch_overlap=overlap,
        num_output_channels=3,
        framework="identity",
        batch_size=4,
        crop_output_margin=False,
    )
    rng = np.random.default_rng(0)
    chunks = [
        Chunk(rng.random(chunk_size, dtype=np.float32))
        for _ in range(n_chunks)
    ]

    # warmup (trace + compile), then calibrate the simulated IO phase to
    # the measured steady per-chunk device time (balanced phases are the
    # double buffer's design regime; floor keeps the sleep meaningful)
    np.asarray(inferencer(chunks[0]).array)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(inferencer(chunks[0]).array)
        times.append(time.perf_counter() - t0)
    io_s = max(min(times), 0.02)

    def source():
        for chunk in chunks:
            time.sleep(io_s)  # simulated host load (file/object store)
            yield chunk

    t0 = time.perf_counter()
    serial = [np.asarray(inferencer(c).array) for c in source()]
    serial_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    pipelined = [
        np.asarray(out.array)
        for out in pipeline_chunks(inferencer, source(), ring=ring)
    ]
    pipelined_s = time.perf_counter() - t0

    for a, b in zip(serial, pipelined):
        if not np.array_equal(a, b):
            raise RuntimeError("pipelined output diverged from serial")
    telemetry.flush()
    events_path = telemetry.configured_path()
    telemetry.configure(None)  # close the sink: in-process callers
    # (tests) must not keep streaming unrelated spans into this file
    return {
        "metric": "pipeline_overlap_speedup",
        "value": round(serial_s / pipelined_s, 2),
        "unit": "x_serial",
        "serial_s": round(serial_s, 3),
        "pipelined_s": round(pipelined_s, 3),
        "n_chunks": n_chunks,
        "ring": ring,
        "simulated_io_s": round(io_s, 4),
        "cache_builds": inferencer._programs.builds,
        "cache_hits": inferencer._programs.hits,
        "telemetry_jsonl": events_path,
    }


def _bench_metrics_dir() -> str:
    """Where bench runs append their telemetry JSONL (gitignored;
    aggregate with `chunkflow log-summary --metrics-dir`)."""
    return os.environ.get(
        "CHUNKFLOW_BENCH_METRICS_DIR", os.path.join(_HERE, "telemetry")
    )


# ---------------------------------------------------------------------------
# bench regression ledger (ISSUE 8): every gate measurement appended as one
# JSONL row stamped with the commit it measured, so `bench.py compare` can
# diff a fresh run against the rolling median of the prior rows.
# ---------------------------------------------------------------------------
_LEDGER_FILE: "str | None" = None  # set by --ledger[=PATH] / env


def _default_ledger_path() -> str:
    return os.environ.get(
        "CHUNKFLOW_BENCH_LEDGER",
        os.path.join(_bench_metrics_dir(), "bench_ledger.jsonl"),
    )


def _git_commit() -> str:
    """Short commit hash of the measured tree, best-effort: a ledger row
    that cannot say what code it measured must say so explicitly."""
    try:
        proc = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=_HERE, capture_output=True, text=True, timeout=10,
        )
    except (OSError, subprocess.TimeoutExpired):
        return "unknown"
    if proc.returncode != 0:
        return "unknown"
    return proc.stdout.strip() or "unknown"


def _append_ledger(payload: dict) -> None:
    """Append one measurement row to the ledger (active only under
    --ledger)."""
    if _LEDGER_FILE is None:
        return
    if not isinstance(payload.get("metric"), str) \
            or not isinstance(payload.get("value"), (int, float)):
        return
    row = {
        "t": time.time(),
        "commit": _git_commit(),
        "metric": payload["metric"],
        "value": payload["value"],
        "unit": payload.get("unit"),
        "config": payload.get("config"),
    }
    if payload.get("gate_pass") is not None:
        row["gate_pass"] = payload["gate_pass"]
    try:
        os.makedirs(os.path.dirname(_LEDGER_FILE), exist_ok=True)
        with open(_LEDGER_FILE, "a") as f:
            f.write(json.dumps(row) + "\n")
    except OSError as e:
        print(f"bench ledger unwritable ({_LEDGER_FILE}): {e}",
              file=sys.stderr)


def load_ledger(path: str) -> list:
    """Parse a bench ledger; torn trailing lines are skipped."""
    rows = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if isinstance(row, dict) and isinstance(
                        row.get("metric"), str):
                    rows.append(row)
    except OSError:
        pass
    return rows


def _median(values: list) -> float:
    ordered = sorted(values)
    n = len(ordered)
    mid = n // 2
    return (ordered[mid] if n % 2
            else (ordered[mid - 1] + ordered[mid]) / 2.0)


def compare_ledger(rows: list, threshold_pct: float = 25.0) -> dict:
    """Diff the newest row of every metric against the rolling median of
    its prior rows.

    Hard regressions (``regressions``) need >= 2 prior rows and a drop
    past ``threshold_pct`` on a higher-is-better metric. Percentage-unit
    metrics (overhead gates) are warn-only — on a loaded 1-core box
    their relative deltas are noise-dominated.
    """
    by_metric: dict = {}
    for row in rows:
        by_metric.setdefault(row["metric"], []).append(row)
    report = {"metrics": {}, "regressions": [], "warnings": []}
    for metric, series in sorted(by_metric.items()):
        current = series[-1]
        prior = [
            r for r in series[:-1]
            if isinstance(r.get("value"), (int, float))
        ]
        info = {
            "current": current,
            "prior": len(prior),
            "baseline": None,
            "delta_pct": None,
            "status": "ok",
        }
        report["metrics"][metric] = info
        if not prior:
            info["status"] = "no-baseline"
            continue
        baseline = _median([r["value"] for r in prior])
        info["baseline"] = baseline
        unit = str(current.get("unit") or "")
        lower_better = "pct" in unit
        if baseline == 0:
            info["status"] = "no-baseline"
            continue
        if lower_better:
            delta = (current["value"] - baseline) / abs(baseline) * 100.0
        else:
            delta = (baseline - current["value"]) / abs(baseline) * 100.0
        info["delta_pct"] = round(delta, 2)
        if delta <= threshold_pct:
            continue
        if lower_better:
            info["status"] = "warn"
            report["warnings"].append(
                f"{metric}: {current['value']:g} vs median "
                f"{baseline:g} (+{delta:.0f}% overhead; warn-only — "
                f"percentage gates are load-sensitive)"
            )
        elif len(prior) >= 2:
            info["status"] = "regression"
            report["regressions"].append(
                f"{metric}: {current['value']:g} vs median "
                f"{baseline:g} (-{delta:.0f}%, threshold "
                f"{threshold_pct:g}%, {len(prior)} baseline rows)"
            )
        else:
            info["status"] = "warn"
            report["warnings"].append(
                f"{metric}: {current['value']:g} vs single prior row "
                f"{baseline:g} (-{delta:.0f}%; need >= 2 prior rows "
                f"for a hard verdict)"
            )
    return report


def compare_main(argv: list) -> int:
    """``bench.py compare [--ledger=PATH] [--threshold PCT]``: rc 0 on
    ok/warnings, 4 on a regression past the threshold."""
    path = _default_ledger_path()
    threshold = 25.0
    it = iter(argv)
    for arg in it:
        if arg.startswith("--ledger="):
            path = arg.split("=", 1)[1]
        elif arg == "--ledger":
            path = next(it, path)
        elif arg.startswith("--threshold="):
            threshold = float(arg.split("=", 1)[1])
        elif arg == "--threshold":
            threshold = float(next(it, threshold))
    rows = load_ledger(path)
    if not rows:
        print(f"bench compare: no ledger rows at {path} (run the gates "
              f"with --ledger first)")
        return 0
    report = compare_ledger(rows, threshold_pct=threshold)
    print(f"bench compare: {len(rows)} row(s) from {path} "
          f"(threshold {threshold:g}%)")
    for metric, info in report["metrics"].items():
        cur = info["current"]
        line = (f"  {metric:<32} {cur.get('value'):>8g} "
                f"[{cur.get('commit') or '?'}]")
        if info["baseline"] is not None:
            line += f" vs median {info['baseline']:g}"
        if info["delta_pct"] is not None:
            line += f" ({info['delta_pct']:+g}% worse)" \
                if info["delta_pct"] > 0 \
                else f" ({-info['delta_pct']:+g}% better)"
        line += f" {info['status']}"
        print(line)
    for warning in report["warnings"]:
        print(f"  WARN {warning}")
    for regression in report["regressions"]:
        print(f"  REGRESSION {regression}")
    return 4 if report["regressions"] else 0


def run_telemetry_overhead(
    n_chunks: int = 6,
    chunk_size=(64, 256, 256),
    input_patch=(16, 64, 64),
    overlap=(4, 16, 16),
    ring: int = 2,
) -> dict:
    """Telemetry-on vs telemetry-off wall time over the pipeline_overlap
    workload (identity engine, calibrated simulated IO, double-buffered
    executor) — the ISSUE 3 overhead gate: telemetry-on must cost <2%.

    Best-of-2 per leg, off leg measured first so a warmed process cannot
    flatter the on leg. Exit semantics (main): the 2% target is reported
    as ``gate_pass``; only a gross regression (>10%, far past any
    shared-box noise) fails the process — the tight bound is asserted
    where the clock is trustworthy, not on a loaded CI runner.
    """
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.flow.pipeline import pipeline_chunks
    from chunkflow_tpu.inference import Inferencer

    inferencer = Inferencer(
        input_patch_size=input_patch,
        output_patch_overlap=overlap,
        num_output_channels=3,
        framework="identity",
        batch_size=4,
        crop_output_margin=False,
    )
    rng = np.random.default_rng(0)
    chunks = [
        Chunk(rng.random(chunk_size, dtype=np.float32))
        for _ in range(n_chunks)
    ]
    np.asarray(inferencer(chunks[0]).array)  # warmup: trace + compile
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(inferencer(chunks[0]).array)
        times.append(time.perf_counter() - t0)
    io_s = max(min(times), 0.02)

    def source():
        for chunk in chunks:
            time.sleep(io_s)  # simulated host load
            yield chunk

    def timed_run() -> float:
        t0 = time.perf_counter()
        for out in pipeline_chunks(inferencer, source(), ring=ring):
            np.asarray(out.array)
        return time.perf_counter() - t0

    prev = os.environ.get("CHUNKFLOW_TELEMETRY")
    try:
        os.environ["CHUNKFLOW_TELEMETRY"] = "0"
        timed_run()  # warm the executor path itself
        off_s = min(timed_run() for _ in range(2))
        os.environ["CHUNKFLOW_TELEMETRY"] = "1"
        telemetry.configure(_bench_metrics_dir())
        on_s = min(timed_run() for _ in range(2))
        telemetry.flush()
        events_path = telemetry.configured_path()
        telemetry.configure(None)  # close the sink (in-process callers)
    finally:
        if prev is None:
            os.environ.pop("CHUNKFLOW_TELEMETRY", None)
        else:
            os.environ["CHUNKFLOW_TELEMETRY"] = prev
    overhead_pct = (on_s - off_s) / off_s * 100.0
    return {
        "metric": "telemetry_overhead",
        "value": round(overhead_pct, 2),
        "unit": "pct_of_untelemetered_wall",
        "on_s": round(on_s, 3),
        "off_s": round(off_s, 3),
        "n_chunks": n_chunks,
        "gate_pct": 2.0,
        "gate_pass": overhead_pct < 2.0,
        "telemetry_jsonl": events_path,
    }


def run_e2e_overlap(
    n_tasks: int = 8,
    chunk_size=(64, 256, 256),
    input_patch=(16, 64, 64),
    overlap=(4, 16, 16),
) -> dict:
    """Serial vs scheduled wall time over the FULL task lifecycle:
    load → H2D → device compute → D2H → host post-processing → async
    storage write (ISSUE 4). CPU-safe: identity engine, smoke geometry,
    and simulated load/post/write latencies each calibrated to the
    measured per-chunk device time — the balanced regime where every
    phase matters and the reference's serial loop pays 4x.

    The serial leg is the reference loop (load, synchronous inference,
    post, commit-before-next-task). The scheduled leg runs the same work
    through the adaptive scheduler's full stage chain
    (flow/scheduler.py): prefetch thread + staging ring + worker-pool
    post + write-behind window. Outputs are asserted bit-identical; the
    gate in tests/test_bench.py requires >= 1.4x. The run's telemetry
    JSONL (stall spans, depth_change events, a final ``depths`` event)
    lands under the bench metrics dir, and the JSON line reports the
    final adapted depths.
    """
    from concurrent.futures import ThreadPoolExecutor

    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.flow.runtime import new_task
    from chunkflow_tpu.flow.scheduler import (
        DepthController,
        scheduled_inference_stage,
        write_behind_stage,
    )
    from chunkflow_tpu.inference import Inferencer

    telemetry.configure(_bench_metrics_dir())

    inferencer = Inferencer(
        input_patch_size=input_patch,
        output_patch_overlap=overlap,
        num_output_channels=3,
        framework="identity",
        batch_size=4,
        crop_output_margin=False,
    )
    rng = np.random.default_rng(0)
    chunks = [
        Chunk(rng.random(chunk_size, dtype=np.float32))
        for _ in range(n_tasks)
    ]

    # warmup (trace + compile), then calibrate every simulated host phase
    # to the measured steady per-chunk device time (floor keeps the
    # sleeps meaningful on a fast box)
    np.asarray(inferencer(chunks[0]).array)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(inferencer(chunks[0]).array)
        times.append(time.perf_counter() - t0)
    phase_s = max(min(times), 0.02)

    write_pool = ThreadPoolExecutor(max_workers=8)

    def post_fn(chunk):
        time.sleep(phase_s)  # simulated connected-components / downsample
        return chunk

    # --- serial leg: the reference loop ---------------------------------
    t0 = time.perf_counter()
    serial = []
    for chunk in chunks:
        time.sleep(phase_s)  # simulated storage read
        out = post_fn(inferencer(chunk))
        serial.append(np.asarray(out.array))
        # commit-before-next-task: the write is async but the loop waits
        write_pool.submit(time.sleep, phase_s).result()
    serial_s = time.perf_counter() - t0

    # --- scheduled leg: the full adaptive stage chain -------------------
    inf_ctl = DepthController()
    write_ctl = DepthController()

    def source(stream):
        for _seed in stream:
            for i, chunk in enumerate(chunks):
                time.sleep(phase_s)  # simulated storage read
                task = new_task()
                task["chunk"] = chunk
                task["i"] = i
                yield task

    def attach_write(stream):
        for task in stream:
            if task is not None:
                # simulated async storage commit latency
                task.setdefault("pending_writes", []).append(
                    write_pool.submit(time.sleep, phase_s))
            yield task

    stages = [
        source,
        scheduled_inference_stage(
            inferencer, postprocess=post_fn, controller=inf_ctl,
            op_name="inference",
        ),
        attach_write,
        write_behind_stage(controller=write_ctl),
    ]
    t0 = time.perf_counter()
    stream = iter([new_task()])
    for stage in stages:
        stream = stage(stream)
    scheduled = [(task["i"], np.asarray(task["chunk"].array))
                 for task in stream]
    scheduled_s = time.perf_counter() - t0

    if [i for i, _ in scheduled] != list(range(n_tasks)):
        raise RuntimeError(f"task order broken: {[i for i, _ in scheduled]}")
    for ref, (_, out) in zip(serial, scheduled):
        if not np.array_equal(ref, out):
            raise RuntimeError("scheduled output diverged from serial")
    write_pool.shutdown(wait=False)

    final_depths = dict(inf_ctl.depths, write=write_ctl.depths["write"])
    telemetry.event("depths", "scheduler/final", **final_depths)
    telemetry.flush()
    events_path = telemetry.configured_path()
    telemetry.configure(None)  # close the sink (in-process callers)
    speedup = serial_s / scheduled_s
    return {
        "metric": "e2e_overlap_speedup",
        "value": round(speedup, 2),
        "unit": "x_serial",
        "serial_s": round(serial_s, 3),
        "scheduled_s": round(scheduled_s, 3),
        "n_tasks": n_tasks,
        "phase_s": round(phase_s, 4),
        "final_depths": final_depths,
        "depth_changes": len(inf_ctl.changes) + len(write_ctl.changes),
        "gate_x": 1.4,
        "gate_pass": speedup >= 1.4,
        "telemetry_jsonl": events_path,
    }


def run_locksmith_overhead(
    n_tasks: int = 6,
    chunk_size=(64, 256, 256),
    input_patch=(16, 64, 64),
    overlap=(4, 16, 16),
) -> dict:
    """Locksmith-on vs -off wall time over the e2e_overlap scheduled
    workload (ISSUE 10): the lock-order sanitizer
    (chunkflow_tpu/testing/locksmith.py) instruments every
    Lock/Condition the adaptive scheduler's stage chain creates —
    prefetch pump conditions, worker pools, write-behind — so this is
    the densest proxied-lock traffic the repo has. Target <5% (reported
    as gate_pass); the process only fails past 25% (a pathological
    regression in the proxy hot path), so shared-box noise cannot
    redden CI. Each leg constructs its own Inferencer/stage chain so
    every lock is created under that leg's install state; the run also
    cross-checks that the full scheduled path raises no lock-order
    violation (it would crash the bench in raise mode — the same
    no-false-positives contract tier-1 enforces).
    """
    from concurrent.futures import ThreadPoolExecutor

    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.flow.runtime import new_task
    from chunkflow_tpu.flow.scheduler import (
        DepthController,
        scheduled_inference_stage,
        write_behind_stage,
    )
    from chunkflow_tpu.inference import Inferencer
    from chunkflow_tpu.testing import locksmith

    rng = np.random.default_rng(0)
    chunks = [
        Chunk(rng.random(chunk_size, dtype=np.float32))
        for _ in range(n_tasks)
    ]

    def timed_leg() -> float:
        # everything lock-bearing is constructed INSIDE the leg, so
        # each leg's locks are created under its install state
        inferencer = Inferencer(
            input_patch_size=input_patch,
            output_patch_overlap=overlap,
            num_output_channels=3,
            framework="identity",
            batch_size=4,
            crop_output_margin=False,
        )
        np.asarray(inferencer(chunks[0]).array)  # warmup trace+compile
        times = []
        for _ in range(2):
            t0 = time.perf_counter()
            np.asarray(inferencer(chunks[0]).array)
            times.append(time.perf_counter() - t0)
        phase_s = max(min(times), 0.02)
        write_pool = ThreadPoolExecutor(max_workers=8)

        def post_fn(chunk):
            time.sleep(phase_s)  # simulated host post-processing
            return chunk

        def source(stream):
            for _seed in stream:
                for i, chunk in enumerate(chunks):
                    time.sleep(phase_s)  # simulated storage read
                    task = new_task()
                    task["chunk"] = chunk
                    task["i"] = i
                    yield task

        def attach_write(stream):
            for task in stream:
                if task is not None:
                    task.setdefault("pending_writes", []).append(
                        write_pool.submit(time.sleep, phase_s))
                yield task

        stages = [
            source,
            scheduled_inference_stage(
                inferencer, postprocess=post_fn,
                controller=DepthController(), op_name="inference",
            ),
            attach_write,
            write_behind_stage(controller=DepthController()),
        ]
        t0 = time.perf_counter()
        stream = iter([new_task()])
        for stage in stages:
            stream = stage(stream)
        for _task in stream:
            pass
        leg_s = time.perf_counter() - t0
        write_pool.shutdown(wait=False)
        return leg_s

    prev = os.environ.get("CHUNKFLOW_LOCKSMITH")
    try:
        os.environ["CHUNKFLOW_LOCKSMITH"] = "0"
        locksmith.uninstall()
        timed_leg()  # warm the executor path itself
        off_s = min(timed_leg() for _ in range(2))
        os.environ["CHUNKFLOW_LOCKSMITH"] = "1"
        locksmith.install()
        on_s = min(timed_leg() for _ in range(2))
        snap = locksmith.report()
    finally:
        locksmith.uninstall()
        if prev is None:
            os.environ.pop("CHUNKFLOW_LOCKSMITH", None)
        else:
            os.environ["CHUNKFLOW_LOCKSMITH"] = prev
    overhead_pct = (on_s - off_s) / off_s * 100.0
    return {
        "metric": "locksmith_overhead",
        "value": round(overhead_pct, 2),
        "unit": "pct_of_unsanitized_wall",
        "on_s": round(on_s, 3),
        "off_s": round(off_s, 3),
        "proxied_locks": snap["locks"],
        "acquires": snap["acquires"],
        "order_edges": snap["edges"],
        "violations": len(snap["violations"]),
        "n_tasks": n_tasks,
        "gate_pct": 5.0,
        "gate_pass": overhead_pct < 5.0,
    }


def run_kernelcheck_overhead(
    B: int = 8,
    co: int = 3,
    pout=(3, 16, 32),
    reps: int = 3,
) -> dict:
    """Kernelcheck-on vs -off wall time over the interpret-mode Pallas
    legs the tier-1 parity suites run (ISSUE 16): the sanitizer's poison
    writes, bounds callback and NaN sweep all ride the traced program,
    so this is the cost every CI interpret test pays for running with
    the kernel sanitizer live (tests/conftest.py defaults it ON).
    Target <5% (reported as gate_pass); the process only fails past 25%
    (the sanitizer landed work somewhere hot), so shared-box noise
    cannot redden CI. Each leg re-traces its own programs — the ``+kc``
    cache-tag suffix means on/off builds can never share a compiled
    program — and the on leg cross-checks that the clean workload
    raises no violation (the same no-false-positives contract tier-1
    enforces). The 5% gate holds because observe_grid's per-grid-step
    RMW-trace callback is gated at TRACE time on arm_grid_trace
    (ISSUE 17): unarmed runs — this bench, all of tier-1 — carry only
    the poison writes plus one bounds and one NaN callback per
    invocation.
    """
    import jax
    import jax.numpy as jnp

    from chunkflow_tpu.ops import pallas_blend, pallas_gather
    from chunkflow_tpu.testing import kernelcheck

    rng = np.random.default_rng(0)
    pz, py, px = pout
    pad_y, pad_x = pallas_blend.buffer_padding(pout)
    Z, Y, X = pz + 4, py * 3, px * 3
    out = np.zeros((co, Z, Y + pad_y, X + pad_x), np.float32)
    weight = np.zeros((Z, Y + pad_y, X + pad_x), np.float32)
    preds = rng.standard_normal((B, co) + pout).astype(np.float32)
    bump = (rng.random(pout) * 5 + 1).astype(np.float32)
    valid = np.ones((B,), np.float32)
    out_starts = np.stack([
        rng.integers(0, Z - pz, B), rng.integers(0, Y - py, B),
        rng.integers(0, X - px, B),
    ], axis=1).astype(np.int32)

    ci, pin = 2, pout
    g_pad_y, g_pad_x = pallas_gather.gather_buffer_padding(pin, np.uint8)
    raw = rng.integers(0, 256, (ci, Z, Y, X), dtype=np.uint8)
    chunk = np.pad(raw, [(0, 0), (0, 0), (0, g_pad_y), (0, g_pad_x)])
    in_starts = out_starts.copy()

    def timed_leg() -> float:
        # fresh device arrays per leg; every call re-traces, so each
        # leg's programs are built under its own env state
        args_b = tuple(jnp.asarray(a) for a in (
            out, weight, preds, valid, bump, out_starts))
        args_g = (jnp.asarray(chunk), jnp.asarray(in_starts))
        t0 = time.perf_counter()
        for _ in range(reps):
            o, w = pallas_blend.fused_accumulate_patches(
                *args_b, interpret=True)
            stack = pallas_gather.gather_patches(
                *args_g, pin, interpret=True)
            jax.block_until_ready((o, w, stack))
        return time.perf_counter() - t0

    prev = os.environ.get("CHUNKFLOW_KERNELCHECK")
    try:
        os.environ["CHUNKFLOW_KERNELCHECK"] = "0"
        timed_leg()  # warm jax/pallas interpret machinery itself
        off_s = min(timed_leg() for _ in range(2))
        os.environ["CHUNKFLOW_KERNELCHECK"] = "1"
        kernelcheck.reset_state()
        on_s = min(timed_leg() for _ in range(2))
        snap = kernelcheck.report()
    finally:
        kernelcheck.reset_state()
        if prev is None:
            os.environ.pop("CHUNKFLOW_KERNELCHECK", None)
        else:
            os.environ["CHUNKFLOW_KERNELCHECK"] = prev
    if snap["violations"]:
        raise RuntimeError(
            f"kernelcheck_overhead: sanitizer flagged a CLEAN workload: "
            f"{snap['violations']}")
    overhead_pct = (on_s - off_s) / off_s * 100.0
    return {
        "metric": "kernelcheck_overhead",
        "value": round(overhead_pct, 2),
        "unit": "pct_of_unsanitized_wall",
        "on_s": round(on_s, 3),
        "off_s": round(off_s, 3),
        "checks": snap["checks"],
        "violations": 0,
        "reps": reps,
        "gate_pct": 5.0,
        "gate_pass": overhead_pct < 5.0,
    }


def run_slo_overhead(
    n_tasks: int = 6,
    chunk_size=(64, 256, 256),
    input_patch=(16, 64, 64),
    overlap=(4, 16, 16),
) -> dict:
    """SLO plane on vs off over the e2e scheduled workload (ISSUE 12):
    the time-series ring sampler (core/telemetry.start_timeseries, run
    here at an aggressive 0.1 s interval — 100x the production default)
    plus the burn-rate evaluator (core/slo.start_slo, default
    objectives) against the same telemetered run without them. Both
    legs keep telemetry + a JSONL sink ON, so the number is the SLO
    plane's *marginal* cost, not telemetry's. Target <2% (reported as
    gate_pass); the process only fails past 10% (the sampler landed a
    lock on the per-task hot path), so shared-box noise cannot redden
    CI. The on leg also sanity-checks the plane actually ran: at least
    one time-series sample must exist and no alert may fire on this
    healthy workload.
    """
    from concurrent.futures import ThreadPoolExecutor

    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import slo, telemetry
    from chunkflow_tpu.flow.runtime import new_task
    from chunkflow_tpu.flow.scheduler import (
        DepthController,
        scheduled_inference_stage,
        write_behind_stage,
    )
    from chunkflow_tpu.inference import Inferencer

    rng = np.random.default_rng(0)
    chunks = [
        Chunk(rng.random(chunk_size, dtype=np.float32))
        for _ in range(n_tasks)
    ]

    inferencer = Inferencer(
        input_patch_size=input_patch,
        output_patch_overlap=overlap,
        num_output_channels=3,
        framework="identity",
        batch_size=4,
        crop_output_margin=False,
    )
    np.asarray(inferencer(chunks[0]).array)  # warmup: trace + compile
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        np.asarray(inferencer(chunks[0]).array)
        times.append(time.perf_counter() - t0)
    phase_s = max(min(times), 0.02)

    def timed_leg(slo_on: bool) -> float:
        telemetry.reset()  # stops any sampler/evaluator from a prior leg
        telemetry.configure(_bench_metrics_dir())
        if slo_on:
            telemetry.start_timeseries(interval=0.1)
            slo.start_slo()
        write_pool = ThreadPoolExecutor(max_workers=8)

        def post_fn(chunk):
            time.sleep(phase_s)  # simulated host post-processing
            return chunk

        def source(stream):
            for _seed in stream:
                for i, chunk in enumerate(chunks):
                    time.sleep(phase_s)  # simulated storage read
                    task = new_task()
                    task["chunk"] = chunk
                    task["i"] = i
                    yield task

        def attach_write(stream):
            for task in stream:
                if task is not None:
                    task.setdefault("pending_writes", []).append(
                        write_pool.submit(time.sleep, phase_s))
                yield task

        stages = [
            source,
            scheduled_inference_stage(
                inferencer, postprocess=post_fn,
                controller=DepthController(), op_name="inference",
            ),
            attach_write,
            write_behind_stage(controller=DepthController()),
        ]
        t0 = time.perf_counter()
        stream = iter([new_task()])
        for stage in stages:
            stream = stage(stream)
        for _task in stream:
            pass
        leg_s = time.perf_counter() - t0
        write_pool.shutdown(wait=False)
        if slo_on:
            series = telemetry.timeseries()
            evaluator = slo.current()
            firing = evaluator.firing() if evaluator is not None else None
            if not telemetry.timeseries_running() or evaluator is None:
                raise RuntimeError("slo_overhead: SLO plane did not run "
                                   "in the on leg")
            if not series:
                raise RuntimeError("slo_overhead: sampler took no "
                                   "samples during the on leg")
            if firing:
                raise RuntimeError(
                    f"slo_overhead: healthy workload fired {firing}")
        telemetry.reset()
        return leg_s

    timed_leg(False)  # warm the executor path itself
    off_s = min(timed_leg(False) for _ in range(2))
    on_s = min(timed_leg(True) for _ in range(2))
    overhead_pct = (on_s - off_s) / off_s * 100.0
    return {
        "metric": "slo_overhead",
        "value": round(overhead_pct, 2),
        "unit": "pct_of_unsampled_wall",
        "on_s": round(on_s, 3),
        "off_s": round(off_s, 3),
        "n_tasks": n_tasks,
        "gate_pct": 2.0,
        "gate_pass": overhead_pct < 2.0,
    }


def run_export_overhead(
    n_tasks: int = 6,
    chunk_size=(32, 128, 128),
    input_patch=(16, 64, 64),
    overlap=(4, 16, 16),
    repeats: int = 2,
    scrape_interval_s: float = 0.05,
) -> dict:
    """Wall-clock cost of the live /metrics exporter (ISSUE 6): the
    e2e_overlap-style scheduled chain run with the exporter OFF vs ON —
    where "on" means a live HTTP listener being scraped continuously
    (every ``scrape_interval_s``, far hotter than a real supervisor's
    poll cadence) while tasks flow. The exporter serves registry
    *snapshots*, so the only hot-path cost candidates are the snapshot
    lock and the GIL time of the server thread; the gate keeps both
    honest. Gate: < 2% (reported as gate_pass; the process only
    hard-fails past 10% — shared-box noise must not redden CI)."""
    from concurrent.futures import ThreadPoolExecutor

    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.flow.runtime import new_task
    from chunkflow_tpu.flow.scheduler import (
        DepthController,
        scheduled_inference_stage,
        write_behind_stage,
    )
    from chunkflow_tpu.inference import Inferencer
    from chunkflow_tpu.parallel.restapi import (
        scrape_worker,
        start_metrics_exporter,
    )

    telemetry.configure(_bench_metrics_dir())

    inferencer = Inferencer(
        input_patch_size=input_patch,
        output_patch_overlap=overlap,
        num_output_channels=3,
        framework="identity",
        batch_size=4,
        crop_output_margin=False,
    )
    rng = np.random.default_rng(0)
    chunks = [
        Chunk(rng.random(chunk_size, dtype=np.float32))
        for _ in range(n_tasks)
    ]

    # warmup + calibrate the simulated host phases to device time
    np.asarray(inferencer(chunks[0]).array)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(inferencer(chunks[0]).array)
        times.append(time.perf_counter() - t0)
    phase_s = max(min(times), 0.02)

    write_pool = ThreadPoolExecutor(max_workers=8)

    def post_fn(chunk):
        time.sleep(phase_s)
        return chunk

    def run_chain() -> float:
        def source(stream):
            for _seed in stream:
                for i, chunk in enumerate(chunks):
                    time.sleep(phase_s)  # simulated storage read
                    task = new_task()
                    task["chunk"] = chunk
                    task["i"] = i
                    yield task

        def attach_write(stream):
            for task in stream:
                if task is not None:
                    task.setdefault("pending_writes", []).append(
                        write_pool.submit(time.sleep, phase_s))
                yield task

        stages = [
            source,
            scheduled_inference_stage(
                inferencer, postprocess=post_fn,
                controller=DepthController(), op_name="inference",
            ),
            attach_write,
            write_behind_stage(controller=DepthController()),
        ]
        t0 = time.perf_counter()
        stream = iter([new_task()])
        for stage in stages:
            stream = stage(stream)
        order = [task["i"] for task in stream]
        elapsed = time.perf_counter() - t0
        if order != list(range(n_tasks)):
            raise RuntimeError(f"task order broken: {order}")
        return elapsed

    run_chain()  # warm the executor path itself
    off_s = min(run_chain() for _ in range(repeats))

    server = start_metrics_exporter(0, host="127.0.0.1")
    if server is None:
        raise RuntimeError(
            "exporter did not start (is CHUNKFLOW_TELEMETRY=0 set?)"
        )
    endpoint = "127.0.0.1:%d" % server.server_address[1]
    stop_scraping = threading.Event()
    scrapes = [0]

    def scraper():
        while not stop_scraping.wait(scrape_interval_s):
            sample = scrape_worker(endpoint, timeout=2.0)
            if sample["error"] is None:
                scrapes[0] += 1

    scraper_thread = threading.Thread(target=scraper, daemon=True)
    scraper_thread.start()
    try:
        on_s = min(run_chain() for _ in range(repeats))
    finally:
        stop_scraping.set()
        scraper_thread.join(timeout=5.0)
        server.shutdown()
        server.server_close()
        write_pool.shutdown(wait=False)

    telemetry.flush()
    events_path = telemetry.configured_path()
    telemetry.configure(None)
    overhead_pct = (on_s / off_s - 1.0) * 100.0
    return {
        "metric": "export_overhead",
        "value": round(overhead_pct, 2),
        "unit": "pct_vs_unexported",
        "off_s": round(off_s, 3),
        "on_s": round(on_s, 3),
        "n_tasks": n_tasks,
        "repeats": repeats,
        "scrapes": scrapes[0],
        "phase_s": round(phase_s, 4),
        "gate_pct": 2.0,
        "gate_pass": overhead_pct < 2.0,
        "telemetry_jsonl": events_path,
    }


def run_resilience_overhead(
    n_tasks: int = 8,
    chunk_size=(32, 128, 128),
    input_patch=(16, 64, 64),
    overlap=(4, 16, 16),
    repeats: int = 3,
) -> dict:
    """Wall-clock cost of the fault-tolerance layer (ISSUE 5): the same
    queue-fed e2e_overlap-style chain — simulated storage read,
    adaptive-scheduled inference, simulated post + async write,
    ack-after-durable-write — run with the lifecycle machinery OFF
    (plain fetch + delete) vs ON (supervised claims + FileLedger
    done-markers + lease heartbeat + supervised commit). Both legs pay
    the queue and ack; the delta is exactly the insurance: ledger
    check/mark, heartbeat thread, retry accounting. Gate: < 3% overhead
    (reported as gate_pass; the process only hard-fails past 15% —
    shared-box noise must not redden CI, a real regression must).
    Best-of-``repeats`` per leg for the same reason."""
    import shutil
    import tempfile
    from concurrent.futures import ThreadPoolExecutor

    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.flow.runtime import drain_pending_writes, new_task
    from chunkflow_tpu.flow.scheduler import (
        DepthController,
        scheduled_inference_stage,
        write_behind_stage,
    )
    from chunkflow_tpu.inference import Inferencer
    from chunkflow_tpu.parallel.lifecycle import (
        FileLedger,
        LifecycleSupervisor,
    )
    from chunkflow_tpu.parallel.queues import MemoryQueue

    telemetry.configure(_bench_metrics_dir())

    inferencer = Inferencer(
        input_patch_size=input_patch,
        output_patch_overlap=overlap,
        num_output_channels=3,
        framework="identity",
        batch_size=4,
        crop_output_margin=False,
    )
    rng = np.random.default_rng(0)
    chunks = [
        Chunk(rng.random(chunk_size, dtype=np.float32))
        for _ in range(n_tasks)
    ]
    bodies = [f"task-{i}" for i in range(n_tasks)]

    # warmup + calibrate the simulated host phases to device time
    np.asarray(inferencer(chunks[0]).array)
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(inferencer(chunks[0]).array)
        times.append(time.perf_counter() - t0)
    phase_s = max(min(times), 0.02)

    write_pool = ThreadPoolExecutor(max_workers=8)
    scratch = tempfile.mkdtemp(prefix="chunkflow-resilience-")
    leg_seq = 0

    def post_fn(chunk):
        time.sleep(phase_s)
        return chunk

    def run_leg(lifecycle_on: bool) -> float:
        nonlocal leg_seq
        leg_seq += 1
        queue = MemoryQueue(f"resilience-{leg_seq}", visibility_timeout=600)
        queue.send_messages(bodies)
        queue.retry_sleep = 0.001
        queue.max_empty_retries = 2
        index = {body: i for i, body in enumerate(bodies)}
        supervisor = (
            LifecycleSupervisor(
                queue,
                ledger=FileLedger(os.path.join(scratch, f"ledger-{leg_seq}")),
                max_retries=3,
                lease_renew=0.2,
            )
            if lifecycle_on else None
        )

        def source(stream):
            for _seed in stream:
                if supervisor is not None:
                    for lc in supervisor.tasks(num=n_tasks):
                        time.sleep(phase_s)  # simulated storage read
                        task = new_task()
                        task["chunk"] = chunks[index[lc.body]]
                        task["i"] = index[lc.body]
                        task["lifecycle"] = lc
                        lc.task = task
                        yield task
                else:
                    pulled = 0
                    for handle, body in queue:
                        time.sleep(phase_s)
                        task = new_task()
                        task["chunk"] = chunks[index[body]]
                        task["i"] = index[body]
                        task["task_handle"] = handle
                        yield task
                        pulled += 1
                        if pulled >= n_tasks:  # symmetric with num=
                            break

        def attach_write(stream):
            for task in stream:
                if task is not None:
                    task.setdefault("pending_writes", []).append(
                        write_pool.submit(time.sleep, phase_s))
                yield task

        def ack(stream):
            # ack-after-durable-write in both legs: the commit point is
            # shared cost, the ledger/heartbeat delta is what we measure
            for task in stream:
                if task is not None:
                    if lifecycle_on:
                        task["lifecycle"].commit(task)
                    else:
                        drain_pending_writes(task)
                        queue.delete(task["task_handle"])
                yield task

        stages = [
            source,
            scheduled_inference_stage(
                inferencer, postprocess=post_fn,
                controller=DepthController(), op_name="inference",
            ),
            attach_write,
            ack,
            write_behind_stage(controller=DepthController()),
        ]
        t0 = time.perf_counter()
        stream = iter([new_task()])
        for stage in stages:
            stream = stage(stream)
        order = [task["i"] for task in stream]
        elapsed = time.perf_counter() - t0
        if order != list(range(n_tasks)):
            raise RuntimeError(f"task order broken: {order}")
        if len(queue) != 0 or queue.invisible:
            raise RuntimeError("queue not drained cleanly")
        if supervisor is not None:
            marks = supervisor.ledger.keys()
            if sorted(marks) != sorted(bodies):
                raise RuntimeError(
                    f"ledger incomplete: {len(marks)}/{n_tasks} markers"
                )
        return elapsed

    try:
        off_s = min(run_leg(False) for _ in range(repeats))
        on_s = min(run_leg(True) for _ in range(repeats))
    finally:
        write_pool.shutdown(wait=False)
        shutil.rmtree(scratch, ignore_errors=True)

    telemetry.flush()
    events_path = telemetry.configured_path()
    telemetry.configure(None)
    overhead_pct = (on_s / off_s - 1.0) * 100.0
    return {
        "metric": "resilience_overhead",
        "value": round(overhead_pct, 2),
        "unit": "pct_vs_unsupervised",
        "off_s": round(off_s, 3),
        "on_s": round(on_s, 3),
        "n_tasks": n_tasks,
        "repeats": repeats,
        "phase_s": round(phase_s, 4),
        "gate_pct": 3.0,
        "gate_pass": overhead_pct < 3.0,
        "telemetry_jsonl": events_path,
    }


def run_serving_throughput(
    n_requests: int = 16,
    rounds: int = 3,
) -> dict:
    """Packed cross-request batching vs sequential per-chunk execution
    on many small concurrent requests (ISSUE 9, CI gate): each request
    carries 3 patches against a device batch of 8, so the per-chunk
    fused program runs every forward batch at 37.5% occupancy while the
    packer fills batches across requests. Gate: >= 1.3x wall-clock
    speedup (reported as ``gate_pass``); the process only fails below
    1.1x — the packer lost its occupancy win outright.

    The engine is a calibrated matmul tower (same compiled work per
    batch on either path), so the speedup measured is occupancy, not
    engine luck; correctness is asserted bitwise against the per-chunk
    reference on every round."""
    import jax.numpy as jnp

    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.inference import Inferencer, engines
    from chunkflow_tpu.serve.packer import PatchPacker

    pin = (4, 16, 16)
    features = int(np.prod(pin))
    rng = np.random.default_rng(0)
    weights = jnp.asarray(
        rng.standard_normal((features, features)).astype(np.float32)
        / np.sqrt(features)
    )

    def apply(params, batch):
        x = batch.reshape(batch.shape[0], -1)
        # enough compiled work per batch (~ms) that the measured ratio
        # is forward-batch count — i.e. occupancy — not dispatch noise
        for _ in range(8):
            x = jnp.tanh(x @ params)
        return x.reshape((batch.shape[0], 1) + pin)

    inferencer = Inferencer(
        input_patch_size=pin,
        num_output_channels=1,
        framework="prebuilt",
        engine=engines.Engine(
            params=weights, apply=apply,
            num_input_channels=1, num_output_channels=1,
        ),
        batch_size=8,
        crop_output_margin=False,
    )
    # (4, 16, 48) with zero overlap -> exactly 3 patches per request:
    # the per-chunk path pads every forward batch 3/8 full
    chunks = [
        Chunk(rng.random((4, 16, 48), dtype=np.float32),
              voxel_offset=(i * 8, 0, 0))
        for i in range(n_requests)
    ]
    refs = [np.asarray(inferencer(c).array) for c in chunks]  # + warmup
    packer = PatchPacker(inferencer, max_wait_ms=4.0)
    np.asarray(packer.infer(chunks[0]).array)  # warm the serve programs

    telemetry.reset()
    seq_s = packed_s = None
    for _ in range(rounds):
        t0 = time.perf_counter()
        outs = [np.asarray(inferencer(c).array) for c in chunks]
        dt = time.perf_counter() - t0
        seq_s = dt if seq_s is None else min(seq_s, dt)
        for ref, out in zip(refs, outs):
            if not np.array_equal(ref, out):
                raise RuntimeError("serving bench: per-chunk round "
                                   "diverged from reference")
        t0 = time.perf_counter()
        handles = [packer.submit(c) for c in chunks]
        outs = [np.asarray(h.result(timeout=120).array) for h in handles]
        dt = time.perf_counter() - t0
        packed_s = dt if packed_s is None else min(packed_s, dt)
        for ref, out in zip(refs, outs):
            if not np.array_equal(ref, out):
                raise RuntimeError(
                    "serving bench: packed output NOT bit-identical to "
                    "the per-chunk path")
    packer.close()
    snap = telemetry.snapshot()
    batches = snap["counters"].get("serving/batches", 0)
    packed_patches = snap["counters"].get("serving/packed_patches", 0)
    occupancy = (packed_patches / (batches * inferencer.batch_size)
                 if batches else 0.0)
    telemetry.reset()
    speedup = seq_s / packed_s if packed_s else 0.0
    return {
        "metric": "serving_throughput",
        "value": round(speedup, 3),
        "unit": "x_packed_vs_per_chunk",
        "seq_s": round(seq_s, 3),
        "packed_s": round(packed_s, 3),
        "requests": n_requests * rounds,
        "patches_per_request": 3,
        "batch_size": inferencer.batch_size,
        "packed_occupancy": round(occupancy, 3),
        "gate_x": 1.3,
        "gate_pass": speedup >= 1.3,
        "bit_identical": True,
    }


def run_multichip_overlap(
    n_chunks: int = 3,
    n_dev: int = 8,
    rounds: int = 3,
    step_s: float = 0.03,
) -> dict:
    """Unified sharded engine vs the single-device reference path on 8
    simulated host devices (ISSUE 13, CI gate).

    The engine is a matmul plus a calibrated per-forward-batch "chip
    step" (a pure_callback that sleeps ``step_s`` — the fixed per-batch
    step time of a compute-bound chip). On the 1-core CI box the 8
    virtual CPU devices still execute their shard programs CONCURRENTLY
    (one runtime thread per device — measured: an 8-way shard_map of
    0.2 s callbacks completes in ~0.2 s), so the sharded leg's
    wall-clock honestly reflects the slice's concurrency while total
    compute stays identical — the same calibrated-latency convention as
    pipeline_overlap's simulated IO. The single leg runs every forward
    batch serially; ``CHUNKFLOW_MESH=data=8`` shards them 8 ways, so
    ideal speedup approaches 8x; the gate is >= 1.3x (reported as
    ``gate_pass``), hard floor 1.1x.

    Bit-identity is asserted between the legs on every round (the
    engine contract: forward sharded, reference accumulation replayed),
    and the sharded program must land in the PR 8 roofline ledger
    (programs.json) — both reported in the JSON line.
    """
    import jax
    import jax.numpy as jnp

    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.inference import Inferencer, engines

    if len(jax.devices()) < n_dev:
        raise RuntimeError(
            f"multichip_overlap needs {n_dev} devices "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count={n_dev})"
        )

    telemetry.configure(_bench_metrics_dir())

    pin = (4, 16, 16)
    features = int(np.prod(pin))
    rng = np.random.default_rng(0)
    weights = jnp.asarray(
        rng.standard_normal((features, features)).astype(np.float32)
        / np.sqrt(features)
    )

    def chip_step(x):
        # the calibrated per-batch device step: identity on the values
        # (bitwise-deterministic), fixed wall cost
        time.sleep(step_s)
        return x

    def apply(params, batch):
        x = batch.reshape(batch.shape[0], -1)
        x = jnp.tanh(x @ params)
        x = jax.pure_callback(
            chip_step, jax.ShapeDtypeStruct(x.shape, x.dtype), x
        )
        return x.reshape((batch.shape[0], 1) + pin)

    inferencer = Inferencer(
        input_patch_size=pin,
        num_output_channels=1,
        framework="prebuilt",
        engine=engines.Engine(
            params=weights, apply=apply,
            num_input_channels=1, num_output_channels=1,
        ),
        batch_size=2,
        crop_output_margin=False,
    )
    # 32 patches along x, zero overlap -> 16 forward batches of 2 per
    # chunk: the single leg pays 16 chip steps serially, the 8-way mesh
    # 2 per chip
    chunks = [
        Chunk(rng.random((4, 16, 16 * 32), dtype=np.float32),
              voxel_offset=(4 * i, 0, 0))
        for i in range(n_chunks)
    ]

    mesh_spec = f"data={n_dev}"
    prev_mesh = os.environ.get("CHUNKFLOW_MESH")

    def leg(spec: str):
        os.environ["CHUNKFLOW_MESH"] = spec
        return [np.asarray(inferencer(c).array) for c in chunks]

    try:
        refs = leg("1")        # warm the single-device program
        sharded = leg(mesh_spec)  # warm the sharded program
        for a, b in zip(refs, sharded):
            if not np.array_equal(a, b):
                raise RuntimeError(
                    "multichip bench: sharded output NOT bit-identical "
                    "to the single-device reference")
        single_s = sharded_s = None
        for _ in range(rounds):
            t0 = time.perf_counter()
            outs = leg("1")
            dt = time.perf_counter() - t0
            single_s = dt if single_s is None else min(single_s, dt)
            for a, b in zip(refs, outs):
                if not np.array_equal(a, b):
                    raise RuntimeError("multichip bench: single-device "
                                       "round diverged from reference")
            t0 = time.perf_counter()
            outs = leg(mesh_spec)
            dt = time.perf_counter() - t0
            sharded_s = dt if sharded_s is None else min(sharded_s, dt)
            for a, b in zip(refs, outs):
                if not np.array_equal(a, b):
                    raise RuntimeError(
                        "multichip bench: sharded round NOT bit-identical "
                        "to the single-device reference")
    finally:
        if prev_mesh is None:
            os.environ.pop("CHUNKFLOW_MESH", None)
        else:
            os.environ["CHUNKFLOW_MESH"] = prev_mesh

    # the sharded program must be in the roofline ledger (PR 8)
    from chunkflow_tpu.core import profiling

    in_ledger = any(
        entry.get("family") == "shard" or "shard" in str(entry.get("key"))
        for entry in profiling.catalog()
    )
    telemetry.flush()
    telemetry.configure(None)
    if not in_ledger:
        raise RuntimeError(
            "multichip bench: sharded program missing from the roofline "
            "ledger (programs.json)")

    speedup = single_s / sharded_s if sharded_s else 0.0
    return {
        "metric": "multichip_overlap",
        "value": round(speedup, 2),
        "unit": "x_sharded_vs_single",
        "single_s": round(single_s, 3),
        "sharded_s": round(sharded_s, 3),
        "mesh": mesh_spec,
        "n_devices": n_dev,
        "chunks": n_chunks * rounds,
        "forward_batches_per_chunk": 16,
        "chip_step_s": step_s,
        "cache_builds": inferencer._programs.builds,
        "cache_hits": inferencer._programs.hits,
        "in_roofline_ledger": in_ledger,
        "gate_x": 1.3,
        "gate_pass": speedup >= 1.3,
        "bit_identical": True,
    }


def run_multichip_sharded_replay(
    n_chunks: int = 2,
    rounds: int = 3,
) -> dict:
    """Sharded blend replay vs replicated replay on the same 8-device
    spatial mesh (ISSUE 19, CI gate).

    A blend-dominated proxy: the identity engine (forward is a crop, so
    the blend replay IS the program) over a heavily-overlapped chunk —
    (0,12,12) overlap on (4,16,16) patches, ~600 windows per chunk.
    Both legs run ``CHUNKFLOW_MESH=y=4,x=2``; the flag under test is
    ``CHUNKFLOW_SHARD_REPLAY``. The replicated leg all_gathers the full
    weighted-window stack and replays EVERY window into a full-chunk
    buffer on every chip (n_chips x total scatter work, full-chunk HBM
    per chip); the sharded leg replays only each chip's slab roster
    into a slab+margin buffer after exchanging fringe window stacks via
    ppermute (~1x total scatter work, slab-sized HBM). On the 1-core CI
    box wall-clock tracks TOTAL work across the device threads, so the
    measured win is exactly the redundant replay work the sharded path
    removes — no calibrated sleeps needed (unlike multichip_overlap,
    which measures concurrency). Ideal ratio approaches n_chips; the
    gate is >= 1.3x (reported as ``gate_pass``), hard floor 1.1x.

    Bit-identity of BOTH legs against the single-device reference is
    asserted on every round (the engine contract: sharded replay is a
    per-slab subsequence of the reference scatter order), and the
    sharded program must land in the PR 8 roofline ledger.
    """
    import jax

    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.inference import Inferencer

    n_dev = 8
    if len(jax.devices()) < n_dev:
        raise RuntimeError(
            f"multichip_sharded_replay needs {n_dev} devices "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count={n_dev})"
        )

    telemetry.configure(_bench_metrics_dir())

    pin = (4, 16, 16)
    rng = np.random.default_rng(0)
    inferencer = Inferencer(
        input_patch_size=pin,
        output_patch_overlap=(0, 12, 12),
        num_output_channels=2,
        framework="identity",
        batch_size=4,
        crop_output_margin=False,
    )
    # (4, 256, 144) with stride (4, 4, 4) windows: 61 * 33 = 2013
    # windows per chunk -> the replay (not the crop forward) dominates
    chunks = [
        Chunk(rng.random((4, 256, 144), dtype=np.float32),
              voxel_offset=(4 * i, 0, 0))
        for i in range(n_chunks)
    ]

    mesh_spec = "y=4,x=2"
    prev_mesh = os.environ.get("CHUNKFLOW_MESH")
    prev_replay = os.environ.get("CHUNKFLOW_SHARD_REPLAY")

    def leg(replay_mode: str):
        os.environ["CHUNKFLOW_MESH"] = mesh_spec
        os.environ["CHUNKFLOW_SHARD_REPLAY"] = replay_mode
        return [np.asarray(inferencer(c).array) for c in chunks]

    try:
        # single-device reference: the bit-identity oracle for both legs
        os.environ["CHUNKFLOW_MESH"] = "1"
        os.environ.pop("CHUNKFLOW_SHARD_REPLAY", None)
        refs = [np.asarray(inferencer(c).array) for c in chunks]
        for mode in ("replicated", "sharded"):  # warm both programs
            for a, b in zip(refs, leg(mode)):
                if not np.array_equal(a, b):
                    raise RuntimeError(
                        f"sharded_replay bench: {mode} leg NOT "
                        f"bit-identical to the single-device reference")
        replicated_s = sharded_s = None
        for _ in range(rounds):
            for mode in ("replicated", "sharded"):
                t0 = time.perf_counter()
                outs = leg(mode)
                dt = time.perf_counter() - t0
                if mode == "replicated":
                    replicated_s = (dt if replicated_s is None
                                    else min(replicated_s, dt))
                else:
                    sharded_s = (dt if sharded_s is None
                                 else min(sharded_s, dt))
                for a, b in zip(refs, outs):
                    if not np.array_equal(a, b):
                        raise RuntimeError(
                            f"sharded_replay bench: {mode} round NOT "
                            f"bit-identical to the reference")
    finally:
        if prev_mesh is None:
            os.environ.pop("CHUNKFLOW_MESH", None)
        else:
            os.environ["CHUNKFLOW_MESH"] = prev_mesh
        if prev_replay is None:
            os.environ.pop("CHUNKFLOW_SHARD_REPLAY", None)
        else:
            os.environ["CHUNKFLOW_SHARD_REPLAY"] = prev_replay

    # the sharded program must be in the roofline ledger (PR 8)
    from chunkflow_tpu.core import profiling

    in_ledger = any(
        entry.get("family") == "shard" or "shard" in str(entry.get("key"))
        for entry in profiling.catalog()
    )
    telemetry.flush()
    telemetry.configure(None)
    if not in_ledger:
        raise RuntimeError(
            "sharded_replay bench: sharded program missing from the "
            "roofline ledger (programs.json)")

    speedup = replicated_s / sharded_s if sharded_s else 0.0
    return {
        "metric": "multichip_sharded_replay",
        "value": round(speedup, 2),
        "unit": "x_sharded_vs_replicated_replay",
        "replicated_s": round(replicated_s, 3),
        "sharded_s": round(sharded_s, 3),
        "mesh": mesh_spec,
        "n_devices": n_dev,
        "chunks": n_chunks * rounds,
        "cache_builds": inferencer._programs.builds,
        "cache_hits": inferencer._programs.hits,
        "in_roofline_ledger": in_ledger,
        "gate_x": 1.3,
        "gate_pass": speedup >= 1.3,
        "bit_identical": True,
    }


def run_blend_fused(rounds: int = 5) -> dict:
    """Fused blend data movement vs the separate-leg structure it
    replaced (ISSUE 14, CI gate).

    On chip, the fused Pallas kernel (ops/pallas_blend.py) removes the
    XLA-side pre-scatter: the pre-fusion path materialized a
    bump-weighted stack, a weight-patch stack and BOTH (8,128)-aligned
    zero-padded window stacks in HBM before the DMA kernel re-read
    them; the fused kernel reads raw predictions and does weighting +
    placement + read-modify-write in one VMEM pass. Interpret mode
    executes the kernel per grid step in Python (~30-50x slower than
    compiled XLA on this box — not a throughput proxy), so the CPU gate
    times both DATA-MOVEMENT structures as compiled XLA programs over
    the same workload:

    - ``blend_sep``: weighting + ``vmap`` place into padded windows,
      stacks forced to materialize by an ``optimization_barrier`` (the
      custom-call boundary that forced them on chip), then the
      sequential aligned-window read-modify-write;
    - ``blend_fused``: the fused kernel's structure — raw predictions,
      in-loop weighting + placement, the same window read-modify-write,
      no materialized stacks.

    Bit-identity is asserted in-run between both proxy legs, the
    production XLA scatter path, AND the real fused Pallas kernel in
    interpret mode (correctness leg, untimed). Both proxies build
    through a ProgramCache, so programs.json carries a roofline row per
    leg and the JSON line reports ``roofline_util`` fused-vs-separate
    on the same workload. Gate: >= 1.2x (reported as ``gate_pass``);
    the process only fails below the 1.1x hard floor."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chunkflow_tpu.core import profiling, telemetry
    from chunkflow_tpu.core.compile_cache import ProgramCache
    from chunkflow_tpu.inference.bump import bump_const
    from chunkflow_tpu.inference.patching import (
        enumerate_patches,
        pad_to_batch,
    )
    from chunkflow_tpu.ops import pallas_blend

    telemetry.configure(_bench_metrics_dir())

    co = 3
    pout = (4, 64, 64)
    shape = (8, 192, 192)
    overlap = (2, 32, 32)
    grid = enumerate_patches(shape, pout, pout, overlap)
    _, out_starts, valid = pad_to_batch(grid, 4)
    n = len(valid)
    rng = np.random.default_rng(0)
    preds = rng.standard_normal((n, co) + pout).astype(np.float32)
    bump_j = bump_const(pout)
    pz, py, px = pout
    py_pad, px_pad = pallas_blend.padded_patch_shape(py, px)
    pad_y, pad_x = pallas_blend.buffer_padding(pout)
    buf = (shape[0], shape[1] + pad_y, shape[2] + pad_x)
    y0a = (out_starts[:, 1] // 8) * 8
    x0a = (out_starts[:, 2] // 128) * 128
    aligned = np.stack([out_starts[:, 0], y0a, x0a], 1).astype(np.int32)
    dyx = np.stack(
        [out_starts[:, 1] - y0a, out_starts[:, 2] - x0a], 1
    ).astype(np.int32)

    def place(patch, d):
        padded = jnp.zeros(patch.shape[:-2] + (py_pad, px_pad),
                           patch.dtype)
        at = (0,) * (patch.ndim - 2) + (d[0], d[1])
        return lax.dynamic_update_slice(padded, patch, at)

    def sep_program(preds, valid, aligned, dyx):
        # leg A: weighting, then BOTH padded stacks materialized (the
        # barrier models the pallas_call operand boundary), then the
        # window RMW the DMA kernel performed
        weighted = preds * bump_j[None, None] \
            * valid[:, None, None, None, None]
        wpatch = bump_j[None] * valid[:, None, None, None]
        preds_pad = jax.vmap(place)(weighted, dyx)
        w_pad = jax.vmap(place)(wpatch, dyx)
        preds_pad, w_pad = lax.optimization_barrier((preds_pad, w_pad))
        out0 = jnp.zeros((co,) + buf, jnp.float32)
        w0 = jnp.zeros(buf, jnp.float32)

        def body(i, bufs):
            out, w = bufs
            z0, y0, x0 = aligned[i, 0], aligned[i, 1], aligned[i, 2]
            win = lax.dynamic_slice(
                out, (0, z0, y0, x0), (co, pz, py_pad, px_pad))
            out = lax.dynamic_update_slice(
                out, win + preds_pad[i], (0, z0, y0, x0))
            wwin = lax.dynamic_slice(
                w, (z0, y0, x0), (pz, py_pad, px_pad))
            w = lax.dynamic_update_slice(
                w, wwin + w_pad[i], (z0, y0, x0))
            return out, w

        out, w = lax.fori_loop(0, n, body, (out0, w0))
        return out[:, :, :shape[1], :shape[2]], w[:, :shape[1], :shape[2]]

    def fused_program(preds, valid, aligned, dyx):
        # leg B: the fused kernel's structure — weighting + placement
        # in-loop (VMEM-resident on chip), same window RMW, no stacks
        out0 = jnp.zeros((co,) + buf, jnp.float32)
        w0 = jnp.zeros(buf, jnp.float32)

        def body(i, bufs):
            out, w = bufs
            z0, y0, x0 = aligned[i, 0], aligned[i, 1], aligned[i, 2]
            dy, dx = dyx[i, 0], dyx[i, 1]
            contrib = preds[i] * bump_j[None] * valid[i]
            placed = lax.dynamic_update_slice(
                jnp.zeros((co, pz, py_pad, px_pad), jnp.float32),
                contrib, (0, 0, dy, dx))
            win = lax.dynamic_slice(
                out, (0, z0, y0, x0), (co, pz, py_pad, px_pad))
            out = lax.dynamic_update_slice(
                out, win + placed, (0, z0, y0, x0))
            wplaced = lax.dynamic_update_slice(
                jnp.zeros((pz, py_pad, px_pad), jnp.float32),
                bump_j * valid[i], (0, dy, dx))
            wwin = lax.dynamic_slice(
                w, (z0, y0, x0), (pz, py_pad, px_pad))
            w = lax.dynamic_update_slice(
                w, wwin + wplaced, (z0, y0, x0))
            return out, w

        out, w = lax.fori_loop(0, n, body, (out0, w0))
        return out[:, :, :shape[1], :shape[2]], w[:, :shape[1], :shape[2]]

    # Build through a ProgramCache so both legs land in the PR 8
    # roofline ledger (programs.json) as their own families — with an
    # ANALYTIC byte model (profiling.stamp_cost): XLA's unoptimized-HLO
    # cost_analysis cannot see through loop bodies consistently, and the
    # comparison must score both legs against the same arithmetic. Both
    # legs pay: the raw prediction read and the aligned-window RMW
    # (read + write, out channels + the weight buffer). The separate-leg
    # structure additionally writes AND re-reads both (8,128)-aligned
    # padded stacks across the custom-call boundary — the traffic the
    # fusion removes.
    window_f32 = pz * py_pad * px_pad * 4
    fused_cost = pallas_blend.fused_kernel_cost(n, co, pout)
    weighting_flops = fused_cost["flops"]
    padded_stack_bytes = n * (co + 1) * window_f32
    bytes_fused = fused_cost["bytes_accessed"]
    bytes_sep = bytes_fused + 2 * padded_stack_bytes

    def _blocking(fn):
        # the ledger times the instrumented call; jax dispatch is async,
        # so a bare jit call would record enqueue (~us), not compute —
        # block inside so the roofline rows score real wall (host-side
        # sync around a compiled program, never inside one)
        def run(*a):
            out = fn(*a)
            jax.block_until_ready(out)
            return out

        run.lower = fn.lower
        return run

    programs = ProgramCache(label="blend_bench")
    sep = programs.get(
        ("blend_sep",),
        lambda: profiling.stamp_cost(
            _blocking(jax.jit(sep_program)), flops=weighting_flops,
            bytes_accessed=bytes_sep))
    fused = programs.get(
        ("blend_fused",),
        lambda: profiling.stamp_cost(
            _blocking(jax.jit(fused_program)), flops=weighting_flops,
            bytes_accessed=bytes_fused,
            vmem_bytes=fused_cost["vmem_bytes"]))
    args = (jnp.asarray(preds), jnp.asarray(valid),
            jnp.asarray(aligned), jnp.asarray(dyx))

    so, sw = sep(*args)
    fo, fw = fused(*args)
    so.block_until_ready()
    fo.block_until_ready()
    if not (np.array_equal(np.asarray(so), np.asarray(fo))
            and np.array_equal(np.asarray(sw), np.asarray(fw))):
        raise RuntimeError(
            "blend_fused bench: proxy legs NOT bit-identical")

    # the production XLA scatter reference (the shipping default path)
    dnums4 = lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3, 4), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(1, 2, 3))
    dnums3 = lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0, 1, 2))

    @jax.jit
    def scatter_ref(preds, valid, starts):
        weighted = preds * bump_j[None, None] \
            * valid[:, None, None, None, None]
        wpatch = bump_j[None] * valid[:, None, None, None]
        out = lax.scatter_add(
            jnp.zeros((co,) + shape, jnp.float32), starts, weighted,
            dnums4)
        w = lax.scatter_add(
            jnp.zeros(shape, jnp.float32), starts, wpatch, dnums3)
        return out, w

    ro, rw = scatter_ref(jnp.asarray(preds), jnp.asarray(valid),
                         jnp.asarray(out_starts))
    if not (np.array_equal(np.asarray(fo), np.asarray(ro))
            and np.array_equal(np.asarray(fw), np.asarray(rw))):
        raise RuntimeError(
            "blend_fused bench: proxy legs NOT bit-identical to the "
            "XLA scatter reference")

    # correctness leg: the REAL fused Pallas kernel, interpret mode
    # (untimed — interpret wall is Python overhead, not kernel cost)
    ko, kw = pallas_blend.fused_accumulate_patches(
        jnp.zeros((co,) + buf, jnp.float32),
        jnp.zeros(buf, jnp.float32),
        jnp.asarray(preds), jnp.asarray(valid), bump_j,
        jnp.asarray(out_starts), interpret=True,
    )
    ko = np.asarray(ko)[:, :, :shape[1], :shape[2]]
    kw = np.asarray(kw)[:, :shape[1], :shape[2]]
    if not (np.array_equal(ko, np.asarray(ro))
            and np.array_equal(kw, np.asarray(rw))):
        raise RuntimeError(
            "blend_fused bench: the fused Pallas kernel (interpret) is "
            "NOT bit-identical to the XLA scatter reference")

    def best_of(program):
        best = None
        for _ in range(rounds):
            t0 = time.perf_counter()
            out, w = program(*args)
            out.block_until_ready()
            w.block_until_ready()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    sep_s = best_of(sep)
    fused_s = best_of(fused)

    entries = {e["family"]: e for e in profiling.catalog()}
    util_sep = (entries.get("blend_sep") or {}).get("roofline_util")
    util_fused = (entries.get("blend_fused") or {}).get("roofline_util")
    telemetry.flush()
    telemetry.configure(None)
    if util_sep is None or util_fused is None:
        raise RuntimeError(
            "blend_fused bench: proxy legs missing from the roofline "
            "ledger (programs.json)")

    speedup = sep_s / fused_s if fused_s else 0.0
    return {
        "metric": "blend_fused",
        "value": round(speedup, 2),
        "unit": "x_fused_vs_separate_legs",
        "sep_s": round(sep_s, 4),
        "fused_s": round(fused_s, 4),
        "patches": n,
        "patch": list(pout),
        "chunk": list(shape),
        "roofline_util_fused": util_fused,
        "roofline_util_sep": util_sep,
        "roofline_ok": bool(util_fused >= util_sep),
        "interpret_kernel_checked": True,
        "gate_x": 1.2,
        "gate_pass": speedup >= 1.2,
        "bit_identical": True,
    }


def run_front_half(rounds: int = 5) -> dict:
    """Device-resident front half vs the host front half it replaced
    (ISSUE 15, CI gate) — the H2D/data-movement STRUCTURE proxy.

    On chip the win is PCIe traffic: the host front converts a chunk to
    float32 on the host, gathers every overlapping patch by host slicing
    and re-uploads the gathered stack — each chunk voxel rides H2D
    ~(patch/stride)^3 times, at 4x the bytes of the raw uint8. The
    device front uploads the RAW chunk once and the program gathers
    windows from the resident buffer by starts-table index
    (ops/pallas_gather.py). The CPU gate times both structures honestly
    (device_put is the boundary copy on every backend):

    - ``front_host``: host int->f32 convert + host patch gather + the
      gathered-stack upload + a compiled pass over the stack;
    - ``front_dev``: the raw chunk upload + one compiled program that
      converts and gathers on device (the XLA reference leg the
      production default runs).

    Bit-identity is asserted in-run between both legs AND the real
    Pallas gather kernel in interpret mode (correctness leg, untimed).
    Both device programs build through a ProgramCache with analytic
    ``profiling.stamp_cost`` byte models, so programs.json carries a
    roofline row per leg. Gate: >= 1.2x (``gate_pass``); the process
    only fails below the 1.1x hard floor."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chunkflow_tpu.core import profiling, telemetry
    from chunkflow_tpu.core.compile_cache import ProgramCache
    from chunkflow_tpu.inference.patching import enumerate_patches
    from chunkflow_tpu.ops import pallas_gather

    telemetry.configure(_bench_metrics_dir())

    ci = 1
    pin = (8, 32, 32)
    shape = (48, 160, 160)
    overlap = (4, 16, 16)  # stride = half patch: ~8x gather coverage
    B = 9
    grid = enumerate_patches(shape, pin, pin, overlap)
    in_starts = grid.input_starts
    n = grid.num_patches
    assert n % B == 0, (n, B)
    rng = np.random.default_rng(0)
    raw = rng.integers(0, 256, (ci,) + shape, dtype=np.uint8)
    scale = np.float32(1.0 / 255.0)
    pvox = int(np.prod(pin))
    stack_f32 = n * ci * pvox * 4
    chunk_raw = int(raw.nbytes)
    chunk_f32 = chunk_raw * 4

    def consume_host(stack):
        # one compiled pass over the UPLOADED gathered stack (x * 1.0 is
        # the exact identity — bitwise, including signed zeros)
        return stack * jnp.float32(1.0)

    def front_dev(chunk, starts):
        # the production device front's structure (the XLA reference
        # leg): in-program convert, scan-gather from the resident chunk
        chunk_f = chunk.astype(jnp.float32) * scale

        def fwd_batch(b):
            i0 = b * B
            s_in = lax.dynamic_slice(starts, (i0, 0), (B, 3))
            return jax.vmap(
                lambda s: lax.dynamic_slice(
                    chunk_f, (0, s[0], s[1], s[2]), (ci,) + pin
                )
            )(s_in)

        _, stack = lax.scan(
            lambda c, b: (c, fwd_batch(b)), None, jnp.arange(n // B)
        )
        # [n_batches, B, ci, pz, py, px] -> [n, ci, pz, py, px]: scan
        # axis folds into the patch axis, zyx spatial axes untouched
        return stack.reshape((n, ci) + pin)

    # ANALYTIC byte models (profiling.stamp_cost): the comparison must
    # score both structures against the same arithmetic. The host leg's
    # program only sees the gathered stack — but the LEG pays the host
    # convert (chunk read + f32 write), the host gather (stack write),
    # the stack H2D and the program read; the device leg pays the raw
    # chunk H2D, the in-program convert and the same gather traffic.
    bytes_host = chunk_raw + chunk_f32 + 3 * stack_f32
    bytes_dev = chunk_raw + chunk_raw + chunk_f32 + 2 * stack_f32

    def _blocking(fn):
        def run(*a):
            out = fn(*a)
            jax.block_until_ready(out)
            return out

        run.lower = fn.lower
        return run

    # both legs' buffers are bench-owned and dead after the call
    # (GL005): the uploaded stack / raw chunk may alias into the output
    programs = ProgramCache(label="front_bench")
    host_prog = programs.get(
        ("front_host",),
        lambda: profiling.stamp_cost(
            _blocking(jax.jit(consume_host, donate_argnums=(0,))),
            flops=stack_f32 // 4, bytes_accessed=bytes_host))
    gather_cost = pallas_gather.gather_kernel_cost(n, ci, pin, raw.dtype)
    dev_prog = programs.get(
        ("front_dev",),
        lambda: profiling.stamp_cost(
            _blocking(jax.jit(front_dev, donate_argnums=(0,))),
            flops=stack_f32 // 4, bytes_accessed=bytes_dev,
            vmem_bytes=gather_cost["vmem_bytes"]))
    starts_dev = jnp.asarray(in_starts)

    def host_leg():
        # host front half: convert + pad-free gather + gathered upload
        arr = raw.astype(np.float32) * scale
        stack = np.empty((n, ci) + pin, dtype=np.float32)
        for i, s in enumerate(in_starts):
            stack[i] = arr[:, s[0]:s[0] + pin[0], s[1]:s[1] + pin[1],
                           s[2]:s[2] + pin[2]]
        return host_prog(jnp.asarray(stack))

    def dev_leg():
        return dev_prog(jnp.asarray(raw), starts_dev)

    ho = np.asarray(host_leg())
    do = np.asarray(dev_leg())
    if not np.array_equal(ho, do):
        raise RuntimeError("front_half bench: legs NOT bit-identical")

    # correctness leg: the REAL Pallas gather kernel, interpret mode
    # (untimed — interpret wall is Python overhead, not kernel cost)
    pad_y, pad_x = pallas_gather.gather_buffer_padding(pin, raw.dtype)
    padded = np.pad(raw, [(0, 0), (0, 0), (0, pad_y), (0, pad_x)])
    ko = np.asarray(pallas_gather.gather_patches(
        jnp.asarray(padded), starts_dev, pin, interpret=True))
    if not np.array_equal(ko, do):
        raise RuntimeError(
            "front_half bench: the Pallas gather kernel (interpret) is "
            "NOT bit-identical to the XLA legs")

    def best_of(leg):
        best = None
        for _ in range(rounds):
            t0 = time.perf_counter()
            out = leg()
            out.block_until_ready()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    host_s = best_of(host_leg)
    dev_s = best_of(dev_leg)

    entries = {e["family"]: e for e in profiling.catalog()}
    util_host = (entries.get("front_host") or {}).get("roofline_util")
    util_dev = (entries.get("front_dev") or {}).get("roofline_util")
    telemetry.flush()
    telemetry.configure(None)
    if util_host is None or util_dev is None:
        raise RuntimeError(
            "front_half bench: proxy legs missing from the roofline "
            "ledger (programs.json)")

    speedup = host_s / dev_s if dev_s else 0.0
    return {
        "metric": "front_half",
        "value": round(speedup, 2),
        "unit": "x_device_vs_host_front",
        "host_s": round(host_s, 4),
        "dev_s": round(dev_s, 4),
        "patches": n,
        "patch": list(pin),
        "chunk": list(shape),
        "h2d_bytes_host": stack_f32,
        "h2d_bytes_dev": chunk_raw,
        "h2d_ratio": round(stack_f32 / chunk_raw, 2),
        "roofline_util_host": util_host,
        "roofline_util_dev": util_dev,
        "interpret_kernel_checked": True,
        "gate_x": 1.2,
        "gate_pass": speedup >= 1.2,
        "bit_identical": True,
    }


def run_fused_pipeline(rounds: int = 5, n_batches: int = 4) -> dict:
    """One fused patch pipeline vs the separate-programs structure it
    replaces (ISSUE 17, CI gate): gather -> forward -> blend as one
    device-resident chain, with no host round trip between the stages.

    On chip, ``CHUNKFLOW_FUSED_PIPELINE`` selects both proven kernel
    legs at once (ops/pallas_gather.py + ops/pallas_blend.py) and the
    serving packer keeps the weighted-prediction stack DEVICE-resident
    (serve/packer.py): forward rows are overlaid into a resident device
    buffer instead of being downloaded per batch into a host stack that
    is re-uploaded wholesale at blend time. Interpret mode executes the
    kernels per grid step in Python (~30-50x slower than compiled XLA
    on this box — not a throughput proxy), so the CPU gate times the
    two SERVING STRUCTURES honestly over the same workload — identical
    compiled stage programs (batched gather+forward, final scatter
    blend), different residency for the stack between them:

    - ``pipe_sep``: the pre-fusion structure — each batch's rows land
      in a HOST numpy stack (``np.asarray`` download + host overlay
      write) and the finished stack is re-uploaded (``jnp.asarray``, a
      real staged copy on every backend — the ``front_half`` bench's
      boundary convention) before the blend consumes it. On the host
      backend the download side is zero-copy, so the CPU gate
      UNDERCOUNTS this leg — conservative, in the fused leg's favor;
    - ``pipe_fused``: the fused pipeline's structure — rows are written
      into the resident device stack by the packer's overlay program
      (``weighted.at[idx].set(rows)``, buffer donated), and the blend
      consumes it in place. No download, no host write, no re-upload.

    Bit-identity is asserted in-run between both proxy legs AND the
    real kernels composed end to end in interpret mode (Pallas gather
    -> the same forward -> weighting -> Pallas fused blend; untimed
    correctness leg) — the composed kernels must reproduce the proxy
    legs' blended volumes exactly. Both legs build through a
    ProgramCache and stamp the SAME analytic byte model — the
    pipeline's logical floor, sharing arithmetic with
    ``ops.blend.pipeline_kernel_cost`` — so ``roofline_util`` directly
    ranks the two structures on identical work: the separate leg moves
    the weighted stack across the host boundary ON TOP of the floor
    and scores lower; that surplus is itemized in its
    ``hbm_intermediate_bytes`` stamp (the fused leg stamps 0). Gate:
    >= 1.2x (``gate_pass``); the process only fails below the 1.1x
    hard floor."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from chunkflow_tpu.core import profiling, telemetry
    from chunkflow_tpu.core.compile_cache import ProgramCache
    from chunkflow_tpu.inference.bump import bump_const
    from chunkflow_tpu.inference.patching import (
        enumerate_patches,
        pad_to_batch,
    )
    from chunkflow_tpu.ops import blend as blend_ops
    from chunkflow_tpu.ops import pallas_blend, pallas_gather

    telemetry.configure(_bench_metrics_dir())

    ci, co = 1, 3
    pin = pout = (4, 32, 128)
    shape = (16, 192, 384)
    overlap = (2, 16, 64)
    grid = enumerate_patches(shape, pin, pout, overlap)
    in_starts, out_starts, valid = pad_to_batch(grid, n_batches)
    n = len(valid)
    assert n % n_batches == 0, (n, n_batches)
    slots = n // n_batches
    rng = np.random.default_rng(0)
    raw = rng.integers(0, 256, (ci,) + shape, dtype=np.uint8)
    scale = np.float32(1.0 / 255.0)
    bump_j = bump_const(pout)
    pz, py, px = pout
    pad_y, pad_x = pallas_blend.buffer_padding(pout)
    buf = (shape[0], shape[1] + pad_y, shape[2] + pad_x)
    # the stand-in forward: a per-channel scaling — elementwise with NO
    # mul+add chain, so every leg applies the exact same scalar IEEE
    # ops per element and stays bitwise comparable to the
    # eager/interpret kernel leg (an affine ``x*w+b`` compiles to an
    # FMA inside the jitted programs — one rounding — while eager ops
    # round the mul and add separately; a real convnet's reductions
    # would likewise re-order under re-batching)
    w_vec = np.asarray([0.5, -1.25, 2.0], np.float32)

    def forward(patch_f32):
        # [ci=1, pz, py, px] f32 -> [co, pz, py, px] f32
        return patch_f32[0][None] * w_vec[:, None, None, None]

    def fwd_program(chunk, s_in, valid_b):
        # one serving batch: convert + gather from the resident chunk,
        # forward, bump weighting — identical in BOTH legs (the legs
        # differ only in where the rows go afterwards)
        chunk_f = chunk.astype(jnp.float32) * scale
        stack = jax.vmap(
            lambda s: lax.dynamic_slice(
                chunk_f, (0, s[0], s[1], s[2]), (ci,) + pin
            )
        )(s_in)
        preds = stack[:, 0][:, None] * w_vec[None, :, None, None, None]
        return preds * bump_j[None, None] \
            * valid_b[:, None, None, None, None]

    dnums4 = lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3, 4), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(1, 2, 3))
    dnums3 = lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0, 1, 2))

    def scatter_program(weighted, valid, starts):
        # the production blend tail (pre-weighted scatter_add) —
        # identical in both legs
        wpatch = bump_j[None] * valid[:, None, None, None]
        out = lax.scatter_add(
            jnp.zeros((co,) + shape, jnp.float32), starts, weighted,
            dnums4)
        w = lax.scatter_add(
            jnp.zeros(shape, jnp.float32), starts, wpatch, dnums3)
        return out, w

    # chunk deliberately NOT donated: both legs gather from the same
    # resident buffer every batch of every round
    fwd = jax.jit(fwd_program)  # graftlint: disable=GL005
    scatter = jax.jit(scatter_program)
    # the packer's overlay program (serve/packer.py): rows written into
    # the resident stack in place (buffer donated)
    overlay = jax.jit(
        lambda stack, rows, idx: stack.at[idx].set(rows),
        donate_argnums=(0,))

    chunk_dev = jnp.asarray(raw)
    valid_dev = jnp.asarray(valid)
    starts_dev = jnp.asarray(out_starts)
    groups = [np.arange(b * slots, (b + 1) * slots, dtype=np.int32)
              for b in range(n_batches)]
    starts_groups = [jnp.asarray(in_starts[g]) for g in groups]
    valid_groups = [jnp.asarray(valid[g]) for g in groups]
    idx_groups = [jnp.asarray(g) for g in groups]

    def sep_leg():
        # pre-fusion serving: rows -> host stack -> wholesale re-upload
        weighted_np = np.zeros((n, co) + pout, np.float32)
        for b in range(n_batches):
            rows = fwd(chunk_dev, starts_groups[b], valid_groups[b])
            weighted_np[groups[b]] = np.asarray(rows)
        weighted_dev = jnp.asarray(weighted_np)
        out, w = scatter(weighted_dev, valid_dev, starts_dev)
        jax.block_until_ready((out, w))
        return out, w

    def fused_leg():
        # fused serving: rows stay device-resident end to end
        weighted_dev = jnp.zeros((n, co) + pout, jnp.float32)
        for b in range(n_batches):
            rows = fwd(chunk_dev, starts_groups[b], valid_groups[b])
            weighted_dev = overlay(weighted_dev, rows, idx_groups[b])
        out, w = scatter(weighted_dev, valid_dev, starts_dev)
        jax.block_until_ready((out, w))
        return out, w

    # ANALYTIC byte model (profiling.stamp_cost): BOTH legs stamp the
    # pipeline's logical floor — raw chunk read, one full-chunk f32
    # materialization (the gather operand the XLA legs build either
    # way), the weighted-stack write + the blend's read of it, and the
    # scatter destination read-modify-write — so roofline_util ranks
    # the two structures on identical work. The separate leg moves the
    # weighted stack across the host boundary ON TOP of that floor
    # (host overlay write + wholesale re-upload): that surplus is the
    # prediction-stack term of ops.blend.pipeline_kernel_cost's
    # hbm_intermediate_bytes (the gathered-stack term does not apply
    # here — both legs fuse gather+forward inside one program; the
    # REAL kernel pipeline deletes that one too) and is stamped on the
    # sep row, the fused row stamping 0.
    pipe_cost = blend_ops.pipeline_kernel_cost(
        n, ci, co, pin, pout, dtype=raw.dtype)
    chunk_raw = int(raw.nbytes)
    chunk_f32 = chunk_raw * 4
    pvox = int(np.prod(pout))
    wstack_bytes = n * co * pvox * 4
    patch_stack_bytes = n * ci * pvox * 4
    hbm_sep = pipe_cost["hbm_intermediate_bytes"] - 2 * patch_stack_bytes
    assert hbm_sep == 2 * wstack_bytes
    scatter_bytes = 3 * n * (co + 1) * pvox * 4
    fwd_flops = n * co * pvox
    weight_flops = n * co * pvox * 2  # bump multiply + valid mask
    flops = pipe_cost["flops"] + fwd_flops + weight_flops
    bytes_floor = chunk_raw + 2 * chunk_f32 + 2 * wstack_bytes \
        + scatter_bytes

    # the legs are plain-python drivers around compiled programs;
    # instrument_program keys on a ``.lower`` attribute to tell
    # programs from cached sentinels, so give them one (its XLA cost
    # analysis is best-effort and simply yields nothing here — the
    # stamped analytic model above is the scored cost)
    sep_leg.lower = None
    fused_leg.lower = None

    programs = ProgramCache(label="pipeline_bench")
    sep = programs.get(
        ("pipe_sep",),
        lambda: profiling.stamp_cost(
            sep_leg, flops=flops, bytes_accessed=bytes_floor,
            hbm_intermediate_bytes=hbm_sep))
    fused = programs.get(
        ("pipe_fused",),
        lambda: profiling.stamp_cost(
            fused_leg, flops=flops, bytes_accessed=bytes_floor,
            vmem_bytes=pipe_cost["vmem_bytes"],
            hbm_intermediate_bytes=0))

    so, sw = sep()
    fo, fw = fused()
    if not (np.array_equal(np.asarray(so), np.asarray(fo))
            and np.array_equal(np.asarray(sw), np.asarray(fw))):
        raise RuntimeError(
            "fused_pipeline bench: proxy legs NOT bit-identical")

    # correctness leg: the REAL kernels composed end to end in
    # interpret mode — Pallas gather from the raw padded chunk, the
    # same forward + weighting, then the Pallas fused blend — must
    # reproduce the proxy legs' blended volumes bit-exactly (untimed:
    # interpret wall is Python overhead, not kernel cost)
    g_pad_y, g_pad_x = pallas_gather.gather_buffer_padding(
        pin, raw.dtype)
    padded = np.pad(raw, [(0, 0), (0, 0), (0, g_pad_y), (0, g_pad_x)])
    stack_k = pallas_gather.gather_patches(
        jnp.asarray(padded), jnp.asarray(in_starts), pin,
        interpret=True)
    preds_k = jax.vmap(forward)(stack_k)
    ko, kw = pallas_blend.fused_accumulate_patches(
        jnp.zeros((co,) + buf, jnp.float32),
        jnp.zeros(buf, jnp.float32),
        preds_k, valid_dev, bump_j, starts_dev, interpret=True,
    )
    ko = np.asarray(ko)[:, :, :shape[1], :shape[2]]
    kw = np.asarray(kw)[:, :shape[1], :shape[2]]
    if not (np.array_equal(ko, np.asarray(fo))
            and np.array_equal(kw, np.asarray(fw))):
        raise RuntimeError(
            "fused_pipeline bench: the composed Pallas kernels "
            "(interpret) are NOT bit-identical to the XLA proxy legs")

    def best_of(leg):
        best = None
        for _ in range(rounds):
            t0 = time.perf_counter()
            leg()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    sep_s = best_of(sep)
    fused_s = best_of(fused)

    entries = {e["family"]: e for e in profiling.catalog()}
    util_sep = (entries.get("pipe_sep") or {}).get("roofline_util")
    util_fused = (entries.get("pipe_fused") or {}).get("roofline_util")
    telemetry.flush()
    telemetry.configure(None)
    if util_sep is None or util_fused is None:
        raise RuntimeError(
            "fused_pipeline bench: proxy legs missing from the "
            "roofline ledger (programs.json)")

    speedup = sep_s / fused_s if fused_s else 0.0
    return {
        "metric": "fused_pipeline",
        "value": round(speedup, 2),
        "unit": "x_fused_vs_separate_programs",
        "sep_s": round(sep_s, 4),
        "fused_s": round(fused_s, 4),
        "patches": n,
        "batches": n_batches,
        "patch": list(pout),
        "chunk": list(shape),
        "hbm_intermediate_sep": int(hbm_sep),
        "hbm_intermediate_fused": 0,
        "roofline_util_fused": util_fused,
        "roofline_util_sep": util_sep,
        "roofline_ok": bool(util_fused >= util_sep),
        "interpret_kernel_checked": True,
        "gate_x": 1.2,
        "gate_pass": speedup >= 1.2,
        "bit_identical": True,
    }



def run_storage_throughput(
    volume_shape=(64, 256, 256),
    block=(16, 64, 64),
    chunk=(32, 128, 128),
    stride=(24, 96, 96),
    latency_s=0.003,
) -> dict:
    """Serial uncached reads vs concurrent block reads vs the hot block
    cache on an overlapping-halo cutout grid (ISSUE 11, CI gate).

    The workload is the storage plane's reason to exist: a task grid
    whose chunks overlap (halo reads), against a store that charges one
    simulated round trip per storage BLOCK (``MemoryBackend`` with
    ``latency_s`` — an object GET per block, how remote stores actually
    bill a cutout; CPU-safe and deterministic, no driver in the loop).
    Three legs over the same grid:

    * ``serial``     — the historical path: one blocking whole-range
      read per cutout, every covered block's latency paid in sequence;
    * ``concurrent`` — cold cache: block reads issued as concurrent
      futures in ``read_concurrency()`` waves; grid overlap already
      turns neighbor halo blocks into hits;
    * ``hot``        — second pass over the grid with the cache warm.

    All three legs are asserted bit-identical against the ground-truth
    array. Gate: the hot-cache leg must be >= 1.3x the serial leg
    (reported as ``gate_pass``, asserted slow/bench-marked in
    tests/test_bench.py); the process only fails below 1.1x. The run's
    telemetry (storage/hits|misses|bytes_read and the storage/read
    span) lands under the bench metrics dir for log-summary.
    """
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.volume.storage import (
        BlockCache,
        MemoryBackend,
        blockwise_cutout,
        serial_cutout,
    )

    telemetry.configure(_bench_metrics_dir())
    rng = np.random.default_rng(0)
    # 1..255: no all-zero block, so every block is cacheable (the cache
    # deliberately never pins possibly-missing zero blocks)
    data = rng.integers(1, 255, size=volume_shape, dtype=np.uint8)
    backend = MemoryBackend(
        data, block_shape=block, latency_s=latency_s, max_workers=16
    )
    boxes = []
    for z in range(0, volume_shape[0] - chunk[0] + 1, stride[0]):
        for y in range(0, volume_shape[1] - chunk[1] + 1, stride[1]):
            for x in range(0, volume_shape[2] - chunk[2] + 1, stride[2]):
                boxes.append(((z, y, x),
                              (z + chunk[0], y + chunk[1], x + chunk[2])))

    t0 = time.perf_counter()
    serial = [serial_cutout(backend, lo, hi) for lo, hi in boxes]
    serial_s = time.perf_counter() - t0

    cache = BlockCache(256 * (1 << 20))
    t0 = time.perf_counter()
    cold = [blockwise_cutout(backend, lo, hi, cache=cache)
            for lo, hi in boxes]
    cold_s = time.perf_counter() - t0
    cold_hits, cold_misses = cache.hits, cache.misses

    t0 = time.perf_counter()
    hot = [blockwise_cutout(backend, lo, hi, cache=cache)
           for lo, hi in boxes]
    hot_s = time.perf_counter() - t0
    backend.close()

    for (lo, hi), ref, a, b in zip(boxes, serial, cold, hot):
        truth = data[lo[0]:hi[0], lo[1]:hi[1], lo[2]:hi[2]]
        for leg, arr in (("serial", ref), ("concurrent", a), ("hot", b)):
            if not np.array_equal(arr, truth):
                raise RuntimeError(
                    f"{leg} cutout diverged from ground truth at "
                    f"[{lo}, {hi})"
                )

    telemetry.flush()
    events_path = telemetry.configured_path()
    telemetry.configure(None)  # close the sink (in-process callers)
    speedup = serial_s / hot_s
    return {
        "metric": "storage_throughput_speedup",
        "value": round(speedup, 2),
        "unit": "x_serial",
        "serial_s": round(serial_s, 3),
        "concurrent_cold_s": round(cold_s, 3),
        "hot_s": round(hot_s, 3),
        "cold_speedup": round(serial_s / cold_s, 2),
        "n_cutouts": len(boxes),
        "cold_cache_hits": cold_hits,
        "cold_cache_misses": cold_misses,
        "hot_cache_hits": cache.hits - cold_hits,
        "hot_cache_misses": cache.misses - cold_misses,
        "cache_bytes": cache.nbytes,
        "simulated_block_latency_s": latency_s,
        "gate_pass": bool(speedup >= 1.3),
        "telemetry_jsonl": events_path,
    }


def run_segmentation_stitch(
    volume_shape=(48, 48, 48),
    chunk=(16, 16, 16),
    latency_s=0.008,
    workers=8,
    connectivity=26,
) -> dict:
    """Stitched map->reduce->map labeling vs monolithic whole-volume
    labeling against latency-charged storage (ISSUE 20, CI gate).

    Both legs label the SAME volume held in ``MemoryBackend``s that
    charge one simulated round trip per storage block (the
    storage_throughput convention — an object GET per block, how remote
    stores bill; CPU-safe, deterministic, no driver in the loop):

    * ``monolithic`` — the historical path: one blocking whole-volume
      read (every block's latency paid in sequence), one host labeling
      pass, one blocking whole-volume write;
    * ``stitched``   — the segmentation plane (segment/driver.run_local):
      per-chunk label tasks fan out over a thread pool, so their block
      reads/writes overlap their latencies; the hierarchical merge runs
      over KV sidecars (host memory, no storage round trips); the
      relabel wave overlaps the same way.

    The stitched output is asserted label-isomorphic to the monolithic
    labeling every run — the speedup only counts if the answer is
    EXACT. Gate: >= 1.3x (reported as ``gate_pass``, asserted
    slow/bench-marked best-of-3 in tests/test_bench.py); the process
    only fails below 1.1x. The run's segment/* counters land under the
    bench metrics dir for log-summary's SEGMENT block.
    """
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.ops import connected_components as cc
    from chunkflow_tpu.segment.driver import run_local
    from chunkflow_tpu.segment.merge_table import labels_isomorphic
    from chunkflow_tpu.segment.plan import SegmentPlan
    from chunkflow_tpu.segment.stages import LABEL_DTYPE, SegmentStore
    from chunkflow_tpu.core.bbox import BoundingBox
    from chunkflow_tpu.volume.storage import MemoryBackend, MemoryKV

    telemetry.configure(_bench_metrics_dir())
    rng = np.random.default_rng(0)
    data = (rng.random(volume_shape) > 0.62).astype(np.uint8)

    # ---- monolithic leg: whole-volume read -> label -> write ----------
    mono_in = MemoryBackend(
        data, block_shape=chunk, latency_s=latency_s, max_workers=16
    )
    mono_seg = np.zeros(volume_shape, dtype=LABEL_DTYPE)
    mono_out = MemoryBackend(
        mono_seg, block_shape=chunk, latency_s=latency_s, max_workers=16
    )
    lo = (0, 0, 0)
    t0 = time.perf_counter()
    src = mono_in.read_async(lo, volume_shape).result()
    mono_labels = cc.label_binary(
        src != 0, connectivity=connectivity
    ).astype(LABEL_DTYPE)
    mono_out.write_async(lo, volume_shape, mono_labels).result()
    monolithic_s = time.perf_counter() - t0
    mono_in.close()
    mono_out.close()

    # ---- stitched leg: the segmentation plane over the same latency --
    plan = SegmentPlan(BoundingBox(lo, volume_shape), chunk)
    stitch_seg = np.zeros(volume_shape, dtype=LABEL_DTYPE)
    store = SegmentStore(
        plan,
        input_backend=MemoryBackend(
            data, block_shape=chunk, latency_s=latency_s, max_workers=16
        ),
        seg_backend=MemoryBackend(
            stitch_seg, block_shape=chunk, latency_s=latency_s,
            max_workers=16,
        ),
        kv=MemoryKV(),
        connectivity=connectivity,
    )
    t0 = time.perf_counter()
    summary = run_local(store, workers=workers)
    stitched_s = time.perf_counter() - t0
    store.input_backend.close()
    store.seg_backend.close()

    # exactness first: the speedup of a wrong answer is worthless
    if not labels_isomorphic(stitch_seg, mono_seg):
        raise RuntimeError(
            "stitched segmentation diverged from the monolithic "
            "labeling — label stitching is broken, not slow"
        )

    telemetry.flush()
    events_path = telemetry.configured_path()
    telemetry.configure(None)  # close the sink (in-process callers)
    speedup = monolithic_s / stitched_s
    return {
        "metric": "segmentation_stitch_speedup",
        "value": round(speedup, 2),
        "unit": "x_monolithic",
        "monolithic_s": round(monolithic_s, 3),
        "stitched_s": round(stitched_s, 3),
        "n_chunks": summary["chunks"],
        "merge_nodes": summary["merge_nodes"],
        "n_objects": int(np.unique(mono_labels).size - 1),
        "connectivity": connectivity,
        "workers": workers,
        "simulated_block_latency_s": latency_s,
        "gate_pass": bool(speedup >= 1.3),
        "telemetry_jsonl": events_path,
    }


def run_fleet_smoke(n_tasks: int = 6) -> dict:
    """Chaos smoke of the fleet supervisor (ISSUE 7, CI gate): a REAL
    multi-process fleet drains a small volume while one worker is
    SIGKILLed mid-run and one spot-drill preemption fires. The run must
    converge — every task committed exactly once (ledger markers ==
    bodies), outputs present, queue drained, nothing dead-lettered —
    or this raises and run_tests.sh goes red. This is the wiring test
    the unit suite cannot give: real subprocesses, real /healthz
    probes, real lease recovery across process boundaries."""
    import shutil
    import tempfile

    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.parallel.fleet import FleetSupervisor
    from chunkflow_tpu.parallel.lifecycle import FileLedger
    from chunkflow_tpu.parallel.queues import open_queue

    telemetry.reset()
    scratch = tempfile.mkdtemp(prefix="chunkflow-fleet-smoke-")
    in_dir = os.path.join(scratch, "in")
    out_dir = os.path.join(scratch, "out")
    metrics = os.path.join(scratch, "metrics")
    for d in (in_dir, out_dir, metrics):
        os.makedirs(d)
    rng = np.random.default_rng(2)
    bodies = []
    for i in range(n_tasks):
        c = Chunk(rng.random((8, 16, 16), dtype=np.float32),
                  voxel_offset=(i * 8, 0, 0))
        c.to_h5(in_dir + "/")
        bodies.append(c.bbox.string)
    qdir = os.path.join(scratch, "q")
    open_queue(qdir).send_messages(bodies)
    slow = os.path.join(scratch, "slow.py")
    with open(slow, "w") as f:  # a kill window on any box
        f.write("import time\n\n\ndef execute(chunk):\n"
                "    time.sleep(0.3)\n    return chunk\n")
    ledger_dir = os.path.join(scratch, "ledger")
    worker_args = [
        "fetch-task-from-queue", "-q", qdir, "-v", "4", "-r", "8",
        "--poll-interval", "0.25", "--max-retries", "50",
        "--lease-renew", "1.0", "--backoff-base", "0.01",
        "--backoff-cap", "0.1", "--ledger", ledger_dir,
        "load-h5", "-f", in_dir + "/",
        "plugin", "--name", slow,
        "inference", "-s", "4", "8", "8", "-v", "1", "2", "2",
        "-c", "1", "-f", "identity", "--no-crop-output-margin",
        "--async-depth", "2",
        "save-h5", "--file-name", out_dir + "/",
        "delete-task-in-queue",
    ]
    sup = FleetSupervisor(
        qdir, worker_args, min_workers=1, max_workers=2, interval=0.5,
        scale_up_backlog=2.0, idle_ticks=2, probe_misses=6,
        probe_timeout=2.0, startup_grace=90.0, term_grace=20.0,
        crash_limit=5, metrics_dir=metrics, seed=1,
        visibility_timeout=4.0,
        worker_env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    summary = {}
    runner = threading.Thread(
        target=lambda: summary.update(sup.run(max_runtime=240.0,
                                              settle_ticks=3)),
        daemon=True,
    )
    ledger = FileLedger(ledger_dir)
    t0 = time.perf_counter()
    try:
        runner.start()

        def live():
            return [w for w in sup.workers
                    if w.active and w.proc.poll() is None]

        deadline = time.time() + 120
        while time.time() < deadline:
            if len(ledger.keys()) >= 2 and live():
                break
            time.sleep(0.1)
        else:
            raise RuntimeError("fleet smoke: no commits within 120s")
        os.kill(live()[0].proc.pid, signal.SIGKILL)  # crash-shaped death
        sup.request_drill()  # and one spot-drill preemption
        runner.join(timeout=240)
        if runner.is_alive():
            raise RuntimeError("fleet smoke: run did not converge")
    finally:
        sup.stop()
        runner.join(timeout=30)
        sup.shutdown()
    wall_s = time.perf_counter() - t0
    marks = ledger.keys()
    if sorted(marks) != sorted(bodies):
        raise RuntimeError(
            f"fleet smoke: ledger incomplete {len(marks)}/{n_tasks}")
    outs = [n for n in os.listdir(out_dir) if n.endswith(".h5")]
    if len(outs) != n_tasks:
        raise RuntimeError(
            f"fleet smoke: {len(outs)}/{n_tasks} outputs written")
    queue = open_queue(qdir)
    stats = queue.stats()
    if stats["pending"] or stats["inflight"] or queue.dead_letters():
        raise RuntimeError(f"fleet smoke: queue not clean: {stats}")
    # the acceptance run's JSONL must round-trip through the Perfetto
    # exporter (ISSUE 18): a schema-valid Chrome trace with one process
    # per fleet worker — validated BEFORE the scratch dir is deleted,
    # because this run is the only real multi-process stream CI has
    from tools.trace_export import export_metrics_dir

    trace_path = os.path.join(scratch, "fleet-trace.json")
    trace_stats = export_metrics_dir(metrics, trace_path)
    if trace_stats["problems"]:
        raise RuntimeError(
            f"fleet smoke: exported trace invalid: "
            f"{trace_stats['problems'][:5]}")
    if trace_stats["workers"] < 2:
        raise RuntimeError(
            f"fleet smoke: trace has {trace_stats['workers']} worker "
            f"process(es), expected >= 2 (supervisor + workers)")
    with open(trace_path) as f:
        json.load(f)  # the file on disk is valid JSON, not just the dict
    shutil.rmtree(scratch, ignore_errors=True)
    return {
        "metric": "fleet_smoke",
        "value": 1.0,
        "unit": "converged",
        "tasks": n_tasks,
        "wall_s": round(wall_s, 2),
        "sessions": summary.get("spawned"),
        "worker_deaths": summary.get("worker_deaths"),
        "drill_preemptions": summary.get("drill_preemptions"),
        "evictions": summary.get("evictions"),
        "trace_events": trace_stats["trace_events"],
        "trace_workers": trace_stats["workers"],
        "trace_flow_pairs": trace_stats["flow_pairs"],
        "gate_pass": True,
    }


def run_trace_export_overhead(
    n_workers: int = 4,
    n_tasks: int = 2000,
    n_spans: int = 20000,
    n_gauges: int = 20000,
    n_snapshots: int = 2000,
    repeats: int = 3,
) -> dict:
    """Exporter runtime pinned on a large synthetic stream (ISSUE 18):
    a deterministic multi-worker event stream — spans, gauges,
    cumulative snapshots, and cross-worker submit/claim/commit hops with
    injected clock skew — pushed through ``export_chrome_trace`` +
    ``validate_chrome_trace``. The exporter runs post-hoc (never on the
    task hot path), so the budget is absolute throughput, not overhead
    vs a baseline: it must stay fast enough that exporting a full chaos
    acceptance run is an interactive operation. Gate: >= 50k telemetry
    events/s soft (reported as gate_pass); the process only hard-fails
    below 5k events/s — an algorithmic regression (quadratic flow
    matching, per-event re-sorts), not shared-box noise. The exported
    trace must validate clean and carry every cross-worker flow, so the
    gate doubles as a scale test of the skew clamp."""
    from tools.trace_export import export_chrome_trace, validate_chrome_trace

    workers = [f"w{i}" for i in range(n_workers)]
    events = []
    # cross-worker task hops: submit on one worker, claim+commit on
    # another, with the claimer's clock skewed BEHIND the submitter's so
    # worker_clock_offsets has real work to do at scale
    skew = {w: 0.25 * i for i, w in enumerate(workers)}
    for i in range(n_tasks):
        sub_w = workers[i % n_workers]
        claim_w = workers[(i + 1) % n_workers]
        t = 10.0 + i * 0.01
        events.append({"kind": "task", "name": "queue/submit", "t": t,
                       "worker": sub_w, "trace_id": f"tr-{i}"})
        events.append({"kind": "task", "name": "lifecycle/claimed",
                       "t": t + 0.002 - skew[claim_w],
                       "worker": claim_w, "trace_id": f"tr-{i}"})
        events.append({"kind": "task", "name": "lifecycle/committed",
                       "t": t + 0.005 - skew[claim_w],
                       "worker": claim_w, "trace_id": f"tr-{i}"})
    for i in range(n_spans):
        w = workers[i % n_workers]
        events.append({"kind": "span",
                       "name": ("op/inference", "pipeline/drain",
                                "scheduler/dispatch")[i % 3],
                       "t": 10.0 + i * 0.001 - skew[w],
                       "dur_s": 0.0005 + (i % 7) * 1e-4, "worker": w})
    for i in range(n_gauges):
        w = workers[i % n_workers]
        events.append({"kind": "gauge",
                       "name": f"shard/chip/{i % 8}/ready_s",
                       "t": 10.0 + i * 0.001 - skew[w],
                       "value": float(i % 100), "worker": w})
    for i in range(n_snapshots):
        w = workers[i % n_workers]
        events.append({"kind": "snapshot",
                       "t": 10.0 + i * 0.01 - skew[w], "worker": w,
                       "counters": {"tasks/committed": float(i),
                                    "shard/halo_bytes": float(i) * 4096}})
    events.sort(key=lambda e: e["t"])

    best_s = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        trace = export_chrome_trace(events)
        problems = validate_chrome_trace(trace)
        elapsed = time.perf_counter() - t0
        best_s = elapsed if best_s is None else min(best_s, elapsed)
    if problems:
        raise RuntimeError(
            f"trace_export_overhead: synthetic trace invalid: "
            f"{problems[:5]}")
    flow_pairs = trace["otherData"]["flow_pairs"]
    if flow_pairs != n_tasks:
        raise RuntimeError(
            f"trace_export_overhead: {flow_pairs}/{n_tasks} "
            f"cross-worker flows survived export")
    events_per_s = len(events) / best_s
    return {
        "metric": "trace_export_overhead",
        "value": round(events_per_s, 1),
        "unit": "events/s",
        "events": len(events),
        "trace_events": len(trace["traceEvents"]),
        "flow_pairs": flow_pairs,
        "best_s": round(best_s, 4),
        "gate_pct": 50000.0,  # soft floor, events/s
        "gate_pass": bool(events_per_s >= 50000.0),
    }


def _check_pallas_oracle():
    """Identity-engine oracle at toy size: catches a miscompiled pallas
    scatter kernel (wrong results, not just crashes) before it can taint
    the measured config."""
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.inference import Inferencer

    inferencer = Inferencer(
        input_patch_size=(4, 16, 16),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=3,
        framework="identity",
        batch_size=2,
        crop_output_margin=False,
    )
    rng = np.random.default_rng(1)
    chunk = rng.random((8, 32, 32)).astype(np.float32)
    out = np.asarray(inferencer(Chunk(chunk)).array)
    mse = float(((out - chunk[None]) ** 2).mean())
    if mse > 1e-8:
        raise RuntimeError(f"pallas identity oracle failed: MSE={mse}")


def _cfg_name(cfg: dict) -> str:
    name = (
        f"{cfg['model_variant']}-{cfg['dtype']}-"
        f"bs{cfg['batch_size']}-pallas{cfg.get('pallas', '0')}"
    )
    if cfg.get("stream"):
        name += f"-stream{cfg['stream']}"
    if cfg.get("output_dtype", "float32") != "float32":
        name += f"-out{cfg['output_dtype']}"
    if "stacked" in cfg:
        name += f"-stacked{cfg['stacked']}"
    if cfg.get("blend", "auto") != "auto":
        name += f"-{cfg['blend']}"
    if "chunk_size" in cfg:
        name += "-" + "x".join(str(s) for s in cfg["chunk_size"])
    if "overlap" in cfg:
        name += "-ov" + "x".join(str(s) for s in cfg["overlap"])
    if cfg.get("input_dtype", "float32") != "float32":
        name += f"-in{cfg['input_dtype']}"
    if cfg.get("tta"):
        name += "-tta8"
    return name


def _emit(payload: dict) -> int:
    print(json.dumps(payload))
    _append_ledger(payload)
    return 0


def headline_main() -> int:
    """``python bench.py``: run every CONFIGS entry in this process on the
    TPU and print the best as one JSON row. No TPU, or no config that
    ran: no row and a non-zero exit."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"bench.py measures on a TPU, and jax found platform "
            f"{dev.platform!r} ({dev.device_kind}); no result "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
            file=sys.stderr,
        )
        return 2
    results: dict = {}
    failed = []
    for cfg in CONFIGS:
        name = _cfg_name(cfg)
        try:
            results[name] = run_config(cfg)
        except Exception:
            failed.append(name)
            print(f"--- bench config {name} failed ---\n"
                  f"{traceback.format_exc()}", file=sys.stderr)
    if not results:
        print("all bench configs failed", file=sys.stderr)
        return 1
    name, stats = max(results.items(), key=lambda kv: kv[1]["mvox_s"])
    return _emit({
        "metric": "affinity_inference_throughput",
        "value": round(stats["mvox_s"], 2),
        "unit": "Mvoxel/s/chip",
        "vs_baseline": round(stats["mvox_s"] / BASELINE_MVOX_S, 2),
        "config": name,
        "failed": failed,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    })


def main() -> int:
    global _LEDGER_FILE
    argv = list(sys.argv[1:])
    if argv and argv[0] == "compare":
        return compare_main(argv[1:])  # reads the ledger, never appends
    # --ledger[=PATH]: append every emitted measurement to the bench
    # regression ledger (CHUNKFLOW_BENCH_LEDGER env enables it too and
    # sets the path); consumed here so subcommand dispatch stays simple
    for arg in [a for a in argv if a == "--ledger"
                or a.startswith("--ledger=")]:
        _LEDGER_FILE = (arg.split("=", 1)[1] if "=" in arg
                        else _default_ledger_path())
        argv.remove(arg)
    if _LEDGER_FILE is None and os.environ.get("CHUNKFLOW_BENCH_LEDGER"):
        _LEDGER_FILE = _default_ledger_path()
    sys.argv = [sys.argv[0]] + argv
    if len(sys.argv) > 1 and sys.argv[1] in (
        "pipeline_overlap", "telemetry_overhead", "e2e_overlap",
        "resilience_overhead", "export_overhead", "fleet_smoke",
        "serving_throughput", "locksmith_overhead", "storage_throughput",
        "slo_overhead", "multichip_overlap", "blend_fused", "front_half",
        "fused_pipeline", "kernelcheck_overhead", "trace_export_overhead",
        "multichip_sharded_replay", "segmentation_stitch",
    ):
        # CPU micro-benchmarks: they measure the EXECUTOR/telemetry
        # layer, not the chip, so force the host backend before jax loads
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if sys.argv[1] in ("multichip_overlap", "multichip_sharded_replay"):
            # the unified sharded engine needs the 8-device virtual CPU
            # mesh; force it before jax first loads in this process
            import re as _re

            flags = _re.sub(
                r"--xla_force_host_platform_device_count=\d+", "",
                os.environ.get("XLA_FLAGS", ""),
            ).strip()
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        if sys.argv[1] == "multichip_sharded_replay":
            result = run_multichip_sharded_replay()
            _emit(result)
            # soft gate at the 1.3x target (reported as gate_pass,
            # asserted slow-marked in tests/test_bench.py); hard floor
            # at 1.1x — below that the sharded replay lost to the
            # replicated replay outright (bit-identity of BOTH legs
            # against the single-device reference and the
            # roofline-ledger presence are asserted inside, raising on
            # any violation)
            return 0 if result["value"] >= 1.1 else 4
        if sys.argv[1] == "multichip_overlap":
            result = run_multichip_overlap()
            _emit(result)
            # soft gate at the 1.3x target (reported as gate_pass,
            # asserted slow-marked in tests/test_bench.py); hard floor
            # at 1.1x — below that the sharded engine lost to the
            # single-device path outright (bit-identity and the
            # roofline-ledger presence are asserted inside, raising on
            # any violation)
            return 0 if result["value"] >= 1.1 else 4
        if sys.argv[1] == "blend_fused":
            result = run_blend_fused()
            _emit(result)
            # soft gate at the 1.2x target (reported as gate_pass,
            # asserted slow-marked in tests/test_bench.py); hard floor
            # at 1.1x — below that the fused data-movement structure
            # lost to the separate-leg baseline outright (bit-identity
            # across both proxies, the XLA scatter reference AND the
            # real interpret-mode kernel is asserted inside, raising on
            # any divergence)
            return 0 if result["value"] >= 1.1 else 4
        if sys.argv[1] == "front_half":
            result = run_front_half()
            _emit(result)
            # soft gate at the 1.2x target (reported as gate_pass,
            # asserted slow-marked in tests/test_bench.py); hard floor
            # at 1.1x — below that the device-resident front lost to
            # the host gather+convert+re-upload structure outright
            # (bit-identity across both legs AND the real interpret-mode
            # gather kernel is asserted inside, raising on divergence)
            return 0 if result["value"] >= 1.1 else 4
        if sys.argv[1] == "fused_pipeline":
            result = run_fused_pipeline()
            _emit(result)
            # soft gate at the 1.2x target (reported as gate_pass,
            # asserted slow-marked in tests/test_bench.py); hard floor
            # at 1.1x — below that the one-program pipeline lost to the
            # separate-programs structure outright (bit-identity across
            # both proxies AND the real gather->forward->blend kernels
            # composed in interpret mode is asserted inside, raising on
            # any divergence)
            return 0 if result["value"] >= 1.1 else 4
        if sys.argv[1] == "pipeline_overlap":
            return _emit(run_pipeline_overlap())
        if sys.argv[1] == "e2e_overlap":
            result = run_e2e_overlap()
            _emit(result)
            # soft gate at the 1.4x target (reported as gate_pass; the
            # suite asserts it best-of-3 in a fresh subprocess); hard
            # floor at 1.1x — below that the scheduler lost its overlap
            return 0 if result["value"] >= 1.1 else 4
        if sys.argv[1] == "resilience_overhead":
            result = run_resilience_overhead()
            _emit(result)
            # soft gate at the 3% target (reported as gate_pass), hard
            # gate at 15%: the fault-tolerance layer must be ~free —
            # a lock/fsync on the per-task path is a real regression,
            # shared-box scheduling noise is not
            return 0 if result["value"] < 15.0 else 4
        if sys.argv[1] == "trace_export_overhead":
            result = run_trace_export_overhead()
            _emit(result)
            # soft floor at 50k events/s (reported as gate_pass), hard
            # floor at 5k: the exporter is post-hoc, so only an
            # algorithmic regression (quadratic flow matching, per-event
            # re-sorts) can push it that slow — shared-box scheduling
            # noise cannot
            return 0 if result["value"] >= 5000.0 else 4
        if sys.argv[1] == "segmentation_stitch":
            result = run_segmentation_stitch()
            _emit(result)
            # soft gate at the 1.3x target (reported as gate_pass,
            # asserted best-of-3 in a fresh subprocess in
            # tests/test_bench.py); hard floor at 1.1x — below that the
            # stitched pipeline lost to the monolithic pass outright
            # (label-isomorphism of the two legs is asserted inside,
            # raising on any divergence)
            return 0 if result["value"] >= 1.1 else 4
        if sys.argv[1] == "fleet_smoke":
            # binary gate: a multi-process chaos run either converges
            # (every task exactly once despite a SIGKILL and a drill)
            # or run_fleet_smoke raises and the process exits nonzero
            return _emit(run_fleet_smoke())
        if sys.argv[1] == "locksmith_overhead":
            result = run_locksmith_overhead()
            _emit(result)
            # soft gate at the 5% target (reported as gate_pass), hard
            # gate at 25%: the sanitizer must stay near-free on the
            # scheduled hot path; shared-box noise must not redden CI
            return 0 if result["value"] < 25.0 else 4
        if sys.argv[1] == "kernelcheck_overhead":
            result = run_kernelcheck_overhead()
            _emit(result)
            # soft gate at the 5% target (reported as gate_pass), hard
            # gate at 25%: the kernel sanitizer must stay near-free on
            # the interpret parity legs tier-1 runs it on; shared-box
            # noise must not redden CI
            return 0 if result["value"] < 25.0 else 4
        if sys.argv[1] == "storage_throughput":
            result = run_storage_throughput()
            _emit(result)
            # soft gate at the 1.3x target (reported as gate_pass,
            # asserted slow-marked in tests/test_bench.py); hard floor
            # at 1.1x — below that the hot cache lost to the serial
            # path outright (bit-identity is asserted inside, raising
            # on any divergence)
            return 0 if result["value"] >= 1.1 else 4
        if sys.argv[1] == "serving_throughput":
            result = run_serving_throughput()
            _emit(result)
            # soft gate at the 1.3x target (reported as gate_pass,
            # asserted in tests/test_bench.py); hard floor at 1.1x —
            # below that the packer lost its occupancy win outright
            # (bit-identity is asserted inside, raising on divergence)
            return 0 if result["value"] >= 1.1 else 4
        if sys.argv[1] == "slo_overhead":
            result = run_slo_overhead()
            _emit(result)
            # soft gate at the 2% target (reported as gate_pass), hard
            # gate at 10%: the SLO plane samples off the hot path — a
            # real regression means the sampler/evaluator landed a lock
            # or per-task work where it must not; shared-box noise must
            # not redden CI
            return 0 if result["value"] < 10.0 else 4
        if sys.argv[1] == "export_overhead":
            result = run_export_overhead()
            _emit(result)
            # soft gate at the 2% target (reported as gate_pass), hard
            # gate at 10%: the exporter serves registry snapshots off
            # the hot path — anything past noise means a lock landed on
            # the per-task path
            return 0 if result["value"] < 10.0 else 4
        result = run_telemetry_overhead()
        _emit(result)
        # soft gate at the 2% target (reported), hard gate at 10x it:
        # shared-box scheduling noise must not redden CI, a real
        # regression (a lock on the hot path, per-event fsync) must
        return 0 if result["value"] < 10.0 else 4
    return headline_main()


if __name__ == "__main__":
    raise SystemExit(main())
