"""bench.py config-matrix smoke: every CONFIGS entry must run end-to-end
(tiny shapes, CPU mesh) so breakage surfaces in CI, not on the chip. The pallas config must fail loudly on a non-TPU backend
rather than silently measuring the XLA path."""
import json

import numpy as np
import pytest

import bench


@pytest.fixture()
def tiny_bench(monkeypatch):
    monkeypatch.setattr(bench, "INPUT_PATCH", (8, 32, 32))
    monkeypatch.setattr(bench, "OUTPUT_OVERLAP", (2, 8, 8))
    # keep env mutations from leaking into other tests
    monkeypatch.setenv("CHUNKFLOW_PALLAS", "0")
    monkeypatch.delenv("CHUNKFLOW_BLEND_STACK_MAX_GB", raising=False)
    return bench


def test_all_nonpallas_configs_run(tiny_bench):
    ran = 0
    for cfg in tiny_bench.CONFIGS:
        if cfg.get("pallas", "0") not in ("0", "off", "false"):
            continue
        cfg = dict(cfg, chunk_size=(16, 64, 64), batch_size=2, iters=1)
        if cfg.get("stream"):
            cfg["stream"] = 2
        stats = tiny_bench.run_config(cfg)
        assert stats["mvox_s"] > 0, cfg
        ran += 1
    assert ran >= 5


def test_pallas_config_fails_loudly_on_cpu(tiny_bench):
    cfg = dict(
        next(
            c for c in tiny_bench.CONFIGS
            if c.get("pallas", "0") == "1"
        ),
        chunk_size=(16, 64, 64),
        batch_size=2,
    )
    # CHUNKFLOW_PALLAS=1 selects the compiled kernel whatever the
    # platform; on CPU the kernel itself then fails in the
    # pre-measurement oracle instead of silently measuring XLA
    with pytest.raises((RuntimeError, ValueError)):
        tiny_bench.run_config(cfg)


@pytest.mark.bench
@pytest.mark.slow
def test_pipeline_overlap_microbench(tmp_path):
    """The double-buffered executor must beat the serial chunk loop on
    the synthetic CPU workload (ISSUE 2 acceptance: >= 1.2x) and stay
    bit-identical — run_pipeline_overlap itself raises on divergence.

    Marked slow/bench (ISSUE 7 satellite): this speedup-RATIO gate is
    load-sensitive — it flips in full tier-1 runs on the 1-core CI box
    even at commits where it passes in isolation (verified in PR 6 by
    stash-and-rerun), so tier-1 (-m 'not slow') no longer reports it as
    a false regression. Coverage is kept by run_tests.sh, which runs
    the same workload as a standalone gate after pytest.

    Measured in a FRESH SUBPROCESS under the benchmark's actual
    contract (`python bench.py pipeline_overlap` from a shell): inside
    the suite's interpreter the ratio is contaminated down to ~1.0
    (observed at the PR 2 commit as well, so suite state, not the
    executor) — chiefly by conftest.py's
    --xla_force_host_platform_device_count=8, which splits the CPU
    client 8 ways and must be scrubbed from the child env too. The
    overlap itself is deterministic; best-of-3 still guards against
    load spikes on a shared CI box."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)  # the 8-device virtual mesh (conftest.py)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "pipeline_overlap"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.2:
            break
    assert best["value"] >= 1.2, best
    assert best["metric"] == "pipeline_overlap_speedup"
    assert best["pipelined_s"] < best["serial_s"], best
    # the run's own telemetry JSONL landed where we pointed it
    assert best["cache_builds"] == 1, best  # one bucket -> one trace
    assert any(
        name.endswith(".jsonl") for name in os.listdir(tmp_path)
    ), best.get("telemetry_jsonl")


@pytest.mark.bench
@pytest.mark.slow
def test_e2e_overlap_microbench(tmp_path):
    """The adaptive scheduler must beat the serial full-lifecycle loop
    (load → compute → post → write) on the calibrated synthetic CPU
    workload (ISSUE 4 acceptance: >= 1.4x) and stay bit-identical —
    run_e2e_overlap itself raises on divergence or broken task order.

    Marked slow/bench (ISSUE 7 satellite): load-sensitive ratio gate —
    see test_pipeline_overlap_microbench; run_tests.sh runs the same
    workload as a standalone gate after pytest.

    Fresh-subprocess pattern from the pipeline_overlap gate: inside the
    suite's interpreter the ratio is contaminated by conftest's 8-device
    virtual mesh; best-of-3 guards against load spikes on a shared CI
    box."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)  # the 8-device virtual mesh (conftest.py)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "e2e_overlap"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.4:
            break
    assert best["value"] >= 1.4, best
    assert best["metric"] == "e2e_overlap_speedup"
    assert best["gate_pass"] is True, best
    assert best["scheduled_s"] < best["serial_s"], best
    # the JSON line reports the final adapted depths, and the run's own
    # telemetry JSONL (incl. the scheduler/final depths event) landed
    # where we pointed it
    assert set(best["final_depths"]) == {
        "prefetch", "ring", "inflight", "post", "write", "storage"
    }, best
    jsonls = [n for n in os.listdir(tmp_path) if n.endswith(".jsonl")]
    assert jsonls, best.get("telemetry_jsonl")
    events = []
    for name in jsonls:
        with open(os.path.join(tmp_path, name)) as f:
            events += [json.loads(line) for line in f if line.strip()]
    finals = [e for e in events
              if e.get("kind") == "depths" and e["name"] == "scheduler/final"]
    assert finals, "no scheduler/final depths event in the run's JSONL"


def test_resilience_overhead_microbench(tmp_path):
    """The fault-tolerance layer (supervised claims + completion ledger
    + lease heartbeat, ISSUE 5) must be ~free over the e2e_overlap-style
    workload: run_resilience_overhead itself raises on a broken task
    order, an undrained queue, or an incomplete ledger; the process
    hard-fails past 15% overhead. The <3% target rides the JSON line as
    gate_pass — asserted loosely here (< half the hard gate) because a
    1-core shared CI box can inflate a sub-millisecond-per-task delta.

    Fresh-subprocess pattern from the other microbench gates: conftest's
    8-device virtual mesh contaminates in-suite measurement."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "resilience_overhead"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] < best["value"]:
            best = stats
        if best["gate_pass"]:
            break
    assert best["metric"] == "resilience_overhead"
    assert best["value"] < 7.5, best  # half the 15% hard gate
    assert best["gate_pct"] == 3.0
    assert best["on_s"] > 0 and best["off_s"] > 0, best
    assert any(
        name.endswith(".jsonl") for name in os.listdir(tmp_path)
    ), best.get("telemetry_jsonl")


def test_export_overhead_microbench(tmp_path):
    """The live /metrics exporter (ISSUE 6) must be ~free over the
    e2e_overlap-style workload even while being scraped continuously:
    run_export_overhead itself raises on a broken task order or a
    missing listener; the process hard-fails past 10% overhead. The <2%
    target rides the JSON line as gate_pass — asserted loosely here
    (< half the hard gate) because a 1-core shared CI box can inflate a
    sub-millisecond-per-task delta.

    Fresh-subprocess pattern from the other microbench gates: conftest's
    8-device virtual mesh contaminates in-suite measurement."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "export_overhead"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] < best["value"]:
            best = stats
        if best["gate_pass"]:
            break
    assert best["metric"] == "export_overhead"
    assert best["value"] < 5.0, best  # half the 10% hard gate
    assert best["gate_pct"] == 2.0
    assert best["scrapes"] > 0, best  # the listener really was being hit
    assert best["on_s"] > 0 and best["off_s"] > 0, best
    assert any(
        name.endswith(".jsonl") for name in os.listdir(tmp_path)
    ), best.get("telemetry_jsonl")


def test_trace_export_overhead_shape_and_invariants():
    """The Perfetto exporter gate (ISSUE 18): run_trace_export_overhead
    raises if the synthetic trace fails validation or drops a
    cross-worker flow, so in-suite we only pin the measurement shape at
    a tiny size — absolute throughput is the CI stage's business (soft
    floor 50k events/s, hard floor 5k)."""
    stats = bench.run_trace_export_overhead(
        n_workers=3, n_tasks=40, n_spans=200, n_gauges=200,
        n_snapshots=40, repeats=1)
    assert stats["metric"] == "trace_export_overhead"
    assert stats["unit"] == "events/s"
    assert stats["value"] > 0 and stats["best_s"] > 0
    assert stats["events"] == 40 * 3 + 200 + 200 + 40
    assert stats["flow_pairs"] == 40  # every synthetic task hops
    assert stats["trace_events"] >= stats["events"]
    assert stats["gate_pct"] == 50000.0
    assert isinstance(stats["gate_pass"], bool)


def test_slo_overhead_microbench(tmp_path):
    """The SLO plane (time-series sampler + burn-rate evaluator,
    ISSUE 12) must be ~free over the e2e_overlap-style workload even at
    a 0.1 s sampling interval (100x the production default):
    run_slo_overhead itself raises when the plane fails to run, takes
    no samples, or fires an alert on the healthy workload; the process
    hard-fails past 10% overhead. The <2% target rides the JSON line as
    gate_pass — asserted loosely here (< half the hard gate) because a
    1-core shared CI box can inflate a sub-millisecond-per-task delta.

    Fresh-subprocess pattern from the other microbench gates: conftest's
    8-device virtual mesh contaminates in-suite measurement."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "slo_overhead"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] < best["value"]:
            best = stats
        if best["gate_pass"]:
            break
    assert best["metric"] == "slo_overhead"
    assert best["value"] < 5.0, best  # half the 10% hard gate
    assert best["gate_pct"] == 2.0
    assert best["on_s"] > 0 and best["off_s"] > 0, best


def test_headline_without_tpu_prints_no_row(capsys):
    """`python bench.py` on a machine without a TPU: non-zero exit, the
    reason on stderr, and nothing a reader could take for a result."""
    assert bench.headline_main() == 2
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "platform 'cpu'" in captured.err


def test_cfg_names_unique():
    names = [bench._cfg_name(c) for c in bench.CONFIGS]
    assert len(names) == len(set(names)), names


# ---------------------------------------------------------------------------
# bench regression ledger (ISSUE 8): --ledger append + compare semantics
# ---------------------------------------------------------------------------
def _ledger_row(metric, value, unit="x_serial", commit="abc1234",
                config=None):
    return {"t": 0.0, "commit": commit, "metric": metric, "value": value,
            "unit": unit, "config": config}


def test_ledger_append_stamps_commit_and_config(tmp_path, monkeypatch):
    path = tmp_path / "ledger.jsonl"
    monkeypatch.setattr(bench, "_LEDGER_FILE", str(path))
    bench._append_ledger({"metric": "e2e_overlap_speedup", "value": 2.5,
                          "unit": "x_serial", "gate_pass": True,
                          "config": "cfg-a"})
    (row,) = bench.load_ledger(str(path))
    assert row["metric"] == "e2e_overlap_speedup"
    assert row["commit"]  # stamped with the measured tree's commit
    assert row["config"] == "cfg-a"
    assert row["gate_pass"] is True


def test_ledger_flag_consumed_by_main(tmp_path, monkeypatch, capsys):
    """`bench.py compare --ledger=PATH` parses and reads that path."""
    path = tmp_path / "ledger.jsonl"
    with open(path, "w") as f:
        f.write(json.dumps(_ledger_row("m", 2.0)) + "\n")
    monkeypatch.setattr(bench.sys, "argv",
                        ["bench.py", "compare", f"--ledger={path}"])
    assert bench.main() == 0
    assert "1 row(s)" in capsys.readouterr().out


def test_compare_flags_fresh_regression(tmp_path):
    """Acceptance: a ledger seeded with two fresh entries flags an
    injected 30% regression (hard, exit 4 through compare_main)."""
    rows = [
        _ledger_row("e2e_overlap_speedup", 2.0),
        _ledger_row("e2e_overlap_speedup", 2.1),
        _ledger_row("e2e_overlap_speedup", 1.4),  # ~32% below median 2.05
    ]
    report = bench.compare_ledger(rows, threshold_pct=25.0)
    info = report["metrics"]["e2e_overlap_speedup"]
    assert info["status"] == "regression"
    assert info["baseline"] == pytest.approx(2.05)
    assert info["delta_pct"] > 25
    assert report["regressions"]

    path = tmp_path / "ledger.jsonl"
    with open(path, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    assert bench.compare_main([f"--ledger={path}"]) == 4


def test_compare_within_threshold_passes(tmp_path):
    rows = [
        _ledger_row("e2e_overlap_speedup", 2.0),
        _ledger_row("e2e_overlap_speedup", 2.1),
        _ledger_row("e2e_overlap_speedup", 1.9),  # ~7%: noise
    ]
    report = bench.compare_ledger(rows)
    assert report["metrics"]["e2e_overlap_speedup"]["status"] == "ok"
    assert not report["regressions"]


def test_compare_single_fresh_baseline_warns_only():
    rows = [
        _ledger_row("e2e_overlap_speedup", 2.0),
        _ledger_row("e2e_overlap_speedup", 1.0),  # 50% down, 1 baseline
    ]
    report = bench.compare_ledger(rows)
    assert report["metrics"]["e2e_overlap_speedup"]["status"] == "warn"
    assert not report["regressions"]


def test_compare_percentage_metrics_warn_only():
    """Overhead gates (pct units) are noise-dominated on a loaded box:
    even a big relative jump warns instead of hard-failing."""
    rows = [
        _ledger_row("telemetry_overhead", 1.0,
                    unit="pct_of_untelemetered_wall"),
        _ledger_row("telemetry_overhead", 1.2,
                    unit="pct_of_untelemetered_wall"),
        _ledger_row("telemetry_overhead", 5.0,
                    unit="pct_of_untelemetered_wall"),
    ]
    report = bench.compare_ledger(rows)
    assert report["metrics"]["telemetry_overhead"]["status"] == "warn"
    assert not report["regressions"]


def test_compare_empty_ledger_is_ok(tmp_path):
    assert bench.compare_main(
        [f"--ledger={tmp_path / 'missing.jsonl'}"]) == 0


@pytest.mark.bench
@pytest.mark.slow
def test_serving_throughput_microbench(tmp_path):
    """Packed cross-request batching must beat sequential per-chunk
    execution on many small concurrent requests (ISSUE 9 acceptance:
    >= 1.3x packed-occupancy speedup) and stay bit-identical —
    run_serving_throughput itself raises on any divergence.

    Marked slow/bench like the other load-sensitive ratio gates;
    run_tests.sh runs the same workload as a standalone gate after
    fleet_smoke. Fresh-subprocess + best-of-3 pattern shared with them."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the 8-device virtual mesh (conftest.py)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "serving_throughput"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.3:
            break
    assert best["metric"] == "serving_throughput"
    assert best["value"] >= 1.3, best
    assert best["gate_pass"] is True, best
    assert best["bit_identical"] is True, best
    # the win is occupancy by construction: the packer must actually
    # have filled its batches from cross-request traffic
    assert best["packed_occupancy"] >= 0.9, best


@pytest.mark.bench
@pytest.mark.slow
def test_storage_throughput_microbench(tmp_path):
    """The hot block cache + concurrent block reads must beat the
    historical serial whole-range read on the overlapping-halo cutout
    grid (ISSUE 11 acceptance: >= 1.3x with a hot cache) and stay
    bit-identical — run_storage_throughput itself raises on any
    divergence between the serial, concurrent and cached legs.

    Marked slow/bench like the other load-sensitive ratio gates (the
    PR 7 deflake convention); run_tests.sh runs the same workload as a
    standalone gate after serving_throughput. Fresh-subprocess +
    best-of-3 pattern shared with them."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)  # the 8-device virtual mesh (conftest.py)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "storage_throughput"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.3:
            break
    assert best["metric"] == "storage_throughput_speedup"
    assert best["value"] >= 1.3, best
    assert best["gate_pass"] is True, best
    # the win is the cache by construction: the hot pass must be pure
    # hits, and the cold pass must already hit on grid overlap
    assert best["hot_cache_misses"] == 0, best
    assert best["cold_cache_hits"] > 0, best
    assert best["concurrent_cold_s"] < best["serial_s"], best
    # the run's storage counters landed in the telemetry JSONL for
    # log-summary (the acceptance visibility criterion)
    jsonls = [n for n in os.listdir(tmp_path) if n.endswith(".jsonl")]
    assert jsonls, best.get("telemetry_jsonl")
    events = []
    for name in jsonls:
        with open(os.path.join(tmp_path, name)) as f:
            events += [json.loads(line) for line in f if line.strip()]
    snaps = [e for e in events if e.get("kind") == "snapshot"]
    assert snaps, "no snapshot event in the run's JSONL"
    counters = snaps[-1].get("counters") or {}
    assert counters.get("storage/hits", 0) > 0, counters
    assert counters.get("storage/misses", 0) > 0, counters
    assert counters.get("storage/bytes_read", 0) > 0, counters


@pytest.mark.bench
@pytest.mark.slow
def test_segmentation_stitch_microbench(tmp_path):
    """The stitched map->reduce->map labeling must beat the monolithic
    whole-volume pass against latency-charged storage (ISSUE 20
    acceptance: >= 1.3x soft / 1.1x hard) and be label-isomorphic to
    it — run_segmentation_stitch itself raises on any divergence, so
    every round the speedup counts is also an exactness round.

    Marked slow/bench like the other load-sensitive ratio gates (the
    PR 7 deflake convention); run_tests.sh runs the same workload as a
    standalone gate. Fresh-subprocess + best-of-3 pattern shared with
    them."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)  # the 8-device virtual mesh (conftest.py)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "segmentation_stitch"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.3:
            break
    assert best["metric"] == "segmentation_stitch_speedup"
    assert best["value"] >= 1.3, best
    assert best["gate_pass"] is True, best
    # the whole grid went through the tree: every chunk labeled, every
    # interior node merged (a binary tree over n leaves has n-1)
    assert best["merge_nodes"] == best["n_chunks"] - 1, best
    # the run's segment counters landed in the telemetry JSONL for
    # log-summary's SEGMENT block (the acceptance visibility criterion)
    jsonls = [n for n in os.listdir(tmp_path) if n.endswith(".jsonl")]
    assert jsonls, best.get("telemetry_jsonl")
    events = []
    for name in jsonls:
        with open(os.path.join(tmp_path, name)) as f:
            events += [json.loads(line) for line in f if line.strip()]
    snaps = [e for e in events if e.get("kind") == "snapshot"]
    assert snaps, "no snapshot event in the run's JSONL"
    counters = snaps[-1].get("counters") or {}
    assert counters.get("segment/chunks_labeled", 0) == best["n_chunks"], \
        counters
    assert counters.get("segment/edges_found", 0) > 0, counters
    assert counters.get("segment/voxels_relabeled", 0) > 0, counters


@pytest.mark.bench
@pytest.mark.slow
def test_blend_fused_microbench(tmp_path):
    """The fused blend data-movement structure must beat the
    separate-leg baseline (ISSUE 14 acceptance: >= 1.2x soft / 1.1x
    hard) with bit-identity asserted in-run across both proxy legs, the
    XLA scatter reference and the real interpret-mode Pallas kernel —
    run_blend_fused itself raises on any divergence — and the fused
    family's roofline_util must be >= the separate-leg baseline in
    programs.json on the same workload.

    Marked slow/bench like the other load-sensitive ratio gates (the
    PR 7 deflake convention); run_tests.sh runs the same workload as a
    standalone gate after the multichip gate. Fresh-subprocess +
    best-of-3 pattern shared with them."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("CHUNKFLOW_PALLAS", None)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "blend_fused"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.2 and best["roofline_ok"]:
            break
    assert best["metric"] == "blend_fused"
    assert best["value"] >= 1.2, best
    assert best["gate_pass"] is True, best
    assert best["bit_identical"] is True, best
    assert best["interpret_kernel_checked"] is True, best
    # the acceptance roofline criterion: fused family util >= the
    # separate-leg baseline on the same workload, from programs.json
    assert best["roofline_ok"] is True, best
    programs = os.path.join(tmp_path, "programs.json")
    assert os.path.exists(programs), os.listdir(tmp_path)
    with open(programs) as f:
        entries = {e["family"]: e for e in json.load(f)["programs"]}
    assert "blend_fused" in entries and "blend_sep" in entries, entries
    assert (entries["blend_fused"]["roofline_util"]
            >= entries["blend_sep"]["roofline_util"]), entries


@pytest.mark.bench
@pytest.mark.slow
def test_front_half_microbench(tmp_path):
    """The device-resident front half must beat the host
    gather+convert+re-upload structure (ISSUE 15 acceptance: >= 1.2x
    soft / 1.1x hard on the H2D/data-movement proxy) with bit-identity
    asserted in-run across both legs and the real interpret-mode Pallas
    gather kernel — run_front_half itself raises on any divergence —
    and both legs must carry roofline rows in programs.json.

    Marked slow/bench like the other load-sensitive ratio gates (the
    PR 7 deflake convention); run_tests.sh runs the same workload as a
    standalone gate after the fused-blend gate. Fresh-subprocess +
    best-of-3 pattern shared with them."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("CHUNKFLOW_GATHER", None)
    env.pop("XLA_FLAGS", None)  # the 8-device virtual mesh (conftest.py)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "front_half"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.2:
            break
    assert best["metric"] == "front_half"
    assert best["value"] >= 1.2, best
    assert best["gate_pass"] is True, best
    assert best["bit_identical"] is True, best
    assert best["interpret_kernel_checked"] is True, best
    # the per-chunk H2D contract: the device leg ships the raw chunk
    # ONCE; the host leg ships every gathered patch as float32
    assert best["h2d_bytes_dev"] < best["h2d_bytes_host"], best
    assert best["h2d_ratio"] >= 4.0, best
    programs = os.path.join(tmp_path, "programs.json")
    assert os.path.exists(programs), os.listdir(tmp_path)
    with open(programs) as f:
        entries = {e["family"]: e for e in json.load(f)["programs"]}
    assert "front_dev" in entries and "front_host" in entries, entries
    assert entries["front_dev"]["roofline_util"] is not None, entries
    assert entries["front_host"]["roofline_util"] is not None, entries


@pytest.mark.bench
@pytest.mark.slow
def test_fused_pipeline_microbench(tmp_path):
    """The one-program patch pipeline (device-resident weighted stacks,
    donated on-device overlay, one scatter) must beat the
    separate-programs serving structure it replaced (ISSUE 17
    acceptance: >= 1.2x soft / 1.1x hard) with bit-identity asserted
    in-run across both proxies AND the composed real Pallas kernels
    (gather -> forward -> fused blend, interpret mode) —
    run_fused_pipeline itself raises on any divergence — and both legs
    must carry roofline rows in programs.json with the fused leg's
    utilization at least the separate leg's (both legs stamp the same
    logical byte floor, so util ranks the structures on identical
    work).

    Marked slow/bench like the other load-sensitive ratio gates (the
    PR 7 deflake convention); run_tests.sh runs the same workload as a
    standalone gate after the front-half gate. Fresh-subprocess +
    best-of-3 pattern shared with them."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("CHUNKFLOW_FUSED_PIPELINE", None)
    env.pop("XLA_FLAGS", None)  # the 8-device virtual mesh (conftest.py)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "fused_pipeline"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.2 and best["roofline_ok"]:
            break
    assert best["metric"] == "fused_pipeline"
    assert best["value"] >= 1.2, best
    assert best["gate_pass"] is True, best
    assert best["bit_identical"] is True, best
    assert best["interpret_kernel_checked"] is True, best
    assert best["roofline_ok"] is True, best
    # the fusion's prize, itemized: the separate structure pays real
    # inter-stage stack traffic; the fused structure pays none
    assert best["hbm_intermediate_sep"] > 0, best
    assert best["hbm_intermediate_fused"] == 0, best
    programs = os.path.join(tmp_path, "programs.json")
    assert os.path.exists(programs), os.listdir(tmp_path)
    with open(programs) as f:
        entries = {e["family"]: e for e in json.load(f)["programs"]}
    assert "pipe_fused" in entries and "pipe_sep" in entries, entries
    assert (entries["pipe_fused"]["roofline_util"]
            >= entries["pipe_sep"]["roofline_util"]), entries


@pytest.mark.bench
@pytest.mark.slow
def test_multichip_overlap_microbench(tmp_path):
    """The unified sharded engine on 8 simulated host devices must beat
    the single-device reference path (ISSUE 13 acceptance: >= 1.3x)
    and stay bit-identical — run_multichip_overlap itself raises on any
    divergence between the legs, and on the sharded program missing
    from the roofline ledger.

    Marked slow/bench like the other load-sensitive ratio gates (the
    PR 7 deflake convention); run_tests.sh runs the same workload as a
    standalone gate after the slo gate. Fresh-subprocess + best-of-3
    pattern shared with them (bench.py forces its own 8-device
    XLA_FLAGS, so the conftest scrub is harmless here)."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    env.pop("CHUNKFLOW_MESH", None)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "multichip_overlap"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.3:
            break
    assert best["metric"] == "multichip_overlap"
    assert best["value"] >= 1.3, best
    assert best["gate_pass"] is True, best
    assert best["bit_identical"] is True, best
    assert best["in_roofline_ledger"] is True, best
    assert best["n_devices"] == 8, best
    # one sharded program build, reused across every sharded dispatch
    # (the compile-cache invariant); builds = scatter + shard families
    assert best["cache_builds"] == 2, best
    # the sharded program catalog landed in programs.json (PR 8 ledger)
    programs = os.path.join(tmp_path, "programs.json")
    assert os.path.exists(programs), os.listdir(tmp_path)
    with open(programs) as f:
        entries = json.load(f)["programs"]
    assert any(e.get("family") == "shard" for e in entries), entries


@pytest.mark.bench
@pytest.mark.slow
def test_multichip_sharded_replay_microbench(tmp_path):
    """Sharded blend replay must beat replicated replay on the same
    8-device spatial mesh (ISSUE 19 acceptance: >= 1.3x soft, 1.1x
    hard) and stay bit-identical — run_multichip_sharded_replay itself
    raises on any divergence of either leg from the single-device
    reference, and on the sharded program missing from the roofline
    ledger.

    The measured win is TOTAL replay work removed (replicated replays
    every window on every chip; sharded replays each chip's slab roster
    once), so it holds on the 1-core CI box without calibrated sleeps.
    Fresh-subprocess + best-of-3 pattern shared with the other ratio
    gates (bench.py forces its own 8-device XLA_FLAGS)."""
    import os
    import subprocess
    import sys

    bench_py = os.path.join(os.path.dirname(bench.__file__), "bench.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               CHUNKFLOW_BENCH_METRICS_DIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    env.pop("CHUNKFLOW_MESH", None)
    env.pop("CHUNKFLOW_SHARD_REPLAY", None)
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [sys.executable, bench_py, "multichip_sharded_replay"],
            capture_output=True, text=True, timeout=300, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        stats = json.loads(proc.stdout.strip().splitlines()[-1])
        if best is None or stats["value"] > best["value"]:
            best = stats
        if best["value"] >= 1.3:
            break
    assert best["metric"] == "multichip_sharded_replay"
    assert best["value"] >= 1.1, best  # hard floor
    assert best["gate_pass"] is True, best  # soft 1.3x gate
    assert best["bit_identical"] is True, best
    assert best["in_roofline_ledger"] is True, best
    assert best["n_devices"] == 8, best
    # three program builds — single reference, replicated-replay shard,
    # sharded-replay shard — each reused across every later dispatch
    # (the compile-cache invariant: the replay mode is part of the key)
    assert best["cache_builds"] == 3, best
    # the sharded program catalog landed in programs.json (PR 8 ledger)
    programs = os.path.join(tmp_path, "programs.json")
    assert os.path.exists(programs), os.listdir(tmp_path)
    with open(programs) as f:
        entries = json.load(f)["programs"]
    assert any(e.get("family") == "shard" for e in entries), entries
