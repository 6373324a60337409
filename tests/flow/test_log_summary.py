"""log-summary: per-device aggregation + Mvoxel/s (reference
flow/log_summary.py:57-75 semantics)."""
import json

import numpy as np
import pytest

from chunkflow_tpu.flow import log_summary


@pytest.fixture
def log_dir(tmp_path):
    d = tmp_path / "log"
    d.mkdir()
    # two tasks on one device, one on another; bbox-coded filenames
    specs = [
        ("0-8_0-16_0-16.json", "tpu:v5e", {"load": 1.0, "inference": 3.0}),
        ("8-16_0-16_0-16.json", "tpu:v5e", {"load": 2.0, "inference": 5.0}),
        ("16-24_0-16_0-16.json", "cpu:x86", {"load": 4.0, "inference": 16.0}),
    ]
    for name, device, timer in specs:
        (d / name).write_text(json.dumps({
            "timer": timer, "compute_device": device,
        }))
    return str(d)


def test_load_and_summarize(log_dir):
    records = log_summary.load_log_dir(log_dir)
    assert len(records) == 3
    assert all(r["_bbox"] is not None for r in records)

    frame = log_summary.summarize(records)
    # grouped by device: v5e mean total = (4 + 7) / 2 = 5.5; cpu total = 20
    v5e = frame.loc["tpu:v5e"]
    cpu = frame.loc["cpu:x86"]
    assert v5e[("_total", "mean")] == pytest.approx(5.5)
    assert cpu[("_total", "mean")] == pytest.approx(20.0)
    # Mvoxel/s = voxels / mean_seconds / 1e6; bbox voxels = 8*16*16 = 2048
    assert v5e[("_mvoxel_per_s", "mean")] == pytest.approx(
        np.mean([2048 / 4 / 1e6, 2048 / 7 / 1e6])
    )


def test_summarize_empty_returns_empty_summary(tmp_path, capsys):
    """An empty log dir (or one with no usable records) must yield an
    empty summary with a warning, not a pandas KeyError (ISSUE 3)."""
    empty = tmp_path / "log"
    empty.mkdir()
    records = log_summary.load_log_dir(str(empty))
    assert records == []
    frame = log_summary.summarize(records)
    assert len(frame) == 0
    assert "no usable task records" in capsys.readouterr().err
    # print_summary end to end on the empty dir
    log_summary.print_summary(str(empty))
    assert "no task logs found" in capsys.readouterr().out


def test_load_log_dir_missing_dir_warns(tmp_path, capsys):
    records = log_summary.load_log_dir(str(tmp_path / "nope"))
    assert records == []
    assert "no such log dir" in capsys.readouterr().err


def test_summarize_tolerates_missing_compute_device(tmp_path):
    d = tmp_path / "log"
    d.mkdir()
    (d / "0-8_0-16_0-16.json").write_text(json.dumps({
        "timer": {"inference": 2.0},  # no compute_device key at all
    }))
    frame = log_summary.summarize(log_summary.load_log_dir(str(d)))
    assert frame.loc[""][("_total", "mean")] == pytest.approx(2.0)


def _write_events(path, events):
    path.write_text("\n".join(json.dumps(e) for e in events) + "\n")


def test_telemetry_aggregation(tmp_path):
    _write_events(tmp_path / "telemetry-1.jsonl", [
        {"kind": "span", "name": "pipeline/stage", "dur_s": 1.0},
        {"kind": "span", "name": "pipeline/drain", "dur_s": 3.0},
        {"kind": "span", "name": "pipeline/drain", "dur_s": 5.0},
        {"kind": "gauge", "name": "pipeline/ring_occupancy", "value": 2},
        {"kind": "gauge", "name": "pipeline/ring_occupancy", "value": 1},
        {"kind": "snapshot", "pid": 1,
         "counters": {"compile_cache/builds": 2, "compile_cache/hits": 7}},
    ])
    _write_events(tmp_path / "telemetry-2.jsonl", [
        {"kind": "span", "name": "pipeline/stage", "dur_s": 1.0},
        {"kind": "snapshot", "pid": 2,
         "counters": {"compile_cache/builds": 1}},
    ])
    (tmp_path / "ignored.txt").write_text("not jsonl")

    agg = log_summary.summarize_telemetry(
        log_summary.load_telemetry_dir(str(tmp_path))
    )
    assert agg["spans"]["pipeline/drain"]["count"] == 2
    assert agg["spans"]["pipeline/drain"]["total_s"] == pytest.approx(8.0)
    assert agg["spans"]["pipeline/drain"]["mean_s"] == pytest.approx(4.0)
    # counters sum across per-pid snapshots
    assert agg["counters"]["compile_cache/builds"] == 3
    assert agg["counters"]["compile_cache/hits"] == 7
    assert agg["gauges"]["pipeline/ring_occupancy"]["mean"] == \
        pytest.approx(1.5)
    # stall shares: stage 2s of 10s, drain 8s of 10s
    assert agg["stall"]["pipeline/stage"]["share"] == pytest.approx(0.2)
    assert agg["stall"]["pipeline/drain"]["share"] == pytest.approx(0.8)


def test_telemetry_snapshot_fills_span_holes_without_double_count(tmp_path):
    # a stream recorded with a late-configured sink: spans only in the
    # snapshot hists; gauges in the snapshot must not become spans
    _write_events(tmp_path / "telemetry-1.jsonl", [
        {"kind": "span", "name": "pipeline/drain", "dur_s": 2.0},
        {"kind": "snapshot", "pid": 1,
         "gauges": {"pipeline/ring_occupancy": 2},
         "hists": {
             "pipeline/drain": {"count": 9, "total": 9.0, "max": 2.0},
             "pipeline/stage": {"count": 4, "total": 1.0, "max": 0.5},
             "pipeline/ring_occupancy": {"count": 4, "total": 8.0,
                                         "max": 2},
         }},
    ])
    agg = log_summary.summarize_telemetry(
        log_summary.load_telemetry_dir(str(tmp_path))
    )
    # live span events win over the snapshot copy (no double count)
    assert agg["spans"]["pipeline/drain"]["count"] == 1
    # hole filled from the snapshot
    assert agg["spans"]["pipeline/stage"]["count"] == 4
    # the gauge's histogram is occupancy, not a span
    assert "pipeline/ring_occupancy" not in agg["spans"]


def test_print_telemetry_summary(tmp_path, capsys):
    assert log_summary.print_telemetry_summary(str(tmp_path)) is None
    assert "no telemetry events" in capsys.readouterr().out
    _write_events(tmp_path / "telemetry-1.jsonl", [
        {"kind": "span", "name": "pipeline/stage", "dur_s": 1.0},
        {"kind": "span", "name": "pipeline/drain", "dur_s": 9.0},
        {"kind": "gauge", "name": "pipeline/ring_occupancy", "value": 2},
        {"kind": "snapshot", "pid": 1,
         "counters": {"compile_cache/builds": 1,
                      "compile_cache/hits": 5}},
    ])
    agg = log_summary.print_telemetry_summary(str(tmp_path))
    out = capsys.readouterr().out
    assert agg["stall"]["pipeline/drain"]["share"] == pytest.approx(0.9)
    assert "dominant phase: pipeline/drain" in out
    assert "ring occupancy" in out
    assert "1 build(s), 5 hit(s)" in out


def test_print_mesh_block_renders_per_chip_table(tmp_path, capsys):
    """ISSUE 18: the MESH block folds shard/chip and device/chip gauges
    into one per-chip table with skew, analytic collective traffic, and
    the largest collective plane by bytes."""
    _write_events(tmp_path / "telemetry-1.jsonl", [
        {"kind": "gauge", "name": "shard/mesh_devices", "value": 2},
        {"kind": "gauge", "name": "shard/chip/0/voxels", "value": 2048.0},
        {"kind": "gauge", "name": "shard/chip/1/voxels", "value": 1024.0},
        {"kind": "gauge", "name": "shard/chip/0/ready_s",
         "value": 0.000004},
        {"kind": "gauge", "name": "shard/chip/1/ready_s",
         "value": 0.000010},
        {"kind": "gauge", "name": "shard/chip_skew_s", "value": 0.000006},
        {"kind": "gauge", "name": "device/chip/0/bytes_in_use",
         "value": 2.0 * 2**20},
        {"kind": "gauge", "name": "device/chip/0/hbm_headroom",
         "value": 14.0 * 2**20},
        {"kind": "gauge", "name": "device/hbm_headroom",
         "value": 14.0 * 2**20},
        {"kind": "gauge", "name": "device/bytes_in_use",
         "value": 2.0 * 2**20},
        {"kind": "snapshot", "pid": 1,
         "counters": {"shard/chunks": 3, "shard/halo_bytes": 1048576.0,
                      "shard/gather_bytes": 2097152.0}},
    ])
    agg = log_summary.print_telemetry_summary(str(tmp_path))
    out = capsys.readouterr().out
    assert "mesh (docs/multichip.md):" in out
    assert "shape data=2 (2 chip(s)), 3 sharded dispatch(es)" in out
    # per-chip rows: chip 0 carries load, HBM and headroom; chip 1 has
    # no watermark samples and renders dashes instead of zeros
    assert "0     " in out and "2048" in out and "1024" in out
    assert "2.0" in out and "14.0" in out
    assert "chip skew (last ready − first ready)" in out
    assert "halo 1.00 MiB, gather 2.00 MiB" in out
    assert "largest collective plane by bytes: weighted-stack gather" in out
    assert "headroom 14.0 MiB (worst chip)" in out
    assert agg["counters"]["shard/gather_bytes"] == 2097152.0


def test_print_mesh_block_spatial_shape_and_quiet_default(capsys):
    from chunkflow_tpu.flow.log_summary import print_mesh_block

    # no sharded engine ever built: quiet
    assert print_mesh_block(
        {"gauges": {}, "counters": {}}) is False
    assert capsys.readouterr().out == ""
    # a 2D spatial mesh renders its y/x shape, not data=N
    agg = {"gauges": {
        "shard/mesh_devices": {"last": 4.0, "mean": 4.0},
        "shard/mesh_y": {"last": 2.0, "mean": 2.0},
        "shard/mesh_x": {"last": 2.0, "mean": 2.0},
    }, "counters": {"shard/chunks": 1}}
    assert print_mesh_block(agg) is True
    out = capsys.readouterr().out
    assert "shape y=2,x=2 (4 chip(s)), 1 sharded dispatch(es)" in out


def test_print_mesh_block_pipeline_shape_and_traffic_planes(capsys):
    """ISSUE 19: a pipeline mesh labels itself pipeline=N (not data=N),
    the traffic line carries the replay-strip and stage-handoff planes,
    and the largest plane is named with its remedy."""
    from chunkflow_tpu.flow.log_summary import print_mesh_block

    agg = {"gauges": {
        "shard/mesh_devices": {"last": 4.0, "mean": 4.0},
        "shard/mesh_y": {"last": 1.0, "mean": 1.0},
        "shard/mesh_x": {"last": 1.0, "mean": 1.0},
        "shard/mesh_pipeline": {"last": 4.0, "mean": 4.0},
    }, "counters": {"shard/chunks": 2,
                    "shard/halo_bytes": 1048576.0,
                    "shard/replay_strip_bytes": 524288.0,
                    "shard/handoff_bytes": 2097152.0}}
    assert print_mesh_block(agg) is True
    out = capsys.readouterr().out
    assert "shape pipeline=4 (4 chip(s)), 2 sharded dispatch(es)" in out
    assert "replay strips 0.50 MiB" in out
    assert "stage handoffs 2.00 MiB" in out
    # handoffs are the largest plane of this pipeline: the line names
    # them, with the remedy for the case that a trace shows them dominate
    assert "largest collective plane by bytes: stage handoffs" in out
    assert "fewer pipeline stages" in out


def test_print_mesh_block_hints_replicated_replay_and_tight_hbm(capsys):
    """The two other hint arms: a mesh whose gather plane has no replay
    strips points at CHUNKFLOW_SHARD_REPLAY; a mesh with a tight chip
    points at the shapes that shrink per-chip footprints."""
    from chunkflow_tpu.flow.log_summary import print_mesh_block

    agg = {"gauges": {
        "shard/mesh_devices": {"last": 2.0, "mean": 2.0},
    }, "counters": {"shard/chunks": 1,
                    "shard/gather_bytes": 2097152.0}}
    assert print_mesh_block(agg) is True
    out = capsys.readouterr().out
    assert ("if it dominates there: flip "
            "CHUNKFLOW_SHARD_REPLAY=sharded") in out

    agg = {"gauges": {
        "shard/mesh_devices": {"last": 2.0, "mean": 2.0},
        "device/chip/1/hbm_headroom": {"last": 2.0 * 2**20,
                                       "mean": 2.0 * 2**20},
    }, "counters": {"shard/chunks": 1}}
    assert print_mesh_block(agg) is True
    out = capsys.readouterr().out
    assert "shape hint: chip(s) [1] have <1 GiB HBM headroom" in out
    assert "sharded replay" in out


def test_log_summary_sweeps_profile_captures(tmp_path, capsys):
    """ISSUE 8: log-summary summarizes every profile-* capture dir under
    the metrics dir through tools/analyze_trace.py."""
    import gzip

    from chunkflow_tpu.flow.log_summary import print_profile_summaries

    capture = tmp_path / "profile-retrace-x-1" / "plugins" / "run"
    capture.mkdir(parents=True)
    with gzip.open(capture / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/device:TPU:0"}},
            {"ph": "X", "pid": 7, "name": "fusion.1", "dur": 800},
            {"ph": "X", "pid": 7, "name": "convolution.2", "dur": 200},
        ]}, f)
    (tmp_path / "profile-empty-2").mkdir()
    print_profile_summaries(str(tmp_path))
    out = capsys.readouterr().out
    assert "profile-retrace-x-1" in out
    assert "fusion 80%" in out
    assert "profile-empty-2: no trace files" in out


# ---------------------------------------------------------------------------
# SLO view: sparklines + fleet-merged timeseries + the SLO block (ISSUE 12)
# ---------------------------------------------------------------------------
def test_sparkline_shapes():
    assert log_summary.sparkline([]) == ""
    flat = log_summary.sparkline([(0, 5.0), (1, 5.0), (2, 5.0)])
    assert len(flat) == 3 and len(set(flat)) == 1  # constant: one glyph
    ramp = log_summary.sparkline([(i, float(i)) for i in range(8)])
    assert len(ramp) == 8
    assert ramp[0] == log_summary._SPARK_BLOCKS[0]
    assert ramp[-1] == log_summary._SPARK_BLOCKS[-1]
    wide = log_summary.sparkline([(i, float(i)) for i in range(500)],
                                 width=40)
    assert len(wide) == 40  # resampled, not truncated


def _ts_event(worker, t, values=None, qhists=None, interval=1.0):
    return {"kind": "timeseries", "worker": worker, "t": t,
            "interval_s": interval, "values": values or {},
            "qhists": qhists or {}}


def test_summarize_timeseries_sums_rates_across_workers():
    events = [
        _ts_event("w1", 10.2, {"rate:serving/requests": 5.0}),
        _ts_event("w2", 10.4, {"rate:serving/requests": 7.0}),
        _ts_event("w1", 11.2, {"rate:serving/requests": 6.0}),
        _ts_event("w2", 11.4, {"rate:serving/requests": 8.0}),
    ]
    merged = log_summary.summarize_timeseries(events)
    series = merged["series"]["rate:serving/requests"]
    # fleet rate = sum across workers, per time bin
    assert [v for _, v in series] == [12.0, 14.0]


def test_summarize_timeseries_fleet_p99_from_bucket_deltas():
    from chunkflow_tpu.core import telemetry

    n = len(telemetry.QUANTILE_BOUNDS) + 1

    def buckets(**at):
        b = [0] * n
        for idx, count in at.items():
            b[int(idx[1:])] = count
        return b

    # worker 1 serves fast (bucket 3 ~ 10 ms), worker 2 slow (bucket 9
    # ~ 1 s); cumulative counts grow between ticks
    events = [
        _ts_event("w1", 10.0, qhists={"serving/latency": {
            "count": 10, "buckets": buckets(i3=10)}}),
        _ts_event("w2", 10.1, qhists={"serving/latency": {
            "count": 10, "buckets": buckets(i9=10)}}),
        _ts_event("w1", 11.0, qhists={"serving/latency": {
            "count": 30, "buckets": buckets(i3=30)}}),
        _ts_event("w2", 11.1, qhists={"serving/latency": {
            "count": 30, "buckets": buckets(i9=30)}}),
    ]
    merged = log_summary.summarize_timeseries(events)
    p99 = dict(merged["series"]["fleet_p99:serving/latency"])
    p50 = dict(merged["series"]["fleet_p50:serving/latency"])
    # second bin: 20 fast + 20 slow deltas -> p50 mid-range, p99 in the
    # slow worker's (0.5, 1.0] bucket — only bucket SUMS can say this
    (bin_t,) = p99.keys()
    assert 0.5 <= p99[bin_t] <= 1.0
    assert p50[bin_t] <= 0.5


def test_print_slo_block_renders_alerts_state_and_timelines(capsys):
    events = [
        {"kind": "alert", "state": "firing", "worker": "w1", "t": 5.0,
         "alert": "availability:fast", "objective": "availability",
         "rule": "fast", "severity": "page", "burn_short": 5.0,
         "burn_long": 3.0, "budget_remaining": 0.4},
        {"kind": "gauge", "worker": "w1", "t": 6.0,
         "name": "slo/availability/firing", "value": 1.0},
        {"kind": "gauge", "worker": "w1", "t": 6.0,
         "name": "slo/availability/budget_remaining", "value": 0.4},
        {"kind": "gauge", "worker": "w2", "t": 6.0,
         "name": "slo/availability/budget_remaining", "value": 0.9},
        _ts_event("w1", 5.5, {"rate:serving/requests": 5.0}),
        _ts_event("w1", 6.5, {"rate:serving/requests": 9.0}),
    ]
    assert log_summary.print_slo_block(events) is True
    out = capsys.readouterr().out
    assert "alerts fired: 1 (0 resolved)" in out
    assert "availability:fast page" in out
    assert "burn_short=5" in out and "budget_remaining=0.4" in out
    # worst (minimum) budget across workers + who is firing
    assert "objective availability:" in out
    assert "budget remaining 40.0%" in out
    assert "FIRING (w1)" in out
    assert "rate:serving/requests" in out  # a sparkline timeline


def test_print_slo_block_quiet_without_slo_plane(capsys):
    events = [{"kind": "span", "name": "op/x", "t": 1.0, "dur_s": 0.5,
               "worker": "w1"}]
    assert log_summary.print_slo_block(events) is False
    assert capsys.readouterr().out == ""


def test_cli_log_summary_slo(tmp_path, capsys):
    """`log-summary --slo` over a real recorded stream — and the stream
    survives the recording process: only JSONL is read."""
    from click.testing import CliRunner

    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.flow.cli import main

    d = tmp_path / "metrics"
    telemetry.reset()
    telemetry.configure(str(d))
    sampler = telemetry.start_timeseries(interval=3600.0)
    telemetry.inc("serving/requests", 10)
    sampler.sample(now=100.0)
    telemetry.inc("serving/requests", 30)
    sampler.sample(now=101.0)
    telemetry.event("alert", "slo/availability", state="firing",
                    alert="availability:fast", objective="availability",
                    rule="fast", severity="page", burn_short=9.0,
                    burn_long=4.0, budget_remaining=0.2)
    telemetry.flush()
    telemetry.reset()
    result = CliRunner().invoke(
        main, ["log-summary", "--metrics-dir", str(d), "--slo"])
    assert result.exit_code == 0, result.output
    assert "alerts fired: 1" in result.output
    assert "availability:fast page" in result.output
    assert "rate:serving/requests" in result.output
