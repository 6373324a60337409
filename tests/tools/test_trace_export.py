"""Perfetto/Chrome-trace export (ISSUE 18): clock-skew normalization,
flow-event pairing, schema validation, and the loader's tolerance of
rotated generations and torn tails — all over synthetic JSONL streams,
so every invariant the CI stage asserts on the real fleet smoke is
pinned in isolation here.
"""
import json

import pytest

from chunkflow_tpu.core import telemetry
from chunkflow_tpu.flow.log_summary import (
    load_telemetry_dir,
    trace_timeline,
    worker_clock_offsets,
)
from tools.trace_export import (
    export_chrome_trace,
    export_metrics_dir,
    validate_chrome_trace,
)


@pytest.fixture(autouse=True)
def clean_telemetry(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


def _write_events(path, events, torn_tail=None):
    with open(path, "w") as f:
        for event in events:
            f.write(json.dumps(event) + "\n")
        if torn_tail is not None:
            f.write(torn_tail)  # no newline: a mid-write crash


def _skewed_stream():
    """Submitter 'wa' runs on the reference clock; claimer 'wb' runs a
    clock 3 seconds BEHIND, so its raw claim stamp (t=97) lands before
    the submit it provably followed (t=100)."""
    return [
        {"kind": "task", "name": "queue/submit", "t": 100.0,
         "worker": "wa", "trace_id": "t1", "body": "bbox-1"},
        {"kind": "span", "name": "pipeline/compute", "t": 101.0,
         "dur_s": 0.5, "worker": "wa"},
        {"kind": "task", "name": "lifecycle/claimed", "t": 97.0,
         "worker": "wb", "trace_id": "t1", "body": "bbox-1"},
        {"kind": "task", "name": "lifecycle/committed", "t": 97.5,
         "worker": "wb", "trace_id": "t1", "body": "bbox-1"},
        {"kind": "gauge", "name": "device/bytes_in_use", "t": 97.2,
         "value": 2048.0, "worker": "wb"},
        {"kind": "snapshot", "t": 98.0, "worker": "wb",
         "counters": {"tasks/committed": 1.0}},
    ]


# ---------------------------------------------------------------------------
# clock-skew normalization (satellite: queue send/receive pairs)
# ---------------------------------------------------------------------------
def test_worker_clock_offsets_minimal_monotone_correction():
    offsets = worker_clock_offsets(_skewed_stream())
    # claim at 97 vs submit at 100: wb shifts forward by exactly the
    # gap (the minimal correction), wa (the reference) stays put
    assert offsets == {"wb": pytest.approx(3.0)}


def test_worker_clock_offsets_no_skew_no_offsets():
    events = _skewed_stream()
    for e in events:
        if e["worker"] == "wb":
            e["t"] += 10.0  # claim now AFTER submit: causality holds
    assert worker_clock_offsets(events) == {}


def test_trace_timeline_orders_across_skewed_clocks():
    timeline = trace_timeline(_skewed_stream(), "t1")
    assert [e["name"] for e in timeline] == [
        "queue/submit", "lifecycle/claimed", "lifecycle/committed",
    ]


# ---------------------------------------------------------------------------
# export: schema, flows, counters
# ---------------------------------------------------------------------------
def test_export_schema_valid_with_cross_worker_flow():
    trace = export_chrome_trace(_skewed_stream())
    assert validate_chrome_trace(trace) == []
    events = trace["traceEvents"]
    assert trace["otherData"]["workers"] == 2
    assert trace["otherData"]["flow_pairs"] == 1
    # two worker processes, named
    procs = {e["args"]["name"] for e in events
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert procs == {"worker wa", "worker wb"}
    # the span renders as a complete event with µs duration
    spans = [e for e in events if e.get("ph") == "X"]
    assert [s["name"] for s in spans] == ["pipeline/compute"]
    assert spans[0]["dur"] == pytest.approx(0.5e6)
    # the gauge and the snapshot counter render as counter tracks
    cats = {e["name"]: e["cat"] for e in events if e.get("ph") == "C"}
    assert cats == {"device/bytes_in_use": "gauge",
                    "tasks/committed": "cumulative"}
    # the hop renders as one paired flow: a start on wa's submit and a
    # finish on wb's claim, finish never before start
    starts = [e for e in events if e.get("ph") == "s"]
    finishes = [e for e in events if e.get("ph") == "f"]
    assert len(starts) == 1 and len(finishes) == 1
    assert starts[0]["id"] == finishes[0]["id"]
    assert starts[0]["pid"] != finishes[0]["pid"]
    assert finishes[0]["ts"] >= starts[0]["ts"]
    assert finishes[0]["bp"] == "e"
    # timestamps are relative to the earliest event: non-negative
    assert min(e["ts"] for e in events) >= 0


def test_export_keeps_every_flow_round_a_ring_of_skewed_workers():
    """Many tasks, each submitted on one worker and claimed and committed
    on the next, every worker's clock further behind than the last's:
    the skew clamp has work to do for each, and still the trace
    validates and every hop is one paired flow."""
    workers = ["w0", "w1", "w2"]
    skew = {w: 0.25 * i for i, w in enumerate(workers)}
    events = []
    for i in range(40):
        submitter, claimer = workers[i % 3], workers[(i + 1) % 3]
        t = 10.0 + i * 0.01
        events.append({"kind": "task", "name": "queue/submit", "t": t,
                       "worker": submitter, "trace_id": f"tr-{i}"})
        for name, dt in (("lifecycle/claimed", 0.002),
                         ("lifecycle/committed", 0.005)):
            events.append({"kind": "task", "name": name,
                           "t": t + dt - skew[claimer], "worker": claimer,
                           "trace_id": f"tr-{i}"})
        events.append({"kind": "span", "name": "op/inference",
                       "t": t - skew[claimer], "dur_s": 0.0005,
                       "worker": claimer})
        events.append({"kind": "gauge", "name": f"shard/chip/{i % 8}/ready_s",
                       "t": t - skew[submitter], "value": float(i),
                       "worker": submitter})
    events.sort(key=lambda e: e["t"])
    trace = export_chrome_trace(events)
    assert validate_chrome_trace(trace) == []
    assert trace["otherData"]["workers"] == 3
    assert trace["otherData"]["flow_pairs"] == 40
    assert len(trace["traceEvents"]) >= len(events)


def test_export_single_worker_task_needs_no_flow():
    events = [e for e in _skewed_stream() if e["worker"] == "wa"]
    events.append({"kind": "task", "name": "lifecycle/claimed",
                   "t": 100.5, "worker": "wa", "trace_id": "t1"})
    trace = export_chrome_trace(events)
    assert validate_chrome_trace(trace) == []
    assert trace["otherData"]["flow_pairs"] == 0
    assert not [e for e in trace["traceEvents"]
                if e.get("ph") in ("s", "t", "f")]


def test_validator_flags_broken_traces():
    problems = validate_chrome_trace({"traceEvents": [
        {"ph": "X", "name": "no-dur", "pid": 1, "tid": 1, "ts": 0.0},
        {"ph": "i", "name": "no-pid", "tid": 1, "ts": 0.0},
        {"ph": "s", "name": "orphan", "id": 9, "pid": 1, "tid": 1,
         "ts": 5.0},
        {"ph": "C", "name": "ctr", "cat": "cumulative", "pid": 1,
         "tid": 0, "ts": 0.0, "args": {"value": 5.0}},
        {"ph": "C", "name": "ctr", "cat": "cumulative", "pid": 1,
         "tid": 0, "ts": 1.0, "args": {"value": 3.0}},
    ]})
    assert any("non-negative dur" in p for p in problems)
    assert any("bad pid" in p for p in problems)
    assert any("flow 9" in p for p in problems)
    assert any("decreases" in p for p in problems)
    assert validate_chrome_trace({"traceEvents": None}) \
        == ["traceEvents is not a list"]


# ---------------------------------------------------------------------------
# per-chip counter tracks (ISSUE 19: shard/chip/<i>/* gauges)
# ---------------------------------------------------------------------------
def test_export_chip_gauges_render_as_per_chip_tracks():
    """``<plane>/chip/<i>/<metric>`` gauges get their own ``chip <i>``
    thread track per worker (so a mesh run shows replay-buffer bytes /
    HBM watermarks side by side per chip), while plain gauges stay on
    the global tid-0 track."""
    events = [
        {"kind": "gauge", "name": "shard/chip/0/replay_buffer_bytes",
         "t": 10.0, "value": 4096.0, "worker": "wa"},
        {"kind": "gauge", "name": "shard/chip/1/replay_buffer_bytes",
         "t": 10.0, "value": 4096.0, "worker": "wa"},
        {"kind": "gauge", "name": "device/chip/1/hbm_headroom",
         "t": 10.5, "value": 1e9, "worker": "wa"},
        {"kind": "gauge", "name": "shard/n_devices", "t": 10.0,
         "value": 2.0, "worker": "wa"},
    ]
    trace = export_chrome_trace(events)
    assert validate_chrome_trace(trace) == []
    counters = [e for e in trace["traceEvents"] if e.get("ph") == "C"]
    chip = [e for e in counters if e["cat"] == "chip_gauge"]
    plain = [e for e in counters if e["cat"] == "gauge"]
    # chip prefix stripped from the counter name, chip carried as an arg
    assert {e["name"] for e in chip} == {"shard/replay_buffer_bytes",
                                         "device/hbm_headroom"}
    assert {e["args"]["chip"] for e in chip} == {0, 1}
    # per-chip samples land on distinct non-global tracks...
    assert all(e["tid"] != 0 for e in chip)
    by_chip = {}
    for e in chip:
        by_chip.setdefault(e["args"]["chip"], set()).add(e["tid"])
    assert by_chip[0].isdisjoint(by_chip[1])
    # ...named "chip <i>" in the thread metadata
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert {"chip 0", "chip 1"} <= names
    # the plain gauge stays on the global track
    assert plain and all(e["tid"] == 0 for e in plain)


def test_validator_flags_broken_chip_tracks():
    problems = validate_chrome_trace({"traceEvents": [
        {"ph": "C", "name": "shard/replay_buffer_bytes",
         "cat": "chip_gauge", "pid": 1, "tid": 3, "ts": 0.0,
         "args": {"value": 1.0}},  # no chip arg
        {"ph": "C", "name": "shard/replay_buffer_bytes",
         "cat": "chip_gauge", "pid": 1, "tid": 4, "ts": 0.0,
         "args": {"value": 1.0, "chip": 0}},
        {"ph": "C", "name": "shard/replay_buffer_bytes",
         "cat": "chip_gauge", "pid": 1, "tid": 4, "ts": 1.0,
         "args": {"value": 1.0, "chip": 1}},  # same track, other chip
    ]})
    assert any("integer chip arg" in p for p in problems)
    assert any("mixes chips 0 and 1" in p for p in problems)


# ---------------------------------------------------------------------------
# loader round trip: rotated generations + torn tail (satellite)
# ---------------------------------------------------------------------------
def test_export_metrics_dir_rotations_and_torn_tail(tmp_path):
    events = _skewed_stream()
    wa = [e for e in events if e["worker"] == "wa"]
    wb = [e for e in events if e["worker"] == "wb"]
    # wa's stream spans three generations: .2 (oldest) -> .1 -> live,
    # and the live file ends in a torn line from a mid-write crash
    _write_events(tmp_path / "telemetry-wa.jsonl.2", wa[:1])
    _write_events(tmp_path / "telemetry-wa.jsonl.1", [])
    _write_events(tmp_path / "telemetry-wa.jsonl", wa[1:],
                  torn_tail='{"kind": "span", "name": "torn"')
    _write_events(tmp_path / "telemetry-wb.jsonl", wb)

    loaded = load_telemetry_dir(str(tmp_path))
    assert len(loaded) == len(events)  # torn tail skipped, not fatal
    assert not any(e.get("name") == "torn" for e in loaded)
    # generations load oldest-first so wa's stream stays in order
    wa_names = [e.get("name") for e in loaded
                if e.get("worker") == "wa"]
    assert wa_names == [e.get("name") for e in wa]
    # the skewed trace still reconstructs in causal order
    assert [e["name"] for e in trace_timeline(loaded, "t1")] == [
        "queue/submit", "lifecycle/claimed", "lifecycle/committed",
    ]

    out = tmp_path / "trace.json"
    stats = export_metrics_dir(str(tmp_path), str(out))
    assert stats["problems"] == []
    assert stats["events"] == len(events)
    assert stats["workers"] == 2
    assert stats["flow_pairs"] == 1
    on_disk = json.loads(out.read_text())
    assert len(on_disk["traceEvents"]) == stats["trace_events"]


def test_cli_export_trace_flag(tmp_path):
    from click.testing import CliRunner

    from chunkflow_tpu.flow.cli import main

    metrics = tmp_path / "metrics"
    metrics.mkdir()
    events = _skewed_stream()
    _write_events(metrics / "telemetry-wa.jsonl",
                  [e for e in events if e["worker"] == "wa"])
    _write_events(metrics / "telemetry-wb.jsonl",
                  [e for e in events if e["worker"] == "wb"])
    out = tmp_path / "trace.json"
    result = CliRunner().invoke(
        main,
        ["log-summary", "--metrics-dir", str(metrics),
         "--export-trace", str(out)],
        catch_exceptions=False,
    )
    assert result.exit_code == 0, result.output
    assert "2 worker process(es)" in result.output
    assert "1 cross-worker flow(s)" in result.output
    assert "trace validation:" not in result.output
    trace = json.loads(out.read_text())
    assert validate_chrome_trace(trace) == []


def test_cli_export_trace_requires_metrics_dir():
    from click.testing import CliRunner

    from chunkflow_tpu.flow.cli import main

    result = CliRunner().invoke(
        main, ["log-summary", "--export-trace", "out.json"])
    assert result.exit_code != 0
