"""core/telemetry.py: registry, spans, JSONL sink, kill switch, summary."""
import json
import os
import threading

import pytest

from chunkflow_tpu.core import telemetry


@pytest.fixture(autouse=True)
def clean_registry(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


def test_counters_gauges_histograms():
    telemetry.inc("a/count")
    telemetry.inc("a/count", 2)
    telemetry.gauge("a/level", 3)
    telemetry.gauge("a/level", 1)
    telemetry.observe("a/dur", 0.5)
    telemetry.observe("a/dur", 1.5)
    snap = telemetry.snapshot()
    assert snap["counters"]["a/count"] == 3
    assert snap["gauges"]["a/level"] == 1  # last value
    h = snap["hists"]["a/dur"]
    assert h["count"] == 2
    assert h["total"] == pytest.approx(2.0)
    assert h["mean"] == pytest.approx(1.0)
    assert h["min"] == 0.5 and h["max"] == 1.5
    # gauges also fold into a histogram so mean occupancy is queryable
    assert snap["hists"]["a/level"]["mean"] == pytest.approx(2.0)


def test_span_records_duration_and_exposes_it():
    with telemetry.span("phase/x") as sp:
        pass
    assert sp.duration >= 0
    snap = telemetry.snapshot()
    assert snap["hists"]["phase/x"]["count"] == 1


def test_span_survives_exceptions():
    with pytest.raises(ValueError):
        with telemetry.span("phase/err"):
            raise ValueError("boom")
    assert telemetry.snapshot()["hists"]["phase/err"]["count"] == 1


def test_jsonl_emission_and_snapshot_event(tmp_path):
    path = telemetry.configure(str(tmp_path))
    assert path is not None and str(tmp_path) in path
    with telemetry.span("pipeline/stage", chunk=3):
        pass
    telemetry.gauge("pipeline/ring_occupancy", 2)
    telemetry.inc("compile_cache/builds")
    telemetry.flush()
    events = [
        json.loads(line)
        for line in open(path).read().splitlines() if line
    ]
    kinds = [e["kind"] for e in events]
    assert kinds == ["span", "gauge", "snapshot"]
    span_event = events[0]
    assert span_event["name"] == "pipeline/stage"
    assert span_event["chunk"] == 3  # attrs ride the event
    assert span_event["dur_s"] >= 0
    assert events[2]["counters"]["compile_cache/builds"] == 1


def test_kill_switch_emits_nothing_and_creates_nothing(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    target = tmp_path / "metrics"
    assert telemetry.configure(str(target)) is None
    assert not target.exists()  # an off run leaves no trace on disk
    telemetry.inc("x")
    telemetry.gauge("g", 1)
    telemetry.observe("h", 1)
    with telemetry.span("s"):
        pass
    telemetry.event("custom", "e")
    telemetry.flush()
    snap = telemetry.snapshot()
    assert snap == {"counters": {}, "gauges": {}, "hists": {}}
    assert telemetry.summary_table() == ""


def test_kill_switch_mid_run(tmp_path, monkeypatch):
    path = telemetry.configure(str(tmp_path))
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    with telemetry.span("late"):
        pass
    telemetry.flush()
    # sink was open, but disabled spans never reach it
    assert open(path).read() == ""


def test_disabled_span_is_cheap():
    # the whole point of the kill switch: ~free when off. 100k no-op
    # spans in well under a second leaves 10x margin on a loaded CI box.
    import time as _time

    os.environ["CHUNKFLOW_TELEMETRY"] = "0"
    try:
        t0 = _time.perf_counter()
        for _ in range(100_000):
            with telemetry.span("x"):
                pass
        assert _time.perf_counter() - t0 < 1.0
    finally:
        del os.environ["CHUNKFLOW_TELEMETRY"]


def test_thread_safety_smoke(tmp_path):
    telemetry.configure(str(tmp_path))

    def work():
        for _ in range(500):
            telemetry.inc("t/count")
            with telemetry.span("t/span"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = telemetry.snapshot()
    assert snap["counters"]["t/count"] == 2000
    assert snap["hists"]["t/span"]["count"] == 2000


def test_summary_table_lists_everything():
    with telemetry.span("op/inference"):
        pass
    telemetry.inc("pipeline/tasks", 4)
    telemetry.gauge("pipeline/ring_occupancy", 2)
    table = telemetry.summary_table()
    assert "op/inference" in table
    assert "pipeline/tasks" in table
    assert "pipeline/ring_occupancy" in table


def test_configure_reconfigure_closes_previous(tmp_path):
    first = telemetry.configure(str(tmp_path / "a"))
    second = telemetry.configure(str(tmp_path / "b"))
    assert first != second
    assert telemetry.configured_path() == second
    with telemetry.span("x"):
        pass
    telemetry.flush()
    assert open(first).read() == ""
    assert "span" in open(second).read()


# ---------------------------------------------------------------------------
# fleet identity + task trace context (ISSUE 6)
# ---------------------------------------------------------------------------
def test_worker_id_stable_and_overridable(monkeypatch):
    first = telemetry.worker_id()
    assert str(os.getpid()) in first
    assert telemetry.worker_id() == first  # cached, stable within a run
    monkeypatch.setenv("CHUNKFLOW_WORKER_ID", "fleet-worker-7")
    assert telemetry.worker_id() == first  # env read only at first use...
    telemetry.reset()
    assert telemetry.worker_id() == "fleet-worker-7"  # ...or after reset


def test_sink_file_named_by_worker_id(tmp_path, monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_WORKER_ID", "worker a/b")
    telemetry.reset()
    path = telemetry.configure(str(tmp_path))
    # unsafe characters sanitized, telemetry-*.jsonl contract preserved
    assert os.path.basename(path) == "telemetry-worker_a_b.jsonl"


def test_events_stamped_with_worker_and_trace(tmp_path):
    path = telemetry.configure(str(tmp_path))
    with telemetry.task_context("trace-123"):
        with telemetry.span("op/x"):
            pass
        telemetry.gauge("g", 1)
        telemetry.event("task", "lifecycle/claimed", body="b")
    with telemetry.span("op/outside"):
        pass
    telemetry.flush()
    events = [json.loads(line) for line in open(path) if line.strip()]
    inside = [e for e in events if e.get("trace_id") == "trace-123"]
    assert {e["kind"] for e in inside} == {"span", "gauge", "task"}
    for e in events:
        assert e["worker"] == telemetry.worker_id()
    outside = next(e for e in events if e.get("name") == "op/outside")
    assert "trace_id" not in outside  # context did not leak past exit
    snap_event = next(e for e in events if e["kind"] == "snapshot")
    assert snap_event["worker"] == telemetry.worker_id()


def test_task_context_nesting_and_none(tmp_path):
    assert telemetry.current_trace_id() is None
    with telemetry.task_context("outer"):
        assert telemetry.current_trace_id() == "outer"
        with telemetry.task_context(None):  # no-op: keeps the outer id
            assert telemetry.current_trace_id() == "outer"
        with telemetry.task_context("inner"):
            assert telemetry.current_trace_id() == "inner"
        assert telemetry.current_trace_id() == "outer"
    assert telemetry.current_trace_id() is None


def test_task_context_is_thread_local(tmp_path):
    seen = {}

    def work(tid):
        with telemetry.task_context(tid):
            import time as _time

            _time.sleep(0.01)
            seen[tid] = telemetry.current_trace_id()

    threads = [
        threading.Thread(target=work, args=(f"t{i}",)) for i in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert seen == {f"t{i}": f"t{i}" for i in range(4)}


# ---------------------------------------------------------------------------
# JSONL rotation (ISSUE 6: long-lived workers must not grow unbounded)
# ---------------------------------------------------------------------------
def test_jsonl_rotation_caps_size(tmp_path, monkeypatch):
    # ~1 KB cap: a few hundred spans must rotate at least once
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY_MAX_MB", "0.001")
    path = telemetry.configure(str(tmp_path))
    for _ in range(200):
        with telemetry.span("op/rotate"):
            pass
    telemetry.flush()
    rotated = path + ".1"
    assert os.path.exists(rotated)
    assert os.path.getsize(path) <= 4096  # live file stays near the cap
    # at most two generations on disk, both valid JSONL
    files = sorted(os.listdir(tmp_path))
    assert files == [os.path.basename(path), os.path.basename(rotated)]
    for name in files:
        for line in open(tmp_path / name):
            json.loads(line)


def test_rotation_off_without_sink_and_when_disabled(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY_MAX_MB", "0.001")
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    assert telemetry.configure(str(tmp_path / "off")) is None
    for _ in range(200):
        with telemetry.span("op/none"):
            pass
    telemetry.flush()
    # kill switch: no files at all, rotated or otherwise
    assert not (tmp_path / "off").exists()


# ---------------------------------------------------------------------------
# quantile histograms (the serving p50/p99 substrate, ISSUE 9)
# ---------------------------------------------------------------------------
def test_quantile_histogram_estimates_and_snapshot_schema():
    telemetry.reset()
    try:
        for v in [0.004] * 50 + [0.02] * 40 + [0.8] * 10:
            telemetry.observe_quantile("serving/latency", v)
        p50 = telemetry.quantile("serving/latency", 0.5)
        p99 = telemetry.quantile("serving/latency", 0.99)
        # 50th sample sits in the (0.0025, 0.005] bucket, 99th in
        # (0.5, 1.0] — the log-bucket estimate must land inside them
        assert 0.0025 <= p50 <= 0.005, p50
        assert 0.5 <= p99 <= 1.0, p99
        snap = telemetry.snapshot()
        h = snap["qhists"]["serving/latency"]
        assert h["count"] == 100
        assert len(h["buckets"]) == len(telemetry.QUANTILE_BOUNDS) + 1
        assert sum(h["buckets"]) == 100
        # fixed bounds mean per-worker buckets sum exactly: merging two
        # copies doubles every estimate's weight but moves no quantile
        merged = {"count": 2 * h["count"],
                  "buckets": [2 * n for n in h["buckets"]]}
        assert telemetry.quantile_from_buckets(merged, 0.5) == \
            pytest.approx(p50)
    finally:
        telemetry.reset()


def test_quantile_histogram_edge_cases():
    telemetry.reset()
    try:
        assert telemetry.quantile("missing", 0.5) is None
        assert telemetry.quantile_from_buckets(
            {"count": 0, "buckets": []}, 0.5) is None
        # an overflow-only histogram saturates at the top bound
        telemetry.observe_quantile("serving/huge", 9999.0)
        assert telemetry.quantile("serving/huge", 0.5) == \
            telemetry.QUANTILE_BOUNDS[-1]
    finally:
        telemetry.reset()


def test_quantile_histogram_respects_kill_switch(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    telemetry.observe_quantile("serving/latency", 0.1)
    assert telemetry.quantile("serving/latency", 0.5) is None
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY")
    telemetry.reset()
    assert "qhists" not in telemetry.snapshot()


# ---------------------------------------------------------------------------
# quantile_from_buckets edge cases (ISSUE 12: it feeds alerting now)
# ---------------------------------------------------------------------------
def test_quantile_from_buckets_empty_and_none_shapes():
    qfb = telemetry.quantile_from_buckets
    assert qfb({}, 0.5) is None                      # empty dict
    assert qfb({"count": 0, "buckets": []}, 0.5) is None
    assert qfb({"count": 5, "buckets": None}, 0.5) is None  # None buckets
    # count claims samples but the bucket list is empty: no estimate,
    # not an IndexError — a torn snapshot must not crash alerting
    assert qfb({"count": 5, "buckets": []}, 0.99) is None


def test_quantile_from_buckets_q0_and_q1():
    buckets = [0] * (len(telemetry.QUANTILE_BOUNDS) + 1)
    buckets[3] = 10  # all samples in (0.005, 0.01]
    h = {"count": 10, "buckets": buckets}
    q0 = telemetry.quantile_from_buckets(h, 0.0)
    q1 = telemetry.quantile_from_buckets(h, 1.0)
    # both land inside the one occupied bucket, ordered
    assert 0.005 <= q0 <= 0.01
    assert 0.005 <= q1 <= 0.01
    assert q0 <= q1
    assert q1 == pytest.approx(0.01)  # q=1 is the bucket's upper bound


def test_quantile_from_buckets_single_bucket_and_overflow_only():
    bounds = telemetry.QUANTILE_BOUNDS
    single = [0] * (len(bounds) + 1)
    single[0] = 7  # everything under the first bound
    h = {"count": 7, "buckets": single}
    for q in (0.01, 0.5, 0.99):
        est = telemetry.quantile_from_buckets(h, q)
        assert 0.0 <= est <= bounds[0]
    overflow = [0] * (len(bounds) + 1)
    overflow[-1] = 3  # only samples past the largest tracked bound
    h = {"count": 3, "buckets": overflow}
    # the estimate saturates at the largest bound instead of inventing
    # a number past the tracked range
    assert telemetry.quantile_from_buckets(h, 0.5) == bounds[-1]


def test_quantile_from_buckets_short_bucket_list():
    # a stream from an older schema may carry fewer buckets than
    # bounds: the reader pads conceptually, never IndexErrors
    h = {"count": 4, "buckets": [4]}
    est = telemetry.quantile_from_buckets(h, 0.5)
    assert 0.0 <= est <= telemetry.QUANTILE_BOUNDS[0]


# ---------------------------------------------------------------------------
# rotation generations (ISSUE 12: CHUNKFLOW_TELEMETRY_KEEP)
# ---------------------------------------------------------------------------
def _spam_spans(n):
    for _ in range(n):
        with telemetry.span("op/rotate"):
            pass


def test_rotation_keeps_configured_generations(tmp_path, monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY_MAX_MB", "0.001")
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY_KEEP", "3")
    path = telemetry.configure(str(tmp_path))
    _spam_spans(800)
    telemetry.flush()
    base = os.path.basename(path)
    files = sorted(os.listdir(tmp_path))
    assert files == [base, f"{base}.1", f"{base}.2"]
    for name in files:  # every generation is valid JSONL
        for line in open(tmp_path / name):
            json.loads(line)


def test_rotation_sweeps_stale_generations_when_keep_drops(
    tmp_path, monkeypatch
):
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY_MAX_MB", "0.001")
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY_KEEP", "4")
    path = telemetry.configure(str(tmp_path))
    _spam_spans(1200)
    base = os.path.basename(path)
    assert f"{base}.3" in os.listdir(tmp_path)
    # KEEP lowered on a live worker: the next rotation sweeps the tail
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY_KEEP", "2")
    _spam_spans(400)
    files = sorted(os.listdir(tmp_path))
    assert files == [base, f"{base}.1"]


def test_load_telemetry_dir_reads_all_generations_in_order(
    tmp_path, monkeypatch
):
    from chunkflow_tpu.flow.log_summary import load_telemetry_dir

    monkeypatch.setenv("CHUNKFLOW_WORKER_ID", "w-rot")
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY_MAX_MB", "0.001")
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY_KEEP", "3")
    telemetry.configure(str(tmp_path))
    for i in range(900):
        telemetry.event("probe", "order/check", seq=i)
    telemetry.flush()
    assert len([n for n in os.listdir(tmp_path)
                if ".jsonl" in n]) == 3  # live + .1 + .2
    events = load_telemetry_dir(str(tmp_path))
    seqs = [e["seq"] for e in events if e.get("name") == "order/check"]
    # every surviving generation was read, oldest first: the tail of
    # the sequence is contiguous and spans more than the live file
    assert seqs == list(range(seqs[0], 900))
    assert len(seqs) > 12  # more events than one capped file holds


# ---------------------------------------------------------------------------
# time-series ring sampler (ISSUE 12)
# ---------------------------------------------------------------------------
def test_timeseries_sampler_rates_gauges_quantiles(tmp_path):
    path = telemetry.configure(str(tmp_path))
    sampler = telemetry.start_timeseries(interval=60.0)  # manual ticks
    assert telemetry.start_timeseries() is sampler  # idempotent
    telemetry.inc("serving/requests", 10)
    telemetry.gauge("serving/inflight", 3)
    telemetry.observe_quantile("serving/latency", 0.01)
    sampler.sample(now=1000.0)
    telemetry.inc("serving/requests", 20)
    sampler.sample(now=1002.0)
    series = telemetry.timeseries()
    # counter rate against the previous tick: 20 events / 2 s
    assert series["rate:serving/requests"][-1] == (1002.0, 10.0)
    assert series["gauge:serving/inflight"][-1][1] == 3.0
    assert 0.005 <= series["p50:serving/latency"][-1][1] <= 0.01
    telemetry.flush()
    events = [json.loads(line) for line in open(path)]
    ts = [e for e in events if e["kind"] == "timeseries"]
    assert len(ts) >= 2
    # the event carries raw cumulative buckets (fleet-summable)
    assert ts[-1]["qhists"]["serving/latency"]["count"] == 1
    assert ts[-1]["values"]["gauge:serving/inflight"] == 3.0


def test_timeseries_ring_is_bounded():
    sampler = telemetry.start_timeseries(interval=60.0, points=5)
    telemetry.inc("x/count")
    for i in range(20):
        sampler.sample(now=1000.0 + i)
    series = telemetry.timeseries()
    assert len(series["rate:x/count"]) == 5  # ring, not a log
    assert series["rate:x/count"][-1][0] == 1019.0


def test_timeseries_knobs_and_kill_switch(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_TS_INTERVAL", "0")
    assert telemetry.start_timeseries() is None  # interval 0: disabled
    monkeypatch.setenv("CHUNKFLOW_TS_INTERVAL", "2.5")
    monkeypatch.setenv("CHUNKFLOW_TS_POINTS", "77")
    assert telemetry.ts_interval() == 2.5
    assert telemetry.ts_points() == 77
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    assert telemetry.start_timeseries() is None
    assert not any(t.name == "chunkflow-timeseries"
                   for t in threading.enumerate())


def test_timeseries_tick_hooks_run_and_clear_on_reset():
    ticks = []
    sampler = telemetry.start_timeseries(interval=60.0)
    telemetry.add_tick_hook(ticks.append)
    telemetry.add_tick_hook(ticks.append)  # idempotent by identity
    telemetry.inc("x/count")
    sampler.sample(now=1000.0)
    assert ticks == [1000.0]

    def explode(now):
        raise RuntimeError("hook down")

    telemetry.add_tick_hook(explode)  # a raising hook never kills a tick
    sampler.sample(now=1001.0)
    assert ticks == [1000.0, 1001.0]
    telemetry.reset()
    assert not telemetry.timeseries_running()
    sampler2 = telemetry.start_timeseries(interval=60.0)
    telemetry.inc("x/count")
    sampler2.sample(now=2000.0)
    assert ticks == [1000.0, 1001.0]  # reset cleared the hooks


def test_flush_takes_a_final_sample(tmp_path):
    path = telemetry.configure(str(tmp_path))
    telemetry.start_timeseries(interval=3600.0)  # would never self-tick
    telemetry.inc("serving/requests", 4)
    telemetry.flush()
    events = [json.loads(line) for line in open(path)]
    assert any(e["kind"] == "timeseries" for e in events)


# ---------------------------------------------------------------------------
# the span record: start, ids, parent, thread (ISSUE 23)
# ---------------------------------------------------------------------------
def _span_events(path):
    with open(path) as f:
        events = [json.loads(line) for line in f if line.strip()]
    return [e for e in events if e["kind"] == "span"]


def test_nested_spans_carry_parent_id_and_the_task(tmp_path):
    path = telemetry.configure(str(tmp_path))
    with telemetry.task_context("task-1"):
        with telemetry.span("op/save") as outer:
            assert telemetry._SPAN_CTX.get() == outer.span_id
            with telemetry.span("storage/write") as inner:
                with telemetry.span("storage/encode"):
                    pass
            assert telemetry._SPAN_CTX.get() == outer.span_id
    assert telemetry._SPAN_CTX.get() is None
    by_name = {e["name"]: e for e in _span_events(path)}
    assert by_name["op/save"]["parent_id"] is None
    assert by_name["storage/write"]["parent_id"] == outer.span_id
    assert by_name["storage/encode"]["parent_id"] == inner.span_id
    assert {e["trace_id"] for e in by_name.values()} == {"task-1"}
    assert len({e["span_id"] for e in by_name.values()}) == 3


def test_span_in_a_pool_thread_keeps_the_submitting_span_as_parent(tmp_path):
    import contextvars
    from concurrent.futures import ThreadPoolExecutor

    path = telemetry.configure(str(tmp_path))

    def work(name):
        with telemetry.span(name):
            return threading.current_thread().name

    with ThreadPoolExecutor(max_workers=1,
                            thread_name_prefix="pooled") as pool:
        with telemetry.task_context("task-2"), \
                telemetry.span("storage/write") as parent:
            # rebound: the pool thread runs under a copy of this context
            thread = pool.submit(contextvars.copy_context().run, work,
                                 "storage/encode").result(timeout=10)
            # not rebound: contextvars do not follow work into a pool
            pool.submit(work, "storage/orphan").result(timeout=10)
    by_name = {e["name"]: e for e in _span_events(path)}
    assert thread.startswith("pooled")
    assert by_name["storage/encode"]["thread"] == thread
    assert by_name["storage/encode"]["parent_id"] == parent.span_id
    assert by_name["storage/encode"]["trace_id"] == "task-2"
    assert by_name["storage/orphan"]["parent_id"] is None
    assert "trace_id" not in by_name["storage/orphan"]
    assert by_name["storage/write"]["thread"] == \
        threading.current_thread().name


def test_span_start_plus_duration_is_its_end_and_old_fields_stay(tmp_path):
    import time

    path = telemetry.configure(str(tmp_path))
    with telemetry.task_context("task-3"), \
            telemetry.span("pipeline/drain", chunk=7):
        time.sleep(0.02)
    (event,) = _span_events(path)
    assert event["t0"] + event["dur_s"] == pytest.approx(event["t"],
                                                         abs=0.005)
    assert event["dur_s"] >= 0.02
    # what benchmarks/cfbench/run_record.py and flow/log_summary.py read
    assert {"kind", "name", "t", "dur_s", "pid", "worker", "trace_id",
            "chunk"} <= set(event)
    assert event["name"] == "pipeline/drain" and event["chunk"] == 7
    assert event["worker"] == telemetry.worker_id()


def test_a_span_takes_the_task_it_waited_for(tmp_path):
    path = telemetry.configure(str(tmp_path))
    with telemetry.task_context("enclosing"):
        with telemetry.span("scheduler/load") as load:
            load.bind("late-task")
        with telemetry.span("scheduler/load") as empty:
            empty.bind(None)        # nothing in hand: the context stands
    with telemetry.span("queue/fetch") as poll:
        poll.cancel()               # an empty poll records nothing
    events = _span_events(path)
    assert [e["trace_id"] for e in events] == ["late-task", "enclosing"]
    assert telemetry.snapshot()["hists"]["scheduler/load"]["count"] == 2
    assert "queue/fetch" not in telemetry.snapshot()["hists"]


def test_record_span_covers_an_interval_across_threads(tmp_path):
    import time

    path = telemetry.configure(str(tmp_path))
    t0 = time.time() - 0.25
    done = threading.Thread(
        target=telemetry.record_span,
        args=("serving/queue", t0), kwargs={"trace_id": "req-1"})
    done.start()
    done.join(timeout=10)
    assert not done.is_alive()
    (event,) = _span_events(path)
    assert event["name"] == "serving/queue" and event["trace_id"] == "req-1"
    assert event["t0"] == t0 and event["dur_s"] >= 0.25
    assert event["parent_id"] is None and event["span_id"] > 0
    assert telemetry.snapshot()["hists"]["serving/queue"]["count"] == 1


def test_kill_switch_span_is_the_shared_null_span(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    sp = telemetry.span("pipeline/stage")
    assert sp is telemetry._NULL_SPAN
    with sp as entered:
        entered.bind("x")
        entered.cancel()
    telemetry.record_span("serving/queue", 0.0)
    assert telemetry.snapshot()["hists"] == {}


def test_span_without_sink_or_profiler_makes_no_id_and_imports_nothing():
    """Run in a fresh interpreter: the module must not pull jax in, and
    with no sink and no profiler session a span makes no id."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from chunkflow_tpu.core import telemetry\n"
        "with telemetry.span('pipeline/stage') as sp:\n"
        "    assert telemetry._SPAN_CTX.get() is None\n"
        "assert sp.span_id is None and sp.duration >= 0\n"
        "assert telemetry.snapshot()['hists']['pipeline/stage']['count'] == 1\n"
        "assert 'jax' not in sys.modules\n"
        "print('ok')\n"
    )
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.dirname(os.path.abspath(__file__)))))
    assert done.returncode == 0, done.stderr
    assert done.stdout.strip() == "ok"


def test_spans_are_profiler_annotations_of_the_same_name(tmp_path):
    """In a running jax.profiler session every span lies on the host
    plane under its JSONL name, with the task's id among its stats; and
    it makes ids although no sink is configured."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.task_context("task-9"), \
                telemetry.span("pipeline/drain") as sp:
            assert sp.span_id is not None
            with telemetry.span("scheduler/load") as load:
                load.bind("late-task")
    finally:
        jax.profiler.stop_trace()
    with telemetry.span("pipeline/drain") as after:
        pass
    assert after.span_id is None        # the session is over
    (xplane,) = list(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    seen = {}
    for plane in ProfileData.from_file(str(xplane)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    if event.name in ("pipeline/drain", "scheduler/load"):
                        seen[event.name] = dict(event.stats)
    assert seen["pipeline/drain"]["trace_id"] == "task-9"
    assert seen["pipeline/drain"]["span_id"] == sp.span_id
    assert seen["scheduler/load"]["trace_id"] == "late-task"
