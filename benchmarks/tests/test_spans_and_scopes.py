"""The reducers that read the program's span record and named scopes
(PR 23), on hand-made tables; the scope reducer is pinned on a recorded
v5e trace in ``test_recorded_scopes.py``."""
import pytest

from cfbench import catalog
from cfbench.run_record import RunRecord


def record(**kw):
    return RunRecord(cell={}, config={}, traffic={}, device={}, **kw)


def tables(ops, host=(), t0=0, t1=1000):
    return {"window_s": (t1 - t0) / 1e9, "t0_ns": t0, "t1_ns": t1,
            "devices": [{"name": "/device:TPU:0", "ops": ops}],
            "host": [list(h) for h in host]}


def reducer(name):
    return catalog.load_module("reducers", name)


# ---------------------------------------------------------------------------
# scope shares
# ---------------------------------------------------------------------------
PROGRAMS = [
    {"family": "scatter", "op_scopes": {
        "forward": ["fusion.1", "copy.1"], "accumulate": ["while.2"],
        "gather": ["fusion.7"], "": ["copy.9", "while.1"]}},
    # a second program that puts fusion.7 elsewhere: ambiguous
    {"family": "serve_forward", "op_scopes": {"forward": ["fusion.7"]}},
    {"family": "from before the scopes", "op_scopes": None},
]
# a scan [0, 1000) holding: a conv and a copy of the model, the gather
# fusion two programs disagree on, a scatter loop with a body op that
# no program lists, and a copy under no scope
OPS = [["while.1 s32[]", "while", 0, 1000],
       ["fusion.1 bf16[4,8]", "convolution", 0, 400],
       ["copy.1 bf16[4,8]", "copy", 400, 100],
       ["fusion.7 f32[4]", "loop fusion", 500, 100],
       ["while.2 s32[]", "while", 600, 200],
       ["dynamic-update-slice.3 f32[8]", "dynamic-update-slice", 650, 100],
       ["copy.9 f32[8]", "copy", 800, 100]]


@pytest.mark.parametrize("args, want", [
    ({"scopes": ["forward"]}, 50.0),
    ({"scopes": ["forward"], "not_category": "^convolution$"}, 10.0),
    # while.2's own 100 ns; its body op is listed by no program
    ({"scopes": ["gather", "accumulate", "normalize"]}, 10.0),
    # copy.9 100 + while.1's own 100 (under "") + the ambiguous fusion.7
    # 100 + the unlisted dynamic-update-slice.3 100
    ({"unscoped": True}, 40.0),
])
def test_scope_shares(args, want):
    r = record(trace=tables(OPS), programs=PROGRAMS)
    assert reducer("trace_scope_share").reduce(r, **args) == \
        pytest.approx(want)


def test_scope_shares_add_up_and_need_the_programs_map():
    scope = reducer("trace_scope_share")
    r = record(trace=tables(OPS), programs=PROGRAMS)
    total = (scope.reduce(r, scopes=["forward"])
             + scope.reduce(r, scopes=["gather", "accumulate", "normalize"])
             + scope.reduce(r, unscoped=True))
    assert total == pytest.approx(100.0)
    # a program from before the scopes (the parent commit): nothing to
    # read, so the line leaves the metric out
    old = record(trace=tables(OPS), programs=[{"family": "scatter"}])
    assert scope.reduce(old, scopes=["forward"]) is None
    assert scope.reduce(old, unscoped=True) is None
    assert scope.reduce(record(programs=PROGRAMS), unscoped=True) is None


# ---------------------------------------------------------------------------
# task extents
# ---------------------------------------------------------------------------
def span(name, t0, dur, trace_id=None, **kw):
    event = {"kind": "span", "name": name, "t": t0 + dur, "dur_s": dur,
             "t0": t0, **kw}
    if trace_id:
        event["trace_id"] = trace_id
    return event


def test_task_extent_and_wait_of_two_overlapping_tasks():
    spans = [
        # task a: fetch [10, 11), stage [11, 12) and a dispatch that
        # overlaps it [11.5, 13), then nothing until the write [16, 18):
        # inside 8 s, covered 3 + 2, waited 3
        span("queue/fetch", 10.0, 1.0, "a"),
        span("pipeline/stage", 11.0, 1.0, "a"),
        span("pipeline/dispatch", 11.5, 1.5, "a"),
        span("storage/write", 16.0, 2.0, "a"),
        # task b runs inside a's gap and overlaps a's spans in time:
        # inside 5 s, covered 1 + 1, waited 3
        span("queue/fetch", 12.0, 1.0, "b"),
        span("storage/write", 16.0, 1.0, "b"),
        # task c ended before the window: not a steady task
        span("queue/fetch", 1.0, 1.0, "c"),
        # the consumer's wait for task b began before b was claimed:
        # ignored by name, it is neither extent nor cover
        span("scheduler/load", 5.0, 7.5, "b"),
        # no task, and a span of the old record (no start): not read
        span("scheduler/load", 14.0, 1.0),
        {"kind": "span", "name": "storage/write", "t": 15.0, "dur_s": 9.0,
         "trace_id": "a"},
    ]
    r = record(spans=spans, window=(9.0, 20.0))
    extent = reducer("task_extent")
    waits = {"ignore": ["scheduler/load"]}
    assert extent.reduce(r, part="inside", q=0.5, **waits) == \
        pytest.approx(6500.0)
    assert extent.reduce(r, part="inside", q=1.0, **waits) == \
        pytest.approx(8000.0)
    assert extent.reduce(r, part="wait", q=0.0, **waits) == \
        pytest.approx(3000.0)
    assert extent.reduce(r, part="wait", q=1.0, **waits) == \
        pytest.approx(3000.0)
    # not ignored, b's extent would start with the consumer's wait
    assert extent.reduce(r, part="inside", q=1.0) == pytest.approx(12000.0)
    with pytest.raises(ValueError):
        extent.reduce(r, part="outside")
    # the parent's spans have no start: nothing to read
    old = record(spans=[{"kind": "span", "name": "queue/fetch", "t": 10.0,
                         "dur_s": 1.0, "trace_id": "a"}], window=(9.0, 20.0))
    assert extent.reduce(old, part="inside") is None


# ---------------------------------------------------------------------------
# idle time under the program's spans
# ---------------------------------------------------------------------------
ANY = ["pipeline/drain", "pipeline/dispatch", "scheduler/load"]


def test_idle_gap_half_under_a_span():
    # busy [0, 400) and [600, 1000): one idle gap [400, 600); a drain
    # span on a pool thread covers its first half, a dispatch span on
    # the main thread its last quarter and much busy time besides
    t = tables([["fusion.1", "convolution", 0, 400],
                ["fusion.1", "convolution", 600, 400]],
               host=[["ThreadPoolExecutor-0_0: pipeline/drain", 300, 200],
                     ["python3: pipeline/dispatch", 550, 400],
                     ["python3: np.asarray(jax.Array)", 400, 200]])
    idle = reducer("trace_idle_under_spans")
    r = record(trace=t)
    assert idle.reduce(r, names=["pipeline/drain"], any_of=ANY) == \
        pytest.approx(50.0)
    assert idle.reduce(r, names=["pipeline/dispatch"], any_of=ANY) == \
        pytest.approx(25.0)
    assert idle.reduce(r, names=["scheduler/load"], any_of=ANY) == \
        pytest.approx(0.0)
    # under neither: [500, 550), a quarter; the profiler's own event
    # that covers the whole gap is no program span
    assert idle.reduce(r, names=ANY, any_of=ANY, complement=True) == \
        pytest.approx(25.0)


def test_idle_under_spans_has_nothing_to_read_without_annotations():
    idle = reducer("trace_idle_under_spans")
    # the parent: no program span on the host plane
    t = tables([["fusion.1", "convolution", 0, 400]],
               host=[["python3: np.asarray(jax.Array)", 400, 600]])
    assert idle.reduce(record(trace=t), names=ANY, any_of=ANY,
                       complement=True) is None
    assert idle.reduce(record(), names=ANY, any_of=ANY) is None
    # a device that was never idle has no idle time to split
    busy = tables([["fusion.1", "convolution", 0, 1000]],
                  host=[["python3: pipeline/dispatch", 0, 1000]])
    assert idle.reduce(record(trace=busy), names=ANY, any_of=ANY) is None


# ---------------------------------------------------------------------------
# codec time per request
# ---------------------------------------------------------------------------
def test_codec_ms_per_request():
    spans = [span("serving/http", 1.0, 3.0), span("serving/decode", 1.0, 0.5),
             span("serving/encode", 3.0, 0.25), span("serving/encode", 3.5, 0.25),
             span("serving/http", 2.0, 4.0), span("serving/decode", 2.0, 1.0),
             span("serving/http", 30.0, 1.0)]      # ended after the window
    r = record(spans=spans, window=(0.0, 10.0))
    per = reducer("span_ms_per_request")
    assert per.reduce(r, names=["serving/decode", "serving/encode"],
                      per="serving/http") == pytest.approx(1000.0)
    assert per.reduce(record(window=(0.0, 10.0)), names=["serving/decode"],
                      per="serving/http") is None
