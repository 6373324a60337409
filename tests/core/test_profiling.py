"""Device-performance plane (core/profiling.py, ISSUE 8): program cost
ledger + roofline accounting, anomaly-triggered bounded profiler
capture, kill-switch compliance, and the GL007 lint gate over the new
module."""
import glob
import json
import os
import warnings

import numpy as np
import pytest

from chunkflow_tpu.core import profiling, telemetry
from chunkflow_tpu.core.compile_cache import ProgramCache, RetraceWarning


@pytest.fixture
def clean_plane(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()  # reset hook clears the ledger + capture state
    yield monkeypatch
    telemetry.reset()


# ---------------------------------------------------------------------------
# cost ledger
# ---------------------------------------------------------------------------
def test_program_cache_build_records_cost_ledger_entry(clean_plane,
                                                       tmp_path):
    """Acceptance: every ProgramCache build records compile seconds
    (always) and FLOPs/bytes (cost_analysis available on CPU), visible
    in the catalog, programs.json, the JSONL stream, and /metrics."""
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.inference import Inferencer

    telemetry.configure(str(tmp_path))
    inferencer = Inferencer(
        input_patch_size=(4, 16, 16),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=3,
        framework="identity",
        batch_size=2,
        crop_output_margin=False,
    )
    rng = np.random.default_rng(0)
    for _ in range(2):
        np.asarray(inferencer(Chunk(
            rng.random((8, 32, 32), dtype=np.float32))).array)

    entries = profiling.catalog()
    assert len(entries) == 1
    entry = entries[0]
    assert entry["family"] == "scatter"
    assert entry["compile_s"] > 0  # first call paid trace + XLA compile
    assert entry["flops"] > 0  # CPU backend exposes cost_analysis
    assert entry["bytes_accessed"] > 0
    assert entry["calls"] == 2
    # roofline derivation against the peak table (CPU fallback row)
    assert entry["roofline_s"] > 0
    assert entry["roofline_util"] is not None
    assert entry["peak_source"] == "table:cpu"

    counters = telemetry.snapshot()["counters"]
    assert counters["program/builds"] == 1
    assert counters["program/compile_seconds"] > 0
    assert counters["program/flops_total"] == entry["flops"]

    # flush writes programs.json (flush hook) + emits the catalog event
    telemetry.flush()
    catalog_path = tmp_path / "programs.json"
    assert catalog_path.exists()
    payload = json.loads(catalog_path.read_text())
    assert payload["programs"][0]["family"] == "scatter"

    kinds = {}
    with open(telemetry.configured_path()) as f:
        for line in f:
            record = json.loads(line)
            kinds.setdefault(record["kind"], []).append(record)
    assert len(kinds["compile"]) == 1
    compile_ev = kinds["compile"][0]
    assert compile_ev["name"] == "program/scatter"
    assert compile_ev["compile_s"] > 0
    assert kinds["programs"][0]["programs"]

    # the program/* counters ride /metrics with zero new mapping code
    from chunkflow_tpu.parallel.restapi import (
        parse_prometheus,
        render_prometheus,
    )

    metrics = parse_prometheus(render_prometheus())
    assert metrics["chunkflow_program_builds_total"] == 1
    assert metrics["chunkflow_program_compile_seconds_total"] > 0
    assert metrics["chunkflow_program_flops_total_total"] == entry["flops"]


def test_instrument_program_passthrough_for_non_programs(clean_plane):
    """Cache entries that are not lowerable jit programs (tests cache
    plain sentinels) pass through untouched."""
    assert profiling.instrument_program("tag", ("k",)) == "tag"
    fn = lambda: 1  # noqa: E731 — callable but no .lower
    assert profiling.instrument_program(fn, ("k",)) is fn
    assert profiling.catalog() == []


def test_instrumented_program_forwards_attributes(clean_plane):
    import jax
    import jax.numpy as jnp

    program = profiling.instrument_program(
        jax.jit(lambda x: x * 2), ("fold", (8, 16, 16)), label="t")
    out = program(jnp.ones((4, 4)))
    assert float(out[0, 0]) == 2.0
    assert program._cache_size() == 1  # PjitFunction API forwards
    entry = profiling.catalog()[0]
    assert entry["family"] == "fold"
    assert entry["key"] == "(8, 16, 16)"


@pytest.mark.parametrize("kind, row", [
    ("TPU v5 lite", "tpu v5 lite"),   # what a v5e reports (chip run, PR 21)
    ("TPU v5e", "tpu v5e"),
    ("TPU v4", "tpu v4"),
    ("cpu", "cpu"),
])
def test_device_peaks_come_from_the_table(kind, row):
    flops, bw = dict(profiling.DEVICE_PEAKS)[row]
    assert profiling.device_peaks(kind) == {
        "flops_per_s": flops, "bytes_per_s": bw, "source": f"table:{row}"}


def test_device_peaks_v5e_values():
    v5e = profiling.device_peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["weird accelerator", "", None])
def test_unknown_device_kind_raises_naming_the_kind(kind):
    """A device that is not in the table is an error, not the CPU's
    made-up row."""
    with pytest.raises(KeyError, match=repr(kind).replace("'", ".")):
        profiling.device_peaks(kind)


# ---------------------------------------------------------------------------
# anomaly-triggered bounded capture
# ---------------------------------------------------------------------------
def test_retrace_fire_captures_exactly_once(clean_plane, tmp_path):
    """Acceptance: an induced retrace-watchdog fire produces exactly ONE
    bounded capture that tools/analyze_trace.py can summarise; a second
    anomaly within the cooldown does not capture again."""
    import jax
    import jax.numpy as jnp

    monkeypatch = clean_plane
    monkeypatch.setenv("CHUNKFLOW_PROFILE_ON_ANOMALY", "1")
    monkeypatch.setenv("CHUNKFLOW_PROFILE_SECONDS", "0.3")
    monkeypatch.setenv("CHUNKFLOW_PROFILE_COOLDOWN", "300")
    telemetry.configure(str(tmp_path))

    cache = ProgramCache(expected_builds=1, label="anomaly")
    cache.get(("a",), lambda: jax.jit(lambda x: x + 1))(jnp.ones((8, 8)))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RetraceWarning)
        program = cache.get(("b",), lambda: jax.jit(lambda x: x * 2))
    # run device work while the window is open so the trace has events
    for _ in range(5):
        program(jnp.ones((16, 16))).block_until_ready()
    profiling.wait_for_captures(30)

    capture_dirs = sorted(glob.glob(str(tmp_path / "profile-*")))
    assert len(capture_dirs) == 1
    assert "retrace-anomaly" in os.path.basename(capture_dirs[0])

    from tools.analyze_trace import summarize_trace_dir

    summary = summarize_trace_dir(capture_dirs[0])
    assert summary["files"] >= 1

    # second anomaly inside the cooldown: no new capture
    profiling.note_retrace("again")
    profiling.wait_for_captures(10)
    assert len(glob.glob(str(tmp_path / "profile-*"))) == 1
    assert telemetry.snapshot()["counters"]["profile/captures"] == 1


def test_stall_streak_triggers_capture(clean_plane, monkeypatch):
    """K consecutive controller ticks with the SAME dominant phase at or
    above the share threshold trigger one capture; dipping below or
    switching phase resets the streak."""
    captured = []
    monkeypatch.setattr(profiling, "maybe_capture",
                        lambda reason: captured.append(reason) or True)
    monkeypatch.setenv("CHUNKFLOW_PROFILE_STALL_SHARE", "0.8")
    monkeypatch.setenv("CHUNKFLOW_PROFILE_STALL_TICKS", "3")

    profiling.note_stall("scheduler/load", 0.9)
    profiling.note_stall("scheduler/load", 0.5)  # dip resets
    profiling.note_stall("scheduler/load", 0.9)
    profiling.note_stall("pipeline/drain", 0.9)  # phase switch resets
    profiling.note_stall("pipeline/drain", 0.9)
    assert captured == []
    profiling.note_stall("pipeline/drain", 0.9)  # third consecutive
    assert captured == ["stall-pipeline-drain"]
    # streak reset after firing: the cooldown owns repeat suppression
    profiling.note_stall("pipeline/drain", 0.9)
    profiling.note_stall("pipeline/drain", 0.9)
    assert len(captured) == 1


def test_scheduler_tick_feeds_stall_anomaly(clean_plane, monkeypatch):
    """The depth controller reports every tick's dominant share to the
    profiling plane (flow/scheduler.py wiring)."""
    from chunkflow_tpu.flow.scheduler import DepthController

    seen = []
    monkeypatch.setattr(profiling, "note_stall",
                        lambda phase, share: seen.append((phase, share)))
    ctl = DepthController(interval=1, watermark_bytes=1 << 40)
    ctl.tick({"scheduler/load": 10.0})
    assert seen == [("scheduler/load", 1.0)]


# ---------------------------------------------------------------------------
# kill switch: CHUNKFLOW_TELEMETRY=0 means the plane does not exist
# ---------------------------------------------------------------------------
def test_kill_switch_creates_nothing(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    telemetry.reset()
    # no instrumentation wrapper...
    program = jax.jit(lambda x: x + 1)
    assert profiling.instrument_program(program, ("a",)) is program
    cached = ProgramCache().get(("a",), lambda: program)
    assert cached is program
    cached(jnp.ones((4, 4)))
    assert profiling.catalog() == []
    # ...no catalog file...
    assert profiling.write_catalog(str(tmp_path)) is None
    # ...no capture threads or files...
    assert profiling.maybe_capture("retrace-x") is False
    target, err = profiling.capture(0.1, "operator", force=True)
    assert target is None and "disabled" in err
    # ...no task window...
    assert profiling.start_task_window(str(tmp_path / "w")) is None
    # ...and no /profile route
    from chunkflow_tpu.parallel.restapi import CoordinationService

    status, payload = CoordinationService().handle(
        "POST", "/profile?seconds=0.1")
    assert status == 404
    assert list(tmp_path.iterdir()) == []
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY")
    telemetry.reset()


def test_capture_requires_a_destination(clean_plane, monkeypatch):
    """No metrics sink and no CHUNKFLOW_PROFILE_DIR: captures refuse
    rather than writing somewhere surprising."""
    monkeypatch.delenv("CHUNKFLOW_PROFILE_DIR", raising=False)
    target, err = profiling.capture(0.1, "operator", force=True)
    assert target is None and "no capture dir" in err


# ---------------------------------------------------------------------------
# lint compliance: no instrumentation inside traced functions (GL007)
# ---------------------------------------------------------------------------
def test_profiling_module_is_gl007_clean():
    from pathlib import Path

    from tools.graftlint.config import load_config
    from tools.graftlint.engine import lint_paths

    repo_root = Path(__file__).resolve().parents[2]
    config = load_config(repo_root / "pyproject.toml")
    findings, _ = lint_paths(
        ["chunkflow_tpu/core/profiling.py"], config, repo_root=repo_root)
    gl007 = [f for f in findings if f.code == "GL007"]
    assert not gl007, [f"{f.path}:{f.line}: {f.message}" for f in gl007]
    assert not findings, [
        f"{f.path}:{f.line}: {f.code} {f.message}" for f in findings
    ]


# ---------------------------------------------------------------------------
# log-summary DEVICE PROGRAMS table + cloud watch pickup
# ---------------------------------------------------------------------------
def test_log_summary_renders_device_programs_table(clean_plane, tmp_path,
                                                   capsys):
    from chunkflow_tpu.flow.log_summary import (
        print_telemetry_summary,
        summarize_programs,
    )

    events = [
        {"kind": "compile", "name": "program/fold", "family": "fold",
         "key": "(8, 32, 32)", "compile_s": 0.5, "flops": 2e9,
         "bytes_accessed": 3e8, "device": "cpu", "worker": "w1",
         "t": 1.0},
        {"kind": "programs", "name": "program/catalog", "worker": "w1",
         "t": 2.0, "programs": [
             {"family": "fold", "key": "(8, 32, 32)", "compile_s": 0.5,
              "flops": 2e9, "bytes_accessed": 3e8, "exec_mean_s": 0.01,
              "roofline_util": 0.42, "device_kind": "cpu"},
             {"family": "scatter", "key": "", "compile_s": 0.2,
              "flops": 1e9, "bytes_accessed": 1e8, "exec_mean_s": 0.02,
              "roofline_util": 0.04, "device_kind": "cpu"},
         ]},
    ]
    programs = summarize_programs(events)
    # the catalog event wins over raw compile events for the same worker
    assert len(programs) == 2
    assert programs[0]["family"] == "fold"  # sorted by compile_s
    assert programs[0]["roofline_util"] == 0.42

    path = tmp_path / "telemetry-w1.jsonl"
    with open(path, "w") as f:
        for record in events:
            f.write(json.dumps(record) + "\n")
    print_telemetry_summary(str(tmp_path))
    out = capsys.readouterr().out
    assert "device programs" in out
    assert "fold" in out and "scatter" in out
    assert "42.0%" in out


def test_device_programs_rank_by_lost_seconds(clean_plane):
    """ISSUE 14 satellite: the DEVICE PROGRAMS ranking key is lost
    seconds ((dispatch_wall − roofline_s) × calls) — the family
    furthest above its cost-model floor leads, regardless of compile
    time; entries without a roofline fall back behind, by compile
    time."""
    from chunkflow_tpu.flow.log_summary import summarize_programs

    events = [
        {"kind": "programs", "name": "program/catalog", "worker": "w1",
         "t": 2.0, "programs": [
             # slow compile but NEAR its floor: little to win
             {"family": "fold", "key": "", "compile_s": 9.0,
              "exec_mean_s": 0.010, "roofline_s": 0.009,
              "lost_s": 0.01, "roofline_util": 0.9},
             # fast compile but far above its floor over many calls:
             # the fusion target
             {"family": "scatter", "key": "", "compile_s": 0.2,
              "exec_mean_s": 0.050, "roofline_s": 0.005,
              "lost_s": 4.5, "roofline_util": 0.1},
             # no roofline figure at all: ranks behind both
             {"family": "mystery", "key": "", "compile_s": 1.0},
         ]},
    ]
    programs = summarize_programs(events)
    assert [p["family"] for p in programs] == \
        ["scatter", "fold", "mystery"]


def test_stamp_cost_wins_over_xla_cost_analysis(clean_plane, tmp_path):
    """profiling.stamp_cost: an analytic cost model attached to a
    program (Pallas custom calls / loop bodies are opaque to XLA's
    cost_analysis) is what the ledger scores — and lost_s derives from
    it."""
    import jax

    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.core.compile_cache import ProgramCache

    telemetry.configure(str(tmp_path))
    try:
        cache = ProgramCache(label="stamped")
        program = cache.get(
            ("stamped_family",),
            lambda: profiling.stamp_cost(
                jax.jit(lambda x: x * 2.0), flops=123.0,
                bytes_accessed=4.5e8),
        )
        import jax.numpy as jnp

        out = program(jnp.ones((4,)))
        out.block_until_ready()
        program(jnp.ones((4,))).block_until_ready()
        entry = {e["family"]: e for e in profiling.catalog()}[
            "stamped_family"]
        assert entry["flops"] == 123.0
        assert entry["bytes_accessed"] == 4.5e8
        assert entry["roofline_s"] is not None
        assert entry["lost_s"] is not None and entry["lost_s"] >= 0.0
    finally:
        telemetry.flush()
        telemetry.configure(None)


def test_program_counters_reach_cloud_watch(clean_plane):
    """Satellite: program_* counters flow through the CloudWatch
    publisher with no new mapping code (and the seconds counter gets a
    real unit)."""
    from chunkflow_tpu.plugins.aws.cloud_watch import snapshot_metric_data

    telemetry.inc("program/builds", 2)
    telemetry.inc("program/compile_seconds", 1.5)
    data = {d["MetricName"]: d for d in snapshot_metric_data()}
    assert data["program/builds"]["Value"] == 2
    assert data["program/builds"]["Unit"] == "Count"
    assert data["program/compile_seconds"]["Unit"] == "Seconds"


def test_task_window_stops_after_n_tasks(clean_plane, tmp_path):
    """--profile-dir windowed capture: the trace closes itself once its
    task budget is spent and releases the profiler session."""
    import jax
    import jax.numpy as jnp

    telemetry.configure(str(tmp_path))
    trace_dir = tmp_path / "win"
    window = profiling.start_task_window(str(trace_dir), tasks=2)
    assert window is not None and window.active
    jax.jit(lambda x: x + 1)(jnp.ones((8, 8))).block_until_ready()
    profiling.note_task_done()
    assert window.active  # 1 of 2
    profiling.note_task_done()
    assert not window.active  # budget spent: trace stopped
    assert glob.glob(str(trace_dir / "**" / "*.trace.json.gz"),
                     recursive=True)
    # the session flag is released: a capture can start again
    assert profiling._TRACE_ACTIVE is False
    profiling.note_task_done()  # past-budget tasks are a no-op
    window.close()  # idempotent


def test_background_capture_thread_is_joined_at_exit(clean_plane, tmp_path,
                                                     monkeypatch):
    """A capture must not be a daemon thread: a process that exits with
    the profiler session still open aborted in interpreter shutdown on
    the chip (exit 134 after all work was done, PR 21). Non-daemon
    threads are joined before shutdown begins."""
    import threading

    telemetry.configure(str(tmp_path))
    started = []
    monkeypatch.setattr(
        profiling, "_run_capture",
        lambda target, seconds, reason: started.append(
            threading.current_thread().daemon) or profiling._release_trace())
    target, err = profiling.capture(0.05, "exit-test", force=True,
                                    background=True)
    assert err is None and target
    profiling.wait_for_captures(10)
    assert started == [False]


# ---------------------------------------------------------------------------
# device scopes, and captures that yield (ISSUE 23)
# ---------------------------------------------------------------------------
_HLO = """\
HloModule jit_program, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %inside.1 = f32[4]{0} negate(f32[4]{0} %p), metadata={op_name="jit(program)/forward/neg"}
}

%scatter_body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %get-tuple-element.1 = f32[4]{0} get-tuple-element((s32[], f32[4]{0}) %arg), index=1
  %dynamic-update-slice.1 = f32[4]{0} dynamic-update-slice(f32[4]{0} %get-tuple-element.1, f32[4]{0} %get-tuple-element.1, s32[] %c)
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(s32[] %c, f32[4]{0} %dynamic-update-slice.1)
}

%scatter_cond (arg: (s32[], f32[4])) -> pred[] {
  ROOT %compare.1 = pred[] compare(s32[] %a, s32[] %b), direction=LT
}

%on_true (t: f32[4]) -> f32[4] {
  ROOT %copy.9 = f32[4]{0} copy(f32[4]{0} %t)
}

ENTRY %main.1 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(program)/while/body/forward/RSUNet/gather/conv" stack_frame_id=3}
  %copy.1 = f32[4]{0:T(128)S(1)} copy(f32[4]{0} %fusion.1)
  %while.1 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t), condition=%scatter_cond, body=%scatter_body, metadata={op_name="jit(program)/accumulate/scatter-add"}
  %conditional.1 = f32[4]{0} conditional(s32[] %i, f32[4]{0} %x, f32[4]{0} %x), branch_computations={%on_true, %on_true}, metadata={op_name="jit(program)/normalize/cond"}
  %gather.1 = f32[4]{0} gather(f32[4]{0} %x, s32[1]{0} %i), metadata={op_name="jit(program)/gather"}
  ROOT %add.1 = f32[4]{0} add(f32[4]{0} %copy.1, f32[4]{0} %gather.1), metadata={op_name="jit(program)/add"}
}
"""


def test_op_scopes_outermost_scope_and_inheritance_through_calls():
    scopes = profiling.op_scopes(_HLO)
    # the outermost scope name on the path wins: a flax module called
    # `gather` inside the model does not move an op out of `forward`
    assert scopes["forward"] == ["fusion.1"]
    # a while's body and condition ops carry no metadata: they take the
    # scope of the while that calls them, as a branch takes its
    # conditional's; free ops (tuple, get-tuple-element) are left out
    assert sorted(scopes["accumulate"]) == [
        "compare.1", "dynamic-update-slice.1", "while.1"]
    assert sorted(scopes["normalize"]) == ["conditional.1", "copy.9"]
    # no metadata, or metadata under none of the scopes: listed under "";
    # a path's last component is the primitive (lax.gather), not a scope
    assert sorted(scopes[""]) == ["add.1", "copy.1", "gather.1"]
    assert "gather" not in scopes
    # ops inside a fusion are not events of their own
    assert not any("inside.1" in ops for ops in scopes.values())
    assert set(scopes) <= set(profiling.DEVICE_SCOPES) | {""}


# What the TPU compiler makes of the RSUNet's last block (PERF.md, PR 38): an
# output fusion named, shaped and annotated after its root, the 1x1x1 head,
# with the 27-tap convolution that takes the time inside a nested fusion.
_PARTS_HLO = """\
HloModule jit_program, is_scheduled=true

%fused_computation.7 (p0: bf16[4,8,112], p1: bf16[3,3,3,112,112]) -> bf16[4,8,112] {
  %p0 = bf16[4,8,112]{2,1,0} parameter(0)
  %p1 = bf16[3,3,3,112,112]{4,3,2,1,0} parameter(1)
  %convolution.5 = bf16[4,8,112]{2,1,0} convolution(%p0, %p1), window={size=3x3x3 pad=1_1x1_1x0_0}, dim_labels=01b2f_012io->01b2f, metadata={op_name="jit(program)/forward/RSUNet/dec0/conv3/conv_general_dilated"}
  ROOT %maximum.5 = bf16[4,8,112]{2,1,0} maximum(%convolution.5, %p0), metadata={op_name="jit(program)/forward/RSUNet/dec0/jit(relu)/max"}
}

%fused_computation.8 (q0: bf16[4,8,112], q1: bf16[3,3,3,112,112], q2: bf16[1,1,1,112,12]) -> bf16[4,8,12] {
  %q0 = bf16[4,8,112]{2,1,0} parameter(0)
  %q1 = bf16[3,3,3,112,112]{4,3,2,1,0} parameter(1)
  %q2 = bf16[1,1,1,112,12]{4,3,2,1,0} parameter(2)
  %fusion.7 = bf16[4,8,112]{2,1,0} fusion(%q0, %q1), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(program)/forward/RSUNet/dec0/jit(relu)/max"}
  ROOT %convolution.6 = bf16[4,8,12]{2,1,0} convolution(%fusion.7, %q2), window={size=1x1x1}, dim_labels=01b2f_012io->01b2f, metadata={op_name="jit(program)/forward/RSUNet/out/conv_general_dilated"}
}

%fused_computation.9 (r0: bf16[4,8,112]) -> bf16[4,8,112] {
  %r0 = bf16[4,8,112]{2,1,0} parameter(0)
  ROOT %add.9 = bf16[4,8,112]{2,1,0} add(%r0, %r0), metadata={op_name="jit(program)/forward/RSUNet/skip0/add"}
}

%body (arg: (s32[], bf16[4,8,12])) -> (s32[], bf16[4,8,12]) {
  %arg = (s32[], bf16[4,8,12]{2,1,0}) parameter(0)
  %get-tuple-element.2 = bf16[4,8,12]{2,1,0} get-tuple-element(%arg), index=1
  %dynamic-update-slice.2 = bf16[4,8,12]{2,1,0} dynamic-update-slice(%get-tuple-element.2, %get-tuple-element.2, %c)
  ROOT %tuple.2 = (s32[], bf16[4,8,12]{2,1,0}) tuple(%c, %dynamic-update-slice.2)
}

%cond (arg: (s32[], bf16[4,8,12])) -> pred[] {
  ROOT %compare.2 = pred[] compare(%a, %b), direction=LT
}

ENTRY %main.1 (x: bf16[4,8,112], w: bf16[3,3,3,112,112], h: bf16[1,1,1,112,12]) -> bf16[4,8,12] {
  %x = bf16[4,8,112]{2,1,0} parameter(0)
  %w = bf16[3,3,3,112,112]{4,3,2,1,0} parameter(1)
  %h = bf16[1,1,1,112,12]{4,3,2,1,0} parameter(2)
  %copy-start.1 = (bf16[3,3,3,112,112]{4,3,2,1,0}, bf16[3,3,3,112,112]{4,3,2,1,0}, u32[]) copy-start(%w)
  %copy-done.1 = bf16[3,3,3,112,112]{4,3,2,1,0} copy-done(%copy-start.1)
  %fusion.9 = bf16[4,8,112]{2,1,0} fusion(%x), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(program)/forward/RSUNet/skip0/add"}
  %copy.3 = bf16[4,8,112]{1,2,0} copy(%fusion.9)
  %bitcast.3 = bf16[4,8,112]{2,1,0} bitcast(%copy.3)
  %copy.4 = bf16[4,8,112]{2,1,0} copy(%x)
  %copy.6 = bf16[4,8,112]{1,2,0} copy(%x)
  %fusion.10 = bf16[4,8,112]{2,1,0} fusion(%copy.6), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(program)/forward/RSUNet/skip0/add"}
  %fusion.1066 = bf16[4,8,12]{2,1,0} fusion(%bitcast.3, %copy-done.1, %h, %copy.6), kind=kOutput, calls=%fused_computation.8, metadata={op_name="jit(program)/forward/RSUNet/out/conv_general_dilated"}
  %add.4 = bf16[4,8,112]{2,1,0} add(%copy.4, %fusion.9), metadata={op_name="jit(program)/forward/RSUNet/add"}
  %reduce.4 = bf16[4,8,112]{2,1,0} reduce(%copy.4, %x), metadata={op_name="jit(program)/normalize/reduce_sum"}
  %while.2 = (s32[], bf16[4,8,12]{2,1,0}) while(%t), condition=%cond, body=%body, metadata={op_name="jit(program)/accumulate/scatter-add"}
  ROOT %copy.5 = bf16[4,8,12]{2,1,0} copy(%fusion.1066)
}
"""


def test_op_parts_names_a_fusion_by_its_widest_convolution():
    parts, convolutions = profiling.op_parts(_PARTS_HLO)
    forward = parts["forward"]
    # (a) the fusion is `dec0`, whatever its root, name and shape say: the
    # 3x3x3 convolution sits in a nested fusion, the 1x1x1 head is the root
    assert "fusion.1066" in forward["dec0"]
    assert "out" not in forward
    assert convolutions == {"fusion.1066": [["dec0/conv3", "3x3x3"],
                                            ["out", "1x1x1"]]}
    # (b) any other op by its own path; the model's own `add` has no part
    assert forward["skip0"] == ["fusion.9", "fusion.10"]
    assert forward[""] == ["add.4"]
    # ops inside fusions are no events of their own
    listed = {op for by_part in parts.values()
              for ops in by_part.values() for op in ops}
    assert not listed & {"convolution.5", "convolution.6", "fusion.7",
                         "add.9", "maximum.5"}


def test_op_parts_of_ops_without_metadata():
    parts, _ = profiling.op_parts(_PARTS_HLO)
    # (c) a while's body and condition take the while's scope (and part)
    assert sorted(parts["accumulate"][""]) == [
        "compare.2", "dynamic-update-slice.2", "while.2"]
    # (d) XLA's own copies take the part of the ops that read them: one
    # reader (through a bitcast, which is no event), and a weight's
    # copy-start through its copy-done. Their scope stays "", as in
    # op_scopes: a part under no scope
    assert sorted(parts[""]["dec0"]) == ["copy-done.1", "copy-start.1",
                                         "copy.3"]
    # readers in two parts (`dec0` and `skip0`), readers with no part
    # (the model's own add, `normalize`'s reduce), nobody reads it: no part
    assert sorted(parts[""][""]) == ["copy.4", "copy.5", "copy.6"]
    # every scope holds the ops op_scopes lists there
    scopes = profiling.op_scopes(_PARTS_HLO)
    assert {scope: sorted(op for ops in by_part.values() for op in ops)
            for scope, by_part in parts.items()} \
        == {scope: sorted(ops) for scope, ops in scopes.items()}


# What the TPU compiler makes of a dilated up-sampling (PERF.md, PR 41;
# cut from the v5e's text for `rsunet-superhuman`): one output fusion that
# holds the zero-dilated convolution, the bias and the skip sum, rooted in
# a bitcast that carries the skip sum's name.
_UP_HLO = """\
HloModule jit_program, is_scheduled=true

%fused_computation.133 (p0: bf16[20,256,32,9,112], p1: bf16[20,128,32,9,72], p2: bf16[112], p3: bf16[1,2,1,72,112]) -> bf16[20,256,4,8,9,112] {
  %p1 = bf16[20,128,32,9,72]{4,2,3,1,0} parameter(1)
  %p3 = bf16[1,2,1,72,112]{3,4,1,2,0} parameter(3)
  %convolution-base-dilated.3 = bf16[20,256,32,9,112]{4,2,3,1,0} convolution(%p1, %p3), window={size=1x2x1 pad=0_0x1_1x0_0 lhs_dilate=1x2x1 rhs_reversal=1x1x0}, dim_labels=01b2f_012io->01b2f, metadata={op_name="jit(program)/forward/RSUNet/up0/conv_general_dilated"}
  %convert.54 = f32[20,256,32,9,112]{4,2,3,1,0} convert(%convolution-base-dilated.3)
  %p2 = bf16[112]{0} parameter(2)
  %broadcast.860 = bf16[20,256,32,9,112]{4,2,3,1,0} broadcast(%p2), dimensions={4}, metadata={op_name="jit(program)/forward/RSUNet/up0/add"}
  %convert.55 = f32[20,256,32,9,112]{4,2,3,1,0} convert(%broadcast.860)
  %add.396 = f32[20,256,32,9,112]{4,2,3,1,0} add(%convert.54, %convert.55), metadata={op_name="jit(program)/forward/RSUNet/up0/add"}
  %p0 = bf16[20,256,32,9,112]{4,2,3,1,0} parameter(0)
  %convert.56 = f32[20,256,32,9,112]{4,2,3,1,0} convert(%p0)
  %add.397 = f32[20,256,32,9,112]{4,2,3,1,0} add(%add.396, %convert.56), metadata={op_name="jit(program)/forward/RSUNet/skip0/add"}
  %convert.57 = bf16[20,256,32,9,112]{4,2,3,1,0} convert(%add.397)
  ROOT %bitcast.250 = bf16[20,256,4,8,9,112]{5,3,2,4,1,0} bitcast(%convert.57), metadata={op_name="jit(program)/forward/RSUNet/skip0/add"}
}

ENTRY %main.2 (skip: bf16[20,256,32,9,112], below: bf16[20,128,32,9,72], b: bf16[112], k: bf16[1,2,1,72,112]) -> bf16[20,256,4,8,9,112] {
  %skip = bf16[20,256,32,9,112]{4,2,3,1,0} parameter(0)
  %below = bf16[20,128,32,9,72]{4,2,3,1,0} parameter(1)
  %b = bf16[112]{0} parameter(2)
  %k = bf16[1,2,1,72,112]{3,4,1,2,0} parameter(3)
  %copy.9 = bf16[1,2,1,72,112]{3,4,1,2,0} copy(%k)
  ROOT %select_bitcast_fusion.1 = bf16[20,256,4,8,9,112]{5,3,2,4,1,0} fusion(%skip, %below, %b, %copy.9), kind=kOutput, calls=%fused_computation.133, metadata={op_name="jit(program)/forward/RSUNet/skip0/add"}
}
"""


def test_op_parts_puts_a_dilated_upsampling_with_its_skip_sum_under_up():
    """The up-sampling's one convolution names the fusion, whatever its
    root says: the bias and the skip sum ride in its epilogue, so `skip0`
    has no op of its own and nothing is left without a part."""
    parts, convolutions = profiling.op_parts(_UP_HLO)
    assert parts["forward"] == {"up0": ["select_bitcast_fusion.1"]}
    assert convolutions == {"select_bitcast_fusion.1": [["up0", "1x2x1"]]}
    # XLA's copy of the kernel takes its reader's part, under no scope
    assert parts[""] == {"up0": ["copy.9"]}
    assert profiling.op_scopes(_UP_HLO)["forward"] == [
        "select_bitcast_fusion.1"]


# What the TPU compiler makes of a Pallas kernel (PERF.md, PR 47; cut from
# the v5e's text for `rsunet-superhuman`): a `custom-call` named after the
# innermost scope on its path, the kernel's own `jit`, whose path carries
# above that the marker scope its wrapper opened (core/profiling.py
# `kernel_convolution`).
_KERNEL_HLO = """\
HloModule jit_program, is_scheduled=true

ENTRY %main.3 (x: bf16[4,20,256,64,112], w: bf16[3,3,112,112]) -> bf16[4,20,256,64,112] {
  %x = bf16[4,20,256,64,112]{4,3,2,1,0} parameter(0)
  %w = bf16[3,3,112,112]{3,2,1,0} parameter(1)
  %copy.7 = bf16[3,3,112,112]{3,2,1,0} copy(%w)
  %folded_conv.8 = bf16[4,20,256,64,112]{4,3,2,1,0:T(8,128)(2,1)} custom-call(%x, %x, %x, %copy.7), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[4,20,256,64,112]{4,3,2,1,0}}, backend_config={"custom_call_config": {"body": "TUxJUg=="}, "metadata={}"}, metadata={op_name="jit(program)/forward/RSUNet/enc0/conv2/kernel_convolution_3x3x3/jit(folded_conv)/pallas_call" stack_frame_id=87}
  %pool0.1 = bf16[4,20,256,64,112]{4,3,2,1,0:T(8,128)(2,1)} custom-call(%folded_conv.8), custom_call_target="tpu_custom_call", metadata={op_name="jit(program)/forward/RSUNet/pool0/pallas_call"}
  %folded_conv.2 = bf16[4,20,256,64,12]{4,3,2,1,0:T(8,128)(2,1)} custom-call(%pool0.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(program)/forward/RSUNet/dec0/conv3/kernel_convolution_1x1x1..out/kernel_convolution_3x3x3/jit(folded_conv)/pallas_call"}
  %custom-call.4 = bf16[4,20,256,64,112]{4,3,2,1,0} custom-call(%folded_conv.2), custom_call_target="ConcatBitcast"
  ROOT %custom-call.5 = s32[1024]{0} custom-call(%custom-call.4), custom_call_target="AssumeGatherIndicesInBound", metadata={op_name="jit(program)/forward/RSUNet/embed/jit(_take)/gather"}
}
"""


def test_op_parts_lists_a_kernel_its_marker_scope_names():
    """A `custom-call` holds no convolution instruction: what its marker
    scope says is listed under its instruction name with the module's
    path (the kernel's own `jit` below the marker left off) and the
    window, and its part comes from its own path (rule (b))."""
    parts, convolutions = profiling.op_parts(_KERNEL_HLO)
    assert convolutions["folded_conv.8"] == [["enc0/conv2", "3x3x3"]]
    assert "folded_conv.8" in parts["forward"]["enc0"]
    # the weights' copy and XLA's own call have no metadata: the part of
    # their reader (rule (d)), under no scope
    assert parts[""] == {"enc0": ["copy.7"], "embed": ["custom-call.4"]}
    assert profiling.op_scopes(_KERNEL_HLO)["forward"] == [
        "folded_conv.8", "pool0.1", "folded_conv.2", "custom-call.5"]


def test_op_parts_lists_an_unmarked_kernel_nowhere():
    """A kernel without the marker, and XLA's own custom calls with and
    without metadata, hold no convolution; each has the part its own
    ``op_name`` gives it."""
    parts, convolutions = profiling.op_parts(_KERNEL_HLO)
    assert not {"pool0.1", "custom-call.4", "custom-call.5"} \
        & set(convolutions)
    assert parts["forward"]["pool0"] == ["pool0.1"]
    assert parts["forward"]["embed"] == ["custom-call.5"]


def test_op_parts_lists_every_convolution_a_kernel_holds():
    """One scope a convolution: the module's own, and behind two dots
    the name of the module below the scope's root that the kernel takes
    along, as the head's from inside ``dec0/conv3``; the widest first, and
    the part is the widest's."""
    parts, convolutions = profiling.op_parts(_KERNEL_HLO)
    assert convolutions["folded_conv.2"] == [
        ["dec0/conv3", "3x3x3"], ["out", "1x1x1"]]
    assert parts["forward"]["dec0"] == ["folded_conv.2"]


@pytest.mark.parametrize("window, name, scope, module", [
    ((3, 3, 3), "", "kernel_convolution_3x3x3", "enc0"),
    ((1, 5, 5), "", "kernel_convolution_1x5x5", "enc0"),
    ((1, 1, 1), "out", "kernel_convolution_1x1x1..out", "out"),
])
def test_kernel_convolution_scope_is_what_the_parser_reads(
        window, name, scope, module):
    import jax
    import jax.numpy as jnp

    def program(x):
        with jax.named_scope("forward"), jax.named_scope("Net"), \
                jax.named_scope("enc0"), \
                profiling.kernel_convolution(window, name):
            return jnp.tanh(x)

    text = jax.jit(program).lower(jnp.ones((8,))).as_text(debug_info=True)
    (path,) = {p for p in profiling._LOWERED_PATH.findall(text)
               if p.endswith("/tanh")}
    assert f"/enc0/{scope}/tanh" in path
    ((listed, size),) = profiling._kernel_convolutions(path)
    assert size == "x".join(map(str, window))
    assert listed.startswith("jit(program)/forward/Net/")
    assert "/".join(profiling._split(listed)[1]) == module


def test_op_parts_on_the_scope_test_module():
    """The module ``op_scopes`` is pinned on: no root module below the
    scope but `RSUNet`, so the only part is that of the path that goes
    on below it."""
    parts, convolutions = profiling.op_parts(_HLO)
    assert parts["forward"] == {"gather": ["fusion.1"]}
    assert parts["normalize"] == {"": ["conditional.1", "copy.9"]}
    # copy.1 has one reader, add.1, which is under no scope itself
    assert sorted(parts[""][""]) == ["add.1", "copy.1", "gather.1"]
    assert convolutions == {}


def test_programs_json_carries_op_scopes_only_with_a_sink(clean_plane,
                                                          tmp_path):
    import jax
    import jax.numpy as jnp

    def build():
        def program(x):
            with jax.named_scope("forward"):
                y = jnp.tanh(x) * 2.0
            with jax.named_scope("normalize"):
                return y / jnp.maximum(y.sum(), 1.0)
        return jax.jit(program)

    x = jnp.ones((8, 128), jnp.float32)
    ProgramCache(label="bare").get(("bare",), build)(x)
    for name in profiling.OP_MAPS:   # nobody reads them
        assert profiling.catalog()[0][name] is None
    assert "program/op_map_seconds" not in telemetry.snapshot()["counters"]
    telemetry.reset()

    telemetry.configure(str(tmp_path))
    ProgramCache(label="scoped").get(("scoped",), build)(x)
    (entry,) = profiling.catalog()
    assert entry["op_scopes"]["forward"] and entry["op_scopes"]["normalize"]
    telemetry.flush()
    payload = json.loads((tmp_path / "programs.json").read_text())
    assert payload["programs"][0]["op_scopes"] == entry["op_scopes"]
    # the same ops again, by part (none below these bare scopes), and no
    # convolution in this program
    assert {scope: sorted(ops) for scope, ops in entry["op_scopes"].items()} \
        == {scope: sorted(by_part[""])
            for scope, by_part in entry["op_parts"].items()}
    assert payload["programs"][0]["op_parts"] == entry["op_parts"]
    assert payload["programs"][0]["op_convolutions"] == {}
    assert telemetry.snapshot()["counters"]["program/op_map_seconds"] > 0
    # the JSONL stream gets the ledger without the per-op maps
    with open(telemetry.configured_path()) as f:
        streamed = [json.loads(line) for line in f
                    if '"kind": "programs"' in line]
    assert streamed and not set(profiling.OP_MAPS) & set(
        streamed[0]["programs"][0])


def test_a_fresh_executable_that_holds_the_kernel_is_not_stale(clean_plane,
                                                               tmp_path):
    """The names the kernel's block puts on its path (its module, the
    marker scope) are on the executable it compiles to, so the ledger reads
    the op maps from that executable and compiles nothing past the cache;
    the gauge of the traced forward is a key of the entry."""
    import jax
    import jax.numpy as jnp

    from chunkflow_tpu.models import rsunet

    block = rsunet.RSBlock(28, dtype=jnp.bfloat16, fold=4, kernel=True,
                           interpret=True, name="enc0")
    x = jnp.ones((1, 2, 4, 16, 112), jnp.bfloat16)
    params = block.init(jax.random.PRNGKey(0), x)

    def build():
        def program(params, x):
            with jax.named_scope("forward"), jax.named_scope("RSUNet"):
                profiling.trace_gauge("forward/kernel_convolutions", 3)
                return block.apply(params, x)
        return jax.jit(program)

    telemetry.configure(str(tmp_path))
    ProgramCache(label="kernel").get(("kernel",), build)(params, x)
    (entry,) = profiling.catalog()
    assert entry["op_parts"]["forward"]["enc0"]
    assert entry["kernel_convolutions"] == 3
    lowered = build().lower(params, x).as_text(debug_info=True)
    assert "/enc0/conv2/kernel_convolution_3x3x3/" in lowered
    counters = telemetry.snapshot()["counters"]
    assert "program/stale_cache_entries" not in counters
    assert counters["program/op_map_seconds"] > 0


def test_trace_gauge_lands_on_the_program_being_built(clean_plane, tmp_path):
    """A model says at trace time which lowering it chose: the value is
    a gauge and sits on that program's entry, not on a later program's.
    ``x_fold`` is level 0's fold; a level below says its own as
    ``forward/x_fold_<level>``, which is the entry's ``x_fold_<level>``
    (1: that level runs unfolded)."""
    import jax
    import jax.numpy as jnp

    def build(fold):
        def program(x):
            if fold:
                profiling.trace_gauge("forward/x_fold", fold)
                profiling.trace_gauge("forward/x_fold_1", fold // 2)
                profiling.trace_gauge("forward/x_fold_2", 1)
            return x * 2.0
        return jax.jit(program)

    telemetry.configure(str(tmp_path))
    x = jnp.ones((8, 128), jnp.float32)
    for fold in (4, 0):
        program = ProgramCache(label=f"fold{fold}").get(
            (f"fold{fold}",), lambda: build(fold))
        program(x)
        program(x)  # no second trace, nothing to overwrite
    by_label = {e["label"]: e["x_fold"] for e in profiling.catalog()}
    assert by_label == {"fold4": 4, "fold0": None}
    below = {e["label"]: (e.get("x_fold_1"), e.get("x_fold_2"))
             for e in profiling.catalog()}
    assert below == {"fold4": (2, 1), "fold0": (None, None)}
    assert telemetry.snapshot()["gauges"]["forward/x_fold"] == 4
    assert telemetry.snapshot()["gauges"]["forward/x_fold_1"] == 2
    profiling.trace_gauge("forward/x_fold", 2)  # outside any first call
    assert telemetry.snapshot()["gauges"]["forward/x_fold"] == 2
    telemetry.flush()
    payload = json.loads((tmp_path / "programs.json").read_text())
    assert sorted(str(e["x_fold"]) for e in payload["programs"]) == [
        "4", "None"]


def test_automatic_capture_yields_to_a_session_it_did_not_start(
        clean_plane, tmp_path):
    """A harness's (or an operator's) jax.profiler session is running:
    an anomaly capture does not start, and says `capture_skipped`, not
    `capture_error`; an operator's request is refused with a reason."""
    import jax

    # the suite's conftest turns anomaly captures off; this test is
    # about them
    clean_plane.setenv("CHUNKFLOW_PROFILE_ON_ANOMALY", "1")
    telemetry.configure(str(tmp_path / "metrics"))
    jax.profiler.start_trace(str(tmp_path / "theirs"))
    try:
        assert profiling.maybe_capture("stall-scheduler-load") is False
        target, why = profiling.capture(0.05, "operator", force=True)
        assert target is None and "already active" in why
        assert profiling.start_task_window(str(tmp_path / "w")) is None
    finally:
        jax.profiler.stop_trace()
    profiling.wait_for_captures()
    telemetry.flush()
    with open(telemetry.configured_path()) as f:
        events = [json.loads(line) for line in f if line.strip()]
    skipped = [e for e in events if e.get("name") == "profile/capture_skipped"]
    assert len(skipped) == 1 and "already active" in skipped[0]["why"]
    assert not [e for e in events
                if e.get("name") == "profile/capture_error"]
    assert "profile/capture_errors" not in telemetry.snapshot()["counters"]
    assert not glob.glob(str(tmp_path / "metrics" / "profile-*"))
    # with their session over, the next anomaly is captured
    clean_plane.setenv("CHUNKFLOW_PROFILE_SECONDS", "0.05")
    assert profiling.maybe_capture("stall-scheduler-load") is True
    profiling.wait_for_captures()
    assert glob.glob(str(tmp_path / "metrics" / "profile-stall-*"))


@pytest.mark.parametrize("phase", profiling.DEVICE_PACED_PHASES)
def test_a_host_that_waits_for_the_device_is_no_anomaly(clean_plane, phase):
    """pipeline/dispatch and pipeline/compute dominating is the healthy
    state of a device-bound worker: never a capture, and it breaks a
    streak of a real stall like a dip does."""
    captured = []
    clean_plane.setattr(profiling, "maybe_capture",
                        lambda reason: captured.append(reason) or True)
    clean_plane.setenv("CHUNKFLOW_PROFILE_STALL_TICKS", "3")
    for _ in range(6):
        profiling.note_stall(phase, 0.99)
    assert captured == []
    profiling.note_stall("scheduler/load", 0.9)
    profiling.note_stall("scheduler/load", 0.9)
    profiling.note_stall(phase, 0.99)
    profiling.note_stall("scheduler/load", 0.9)
    assert captured == []


@pytest.mark.parametrize("hidden, lacks", [
    # a checkout from before the scopes: its executable names none
    ("", "forward"),
    # a checkout from before the model named what it emits itself: its
    # executable has `forward` and `enc0` and lacks `pool0`
    ("pool", "forward/pool0"),
])
def test_op_scopes_survive_a_cache_entry_from_before_the_scopes(
        clean_plane, tmp_path, hidden, lacks):
    """JAX leaves metadata out of the compile-cache key: a checkout
    without (some of) the names fills the cache, and today's program is
    then handed that executable. The ledger notices (the lowered module
    names what the executable's metadata lacks) and reads the maps from
    one compile past the cache."""
    import contextlib

    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    def build():
        def program(x):
            with jax.named_scope("forward"), jax.named_scope("Net"):
                with jax.named_scope("enc0"):
                    y = jnp.tanh(x) * 2.0
                with jax.named_scope("pool0"):
                    y = y.reshape(4, 2, 128).max(axis=1)
            with jax.named_scope("normalize"):
                return y / jnp.maximum(y.sum(), 1.0)
        return jax.jit(program)

    named_scope = jax.named_scope
    x = jnp.ones((8, 128), jnp.float32)
    saved = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        # the other checkout: the same program, fewer names, fills the cache
        with clean_plane.context() as patch:
            patch.setattr(
                jax, "named_scope",
                lambda name: contextlib.nullcontext()
                if name.startswith(hidden) else named_scope(name))
            theirs = build()
            theirs(x).block_until_ready()
            assert lacks not in profiling._names_of(
                profiling._HLO_OP_NAME.findall(
                    theirs.lower(x).compile().as_text()))
        assert list((tmp_path / "cache").iterdir())
        telemetry.configure(str(tmp_path / "metrics"))
        ProgramCache(label="scoped").get(("scoped",), build)(x)
        (entry,) = profiling.catalog()
        assert entry["op_scopes"]["forward"]
        assert entry["op_scopes"]["normalize"]
        assert entry["op_parts"]["forward"]["pool0"]
        counters = telemetry.snapshot()["counters"]
        assert counters["program/stale_cache_entries"] == 1
        # the cache is on again afterwards
        assert jax.config.jax_enable_compilation_cache
        # the stale entry lives on (nothing past the cache is written to
        # it); once it is gone, today's own entry is read as it is
        for stale in (tmp_path / "cache").iterdir():
            stale.unlink()
        compilation_cache.reset_cache()
        telemetry.reset()
        telemetry.configure(str(tmp_path / "metrics2"))
        for label in ("fills", "reads"):
            ProgramCache(label=label).get((label,), build)(x)
        assert list((tmp_path / "cache").iterdir())
        assert all(e["op_parts"]["forward"]["pool0"]
                   for e in profiling.catalog())
        assert "program/stale_cache_entries" not in \
            telemetry.snapshot()["counters"]
    finally:
        for name, value in saved.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
