#!/bin/bash
# CI entry point: graftlint gate, then the test suite on a clean 8-device
# virtual CPU mesh. Prints per-stage wall time so tier-1 latency creep is
# visible in every CI log.
set -u
cd "$(dirname "$0")"

stage_start=$SECONDS
stage_time() {
    echo "== stage '$1' took $((SECONDS - stage_start))s =="
    stage_start=$SECONDS
}

# --- baseline guard -------------------------------------------------------
# The graftlint baseline was emptied in PR 2 (all GL005 donate_argnums
# findings fixed) and has stayed empty through the GL010-series
# concurrency rules (ISSUE 10) and the GL020-series Pallas kernel rules
# (ISSUE 16): any entry reappearing — for ANY rule, and a GL010+/GL020+
# key especially, since every real concurrency or kernel-soundness hit
# was fixed or inline-annotated, never grandfathered — means someone
# re-grandfathered a finding instead of fixing it. Fail loudly
# (docs/linting.md).
python - <<'EOF' || exit 1
import json, sys
with open("tools/graftlint/baseline.json") as f:
    findings = json.load(f).get("findings", {})
if findings:
    concurrency = [k for k in findings if "::GL01" in k]
    pallas = [k for k in findings if "::GL02" in k]
    print(
        f"graftlint baseline is not empty ({len(findings)} grandfathered "
        f"finding(s), {len(concurrency)} from the GL010-series, "
        f"{len(pallas)} from the GL020-series); fix the "
        "findings instead of re-grandfathering them (docs/linting.md)",
        file=sys.stderr,
    )
    sys.exit(1)
EOF
stage_time "baseline guard"

# --- static analysis gate -------------------------------------------------
# graftlint (tools/graftlint, docs/linting.md) fails on any finding not in
# the (empty) baseline; --stats prints the per-rule-family hit counts so
# the CI log shows which families (jit vs concurrency) carry weight.
# Warm runs are served from .graftlint_cache/ (content-hash keyed). Skip
# with CHUNKFLOW_SKIP_LINT=1 (e.g. when iterating on a single test).
if [ "${CHUNKFLOW_SKIP_LINT:-0}" != "1" ]; then
    echo "== graftlint gate =="
    python -m tools.graftlint --stats || exit 1
    stage_time "graftlint"
fi

# --- tests ----------------------------------------------------------------
# CHUNKFLOW_LOCKSMITH defaults ON for the suite (tests/conftest.py): every
# Lock/Condition the codebase creates is proxied and lock-order cycles
# raise in place, so the chaos/acceptance tests double as concurrency
# tests (docs/linting.md "Concurrency lint"). CHUNKFLOW_LOCKSMITH=0
# switches the sanitizer off wholesale.
JAX_PLATFORMS=cpu \
    CHUNKFLOW_LOCKSMITH="${CHUNKFLOW_LOCKSMITH:-1}" \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m pytest tests/ "$@"
rc=$?
stage_time "pytest"

# --- locksmith overhead gate ------------------------------------------------
# Sanitizer-on vs -off wall time over the e2e_overlap scheduled workload
# (docs/observability.md "Locksmith"). The JSON line reports the <5%
# target as gate_pass; the process only fails past 25% (a pathological
# proxy-hot-path regression), so shared-box noise cannot redden CI. The
# run also proves the full scheduled path is lock-order clean (a
# violation raises and fails the stage).
echo "== locksmith overhead gate =="
JAX_PLATFORMS=cpu \
    python bench.py locksmith_overhead --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "locksmith overhead gate"

# --- kernelcheck overhead gate ----------------------------------------------
# Kernel-sanitizer-on vs -off wall time over the interpret-mode Pallas
# parity legs (docs/linting.md "Runtime kernel sanitizer"). The JSON
# line reports the <5% target as gate_pass; the process only fails past
# 25% (the sanitizer landed work somewhere hot), so shared-box noise
# cannot redden CI. The on leg also proves a clean workload raises no
# violation (the tier-1 no-false-positives contract).
echo "== kernelcheck overhead gate =="
JAX_PLATFORMS=cpu \
    python bench.py kernelcheck_overhead --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "kernelcheck overhead gate"

# --- telemetry overhead gate ----------------------------------------------
# Telemetry-on vs -off wall time on the pipeline_overlap workload
# (docs/observability.md). The JSON line reports the <2% target as
# gate_pass; the process only fails past 10% (gross regression — a lock
# on the hot path, per-event fsync), so shared-box noise cannot redden CI.
echo "== telemetry overhead gate =="
JAX_PLATFORMS=cpu \
    python bench.py telemetry_overhead --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "telemetry overhead gate"

# --- pipeline overlap gate --------------------------------------------------
# Serial vs double-buffered executor on the synthetic chunk workload
# (docs/performance.md). The in-suite copy of this ratio gate is marked
# slow/bench (it flips under full-suite load on a 1-core box — ISSUE 7
# satellite); this standalone run, on a quiet interpreter, is the gate
# of record. The run itself raises on bit-divergence.
echo "== pipeline overlap gate =="
JAX_PLATFORMS=cpu \
    python bench.py pipeline_overlap --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "pipeline overlap gate"

# --- e2e overlap gate ------------------------------------------------------
# Serial vs adaptive-scheduler wall time over the full task lifecycle
# (load → compute → post → write, docs/performance.md "Adaptive
# scheduler"). Reports the >=1.4x target as gate_pass (asserted
# best-of-3 in tests/test_bench.py); the process only fails below 1.1x.
echo "== e2e overlap gate =="
JAX_PLATFORMS=cpu \
    python bench.py e2e_overlap --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "e2e overlap gate"

# --- resilience overhead gate ----------------------------------------------
# Fault-tolerance layer on-vs-off over the e2e_overlap workload
# (docs/fault_tolerance.md): supervised claims + completion ledger +
# lease heartbeat must cost < 3% wall-clock (reported as gate_pass);
# the process only fails past 15% (a lock/fsync landed on the per-task
# hot path), so shared-box noise cannot redden CI.
echo "== resilience overhead gate =="
JAX_PLATFORMS=cpu \
    python bench.py resilience_overhead --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "resilience overhead gate"

# --- export overhead gate ---------------------------------------------------
# Live /metrics exporter on-vs-off over the e2e_overlap workload, scraped
# continuously while tasks flow (docs/observability.md "Fleet view"):
# serving registry snapshots must cost < 2% wall-clock (reported as
# gate_pass); the process only fails past 10% (a lock landed on the
# per-task hot path), so shared-box noise cannot redden CI.
echo "== export overhead gate =="
JAX_PLATFORMS=cpu \
    python bench.py export_overhead --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "export overhead gate"

# --- fleet chaos smoke ------------------------------------------------------
# A REAL multi-process fleet (parallel/fleet.py) drains a small volume
# while one worker is SIGKILLed mid-run and one spot-drill preemption
# fires (docs/fault_tolerance.md "Running a fleet"). Binary gate: the
# run either converges — every task committed exactly once, queue
# clean — or the process exits nonzero.
echo "== fleet chaos smoke =="
JAX_PLATFORMS=cpu \
    python bench.py fleet_smoke --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "fleet chaos smoke"

# --- trace export overhead gate ----------------------------------------------
# Perfetto/Chrome-trace exporter (tools/trace_export.py) pinned on a
# large synthetic multi-worker stream with injected clock skew
# (docs/observability.md "Timeline view"). The run raises unless the
# exported trace validates clean and every cross-worker flow survives;
# reports the >=50k events/s soft floor as gate_pass; the process only
# fails below 5k events/s (an algorithmic regression, not box noise).
# The fleet chaos smoke above already round-trips its REAL acceptance
# JSONL through the same exporter + validator.
echo "== trace export overhead gate =="
JAX_PLATFORMS=cpu \
    python bench.py trace_export_overhead --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "trace export overhead gate"

# --- serving throughput gate -------------------------------------------------
# Packed cross-request batching vs sequential per-chunk execution on many
# small concurrent requests (docs/serving.md). Reports the >=1.3x target
# as gate_pass (asserted slow-marked in tests/test_bench.py); the process
# only fails below 1.1x. The run itself raises on any bit-divergence
# between the packed and per-chunk paths.
echo "== serving throughput gate =="
JAX_PLATFORMS=cpu \
    python bench.py serving_throughput --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "serving throughput gate"

# --- storage throughput gate -------------------------------------------------
# Serial uncached reads vs concurrent block reads + hot block cache on an
# overlapping-halo cutout grid (docs/storage.md). Reports the >=1.3x
# target as gate_pass (asserted slow-marked in tests/test_bench.py); the
# process only fails below 1.1x. The run itself raises on any
# bit-divergence between the serial, concurrent and cached legs.
echo "== storage throughput gate =="
JAX_PLATFORMS=cpu \
    python bench.py storage_throughput --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "storage throughput gate"

# --- segmentation stitch gate -------------------------------------------------
# Stitched map->reduce->map whole-volume labeling vs one monolithic pass
# against latency-charged storage (docs/segmentation.md). Reports the
# >=1.3x target as gate_pass (asserted best-of-3 in tests/test_bench.py);
# the process only fails below 1.1x. The run itself raises unless the
# stitched output is label-isomorphic to the monolithic labeling.
echo "== segmentation stitch gate =="
JAX_PLATFORMS=cpu \
    python bench.py segmentation_stitch --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "segmentation stitch gate"

# --- slo overhead gate --------------------------------------------------------
# Time-series sampler + burn-rate evaluator on-vs-off over the e2e
# scheduled workload (docs/observability.md "SLO view"): the SLO plane
# must cost < 2% wall-clock on top of plain telemetry (reported as
# gate_pass); the process only fails past 10% (sampling work landed on
# the per-task hot path), so shared-box noise cannot redden CI. The on
# leg also asserts the plane actually sampled and that a healthy
# workload fires no alert.
echo "== slo overhead gate =="
JAX_PLATFORMS=cpu \
    python bench.py slo_overhead --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "slo overhead gate"

# --- multichip overlap gate ---------------------------------------------------
# Unified sharded engine (CHUNKFLOW_MESH=data=8) vs the single-device
# reference path on 8 simulated host devices (docs/multichip.md). The
# run asserts bitwise identity between the legs and that the sharded
# program landed in the roofline ledger; reports the >=1.3x target as
# gate_pass (asserted slow-marked in tests/test_bench.py); the process
# only fails below 1.1x.
echo "== multichip overlap gate =="
JAX_PLATFORMS=cpu \
    python bench.py multichip_overlap --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "multichip overlap gate"

# --- sharded replay gate ------------------------------------------------------
# Sharded blend replay (per-slab rosters + ppermute fringe exchange)
# vs replicated replay on the same 8-device spatial mesh, blend-
# dominated identity proxy (docs/multichip.md "Sharded blend replay").
# The run asserts bitwise identity of BOTH legs against the
# single-device reference and that the sharded program landed in the
# roofline ledger; reports the >=1.3x target as gate_pass (asserted
# slow-marked in tests/test_bench.py); the process only fails below
# 1.1x.
echo "== sharded replay gate =="
env -u CHUNKFLOW_SHARD_REPLAY JAX_PLATFORMS=cpu \
    python bench.py multichip_sharded_replay --ledger \
    || rc=$((rc == 0 ? 1 : rc))
stage_time "sharded replay gate"

# --- fused blend gate ---------------------------------------------------------
# Fused blend data movement (weighting + aligned-window placement + RMW in
# one pass) vs the separate-leg structure it replaced, as compiled XLA
# proxies of both structures (docs/performance.md "The fused Pallas blend
# kernel"). The run asserts bit-identity across both proxies, the XLA
# scatter reference AND the real fused Pallas kernel in interpret mode,
# and that both legs carry roofline rows in programs.json; reports the
# >=1.2x target as gate_pass (asserted slow-marked in tests/test_bench.py);
# the process only fails below 1.1x.
echo "== fused blend gate =="
JAX_PLATFORMS=cpu \
    python bench.py blend_fused --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "fused blend gate"

# Device-resident front half (raw chunk uploaded once, convert+gather on
# device) vs the host gather+convert+re-upload structure it replaced
# (docs/performance.md "The device-resident front half"). The run asserts
# bit-identity across both legs AND the real Pallas gather kernel in
# interpret mode, and that both legs carry roofline rows in
# programs.json; reports the >=1.2x target as gate_pass (asserted
# slow-marked in tests/test_bench.py); the process only fails below 1.1x.
echo "== front half gate =="
JAX_PLATFORMS=cpu \
    python bench.py front_half --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "front half gate"

# Fused patch pipeline (ISSUE 17): the per-bucket serving structure with
# device-resident weighted stacks (one upload, donated on-device overlay,
# one scatter) vs the host round-trip structure it replaced (per-batch
# download, host stack, wholesale re-upload), as compiled proxies of both
# structures (docs/performance.md "The fused patch pipeline"). The run
# asserts bit-identity across both proxies AND the composed real Pallas
# kernels (gather -> forward -> fused blend) in interpret mode, and that
# both legs carry roofline rows in programs.json; reports the >=1.2x
# target as gate_pass (asserted slow-marked in tests/test_bench.py); the
# process only fails below 1.1x.
echo "== fused pipeline gate =="
JAX_PLATFORMS=cpu \
    python bench.py fused_pipeline --ledger || rc=$((rc == 0 ? 1 : rc))
stage_time "fused pipeline gate"

# --- bench regression ledger ------------------------------------------------
# Every gate above appended its measurement (commit-stamped) to
# telemetry/bench_ledger.jsonl; compare diffs this run against the
# rolling median of prior FRESH rows (cached: rows loudly refused as
# baselines). Soft gate on this load-sensitive 1-core box: compare
# itself exits nonzero only on a >25% fresh-vs-fresh regression of a
# throughput/speedup metric (docs/observability.md "Device program
# view" — bench-ledger cookbook).
echo "== bench regression ledger compare =="
JAX_PLATFORMS=cpu \
    python bench.py compare || rc=$((rc == 0 ? 1 : rc))
stage_time "bench ledger compare"
exit $rc
