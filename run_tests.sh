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

exit $rc
