#!/usr/bin/env bash
# CI entry point (SURVEY.md C23 parity): static analysis first (fast,
# no device), then unit + in-process integration tests on a virtual
# 8-device CPU mesh, then the native-component build.
#
# Always ends with four machine-readable lines:
#   STORE_SUMMARY hit_rate=<r> growth_rows=<n> cache_dtype=<d> \
#       device_cache_bytes=<b> int8_bytes_reduction=<x> \
#       per_chip_cache_bytes=<b/8>
#   ONLINE_SUMMARY train_eps=<e> qps=<q> staleness_p99_s=<s> burn=<b> \
#       freshness_budget_worst_phase=<p> lineage_windows=<n>
#   COST_SUMMARY programs=<n> recompiles=<n> mfu=<f> bytes_per_step=<b>
#   TIER1_SUMMARY passed=<N> wall_s=<S> lint_findings=<L> status=<ok|fail>
# so CI (and the roadmap driver) can scrape the tier-1 outcome — and the
# tiered store's cache efficacy (docs/PERF.md "Tiered embedding store")
# — without parsing pytest's human output.
set -uo pipefail
cd "$(dirname "$0")/.."

# The single lint gate: all graftlint rules in one process
# (docs/LINTS.md).  The legacy check_*.py scripts remain as shims over
# the same rules, so running them separately here would be redundant.
lint_json=$(python -m scripts.graftlint --json 2>&1)
lint_rc=$?
lint_findings=$(printf '%s' "$lint_json" \
  | python -c 'import json,sys
try:
    print(json.load(sys.stdin).get("count", -1))
except Exception:
    print(-1)')
if [ "$lint_rc" -ne 0 ]; then
  printf '%s\n' "$lint_json"
fi

make -C native
make_rc=$?

start_s=$SECONDS
pytest_log=$(mktemp)
python -m pytest tests/ -q "$@" 2>&1 | tee "$pytest_log"
pytest_rc=${PIPESTATUS[0]}
wall_s=$((SECONDS - start_s))
passed=$(grep -Eo '[0-9]+ passed' "$pytest_log" | tail -1 | grep -Eo '[0-9]+' || echo 0)
rm -f "$pytest_log"

status=ok
rc=0
if [ "$lint_rc" -ne 0 ] || [ "$make_rc" -ne 0 ] || [ "$pytest_rc" -ne 0 ]; then
  status=fail
  rc=1
fi

# A red tier-1 run leaves forensics behind: capture an incident bundle
# (docs/OBSERVABILITY.md "Request tracing & incident bundles") with the
# exit codes as evidence, into ${TIER1_INCIDENT_DIR:-/tmp/elasticdl-ci-incidents}.
if [ "$pytest_rc" -ne 0 ]; then
  TIER1_INCIDENT_DIR="${TIER1_INCIDENT_DIR:-/tmp/elasticdl-ci-incidents}" \
  PYTEST_RC="$pytest_rc" LINT_RC="$lint_rc" MAKE_RC="$make_rc" \
  python - <<'EOF' || true
import os
from elasticdl_tpu.common.flight import FlightRecorder

recorder = FlightRecorder(incident_dir=os.environ["TIER1_INCIDENT_DIR"])
path = recorder.capture("tier1_failure", evidence={
    "pytest_rc": int(os.environ["PYTEST_RC"]),
    "lint_rc": int(os.environ["LINT_RC"]),
    "make_rc": int(os.environ["MAKE_RC"]),
})
print(f"tier1 incident bundle: {path}")
EOF
fi
# Tiered-store cache efficacy over the canonical zipfian stream (pure
# numpy, sub-second); failure is non-fatal here — the matching unit
# test in tests/test_tiered_store.py owns the hard floor.
python -m scripts.store_summary || true
# Online continuous-learning loop smoke (docs/ONLINE.md): two stream
# windows through train -> checkpoint -> hot-reload behind live
# predicts, a few seconds on CPU; non-fatal here — the matching test
# in tests/test_online_pipeline.py owns the hard assertions.
python -m scripts.online_summary || true
echo "TIER1_SUMMARY passed=${passed} wall_s=${wall_s} lint_findings=${lint_findings} status=${status}"
exit "$rc"
