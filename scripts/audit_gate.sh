#!/usr/bin/env bash
# Compiled-program audit gate (PR 17, CPU-runnable — no TPU window needed).
#
# Two checks, both against the program XLA actually built:
#
#   1. auditbench run — compile the tieable engine matrix at tiny shapes
#      (dp ZeRO-1 bucketed, dp int8 incl. scale sidecars, gpipe replicated
#      + hybrid ZeRO-1, the tp-in-stage pipeline) plus the serve layouts
#      (kv_dtype x tp), and cross-check every analytic byte formula
#      (comm_stats wire bytes, pool_page_bytes) against the optimized-HLO
#      collective ledger. Any tie-out failure exits nonzero.
#   2. auditbench diff — compare the fresh ledger against the committed
#      golden (perf_runs/audit_golden/cpu8.json). Unexplained growth in
#      flops / peak HBM / wire bytes / per-kind collective counts exits
#      nonzero: a regression gate that needs no chip.
#
# An INTENDED program change (new collective, different bucketing) fails
# the diff by design — regenerate and commit the golden with it:
#
#   scripts/audit_gate.sh --update-golden
#
# Usage: scripts/audit_gate.sh [--update-golden] [--out PATH]
set -euo pipefail
cd "$(dirname "$0")/.."

GOLDEN=perf_runs/audit_golden/cpu8.json
OUT=${TMPDIR:-/tmp}/audit_fresh_$$.json
UPDATE=0
while [ $# -gt 0 ]; do
    case "$1" in
        --update-golden) UPDATE=1 ;;
        --out) OUT=$2; shift ;;
        *) echo "usage: $0 [--update-golden] [--out PATH]" >&2; exit 2 ;;
    esac
    shift
done

export JAX_PLATFORMS=cpu
export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"

if [ "$UPDATE" = 1 ]; then
    python -m ddlbench_tpu.tools.auditbench run --out "$GOLDEN"
    echo "audit_gate: golden regenerated -> $GOLDEN (commit it)"
    exit 0
fi

python -m ddlbench_tpu.tools.auditbench run --out "$OUT"

if [ ! -f "$GOLDEN" ]; then
    echo "audit_gate: no golden at $GOLDEN — run $0 --update-golden" >&2
    exit 1
fi
python -m ddlbench_tpu.tools.auditbench diff "$GOLDEN" "$OUT"
rm -f "$OUT"
echo "audit_gate: clean (ties exact, no growth vs golden)"
