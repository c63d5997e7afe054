#!/usr/bin/env bash
# tools/lint.sh — the graftlint CI gate, all four tiers.
#
# Gate 1 (AST): the repo-native static-analysis suite over the default
# lint surface (bnsgcn_tpu/, tools/, bench.py, chip_smoke.py,
# __graft_entry__.py),
# report to tools/lint_report.json (override with LINT_REPORT=path).
# Gate 2 (IR): the jaxpr-level contract audit (`analysis ir`) — traces
# every tune-reachable step/eval/exchange program on a host-only
# abstract mesh and verifies the collective/donation/wire/transfer
# contracts; report to tools/ir_report.json (override with
# IR_REPORT=path).
# Gate 3 (proto): the coordination-protocol model checker
# (`analysis proto`) — runs the real Coordinator/ResilienceManager code
# under a deterministic scheduler across enumerated interleavings and
# fault schedules; report to tools/proto_report.json (override with
# PROTO_REPORT=path).
# Gate 4 (perf): the predictive roofline audit (`analysis perf`) —
# calibration schema, cost-model drift against the repo's recorded
# measurements, monotonicity, and wire/step pricing of every
# tune-reachable lever state; report to tools/perf_report.json
# (override with PERF_REPORT=path).
# Gates 2-4 are skipped when gate 1 fails (same signal, cheaper) or
# when explicit paths are passed (file-scoped lint run).
#
# Exit code: the first failing gate's — 0 clean, 1 findings, 2 parse/
# trace/explore/eval errors — straight from `python -m bnsgcn_tpu.analysis`.
# LINT_SKIP_IR=1 skips gate 2 (the IR tier traces ~60 programs, ~2 min
# on a laptop CPU); LINT_SKIP_PROTO=1 skips gate 3 (~2000 schedules,
# a few seconds); LINT_SKIP_PERF=1 skips gate 4 (host arithmetic over
# the calibration tables, well under a second).
#
# Usage:
#   tools/lint.sh                  # full default surface, all gates
#   tools/lint.sh bnsgcn_tpu/run.py  # specific files/dirs (AST only)
#   LINT_REPORT=/tmp/r.json tools/lint.sh
set -u
cd "$(dirname "$0")/.."

REPORT="${LINT_REPORT:-tools/lint_report.json}"
IR_REPORT="${IR_REPORT:-tools/ir_report.json}"
PROTO_REPORT="${PROTO_REPORT:-tools/proto_report.json}"
PERF_REPORT="${PERF_REPORT:-tools/perf_report.json}"
PY="${PYTHON:-python}"

# The AST tier is pure-AST (no jax import), but keep the env pinned the
# same way the test tier does so the IR tier (which DOES import jax,
# CPU-only and device-free) and any future runtime hook stay CPU-safe.
JAX_PLATFORMS=cpu \
    "$PY" -m bnsgcn_tpu.analysis --json "$REPORT" "$@"
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "lint.sh: graftlint gate FAILED (rc=$rc, report: $REPORT)" >&2
    exit "$rc"
fi

# gates 2+3 only on full-surface runs: explicit paths mean a file-scoped
# AST pass, and the IR matrix / protocol schedules are path-independent
if [ "$#" -eq 0 ] || { [ "$#" -eq 1 ] && [ "${1:-}" = "-q" ]; }; then
    if [ "${LINT_SKIP_IR:-0}" != "1" ]; then
        JAX_PLATFORMS=cpu \
            "$PY" -m bnsgcn_tpu.analysis ir --json "$IR_REPORT" "$@"
        rc=$?
        if [ "$rc" -ne 0 ]; then
            echo "lint.sh: graftlint-ir gate FAILED (rc=$rc, report:" \
                 "$IR_REPORT)" >&2
            exit "$rc"
        fi
    fi
    if [ "${LINT_SKIP_PROTO:-0}" != "1" ]; then
        JAX_PLATFORMS=cpu \
            "$PY" -m bnsgcn_tpu.analysis proto --json "$PROTO_REPORT" "$@"
        rc=$?
        if [ "$rc" -ne 0 ]; then
            echo "lint.sh: graftcheck-proto gate FAILED (rc=$rc, report:" \
                 "$PROTO_REPORT)" >&2
            exit "$rc"
        fi
    fi
    if [ "${LINT_SKIP_PERF:-0}" != "1" ]; then
        JAX_PLATFORMS=cpu \
            "$PY" -m bnsgcn_tpu.analysis perf --json "$PERF_REPORT" "$@"
        rc=$?
        if [ "$rc" -ne 0 ]; then
            echo "lint.sh: graftperf gate FAILED (rc=$rc, report:" \
                 "$PERF_REPORT)" >&2
            exit "$rc"
        fi
    fi
fi
exit 0
