#!/usr/bin/env bash
# Bench regression gate: run the ladder (BENCH_FAST) and compare against
# the most recent recorded round (BENCH_r*.json), failing on >200%
# regression — the reference's CI discipline
# (/root/reference/.github/workflows/on-pull-request.yml go-bench job).
#
# Usage: scripts/run_bench_gate.sh [baseline.json]
set -euo pipefail
cd "$(dirname "$0")/.."

# Gate like-for-like: the committed fast-mode CPU baseline matches the
# candidate's BENCH_FAST workload shapes, so rungs actually gate instead
# of skipping on shape mismatch (advisor r3: a full-size BENCH_r*.json
# baseline made the gate pass vacuously).  Regenerate it after intended
# perf changes with:
#   JAX_PLATFORMS=cpu BENCH_FAST=1 python bench.py | tail -1 > BENCH_FAST_BASELINE.json
baseline="${1:-}"
if [ -z "$baseline" ] && [ -f BENCH_FAST_BASELINE.json ]; then
    baseline=BENCH_FAST_BASELINE.json
fi
if [ -z "$baseline" ]; then
    baseline=$(ls BENCH_r*.json 2>/dev/null | sort | tail -1 || true)
fi
if [ -z "$baseline" ]; then
    echo "no baseline BENCH_r*.json found; nothing to gate against"
    exit 0
fi

out=$(mktemp)
# Pin the CPU backend: the gate compares against a CPU baseline, and a
# stale JAX_PLATFORMS from the environment (e.g. a TPU-plugin dev shell)
# must not leak into the candidate run.
JAX_PLATFORMS=cpu BENCH_FAST=1 python bench.py | tail -1 > "$out"
echo "candidate: $(cat "$out" | head -c 300)..."
python scripts/check_bench_regression.py "$baseline" "$out"
