#!/usr/bin/env bash
# Regenerate EVERY perf artifact from the current code (a round must never
# ship stale numbers). Requires the real TPU (do NOT set JAX_PLATFORMS=cpu).
# Runs three device processes in sequence; a chip belongs to one process at
# a time, so none may be left running. Usage: contrib/bench_all.sh <round-tag>
set -euo pipefail
cd "$(dirname "$0")/.."

TAG="${1:?usage: contrib/bench_all.sh <round-tag>}"

echo "== kernel roofline -> KERNEL_${TAG}.json" >&2
python scripts/kernel_roofline.py --out "KERNEL_${TAG}.json"

echo "== all five BASELINE configs -> BENCH_CONFIGS.json" >&2
python scripts/bench_configs.py

echo "== headline mixed bench (bench.py single line)" >&2
python bench.py

echo "artifacts regenerated: KERNEL_${TAG}.json BENCH_CONFIGS.json" >&2
