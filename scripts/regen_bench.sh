#!/usr/bin/env bash
# Regenerate the committed bench artifacts (docs/bench_*.jsonl).
#
# Every "regen on TPU with the same command" note in README.md and
# docs/ARCHITECTURE.md points here: this script IS the list of commands
# that produced the committed lines, one target per artifact, so the
# regen recipe has a single runnable home instead of prose scattered
# across the docs.
#
# Default is the CPU-safe emulated run (JAX_PLATFORMS=cpu, the exact
# flags the committed artifacts were measured with — including --small
# where the committed line used harness-validation dims). `--tpu` drops
# the CPU pin and runs the same sweeps on the attached accelerator;
# numbers land in $OUT_DIR (default: ./bench_regen, NEVER docs/ — diff
# and copy over deliberately, the committed artifacts are review-gated).
#
# Usage:
#   scripts/regen_bench.sh                 # all targets, CPU emulation
#   scripts/regen_bench.sh --tpu           # all targets on the accelerator
#   scripts/regen_bench.sh --only tenants  # one target (name column below)
#   OUT_DIR=/tmp/b scripts/regen_bench.sh --only fleet,serving
set -euo pipefail

REPO="$(cd "$(dirname "$0")/.." && pwd)"
OUT_DIR="${OUT_DIR:-$REPO/bench_regen}"
ONLY=""
TPU=0
while [ $# -gt 0 ]; do
  case "$1" in
    --tpu) TPU=1 ;;
    --only) ONLY="$2"; shift ;;
    *) echo "unknown arg: $1" >&2; exit 2 ;;
  esac
  shift
done
mkdir -p "$OUT_DIR"

run() { # run <name> <outfile> <bench args...>
  local name="$1" out="$2"; shift 2
  if [ -n "$ONLY" ] && ! [[ ",$ONLY," == *",$name,"* ]]; then return 0; fi
  echo "== $name -> $OUT_DIR/$out" >&2
  if [ "$TPU" = 1 ]; then
    (cd "$REPO" && python bench.py "$@") > "$OUT_DIR/$out"
  else
    (cd "$REPO" && JAX_PLATFORMS=cpu python bench.py "$@") > "$OUT_DIR/$out"
  fi
}

# name       artifact (docs/)                 command (verbatim from the docs)
run sites    bench_sites_scaling_r12.jsonl    --sites 8,32,128,512 --small --sanitize
run slices   bench_slices_scaling_r18.jsonl   --sites 128,512,2048 --slices 1,2,4 --wire-quant int8
run serving  bench_serving_r15.jsonl          --serve
run fleet    bench_fleet_r21.jsonl            --serve --replicas 1,2,4 --swap 4
# r22 composition: the fleet sweep on a sliced pod (replicas pin
# slice-major across 2 bands of 2 devices; rows record the topology)
run fleet-sliced bench_fleet_sliced_r22.jsonl --serve --replicas 1,2 --swap 4 --slices 2 --pack 2
run tenants  bench_tenants_r22.jsonl          --tenants 2
run attacks  bench_attacks_ab_r17.jsonl       --attacks '{"sign_flip": [[3, 0, -1], [11, 0, -1], [19, 0, -1]], "scale": [[27, 0, -1]], "scale_factor": 25}' --robust-agg trimmed_mean
run privacy  bench_privacy_ab_r20.jsonl       --dp-noise 0.5 --dp-clip 1.0 --secure-agg mask

echo "done: $(ls "$OUT_DIR" | wc -l) artifact(s) in $OUT_DIR" >&2
