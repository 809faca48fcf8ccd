#!/bin/bash
# Two sets of contract runs of one cell, the same seeds in both, one process
# a run, then the spreads (tools/spread.py). On the machine with the chip:
#
#   bash benchmarks/tools/two_sets.sh <workload> <seconds> <out dir> <seed> [<seed> ...]
#
# Every run's line goes to <out dir>/lines.jsonl, its stderr to
# <out dir>/run_<set>_<seed>.err. The first run of a checkout builds the index.
set -u
workload=$1; seconds=$2; out=$3; shift 3
mkdir -p "$out"
: > "$out/lines.jsonl"
for set in 1 2; do
  for seed in "$@"; do
    python3 benchmarks/run.py --workload "$workload" --seed "$seed" --seconds "$seconds" \
      --trace 0 2> "$out/run_${set}_${seed}.err" | tail -n 1 >> "$out/lines.jsonl"
    echo "set $set seed $seed rc=${PIPESTATUS[0]}: $(tail -n 1 "$out/lines.jsonl" | cut -c1-260)"
  done
done
python3 benchmarks/tools/spread.py "$out/lines.jsonl" $#
