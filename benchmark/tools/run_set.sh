#!/bin/bash
# run_set.sh <cell> <seconds> <sets> <seed>...: the contract's sets of runs of
# one cell in one call; each run's result line is appended to
# chiprun_out/sets/<cell>.jsonl, its compared numbers to <cell>.checks.log
cell=$1; seconds=$2; sets=$3; shift 3
mkdir -p chiprun_out/sets
for set in $(seq 1 "$sets"); do
  for seed in "$@"; do
    python3 benchmark/run.py --workload "$cell" --seed "$seed" --seconds "$seconds" --trace 0 \
      > chiprun_out/sets/last.log 2>&1
    rc=$?
    grep "check FAIL\|Error\|window:" chiprun_out/sets/last.log | cut -c1-700
    grep "check \|window:\|reference\|seconds between" chiprun_out/sets/last.log | sed "s/^/set=$set seed=$seed /" \
      >> "chiprun_out/sets/$cell.checks.log"
    line=$(grep "^{\"correct\"" chiprun_out/sets/last.log | tail -n 1)  # standard error ends with the checks
    echo "set=$set seed=$seed rc=$rc $line"
    echo "{\"cell\": \"$cell\", \"set\": $set, \"seed\": $seed, \"rc\": $rc, \"line\": $line}" \
      >> "chiprun_out/sets/$cell.jsonl"
  done
done
