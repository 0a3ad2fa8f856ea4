#!/bin/bash
# usage: sdar_read.sh <call tag> <seed> ... : the PARENT's rollout-sdar-gsm8k (unpacked under _parent/) at each
# seed through name_compiles.py; a line a run: correct, the window's compile requests, the failed comparisons.
tag=$1; shift
mkdir -p chiprun_out
for s in "$@"; do
  log=$PWD/chiprun_out/pr47_${tag}_sdar_parent_$s.log
  python3 bench_artifacts/pr47/name_compiles.py --root _parent --workload rollout-sdar-gsm8k --seed $s --seconds 51 --trace 0 > $log 2>&1
  echo "sdar parent seed=$s RC=$? $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"compile_requests_in_window": {[^}]*}' $log | tail -1)"
  grep -c '^compiled:' $log; grep '^compiled:.*MISS' $log | tail -5
done
