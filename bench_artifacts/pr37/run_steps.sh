#!/bin/bash
# per-step seconds of a cell: run_steps.sh <cell> <tag> <side> <seed>...
cell=$1; tag=$2; side=$3; shift 3
out=/root/repo/chiprun_out
dir=/root/repo; [ "$side" = parent ] && dir=/root/repo/_parent
for s in "$@"; do
  (cd $dir && python3 benchmark/run.py --workload $cell --seed $s --seconds 51 --trace 0 2>/dev/null | grep -v "^note: {\"checks" > $out/${tag}_${side}_${s}.out)
  grep -o '"step_seconds": \[[^]]*\]' $out/${tag}_${side}_${s}.out | cut -c1-700
  tail -1 $out/${tag}_${side}_${s}.out | cut -c1-200
done
