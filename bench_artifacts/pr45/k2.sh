#!/bin/bash
# calls k2..: the new cell on the change over one compile cache: `bash k2.sh <tag> <seed>:<trace> ...`
mkdir -p ${PR45_OUT:-chiprun_out}
OUT=${PR45_OUT:-$PWD/chiprun_out}  # (PR45_OUT: where to write when run from an unpacked archive)
export JAX_COMPILATION_CACHE_DIR=${TMPDIR:-/tmp}/pr45_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=rollout-kimilinear-mixedlen
tag=$1; shift
n=0
for spec in "$@"; do  # <seed>:<trace>[:<what to lower>]
  n=$((n + 1))
  IFS=: read seed trace lower <<< "$spec"
  cmd="python3 benchmark/run.py"
  [ -n "$lower" ] && cmd="python3 bench_artifacts/pr45/lower_precision.py $lower"
  $cmd --workload $CELL --seed $seed --seconds 51 --trace $trace > $OUT/pr45_${tag}_cell_$n.log 2>&1
  echo "cell run $n seed=$seed trace=$trace ${lower:+lowered: $lower} RC=$?"
  grep -E "^\{|Traceback|Error" $OUT/pr45_${tag}_cell_$n.log | cut -c1-7000 | tail -2
  if [ "$trace" = 1 ]; then
    python3 tools/trace_report.py .bench_work/$CELL/trace --top 25 > $OUT/pr45_${tag}_trace_$n.txt 2>&1
  fi
done
