#!/bin/bash
# calls c5 and c7 (`bash c5.sh [f8pool] <cell>:<seed> ...`): the neighbours, parent (`_parent/`: `git archive 1071463 | tar -x -C _parent`) against
# change, one seed a pair; a cache a side, since a program with a Pallas kernel carries its path
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
export JAX_COMPILATION_CACHE_MAX_SIZE=-1
pair() { # cell, seed
  for side in parent change; do
    dir=.; [ $side = parent ] && dir=_parent
    (cd $dir && JAX_COMPILATION_CACHE_DIR=/tmp/pr38_cache_$side python3 benchmark/run.py \
      --workload $1 --seconds 51 --seed $2 --trace 0) > $OUT/pr38_c5_$1_$side.log 2>&1
    echo "$1 $side RC=$?" | tee -a $OUT/pr38_c5_$1_$side.log
    grep -E "^\{|Traceback|Error" $OUT/pr38_c5_$1_$side.log | cut -c1-700 | tail -2
  done
}
for spec in "$@"; do
  if [ $spec = f8pool ]; then  # the must-fail reading of the pool's rows, on the final kernel
    JAX_COMPILATION_CACHE_DIR=/tmp/pr38_cache_change python3 bench_artifacts/pr38/lower_precision.py pool \
      --workload rollout-dsv2-longctx --seconds 51 --seed 2750000029 --trace 0 > $OUT/pr38_c5_f8pool.log 2>&1
    echo "f8pool RC=$?" | tee -a $OUT/pr38_c5_f8pool.log
    grep -E "^\{|Traceback|Error" $OUT/pr38_c5_f8pool.log | cut -c1-400 | tail -2
  else
    pair ${spec%%:*} ${spec##*:}
  fi
done
