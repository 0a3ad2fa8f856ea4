#!/bin/bash
# call c4 (the kernel a page an iteration): the committed files alone (`git archive $(git write-tree) | tar -x -C _proof`): six
# untraced seeds and two traced runs of the new cell from that copy
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
export JAX_COMPILATION_CACHE_DIR=/tmp/pr38_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
cd _proof || exit 1
run() { # name, seed, trace
  python3 benchmark/run.py --workload rollout-dsv2-longctx --seconds 51 --seed $2 --trace $3 \
    > $OUT/pr38_$1.log 2>&1
  echo "$1 RC=$?" | tee -a $OUT/pr38_$1.log
  grep -E "^\{|Traceback|Error" $OUT/pr38_$1.log | cut -c1-1800 | tail -3
}
run c4_u1 2147483777 0
run c4_u2 3200000023 0
run c4_u3 2300000047 0
run c4_u4 2800000051 0
run c4_u5 3300000077 0
run c4_u6 2050000093 0
run c4_t1 2950000111 1
run c4_t2 2450000131 1
