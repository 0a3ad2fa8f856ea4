#!/bin/bash
# call c6 (c4 again, after the kernel took groups of pages): the committed files alone (`git archive $(git write-tree) | tar -x -C _proof`): six
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
run c6_u1 2147483801 0
run c6_u2 3250000019 0
run c6_u3 2350000043 0
run c6_u4 2850000059 0
run c6_u5 3350000071 0
run c6_u6 2080000091 0
run c6_t1 2970000113 1
run c6_t2 2470000137 1
