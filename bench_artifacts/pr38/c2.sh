#!/bin/bash
# call c2: the cell as it is (cold), the reference at float8 weights (warm), the pool at float8 rows
mkdir -p chiprun_out
export JAX_COMPILATION_CACHE_DIR=/tmp/pr38_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
run() { # name, then the command
  name=$1; shift
  "$@" > chiprun_out/pr38_$name.log 2>&1
  echo "$name RC=$?" | tee -a chiprun_out/pr38_$name.log
  grep -E "^\{|not_correct|checks|Traceback|Error" chiprun_out/pr38_$name.log | cut -c1-3000 | tail -8
}
ARGS="--workload rollout-dsv2-longctx --seconds 51"
run c2_plain python3 benchmark/run.py $ARGS --seed 3000000019 --trace 0
run c2_f8weights python3 bench_artifacts/pr38/lower_precision.py weights $ARGS --seed 2500000033 --trace 0
run c2_traced python3 benchmark/run.py $ARGS --seed 2200000077 --trace 1
run c2_f8pool python3 bench_artifacts/pr38/lower_precision.py pool $ARGS --seed 2700000041 --trace 0
du -sh /tmp/pr38_cache
