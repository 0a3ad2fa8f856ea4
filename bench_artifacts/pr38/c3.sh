#!/bin/bash
# call c3: the latent kernel alone (tools/tpu_smoke.py's new cases, then the cell's shape), three
# more seeds of the cell, and the two must-fail readings under the comparison as it now stands
mkdir -p chiprun_out
export JAX_COMPILATION_CACHE_DIR=/tmp/pr38_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
run() { # name, then the command
  name=$1; shift
  "$@" > chiprun_out/pr38_$name.log 2>&1
  echo "$name RC=$?" | tee -a chiprun_out/pr38_$name.log
  grep -E "^\{|^OK|^FAIL|Traceback|Error" chiprun_out/pr38_$name.log | cut -c1-1500 | tail -8
}
ARGS="--workload rollout-dsv2-longctx --seconds 51"
run c3_kernel python3 bench_artifacts/pr38/kernel_alone.py
run c3_s1 python3 benchmark/run.py $ARGS --seed 2147483659 --trace 0
run c3_s2 python3 benchmark/run.py $ARGS --seed 3100000007 --trace 0
run c3_s3 python3 benchmark/run.py $ARGS --seed 2600000011 --trace 0
run c3_f8weights python3 bench_artifacts/pr38/lower_precision.py weights $ARGS --seed 2900000017 --trace 0
run c3_f8pool python3 bench_artifacts/pr38/lower_precision.py pool $ARGS --seed 2400000023 --trace 0
