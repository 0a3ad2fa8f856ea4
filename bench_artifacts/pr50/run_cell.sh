#!/bin/bash
# chiprun -- bash bench_artifacts/pr50/run_cell.sh <tag> <trace 0|1> <seed> [<seed> ...]
# PR50_RUN="python3 bench_artifacts/pr47/name_compiles.py" names every program compiled;
# PR50_RUN="python3 bench_artifacts/pr50/lower_precision.py state" is a must-fail reading.
# The new cell at each seed in turn over ONE uncapped compile cache of the
# call's own (the first run cold, the rest warm); each run's whole output to
# chiprun_out/pr50_<tag>_<seed>.log, its last line echoed.
tag=$1; trace=$2; shift 2
# (PR50_SHARED_CACHE=1: the machine's own capped cache, which the next call finds again)
if [ -z "$PR50_SHARED_CACHE" ]; then
  export JAX_COMPILATION_CACHE_DIR=/tmp/pr50_cache_$tag JAX_COMPILATION_CACHE_MAX_SIZE=-1
fi
mkdir -p chiprun_out
for seed in "$@"; do
  log=chiprun_out/pr50_${tag}_${seed}.log
  ${PR50_RUN:-python3 benchmark/run.py} --workload ${PR50_CELL:-rollout-jamba2-reasoning} --seed $seed --seconds 51 --trace $trace > $log 2>&1
  echo "== seed $seed rc=$? $(grep -c . $log) lines"
  grep -E "^note: .*(compile_requests_in_window|memory_peak_bytes_by_stage)" $log | cut -c1-600
  grep -E "first_setup|setup" $log | tail -2 | cut -c1-300
  tail -n 1 $log | cut -c1-1500
done
du -sh $JAX_COMPILATION_CACHE_DIR
