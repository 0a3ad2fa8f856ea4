#!/bin/bash
# call f4 (one chip): why a dispatch is not held where the replay said it would be: a timeline run of the change
# in Jamba2's and Kimi-Linear's cells (`no hold` events: the condition that said so), caches of the call's own
mkdir -p chiprun_out
for cell in "$@"; do
  export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_cache/pr51_$cell JAX_COMPILATION_CACHE_MAX_SIZE=-1
  PR51_TIMELINE=$PWD/chiprun_out/pr51_f4_${cell}_timeline.jsonl python3 bench_artifacts/pr51/run_cell.py \
    --workload $cell --seed 5100012345 --seconds 51 --trace 0 > chiprun_out/pr51_f4_$cell.log 2>&1
  echo "$cell RC=$? $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' chiprun_out/pr51_f4_$cell.log | tail -1) $(grep -o '"correct": [a-z]*' chiprun_out/pr51_f4_$cell.log | tail -1)"
  python3 bench_artifacts/pr51/timeline_table.py chiprun_out/pr51_f4_${cell}_timeline.jsonl | tail -8
  grep '"no hold"' chiprun_out/pr51_f4_${cell}_timeline.jsonl | tail -45
done
