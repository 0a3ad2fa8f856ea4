#!/bin/bash
# call c3 (a cell a call: `bash c3.sh <cell>`): the two mixed-length cells whose kernel takes a group (Qwen3-Next
# 4 pages, K-EXAONE 2), parent and change: a cold run each that fills a cache of the call's own (the tool's is
# capped under Qwen3-Next's programs), then two warm pairs parent, change, change, parent, then the change traced
mkdir -p chiprun_out
export JAX_COMPILATION_CACHE_DIR=/tmp/pr42_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
run() { # tag, root, cell, seed, trace
  log=$PWD/chiprun_out/pr42_c3_$3_$1_$4_t$5.log
  (cd $2 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $log 2>&1)
  echo "$3 $1 seed=$4 trace=$5 RC=$? $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o 'compile cache over the run.*' $log)"
}
for cell in "$@"; do
  run cold_parent _parent $cell 4200000501 0
  run cold_change . $cell 4200000501 0
  run parent _parent $cell 4200000511 0; run change . $cell 4200000511 0
  run change . $cell 4200000523 0; run parent _parent $cell 4200000523 0
  run change . $cell 4200000537 1
  grep -h '^{' chiprun_out/pr42_c3_${cell}_change_4200000537_t1.log | cut -c1-4000
  grep -h "paged kernel:" chiprun_out/pr42_c3_${cell}_change_*.log | tail -1
done
du -sh /tmp/pr42_cache
