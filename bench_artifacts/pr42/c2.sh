#!/bin/bash
# call c2: rollout-1.5b-gsm8k traced, parent and change (the breakdown and the per-layer metrics), then
# untraced seeds of the change alone for the spread
mkdir -p chiprun_out
run() { # tag, root, cell, seed, trace
  log=$PWD/chiprun_out/pr42_c2_$3_$1_$4_t$5.log
  (cd $2 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $log 2>&1)
  echo "$3 $1 seed=$4 trace=$5 RC=$? $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o 'compile cache over the run.*' $log)"
}
D=rollout-1.5b-gsm8k
run warmup_parent _parent $D 4200000301 0
run warmup_change . $D 4200000301 0
run parent _parent $D 4200000311 1
run change . $D 4200000311 1
grep -h '^{' chiprun_out/pr42_c2_${D}_*_4200000311_t1.log | cut -c1-6000
for seed in 4200000323 4200000331 2147484211 4200000347; do run change . $D $seed 0; done
grep -h "paged kernel:" chiprun_out/pr42_c2_${D}_change_*.log | tail -2
