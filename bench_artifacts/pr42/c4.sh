#!/bin/bash
# call c4: (a) the dense cell with the group capped at 4 pages (this tree under _proof/ with MAX_GROUP_PAGES = 4:
# the kernel alone read 124.5 us at 4 and 136.5 at 8 on a batch of 2.8 live columns a slot) against the rule's 8,
# two pairs after a cold run that fills its cache; (b) four more warm pairs of parent and change by phase
mkdir -p chiprun_out
D=rollout-1.5b-gsm8k
run() { # tag, root, seed
  log=chiprun_out/pr42_c4_${D}_$1_$3_t0.log
  python bench_artifacts/pr42/phase_clock.py --root $2 --workload $D --seed $3 --seconds 51 --trace 0 > $log 2>&1
  echo "$1 seed=$3 RC=$? $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o 'compile cache over the run.*' $log) $(grep -o 'walked in.*' $log)"
}
grep -n "^MAX_GROUP_PAGES" areal_tpu/ops/paged_attention.py _proof/areal_tpu/ops/paged_attention.py
run cold_change . 4200000601
run cold_four _proof 4200000601
run change . 4200000611; run four _proof 4200000611
run four _proof 4200000623; run change . 4200000623
for seed in 4200000641 4200000653; do
  run parent _parent $seed; run change . $seed
  seed2=$((seed + 100))
  run change . $seed2; run parent _parent $seed2
done
