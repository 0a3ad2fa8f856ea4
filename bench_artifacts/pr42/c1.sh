#!/bin/bash
# call c1: the smoke's paged cases; the kernel alone at the cells' shapes (parent under _parent/ and
# groups 1-8); then Step 0: rollout-1.5b-gsm8k by phase, parent and change, one cold run each and
# four warm pairs alternating (parent, change, change, parent, ...), a seed a pair
mkdir -p chiprun_out
env | grep -i "JAX_COMP"
python bench_artifacts/pr42/smoke_paged.py > chiprun_out/pr42_s1_smoke_paged.log 2>&1
echo "smoke_paged RC=$?"; grep -E "^(OK|FAIL|RESULT)" chiprun_out/pr42_s1_smoke_paged.log | cut -c1-200
python bench_artifacts/pr42/kernel_groups.py > chiprun_out/pr42_k1_kernel_groups.log 2>&1
echo "kernel_groups RC=$?"; grep -v Warn chiprun_out/pr42_k1_kernel_groups.log | tail -50
run() { # tag, root, seed, trace
  log=chiprun_out/pr42_c1_rollout-1.5b-gsm8k_$1_$3_t$4.log
  python bench_artifacts/pr42/phase_clock.py --root $2 --workload rollout-1.5b-gsm8k --seed $3 --seconds 51 --trace $4 > $log 2>&1
  echo "$1 seed=$3 RC=$? $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o 'compile cache over the run.*' $log)"
}
run parent_cold _parent 4200000002 0
run change_cold . 4200000002 0
for seed in 4200000041 4200000053; do
  run parent _parent $seed 0; run change . $seed 0
  seed2=$((seed + 100))
  run change . $seed2 0; run parent _parent $seed2 0
done
grep -h "^phase:" chiprun_out/pr42_c1_*_4200000041_t0.log | cut -c1-200
