#!/bin/bash
# warm set-up of train-0.5b-gsm8k by phase (bench_artifacts/pr43/phase_clock.py), parent, change, change, parent
mkdir -p chiprun_out
i=0
for side in parent change change parent; do
  i=$((i + 1)); root=_parent; [ $side = change ] && root=${PR47_CHANGE:-.}
  log=chiprun_out/pr47_c3_${i}_${side}.log
  python3 bench_artifacts/pr43/phase_clock.py --root $root --workload train-0.5b-gsm8k --seed $((4700005000 + i)) --seconds 51 --trace 0 > $log 2>&1
  echo "$i $side RC=$? $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"train_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o 'compile cache over the run[^"]*' $log | cut -c28-90)"
  grep '^phase:' $log
done
