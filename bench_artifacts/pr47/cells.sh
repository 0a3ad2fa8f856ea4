#!/bin/bash
# usage: cells.sh <call tag> <cell> <pairs> <first seed> [traced pairs, default 1]
# <pairs> untraced pairs of <cell>, parent (_parent/) and change (PR47_CHANGE, default the working tree), a seed
# a pair, in the order parent, change, change, parent, ...; then traced pairs at seeds of their own. Each line
# names the set each new jit_grad_step keeps (the engine's log) and the chip's peak.
tag=$1; cell=$2; pairs=$3; seed=$4; traced=${5:-1}; change=${PR47_CHANGE:-.}
mkdir -p chiprun_out
run() { # side, root, seed, trace
  log=$PWD/chiprun_out/pr47_${tag}_${cell}_$1_$3_t$4.log
  (cd $2 && python3 benchmark/run.py --workload $cell --seed $3 --seconds 51 --trace $4 > $log 2>&1)
  echo "$cell $1 seed=$3 trace=$4 RC=$? $(grep -o '"train_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o '"memory_peak_bytes": [0-9]*' $log | tail -1) $(grep -o '"compile_requests_in_window": {[^}]*}' $log | tail -1)"
  grep -o 'grad_step T=.*room\|the chip has .* in use.*\|grad_step T=.*refused.*' $log | sort | uniq -c
}
for i in $(seq 1 $pairs); do
  s=$((seed + 37 * i))
  if [ $((i % 2)) = 1 ]; then run parent _parent $s 0; run change $change $s 0; else run change $change $s 0; run parent _parent $s 0; fi
done
for j in $(seq 1 $traced); do
  t=$((seed + 1000 * j))
  run change $change $t 1; run parent _parent $t 1
  for side in change parent; do
    echo "traced $side:"; grep -h '^{' chiprun_out/pr47_${tag}_${cell}_${side}_${t}_t1.log | tail -1 | cut -c1-7000
  done
done
