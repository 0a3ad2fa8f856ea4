#!/bin/bash
# usage: cells.sh <call tag> <cell> <pairs> <first seed> [clock]
# <pairs> untraced pairs of <cell>, parent (_parent/) and change, a seed a pair, in the order parent, change,
# change, parent, ...; then one traced pair at a seed of its own. With "clock" the parent's untraced runs go
# through bench_artifacts/pr43/phase_clock.py (benchmark/run.py with the host clock printed a phase).
tag=$1; cell=$2; pairs=$3; seed=$4; clock=$5
mkdir -p chiprun_out
run() { # side, root, seed, trace
  log=$PWD/chiprun_out/pr43_${tag}_${cell}_$1_$3_t$4.log
  if [ "$clock" = clock ] && [ $4 = 0 ] && [ $1 = parent ]; then
    python3 bench_artifacts/pr43/phase_clock.py --root $2 --workload $cell --seed $3 --seconds 51 --trace $4 > $log 2>&1
  else
    (cd $2 && python3 benchmark/run.py --workload $cell --seed $3 --seconds 51 --trace $4 > $log 2>&1)
  fi
  echo "$cell $1 seed=$3 trace=$4 RC=$? $(grep -o '"train_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o 'compile cache over the run.*' $log | cut -c1-120)"
}
for i in $(seq 1 $pairs); do
  s=$((seed + 37 * i))
  if [ $((i % 2)) = 1 ]; then run parent _parent $s 0; run change . $s 0; else run change . $s 0; run parent _parent $s 0; fi
done
t=$((seed + 1000))
run change . $t 1; run parent _parent $t 1
for side in change parent; do
  echo "traced $side:"; grep -h '^{' chiprun_out/pr43_${tag}_${cell}_${side}_${t}_t1.log | tail -1 | cut -c1-6000
done
