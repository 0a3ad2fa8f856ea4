#!/bin/bash
# call c5: the committed files alone (`git archive $(git write-tree) | tar -x -C _proof`): the dense cell traced and
# untraced from the archive; then the two rollout cells whose kernel keeps one page a group, a pair each of parent
# (_parent/) and archive (their chunk programs are text-equal: what can differ is the host's counting at dispatch)
mkdir -p chiprun_out
run() { # tag, root, cell, seed, trace
  log=$PWD/chiprun_out/pr42_c5_$3_$1_$4_t$5.log
  (cd $2 && python3 benchmark/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $log 2>&1)
  echo "$3 $1 seed=$4 trace=$5 RC=$? $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o 'compile cache over the run.*' $log) $(grep -o 'walked in.*' $log)"
}
test -e _proof/.git && echo "_proof is not an archive"
run archive_cold _proof rollout-1.5b-gsm8k 4200000801 0
run archive _proof rollout-1.5b-gsm8k 4200000811 1
grep -h '^{' chiprun_out/pr42_c5_rollout-1.5b-gsm8k_archive_4200000811_t1.log | cut -c1-1500
run archive _proof rollout-1.5b-gsm8k 2147484301 0
for cell in rollout-olmoe-gsm8k rollout-sdar-gsm8k; do
  run parent _parent $cell 4200000821 0; run archive _proof $cell 4200000821 0
done
