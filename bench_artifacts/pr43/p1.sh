#!/bin/bash
# call p1 (one chip): the committed files alone (`git archive $(git write-tree) | tar -x -C _proof`):
# train-0.5b-gsm8k from the archive, a first (cold) run, two untraced pairs with the parent (_parent/), a traced run
mkdir -p chiprun_out
test -e _proof/.git && echo "_proof is not an archive"
cell=train-0.5b-gsm8k
run() { # side, root, seed, trace
  log=$PWD/chiprun_out/pr43_p1_${cell}_$1_$3_t$4.log
  (cd $2 && python3 benchmark/run.py --workload $cell --seed $3 --seconds 51 --trace $4 > $log 2>&1)
  echo "$cell $1 seed=$3 trace=$4 RC=$? $(grep -o '"train_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o 'compile cache over the run.*' $log | cut -c1-120)"
}
run archive_cold _proof 4300002001 0
run parent_cold _parent 4300002001 0
run archive _proof 4300002038 0; run parent _parent 4300002038 0
run parent _parent 2147484075 0; run archive _proof 2147484075 0
run archive _proof 4300002112 1
grep -h '^{' chiprun_out/pr43_p1_${cell}_archive_4300002112_t1.log | tail -1 | cut -c1-3000
