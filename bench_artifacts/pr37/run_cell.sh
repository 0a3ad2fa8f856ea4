#!/bin/bash
# usage: run_cell.sh <cell> <tag> <seedA> <seedB> <seedT>
cell=$1; tag=$2; a=$3; b=$4; t=$5
out=/root/repo/chiprun_out
run() { # side seed trace
  dir=/root/repo; [ "$1" = parent ] && dir=/root/repo/_parent
  (cd $dir && python3 benchmark/run.py --workload $cell --seed $2 --seconds 51 --trace $3 2> $out/${tag}_$1_$2_t$3.err | tail -1 > $out/${tag}_$1_$2_t$3.json)
  echo "$1 seed $2 trace $3 rc=$?: $(cut -c1-300 $out/${tag}_$1_$2_t$3.json)"
}
run parent $a 0; run change $a 0; run change $b 0; run parent $b 0
run change $t 1; run parent $t 1
