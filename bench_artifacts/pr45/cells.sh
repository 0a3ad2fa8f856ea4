#!/bin/bash
# (PR 44's `cells.sh`, with the number of runs a cell as PR45_RUNS: 4, or 2 for the first pair alone)
# usage: cells.sh <call tag> <first seed> <cell> [<cell> ...]
# For each cell four untraced runs of benchmark/run.py, parent (_parent/) then change, change, parent, all
# from ONE path ($base/run: each tree is moved there for its run), so that a Mosaic kernel's serialised
# module, which carries the source lines of its Python, is the same text in both trees, and sharing ONE
# compile cache that starts empty: the parent's first run fills it, the change's first run says how many
# of its programs it finds there (jax._src.compiler's debug lines name each hit and miss), and the last
# two runs are both warm (setup_s against setup_s). A pair shares a seed.
tag=$1; seed=$2; shift 2
repo=$PWD; base=${PR45_BASE:-${TMPDIR:-/tmp}/pr45}
export JAX_COMPILATION_CACHE_DIR=$base/cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
mkdir -p chiprun_out $base/cache $base/change
cp -r _parent $base/parent
# the change is the working tree, or with PR45_CHANGE=_proof the unpacked `git archive $(git write-tree)`
(cd ${PR45_CHANGE:-.} && tar -c --exclude=./_parent --exclude=./chiprun_out --exclude=./.bench_work \
    --exclude=./.jax_cache --exclude=./_proof .) | tar -x -C $base/change
run() { # cell, n, side, seed, [names]
  log=$repo/chiprun_out/pr45_${tag}_$1_$2_$3.log
  mv $base/$3 $base/run
  (cd $base/run && ${5:+env JAX_DEBUG_LOG_MODULES=jax._src.compiler} \
     python3 benchmark/run.py --workload $1 --seed $4 --seconds 51 --trace 0 > $log 2>&1)
  rc=$?
  mv $base/run $base/$3
  echo "$1 run$2 $3 seed=$4 RC=$rc $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' $log | tail -1)" \
       "$(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1)" \
       "$(grep -o 'compile cache over the run.*' $log | cut -c1-160)"
  if [ -n "$5" ]; then
    echo "  found in the parent's cache: $(grep -c 'Persistent compilation cache hit for' $log);" \
         "not found: $(grep -c 'PERSISTENT COMPILATION CACHE MISS for' $log)"
    grep -o "PERSISTENT COMPILATION CACHE MISS for '[^']*'" $log | sort | uniq -c | sed 's/^/    miss /'
    grep -v 'jax._src.compiler\|Persistent compilation cache hit\|PERSISTENT COMPILATION CACHE MISS' $log > $log.tmp
    grep 'Persistent compilation cache hit for\|PERSISTENT COMPILATION CACHE MISS for' $log \
      | grep -o "\(hit\|MISS\) for '[^']*'" | sort | uniq -c > ${log%.log}.programs
    mv $log.tmp $log
  fi
}
for cell in "$@"; do
  run $cell 1 parent $seed
  run $cell 2 change $seed names
  runs="1_parent 2_change"
  if [ ${PR45_RUNS:-4} = 4 ]; then
    run $cell 3 change $((seed + 37))
    run $cell 4 parent $((seed + 37))
    runs="$runs 3_change 4_parent"
  fi
  for n in $runs; do
    echo "$cell $n:"; grep -h '^{' chiprun_out/pr45_${tag}_${cell}_$n.log | tail -1 | cut -c1-1800
  done
done
