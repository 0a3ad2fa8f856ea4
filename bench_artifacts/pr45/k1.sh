#!/bin/bash
# call k1: the parent at the new cell (it must fail in seconds, `_parent/` = `git archive 404ab1f` with this
# PR's BENCHMARK.json and benchmark/ laid over it), the state kernels alone, then the new cell untraced and
# traced on the change over one compile cache.
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
export JAX_COMPILATION_CACHE_DIR=${TMPDIR:-/tmp}/pr45_cache JAX_COMPILATION_CACHE_MAX_SIZE=-1
CELL=rollout-kimilinear-mixedlen
t0=$(date +%s)
(cd _parent && timeout 600 python3 benchmark/run.py --workload $CELL --seed 3141592653 --seconds 51 --trace 0) \
  > $OUT/pr45_k1_parent.log 2>&1
echo "parent at $CELL: RC=$? after $(( $(date +%s) - t0 )) s"; tail -3 $OUT/pr45_k1_parent.log | cut -c1-400
python3 bench_artifacts/pr45/kernel_alone.py > $OUT/pr45_k1_kernels.log 2>&1
echo "kernels RC=$?"; grep -v "^W\|^I" $OUT/pr45_k1_kernels.log | tail -12
n=0
for spec in "$@"; do  # <seed>:<trace>
  n=$((n + 1))
  python3 benchmark/run.py --workload $CELL --seed ${spec%%:*} --seconds 51 --trace ${spec##*:} \
    > $OUT/pr45_k1_cell_$n.log 2>&1
  echo "cell run $n seed=${spec%%:*} trace=${spec##*:} RC=$?"
  grep -E "^\{|Traceback|Error" $OUT/pr45_k1_cell_$n.log | cut -c1-6000 | tail -2
done
