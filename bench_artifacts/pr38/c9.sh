#!/bin/bash
# call c9 (after the refusal over tests/benchmark/test_bench_sdar.py, restored): the parent with this PR's benchmark files laid
# over it (`git archive HEAD | tar -x -C _parent; cp -r BENCHMARK.json benchmark tests/benchmark _parent/`) refuses the new cell
# at once and still gives a traced line in a cell it ran before
mkdir -p chiprun_out
OUT=$PWD/chiprun_out
cd _parent || exit 1
t0=$(date +%s)
python3 benchmark/run.py --workload rollout-dsv2-longctx --seed 2147483999 --seconds 51 --trace 0 > $OUT/pr38_c9_parent_new.log 2>&1
echo "PARENT_NEW_RC=$? after $(( $(date +%s) - t0 )) s" | tee -a $OUT/pr38_c9_parent_new.log
tail -3 $OUT/pr38_c9_parent_new.log | cut -c1-600
python3 benchmark/run.py --workload rollout-1.5b-gsm8k --seed 2911000051 --seconds 51 --trace 1 > $OUT/pr38_c9_parent_old_traced.log 2>&1
echo "PARENT_OLD_TRACED_RC=$?" | tee -a $OUT/pr38_c9_parent_old_traced.log
grep -E "^\{|Traceback|Error" $OUT/pr38_c9_parent_old_traced.log | cut -c1-2500 | tail -2
