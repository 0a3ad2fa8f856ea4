#!/bin/bash
# call c1: the parent (with this PR's benchmark files laid over it) refuses the
# new cell at once; the change with the engine's default pool (slots x context)
mkdir -p chiprun_out
t0=$(date +%s)
(cd _parent && python3 benchmark/run.py --workload rollout-dsv2-longctx --seed 2147483999 --seconds 51 --trace 0) > chiprun_out/pr38_c1_parent.log 2>&1
echo "PARENT_RC=$? after $(( $(date +%s) - t0 )) s" | tee -a chiprun_out/pr38_c1_parent.log
tail -5 chiprun_out/pr38_c1_parent.log
python3 benchmark/run.py --workload rollout-dsv2-longctx --seed 3000000019 --seconds 51 --trace 0 > chiprun_out/pr38_c1_change.log 2>&1
echo "CHANGE_RC=$?" | tee -a chiprun_out/pr38_c1_change.log
grep -v "^$" chiprun_out/pr38_c1_change.log | tail -60
