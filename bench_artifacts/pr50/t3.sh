#!/bin/bash
# call t3: the kernels alone; the parent on the new cell's name (bare, and with this PR's benchmark
# files laid over it, as the driver does); the two must-fail readings
python3 bench_artifacts/pr50/kernel_alone.py 2>&1 | grep -v "^WARNING\|UserWarning\|warnings.warn" | tee chiprun_out/pr50_t3_kernel_alone.txt
echo "== the parent (_parent/) on the new cell's name"
( cd _parent && /usr/bin/time -f "%es rc=%x" python3 benchmark/run.py --workload rollout-jamba2-reasoning --seed 5 --seconds 51 --trace 0 2>&1 | tail -n 3 )
rm -rf _scratch/overlay && mkdir -p _scratch/overlay && cp -r _parent/. _scratch/overlay/ && cp BENCHMARK.json _scratch/overlay/ && cp -r benchmark/. _scratch/overlay/benchmark/
echo "== the parent with this PR's benchmark files laid over it"
( cd _scratch/overlay && /usr/bin/time -f "%es rc=%x" python3 benchmark/run.py --workload rollout-jamba2-reasoning --seed 5 --seconds 51 --trace 0 2>&1 | tail -n 4 | cut -c1-400 )
( cd _scratch/overlay && python3 benchmark/run.py --workload rollout-1.5b-gsm8k --seed 5000000999 --seconds 51 --trace 1 2>&1 | tail -n 1 | cut -c1-600 )
export PR50_SHARED_CACHE=1
PR50_RUN="python3 bench_artifacts/pr50/lower_precision.py state" bash bench_artifacts/pr50/run_cell.sh t3state 0 5000000505
grep -o '"checks": \[.*\], "parameters"' chiprun_out/pr50_t3state_5000000505.log | cut -c1-2500
PR50_RUN="python3 bench_artifacts/pr50/lower_precision.py weights" bash bench_artifacts/pr50/run_cell.sh t3weights 0 5000000606
grep -o '"checks": \[.*\], "parameters"' chiprun_out/pr50_t3weights_5000000606.log | cut -c1-2500
