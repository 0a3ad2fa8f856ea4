#!/bin/bash
# call t2: the cell untraced with every compile named (the prefill's scan now a kernel, the
# warm-up explicit by wave size), then traced; the machine's own compile cache
export PR50_SHARED_CACHE=1
PR50_RUN="python3 bench_artifacts/pr47/name_compiles.py" bash bench_artifacts/pr50/run_cell.sh t2 0 5000000303
grep -E "^compiled: " chiprun_out/pr50_t2_5000000303.log | awk '{print $2, $3, $4, $5, $6}' > chiprun_out/pr50_t2_compiles.txt
echo "last programs compiled:"; tail -n 6 chiprun_out/pr50_t2_compiles.txt
grep -E "prefill_programs" chiprun_out/pr50_t2_5000000303.log | cut -c1-700
bash bench_artifacts/pr50/run_cell.sh t2 1 5000000404
grep -E "traced_work|traced_fields" chiprun_out/pr50_t2_5000000404.log | cut -c1-1200
