#!/bin/bash
# call t1: the cell traced with every compile named, then the precision probe
export PR50_SHARED_CACHE=1
PR50_RUN="python3 bench_artifacts/pr47/name_compiles.py" bash bench_artifacts/pr50/run_cell.sh t1 1 5000000202
grep -E "^compiled: " chiprun_out/pr50_t1_5000000202.log | awk '{print $2, $3, $4, $5, $6}' > chiprun_out/pr50_t1_compiles.txt
setup=$(grep -o '"setup_s": {"value": [0-9.]*' chiprun_out/pr50_t1_5000000202.log | tail -1 | grep -o '[0-9.]*$')
echo "setup_s=$setup; programs compiled after it:"; awk -v s="$setup" '{t=substr($1,3)+0; if (t > s+0.5) print}' chiprun_out/pr50_t1_compiles.txt | head -20
grep -E "traced_work|traced_fields" chiprun_out/pr50_t1_5000000202.log | cut -c1-1500
PROBE_VARIANTS=bf16,uz32 python3 bench_artifacts/pr50/precision_probe.py 1024 2>&1 | grep -E "variant|layer" | tee chiprun_out/pr50_t1_probe.txt | grep -E "variant|layer (0|6|7|13|20|21|27) "
