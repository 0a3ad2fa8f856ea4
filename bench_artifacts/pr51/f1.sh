#!/bin/bash
# call f1 (one chip): the dense cell on the final code: one timeline run of the change (what ends each hold,
# which call of an admission waits for the device), then untraced pairs and a traced pair
mkdir -p chiprun_out
PR51_TIMELINE=$PWD/chiprun_out/pr51_f1_timeline.jsonl python3 bench_artifacts/pr51/run_cell.py \
  --workload rollout-1.5b-gsm8k --seed 5100004411 --seconds 51 --trace 0 > chiprun_out/pr51_f1_tl.log 2>&1
echo "timeline run RC=$? $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' chiprun_out/pr51_f1_tl.log | tail -1)"
grep "^hold:" chiprun_out/pr51_f1_tl.log
python3 bench_artifacts/pr51/timeline_table.py chiprun_out/pr51_f1_timeline.jsonl | tail -12
bash bench_artifacts/pr51/cells.sh f1 rollout-1.5b-gsm8k ${1:-3} 5100005000 ${2:-1}
