#!/bin/bash
# rollout-1.5b-gsm8k traced, parent against change: run_prefill.sh <tag> <seed>
tag=$1; seed=$2
out=/root/repo/chiprun_out
for side in parent change; do
  dir=/root/repo; [ "$side" = parent ] && dir=/root/repo/_parent
  (cd $dir && python3 benchmark/run.py --workload rollout-1.5b-gsm8k --seed $seed --seconds 51 --trace 1 2> $out/${tag}_${side}.err | tail -1 > $out/${tag}_${side}.json)
  python3 - "$out/${tag}_${side}.json" "$side" <<'PY'
import json, sys
d = json.load(open(sys.argv[1])); m = d["metrics"]
print(sys.argv[2], "correct", d["correct"], {k: round(v["value"], 3) for k, v in m.items() if "prefill" in k or "chunk_device" in k or "idle" in k}, flush=True)
PY
done
