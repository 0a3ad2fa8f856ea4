#!/bin/bash
# the rollout cells that run flash in their prefill, parent against change:
# run_rollout.sh <tag> <seed>
tag=$1; seed=$2
out=/root/repo/chiprun_out
run() { # cell side trace
  dir=/root/repo; [ "$2" = parent ] && dir=/root/repo/_parent
  (cd $dir && python3 benchmark/run.py --workload $1 --seed $seed --seconds 51 --trace $3 2> $out/${tag}_$1_$2_t$3.err | tail -1 > $out/${tag}_$1_$2_t$3.json)
  python3 - "$out/${tag}_$1_$2_t$3.json" "$1 $2 trace $3" <<'PY'
import json, sys
d = json.load(open(sys.argv[1])); m = d["metrics"]
keep = ("rollout_tokens_per_s", "setup_s", "prefill_device_ms.rollout", "chunk_device_ms.rollout", "device_idle_pct.rollout")
print(sys.argv[2], "correct", d["correct"], {k: round(m[k]["value"], 2) for k in keep if k in m}, flush=True)
PY
}
run rollout-1.5b-gsm8k parent 0; run rollout-1.5b-gsm8k change 0
run rollout-1.5b-gsm8k change 1; run rollout-1.5b-gsm8k parent 1
run rollout-kexaone-mixedlen parent 1; run rollout-kexaone-mixedlen change 1
