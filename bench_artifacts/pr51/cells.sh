#!/bin/bash
# usage: cells.sh <call tag> <cell> <untraced pairs> <first seed> [traced pairs, default 0]
# Pairs of <cell>, parent (_parent/) against change (PR51_CHANGE, default the working tree), a seed a pair, in the
# order parent, change, change, parent, ...; then traced pairs at seeds of their own. Every run goes through
# run_cell.py (the cell's own command with the live slots a chunk and the scheduler's counters in its note).
# The compile cache is the machine's (JAX_COMPILATION_CACHE_DIR as the tool sets it) unless PR51_CACHE names one of
# the call's own, which is then uncapped (Qwen3-Next's, Kimi-Linear's, Jamba2's programs are over the tool's cap):
# a directory under the checkout's .jax_cache/ (which .gitignore lists), never a path outside the checkout.
tag=$1; cell=$2; pairs=$3; seed=$4; traced=${5:-0}; change=${PR51_CHANGE:-.}
here=$PWD; mkdir -p chiprun_out
if [ -n "$PR51_CACHE" ] || [ -z "$JAX_COMPILATION_CACHE_DIR" ]; then
  export JAX_COMPILATION_CACHE_DIR=$here/.jax_cache/${PR51_CACHE:-pr51} JAX_COMPILATION_CACHE_MAX_SIZE=-1
fi
run() { # side, root, seed, trace
  log=$here/chiprun_out/pr51_${tag}_${cell}_$1_$3_t$4.log
  python3 $here/bench_artifacts/pr51/run_cell.py --root $2 --workload $cell --seed $3 --seconds 51 --trace $4 > $log 2>&1
  rc=$?
  echo "$cell $1 seed=$3 trace=$4 RC=$rc $(grep -o '"rollout_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"end_to_end_in_traced_run": {[^}]*}' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o '"memory_peak_bytes": [0-9]*' $log | tail -1) $(grep -o '"compile_requests_in_window": {[^}]*}' $log | tail -1) $(grep -o '"tpot_p95": {[^}]*}' $log | tail -1)"
  python3 - $log <<'PY'
import json, re, sys
text = open(sys.argv[1]).read()
c = w = None
for line in text.splitlines():
    if line.startswith("note: ") and '"counters"' in line:
        note = json.loads(line[6:])
        c, w = note.get("counters", c), note.get("window_s", w)
if c:
    adm = sum(c.get(k, 0) for k in ("prefills_total", "prefix_forks_total", "prefix_inplace_total", "suffix_prefills_total"))
    chunks = max(c["chunks_dispatched_total"], 1)
    print("   counters: chunks %d, live slots a chunk %.2f of %d, occupancy %.1f%%, admissions %d, handed over %s, queue ms a request %.0f, discarded %d" % (
        c["chunks_dispatched_total"], c.get("live_slots_dispatched_total", 0) / chunks, c["max_running_requests"],
        100.0 * c["generated_tokens_total"] / (chunks * c["new_tokens_per_chunk"] * c["max_running_requests"]),
        adm, c.get("slots_handed_over_total", "-"), 1e3 * c["queue_secs_total"] / max(adm, 1),
        c["runahead_discarded_tokens_total"]))
    print("   hold: held %s of %d chunks, held admissions %s of %d, late %s, late in admit %s, device_idle_s %s; thread s of a %.1f s window: hold %s, wait_device %s, admit %s, dispatch %s, consume %s" % (
        c.get("chunks_held_total", "-"), c["chunks_dispatched_total"], c.get("held_admissions_total", "-"), adm,
        c.get("chunks_dispatched_late_total", "-"), c.get("chunks_late_in_admit_total", "-"), c.get("device_idle_s", "-"), w or 0.0,
        *(("%.2f" % c[k]) if k in c else "-" for k in ("sched_hold_secs_total", "sched_wait_device_secs_total",
          "sched_admit_secs_total", "sched_dispatch_secs_total", "sched_consume_secs_total"))))
m = re.findall(r'^hold: .*$', text, re.M)
if m: print("   " + m[-1][:600])
m = re.findall(r'"traced_work": {[^}]*}', text)
if m: print("   " + m[-1])
PY
}
for i in $(seq 1 $pairs); do
  s=$((seed + 37 * i))
  if [ $((i % 2)) = 1 ]; then run parent _parent $s 0; run change $change $s 0; else run change $change $s 0; run parent _parent $s 0; fi
done
for j in $(seq 1 $traced); do
  t=$((seed + 1000 * j))
  run parent _parent $t 1; run change $change $t 1
  for side in parent change; do
    echo "traced $side:"; grep -h '^{' chiprun_out/pr51_${tag}_${cell}_${side}_${t}_t1.log | tail -1 | cut -c1-5000
  done
done
