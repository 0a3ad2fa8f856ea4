#!/usr/bin/env bash
# Full-suite runner for a single dev box: fast gate first, then the slow
# tier in SERIAL batches (parallel heavy batches starve each other into
# timeouts here — see tests/README.md). Exit 0 iff everything passed.
set -u
cd "$(dirname "$0")/.."

PYTEST=(python -m pytest -q -p no:cacheprovider)
fail=0
slow_batch_files=""

run() {
  echo "=== ${*}"
  local t0=$SECONDS
  "${PYTEST[@]}" "$@" || fail=1
  echo "    (batch took $((SECONDS - t0))s)"
}

run_slow() {
  slow_batch_files="$slow_batch_files $(printf '%s\n' "$@" | grep '^tests/')"
  run "$@"
}

# Fast gate (~10 min)
run tests/ -m "not slow"

# Slow batches, serial, grouped by resource profile (~12 min total).
run_slow tests/test_grpo_e2e.py tests/test_grpo_learning.py -m slow
run_slow tests/test_multiprocess.py tests/test_weight_transfer.py tests/test_rpc.py -m slow
run_slow tests/test_pipeline_pp.py tests/test_moe.py tests/test_ring_attention.py -m slow
run_slow tests/test_jax_decode.py tests/test_decode_stress.py tests/test_kv_pool.py -m slow
run_slow tests/test_model_families.py tests/test_model_qwen2.py tests/test_qwen2_vl.py -m slow
run_slow tests/test_flash_attention.py tests/test_chunked_attention.py -m slow
run_slow tests/test_jax_engine.py tests/test_ppo_actor.py tests/test_critic_rw.py \
    tests/test_lora.py tests/test_aent.py tests/test_hbm.py -m slow
run_slow tests/test_examples_smoke.py tests/test_local_launcher.py \
    tests/test_controllers.py -m slow

# Completeness guard: every slow-marked test file must be in some batch
# above — a new slow file silently missing from the batches must not let
# this runner print ALL GREEN.
missing=$(
  "${PYTEST[@]}" tests/ -m slow --collect-only -q 2>/dev/null \
    | sed -n 's/^\(tests\/[^:]*\)::.*/\1/p' | sort -u \
    | grep -F -x -v -f <(printf '%s\n' $slow_batch_files | sort -u) || true
)
if [ -n "$missing" ]; then
  echo "FAILED: slow-marked test files missing from every batch:"
  echo "$missing"
  fail=1
fi

if [ "$fail" -ne 0 ]; then
  echo "FAILED: at least one batch had failures"
  exit 1
fi
echo "ALL GREEN"
