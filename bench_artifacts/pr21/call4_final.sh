#!/usr/bin/env bash
set -u
OUT=/root/repo/chiprun_out; mkdir -p $OUT
cd /root/repo/_scratch/alone
python chip_smoke.py > $OUT/alone.log 2>&1; echo "ALONE_RC=$? (must be non-zero)"; tail -3 $OUT/alone.log
cd /root/repo/_scratch/proof
ls -a | head -40
set -o pipefail
python chip_smoke.py 2>&1 | tee $OUT/proof_cold.log | tail -12; echo "PROOF_COLD_RC=$?"
python chip_smoke.py 2>&1 | tee $OUT/proof_warm.log | tail -4; echo "PROOF_WARM_RC=$?"
echo "##### arith_grpo_smoke on the TPU"
timeout 300 python examples/gsm8k_grpo.py --config examples/configs/arith_grpo_smoke.yaml total_train_steps=1 cluster.fileroot=/tmp/arith 2>&1 | tee $OUT/arith_tpu.log | grep -E "Error|error|global step" | tail -5; echo "ARITH_RC=$?"
echo "##### non-default decode options end to end (not gating)"
python chip_smoke.py decode.kv_dtype=int8 decode.spec_decode=ngram 2>&1 | tee $OUT/opt_kvint8_spec.log | grep -E "check failed|importance weight|\"ok\"|Error" | tail -6; echo "OPT1_RC=$?"
python chip_smoke.py decode.weight_dtype=int8 2>&1 | tee $OUT/opt_wint8.log | grep -E "check failed|importance weight|\"ok\"|Error" | tail -6; echo "OPT2_RC=$?"
echo DONE
