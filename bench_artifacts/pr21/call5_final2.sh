#!/usr/bin/env bash
set -u -o pipefail
OUT=/root/repo/chiprun_out; mkdir -p $OUT
cd /root/repo/_scratch/proof
python chip_smoke.py 2>&1 | tee $OUT/proof_final.log | tail -6; echo "PROOF_FINAL_RC=$?"
python chip_smoke.py decode.weight_dtype=int8 2>&1 | tee $OUT/opt_wint8.log | grep -E "check failed|param leaf|\"ok\"|Error" | tail -6; echo "OPT2_RC=$?"
