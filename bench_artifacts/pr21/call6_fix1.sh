#!/usr/bin/env bash
# One chip, final tree from `git archive`: chip_smoke.py alone (must fail),
# then cold and warm from the unpacked archive.
set -u
OUT=/root/repo/chiprun_out; mkdir -p $OUT
cd /root/repo/_scratch/alone
python chip_smoke.py > $OUT/fix_alone.log 2>&1; echo "ALONE_RC=$? (must be non-zero)"; tail -2 $OUT/fix_alone.log
cd /root/repo/_scratch/proof
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-<unset>}"
set -o pipefail
python chip_smoke.py 2>&1 | tee $OUT/fix_proof_cold.log | tail -45; echo "PROOF_COLD_RC=$?"
python chip_smoke.py 2>&1 | tee $OUT/fix_proof_warm.log | tail -5; echo "PROOF_WARM_RC=$?"
echo DONE
