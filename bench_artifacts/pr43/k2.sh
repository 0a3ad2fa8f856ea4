#!/bin/bash
# call k2 (one chip): the smoke's flash cases, Step 0's table again with the module as handed in (and the
# stop rule's split: no_mxu, no_exp, no_mask), then train-0.5b-gsm8k: four untraced pairs and a traced pair
mkdir -p chiprun_out
python bench_artifacts/pr37/flash_smoke.py 2>&1 | grep -v -i warn > chiprun_out/pr43_k2_flash_smoke.log
echo "flash_smoke RC=$?"; grep -E "^(OK|FAIL|RESULT)" chiprun_out/pr43_k2_flash_smoke.log | cut -c1-220
python bench_artifacts/pr43/flash_pair.py 2>&1 | grep -v -i warn > chiprun_out/pr43_k2_flash_pair.log
echo "flash_pair RC=$?"; cut -c1-260 chiprun_out/pr43_k2_flash_pair.log
bash bench_artifacts/pr43/cells.sh k2 train-0.5b-gsm8k 4 4300000100
