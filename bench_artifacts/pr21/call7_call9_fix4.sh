#!/usr/bin/env bash
# Four-chip host, final tree from `git archive`: the decoupled launcher run
# (two one-chip servers, a two-chip trainer, weights over the DCN push).
set -u
OUT=/root/repo/chiprun_out/fix_launcher3; mkdir -p $OUT
cd /root/repo/_scratch/proof
timeout 700 python -m areal_tpu.launcher.local examples/gsm8k_grpo.py \
  --config examples/configs/qwen2.5_0.5b_grpo_smoke.yaml allocation_mode=jax:d2t1+d2 \
  cluster.fileroot=$OUT cluster.name_resolve.nfs_record_root=/tmp/nr4 2>&1 | tee $OUT/launcher.log | grep -vE "hugepages|warnings.warn" | tail -14
echo "LAUNCHER_RC=${PIPESTATUS[0]}"
L=$OUT/logs/qwen2.5-0.5b-grpo-smoke/run0
for f in decode_server_0 decode_server_1 trainer_0; do
  echo "--- $f"; grep -hE "decode mesh|mesh built|device\(s\) visible|global step|Traceback|rror:|behave_imp_weight|grad_norm |grpo_actor/loss |dcn weight push|scratch" $L/$f.log | cut -c1-260 | tail -24
done
echo "--- weight versions that episodes were generated under (generated/<version>/):"
for d in $L/generated/*; do echo "v$(basename $d): $(ls $d | wc -l) episode files"; done
ps -ef | grep -c "[d]ecode_server" ; pkill -f decode_server || true
echo DONE
