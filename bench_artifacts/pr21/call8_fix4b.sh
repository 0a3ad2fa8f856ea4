#!/usr/bin/env bash
# Four-chip host: why did the two-chip trainer exit 1? Probe the pairs, then
# rerun the launcher from the final tree and keep libtpu's log for a failure.
set -u
OUT=/root/repo/chiprun_out/fix_launcher2; mkdir -p $OUT
python _scratch/probe5.py 2>&1 | tee /root/repo/chiprun_out/probe5.log
rm -rf /tmp/tpu_logs
cd /root/repo/_scratch/proof
PYTHONFAULTHANDLER=1 timeout 700 python -m areal_tpu.launcher.local examples/gsm8k_grpo.py \
  --config examples/configs/qwen2.5_0.5b_grpo_smoke.yaml allocation_mode=jax:d2t1+d2 \
  cluster.fileroot=$OUT cluster.name_resolve.nfs_record_root=/tmp/nr4 2>&1 | tee $OUT/launcher.log | tail -8
RC=${PIPESTATUS[0]}; echo "LAUNCHER_RC=$RC"
L=$OUT/logs/qwen2.5-0.5b-grpo-smoke/run0
for f in decode_server_0 decode_server_1 trainer_0; do
  echo "--- $f"; grep -hE "decode mesh|mesh built|device\(s\) visible|global step|Traceback|rror:|behave_imp_weight|grad_norm |grpo_actor/loss |dcn weight push" $L/$f.log | cut -c1-260 | tail -24
done
echo "--- weight versions that episodes were generated under (generated/<version>/):"
for d in $L/generated/*; do echo "v$(basename $d): $(ls $d | wc -l) episode files"; done
if [ "$RC" != "0" ]; then
  echo "--- libtpu logs"; ls -la /tmp/tpu_logs | tail; for f in /tmp/tpu_logs/*; do echo "## $f"; grep -E "rror|ERROR|FATAL|Check|ounds|opology" $f | cut -c1-300 | tail -12; done
  mkdir -p $OUT/tpu_logs; for f in /tmp/tpu_logs/*; do tail -c 20000 $f > $OUT/tpu_logs/$(basename $f); done
fi
pkill -f decode_server || true
echo DONE
