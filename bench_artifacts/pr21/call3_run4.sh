#!/usr/bin/env bash
# Four-chip host session: partition probe, colocated chip_smoke, decoupled launcher.
set -u
mkdir -p chiprun_out
python - <<'PY'
import os; print("host cores", os.cpu_count()); print(sorted(k for k in os.environ if "TPU" in k or "JAX" in k or "XLA" in k))
PY
ls /dev | grep -iE "accel|vfio" | head
echo "##### PROBE A B C"
python _scratch/probe4.py A B C 2>&1 | tee chiprun_out/probe4.log
A_OK=$(awk '/=== variant A/{f=1} /=== variant B/{f=0} f && /CHILD_OK/{n++} END{print n+0}' chiprun_out/probe4.log)
B_OK=$(awk '/=== variant B/{f=1} /=== variant C/{f=0} f && /CHILD_OK/{n++} END{print n+0}' chiprun_out/probe4.log)
echo "A_OK=$A_OK B_OK=$B_OK"
echo "##### CHIP_SMOKE colocated on 4 chips"
timeout 600 python chip_smoke.py 2>&1 | tee chiprun_out/smoke_4chip.log | tail -75
echo "SMOKE4_RC=${PIPESTATUS[0]}"
if [ "$A_OK" != "3" ] && [ "$B_OK" = "3" ]; then
  echo "##### using x-pair bounds 2,1,1 for two-chip processes"
  sed -i 's/2: "1,2,1"/2: "2,1,1"/' areal_tpu/launcher/local.py
fi
if [ "$A_OK" = "3" ] || [ "$B_OK" = "3" ]; then
  echo "##### LAUNCHER jax:d2t1+d2"
  timeout 600 python -m areal_tpu.launcher.local examples/gsm8k_grpo.py \
    --config examples/configs/qwen2.5_0.5b_grpo_smoke.yaml allocation_mode=jax:d2t1+d2 \
    cluster.fileroot=/root/repo/chiprun_out/launcher cluster.name_resolve.nfs_record_root=/tmp/nr4 2>&1 | tail -30
  echo "LAUNCHER_RC=${PIPESTATUS[0]}"
  L=chiprun_out/launcher/logs/qwen2.5-0.5b-grpo-smoke/run0
  for f in decode_server_0 decode_server_1 trainer_0; do
    echo "--- $f"; grep -hE "decode mesh|mesh built|global step|Traceback|Error|rror:|behave_imp_weight|rollout_version_m|grad_norm |grpo_actor/loss |update_weights|dcn weight push" $L/$f.log | cut -c1-300 | tail -40
  done
else
  echo "##### launcher skipped: the runtime did not split the host's chips"
fi
echo "##### PROBE D (two unrestricted processes)"
python _scratch/probe4.py D 2>&1 | tee -a chiprun_out/probe4.log
pkill -f decode_server || true
echo "##### DONE"
