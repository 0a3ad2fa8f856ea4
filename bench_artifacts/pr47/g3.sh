# four chips, after g2 left both trees' programs in the machine's compile cache: change, then parent, one seed,
# for the warm set-up of each and a second pair
cell=train-1.5b-fsdp4; s=4700300074
for side in change parent; do
  root=_proof; [ $side = parent ] && root=_parent
  log=$PWD/chiprun_out/pr47_g3_${cell}_${side}_${s}_t0.log
  (cd $root && python3 benchmark/run.py --workload $cell --seed $s --seconds 51 --trace 0 > $log 2>&1)
  echo "$cell $side seed=$s RC=$? $(grep -o '"train_tokens_per_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"setup_s": {"value": [0-9.]*' $log | tail -1) $(grep -o '"correct": [a-z]*' $log | tail -1) $(grep -o '"compile_requests_in_window": {[^}]*}' $log | tail -1)"
  grep -o 'grad_step T=.*room\|the chip has .* in use.*\|grad_step T=.*refused.*' $log | sort | uniq -c
done
