# one chip: the train cell from _proof (2 untraced pairs, 1 traced pair), the rollout control pair, the colocated loop
export PR47_CHANGE=_proof
bash bench_artifacts/pr47/cells.sh g1 train-0.5b-gsm8k 2 4700100000 1
bash bench_artifacts/pr47/cells.sh g1 rollout-1.5b-gsm8k 1 4700110000 0
(cd _proof && python bench_artifacts/pr47/loop_only.py > ../chiprun_out/pr47_g1_loop.log 2>&1; echo "loop RC=$?"; grep -v "^E[0-9]\|^W[0-9]\|^I[0-9]" ../chiprun_out/pr47_g1_loop.log | grep "declared a chip\|^step \|grad_step T=\|not in the account\|refused\|allocator at\|\"ok\"\|Error\|error" | cut -c1-400 | tail -40)
