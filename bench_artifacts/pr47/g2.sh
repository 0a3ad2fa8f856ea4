# four chips: the sharded train cell from _proof, one untraced pair (parent, change)
export PR47_CHANGE=_proof
bash bench_artifacts/pr47/cells.sh g2 train-1.5b-fsdp4 1 4700200000 0
