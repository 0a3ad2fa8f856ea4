#!/bin/bash
# call f5 (one chip), on the final tree: the claimed cell from the COMMITTED files (`git archive $(git write-tree)`
# unpacked under _proof/) against the parent (_parent/), pairs in the order parent, change, change, parent; one more
# pair of the slot-bound control; then why a dispatch is not held in Jamba2's and Kimi-Linear's cells (f4.sh).
PR51_CHANGE=_proof bash bench_artifacts/pr51/cells.sh f5 rollout-1.5b-gsm8k ${1:-4} 5100013000 0
PR51_CHANGE=_proof bash bench_artifacts/pr51/cells.sh f5 rollout-olmoe-gsm8k 1 5100014000 0
bash bench_artifacts/pr51/f4.sh rollout-jamba2-reasoning rollout-kimilinear-mixedlen
