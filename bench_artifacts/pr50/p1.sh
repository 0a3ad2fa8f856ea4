#!/bin/bash
# call p1: the committed files alone (`git archive $(git write-tree) | tar -x -C _proof`): the new cell
# untraced and traced from _proof/, over one uncapped compile cache of the call's own (the first cold)
cd _proof
PR50_OUT=../chiprun_out bash bench_artifacts/pr50/run_cell.sh p1 0 5000007101
bash bench_artifacts/pr50/run_cell.sh p1 1 5000007202
cp chiprun_out/pr50_p1_*.log ../chiprun_out/
