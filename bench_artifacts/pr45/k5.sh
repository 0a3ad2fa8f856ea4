#!/bin/bash
# call k5: the committed files alone (`_proof/` = `git archive $(git write-tree) | tar -x -C _proof`): the new
# cell untraced, traced and with the latent rows at float8, all from the archive
export PR45_OUT=$PWD/chiprun_out
mkdir -p $PR45_OUT
cd _proof && bash bench_artifacts/pr45/k2.sh "$@"
