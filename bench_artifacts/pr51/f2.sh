#!/bin/bash
# calls f2.. (one chip each): the cells beside the claimed one, a pair or two each. Kimi-Linear's, Jamba2's and
# K-EXAONE's programs are over the tool's cache cap: a cache of the call's own under .jax_cache/.
# usage: f2.sh <cell>:<pairs>:<first seed>[:own] ...
for spec in "$@"; do
  IFS=: read cell pairs seed own <<< "$spec"
  if [ -n "$own" ]; then PR51_CACHE=pr51_$cell bash bench_artifacts/pr51/cells.sh f2 $cell $pairs $seed 0
  else bash bench_artifacts/pr51/cells.sh f2 $cell $pairs $seed 0; fi
done
