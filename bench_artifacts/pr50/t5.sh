#!/bin/bash
# call t5: the two accepted hybrids that share `kv_pool.py`'s state shapes, `_conv_step` / `_gdn_conv` and the
# state pools' placement with the new model, parent (_parent/) against change, two pairs each (parent, change,
# change, parent), each cell over one uncapped compile cache of its own
PR50_CACHE=/tmp/pr50_q bash bench_artifacts/pr50/cells.sh t5 rollout-qwen3next-mixedlen 2 5000002000
PR50_CACHE=/tmp/pr50_k bash bench_artifacts/pr50/cells.sh t5 rollout-kimilinear-mixedlen 2 5000003000
