#!/bin/bash
# call t4: the parent on the new cell's name (bare, and with this PR's benchmark files laid over it,
# as the driver does): each must fail at once; then the cell at six seeds over one uncapped compile
# cache of the call's own: the first run cold, five warm
echo "== the parent (_parent/) on the new cell's name"
( cd _parent; time python3 benchmark/run.py --workload rollout-jamba2-reasoning --seed 5 --seconds 51 --trace 0 2>&1 | tail -n 3 | cut -c1-300; echo "rc=${PIPESTATUS[0]}" )
rm -rf _scratch/overlay && mkdir -p _scratch/overlay && cp -r _parent/. _scratch/overlay/ && cp BENCHMARK.json _scratch/overlay/ && cp -r benchmark/. _scratch/overlay/benchmark/
echo "== the parent with this PR's benchmark files laid over it"
( cd _scratch/overlay; time python3 benchmark/run.py --workload rollout-jamba2-reasoning --seed 5 --seconds 51 --trace 0 2>&1 | tail -n 3 | cut -c1-400; echo "rc=${PIPESTATUS[0]}" )
bash bench_artifacts/pr50/run_cell.sh t4 0 5000001101 5000001202 5000001303 5000001404 5000001505 5000001606
