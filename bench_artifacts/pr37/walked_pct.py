"""`attn_live_block_pct` and `attn_walked_block_pct` of a train cell's steps (PR 37).

    chiprun [--chips 4] -- python bench_artifacts/pr37/walked_pct.py <cell> <seed>

Runs the cell through the benchmark's own entry point with a short window
and a spy on `JaxTrainEngine._attn_block_pcts` (the host's NumPy pass), and
prints what each `train_batch` put into its stats."""

import os
import runpy
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.chdir(ROOT)

from areal_tpu.engine.jax_engine import JaxTrainEngine  # noqa: E402

seen = []
plain = JaxTrainEngine._attn_block_pcts


def spy(self, mbs):
    out = plain(self, mbs)
    seen.append(out)
    # (the benchmark's entry point leaves through os._exit: say it now)
    print(f"train_batch call {len(seen)}: attn_live_block_pct {out[0]:.3f} "
          f"attn_walked_block_pct {out[1]:.3f}", flush=True)
    return out


JaxTrainEngine._attn_block_pcts = spy
sys.argv = ["benchmark/run.py", "--workload", sys.argv[1], "--seed", sys.argv[2],
            "--seconds", "6", "--trace", "0"]
try:
    runpy.run_path("benchmark/run.py", run_name="__main__")
except SystemExit:
    pass
