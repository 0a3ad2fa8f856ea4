"""READ, not repair: a rollout cell's own command (`benchmark/run.py` of
`--root`) run in this process with two counters more in the window's deltas
(the `counters` note), so that parent and change are read alike:

    python bench_artifacts/pr48/run_cell.py --root _parent \
        --workload rollout-olmoe-gsm8k --seed <n> --seconds 51 --trace 0

- `live_slots_dispatched_total`: the live slots of every chunk dispatched (the
  `active=` of `decode/dispatch_chunk` after the saturation mask, summed), so
  live slots a chunk is this over `chunks_dispatched_total`;
- `slots_handed_over_total`, where the engine has it (ISSUE 48): over the
  window's admissions (`prefills_total` + `prefix_forks_total` +
  `prefix_inplace_total` + `suffix_prefills_total`) it is the share of
  admissions that took a spent slot.

The benchmark's notes carry only its own `COUNTERS`; this wraps
`harness.engine_counters` and the engine's `_dispatch_chunk` / `get_metrics`
in memory. Nothing of the run is changed: one integer sum a chunk."""

import os
import runpy
import sys


def main():
    argv = sys.argv[1:]
    root = os.getcwd()
    if "--root" in argv:
        i = argv.index("--root")
        root = os.path.abspath(argv[i + 1])
        del argv[i:i + 2]
    os.chdir(root)
    sys.path.insert(0, root)
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from benchmark.lib import harness

    dispatch, metrics, deltas = (JaxDecodeEngine._dispatch_chunk, JaxDecodeEngine.get_metrics,
                                 harness.engine_counters)
    live = {"slots": 0}

    def dispatch_chunk(self, active):
        rec = dispatch(self, active)
        if rec is not None:
            live["slots"] += int(rec.active.sum())
        return rec

    def get_metrics(self):
        return {**metrics(self), "live_slots_dispatched_total": live["slots"]}

    def engine_counters(m0, m1, names, decode_config):
        more = [k for k in ("live_slots_dispatched_total", "slots_handed_over_total")
                if k in m1 and k not in names]
        return deltas(m0, m1, tuple(names) + tuple(more), decode_config)

    JaxDecodeEngine._dispatch_chunk = dispatch_chunk
    JaxDecodeEngine.get_metrics = get_metrics
    harness.engine_counters = engine_counters
    sys.argv = [os.path.join(root, "benchmark", "run.py")] + argv
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
