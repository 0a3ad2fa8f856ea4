"""READ, not repair: a rollout cell's own command (`benchmark/run.py` of
`--root`) run in this process with the scheduler's counters in the window's
deltas (the `counters` note), so that parent and change are read alike:

    python bench_artifacts/pr51/run_cell.py --root _parent \
        --workload rollout-1.5b-gsm8k --seed <n> --seconds 51 --trace 0

PR 48's `run_cell.py` with ISSUE 51's counters beside its two:

- `live_slots_dispatched_total`: the live slots of every chunk dispatched (the
  `active=` of `decode/dispatch_chunk` after the saturation mask, summed);
- `slots_handed_over_total` (ISSUE 48);
- `chunks_held_total`, `held_admissions_total`, `chunks_dispatched_late_total`,
  `chunks_late_in_admit_total`
  and the thread's `sched_hold_secs_total`, `sched_wait_device_secs_total`,
  `sched_admit_secs_total`, `sched_dispatch_secs_total` (ISSUE 51), where the
  engine has them (the parent has the last three), and `device_idle_s`.

The benchmark's notes carry only its own `COUNTERS`; this wraps
`harness.engine_counters` and the engine's `_dispatch_chunk` / `get_metrics`
in memory. Nothing of the run is changed: one integer sum a chunk. At the end
it prints the lead and the estimates the engine ended with (`hold: ...`).

`PR51_TIMELINE=<file>`: every call of the scheduler's steps (`_admit`,
`_dispatch_chunk`, `_consume_chunk`, `_wait_held`) with its host times, and of
the programs an admission enqueues (`_run_copies`, a fork's; a `decode/prefill`
span's) each call that took 20 ms or more with its number among the programs
enqueued since the last chunk went out: which call waits for the device.
`timeline_table.py` reads it."""

import os
import runpy
import sys

MORE = ("live_slots_dispatched_total", "slots_handed_over_total", "chunks_held_total",
        "held_admissions_total", "chunks_dispatched_late_total", "chunks_late_in_admit_total",
        "sched_hold_secs_total",
        "sched_wait_device_secs_total", "sched_admit_secs_total", "sched_dispatch_secs_total",
        "sched_consume_secs_total", "device_idle_s")


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

    dispatch, metrics, deltas, destroy = (
        JaxDecodeEngine._dispatch_chunk, JaxDecodeEngine.get_metrics, harness.engine_counters,
        JaxDecodeEngine.destroy)
    live = {"slots": 0}

    def dispatch_chunk(self, active):
        rec = dispatch(self, active)
        if rec is not None:
            live["slots"] += int(rec.active.sum())
        return rec

    def get_metrics(self):
        return {**metrics(self), "live_slots_dispatched_total": live["slots"]}

    def engine_counters(m0, m1, names, decode_config):
        more = [k for k in MORE if k in m1 and k not in names]
        return deltas(m0, m1, tuple(names) + tuple(more), decode_config)

    def destroy_and_say(self):
        seen = getattr(self, "_chunk_dev_s", None)
        if seen is not None:
            host = sorted(getattr(self, "_dispatch_host_s", None) or [0.0])
            host = host[len(host) // 2]
            # (a program by the key its cache holds it under: sampler variant, `nb` bucket, width)
            names = {fn: key for cache in ("_chunk_fns", "_verify_fns")
                     for key, fn in getattr(self, cache, {}).items()}
            print("hold: dispatch host s (median of the last 8) %.4f; estimates s by program: %s" % (
                host, {str(names.get(k, "?")): [round(min(v), 4), round(max(v), 4), len(v)]
                       for k, v in seen.items()}), flush=True)
        destroy(self)

    timeline = os.environ.get("PR51_TIMELINE")
    if timeline:
        # every call of the scheduler's three steps with its host times (s since
        # the first), written as JSON lines when the engine goes: who waited for whom
        import contextlib
        import json
        import time

        events, t0 = [], time.monotonic()

        def timed(name, fn, more):
            def call(self, *a, **k):
                t = time.monotonic() - t0
                out = fn(self, *a, **k)
                events.append({"what": name, "t0": round(t, 4),
                               "t1": round(time.monotonic() - t0, 4), **more(self, a, out)})
                return out
            return call

        JaxDecodeEngine._admit = timed(
            "admit", JaxDecodeEngine._admit,
            lambda self, a, out: {"admissions": getattr(self, "_n_admissions", None),
                                  "prefills": self._n_prefills, "forks": self._n_prefix_forks,
                                  })
        JaxDecodeEngine._consume_chunk = timed(
            "consume", JaxDecodeEngine._consume_chunk,
            lambda self, a, out: {"chunk": a[0].chunk,
                                  "seen_to_end": getattr(a[0], "t_ended", None) is not None})
        if hasattr(JaxDecodeEngine, "_wait_held"):
            JaxDecodeEngine._wait_held = timed(
                "hold", JaxDecodeEngine._wait_held,
                lambda self, a, out: {"chunk": a[0].rec.chunk + 1,
                                      "deadline": round(a[0].deadline - t0, 4),
                                      "ended": a[0].rec.t_ended is not None})
            hold_dispatch = JaxDecodeEngine._hold_dispatch

            def why_not(self, hold, budget):
                # a pass that goes on to dispatch with no hold: which condition said so
                out = hold_dispatch(self, hold, budget)
                if out is None and self._inflight:
                    slots = self._slots
                    spent = sum(s is not None and self._spent(i, s) for i, s in enumerate(slots))
                    deadline = self._dispatch_deadline()
                    events.append({
                        "what": "no hold", "t0": round(time.monotonic() - t0, 4), "was held": hold is not None,
                        "left queued": len(self._overflow), "arrived": self._request_q.qsize(),
                        "free": sum(s is None for s in slots), "spent": spent,
                        "live": len(slots) - spent - sum(s is None for s in slots),
                        "estimate": self._chunk_estimate(self._inflight[-1]),
                        "to deadline": None if deadline is None else round(deadline - self._clock(), 4),
                        "ended": self._chunk_ready(self._inflight[-1])})
                return out

            JaxDecodeEngine._hold_dispatch = why_not
        enqueued = {"n": 0}  # programs of admissions since the last chunk went out

        def slow(name, fn):
            def call(self, *a, **k):
                t = time.monotonic() - t0
                enqueued["n"] += 1
                out = fn(self, *a, **k)
                t1 = time.monotonic() - t0
                if t1 - t >= 0.02:
                    events.append({"what": name, "t0": round(t, 4), "t1": round(t1, 4),
                                   "nth": enqueued["n"]})
                return out
            return call

        JaxDecodeEngine._run_copies = slow("fork", JaxDecodeEngine._run_copies)
        prefill_span = JaxDecodeEngine._prefill_dispatch

        @contextlib.contextmanager
        def prefill_dispatch(self, bucket, n=1):
            t = time.monotonic() - t0
            enqueued["n"] += 1
            with prefill_span(self, bucket, n):
                yield
            t1 = time.monotonic() - t0
            if t1 - t >= 0.02:
                events.append({"what": "prefill", "t0": round(t, 4), "t1": round(t1, 4),
                               "nth": enqueued["n"], "bucket": bucket, "batch": n})

        JaxDecodeEngine._prefill_dispatch = prefill_dispatch
        def went_out(self, a, rec):
            ahead, enqueued["n"] = enqueued["n"], 0
            return {"chunk": None if rec is None else rec.chunk,
                    "live": None if rec is None else int(rec.active.sum()),
                    "queued": self._request_q.qsize(), "programs_ahead": ahead}

        dispatch = timed("dispatch", dispatch, went_out)

        def dump(self):
            with open(timeline, "w") as f:
                for e in events:
                    f.write(json.dumps(e) + "\n")
            destroy_and_say(self)

        JaxDecodeEngine.destroy = dump
    JaxDecodeEngine._dispatch_chunk = dispatch_chunk
    JaxDecodeEngine.get_metrics = get_metrics
    if not timeline:
        JaxDecodeEngine.destroy = destroy_and_say
    harness.engine_counters = engine_counters
    sys.argv = [os.path.join(root, "benchmark", "run.py")] + argv
    runpy.run_path(sys.argv[0], run_name="__main__")


if __name__ == "__main__":
    main()
