"""A train cell's set-up by phase (PR 43; `bench_artifacts/pr42/phase_clock.py`
fitted to the train kind): the benchmark's own command (`benchmark/run.py`)
run in this process with the host clock printed at each phase of its set-up,
so that a `setup_s` that grew can be put on a phase. Nothing of the run is
changed.

    python bench_artifacts/pr43/phase_clock.py [--root <checkout>] \
        --workload train-1.5b-fsdp4 --seed <n> --seconds 51 --trace 0

`--root` is the checkout whose program and benchmark run (this one if not
given; `_parent` for the parent commit unpacked there). A line a phase:

    phase: <name> at=<s since the process began> took=<s since the phase before>
           [trace=.. lower=.. compile=.. cache_read=.. programs=..]

where the bracket sums JAX's own events inside the phase: tracing to a jaxpr,
lowering to MLIR, the backend's compile call (on a warm cache: the read and
deserialisation of the executable, `cache_read` being the read alone), and how
many programs asked the persistent cache. What a phase took beyond those is
host work of the program and the device's execution. The window opens when
the last warm-up `ppo_step` returns (`warmup_steps` x the cell's two distinct
batches: the first two `ppo_step` marks in both train cells).
`bench_artifacts/pr42/phase_table.py` makes the table of medians from such logs."""

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}


class Clock:
    def __init__(self):
        self.last = T0
        self.acc = dict.fromkeys(EVENTS.values(), 0.0)
        self.programs = 0
        self.rows = []

    def on_duration(self, event, secs, **_):
        if event in EVENTS:
            self.acc[EVENTS[event]] += secs

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.programs += 1

    def mark(self, name):
        now = time.monotonic()
        jaxs = " ".join(f"{k}={v:.3f}" for k, v in self.acc.items())
        print(f"phase: {name} at={now - T0:.3f} took={now - self.last:.3f} "
              f"[{jaxs} programs={self.programs}]", flush=True)
        self.rows.append((name, now - T0, now - self.last, dict(self.acc), self.programs))
        self.last = now
        self.acc = dict.fromkeys(EVENTS.values(), 0.0)
        self.programs = 0


def after(obj, name, clock, label, wait=None):
    """Mark `label` when `obj.name` returns (a coroutine function: when it is
    done); `label` may be a function of the call's arguments."""
    import asyncio
    import functools

    fn = getattr(obj, name)

    def text(args, kw):
        return label(*args, **kw) if callable(label) else label

    if asyncio.iscoroutinefunction(fn):
        @functools.wraps(fn)
        async def wrapped(*args, **kw):
            out = await fn(*args, **kw)
            clock.mark(text(args, kw))
            return out
    else:
        @functools.wraps(fn)
        def wrapped(*args, **kw):
            out = fn(*args, **kw)
            if wait is not None:
                wait(*args)
            clock.mark(text(args, kw))
            return out
    setattr(obj, name, wrapped)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=HERE)
    args, rest = ap.parse_known_args()
    root = os.path.abspath(args.root)
    os.chdir(root)
    sys.path.insert(0, root)
    clock = Clock()
    clock.mark("interpreter up, arguments read")

    import jax
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(clock.on_duration)
    jax.monitoring.register_event_listener(clock.on_event)
    clock.mark("jax imported")

    from benchmark import run as bench_run  # its T_START is taken here

    print(f"phase-clock: benchmark/run.py's clock starts {bench_run.T_START - T0:.3f} s "
          f"after this process's; root={root}", flush=True)
    import areal_tpu  # noqa: F401
    from areal_tpu.engine.ppo.actor import JaxPPOActor
    from benchmark.lib import harness, kind_train, weights

    clock.mark("program and benchmark imported")
    jax.devices()
    clock.mark("backend up")

    n = {"logp": iter(range(1, 1000)), "step": iter(range(1, 1000))}
    after(harness, "experiment_config", clock, "experiment_config (model dir, YAML)")
    after(JaxPPOActor, "create_process_group", clock, "create_process_group (mesh)")
    after(JaxPPOActor, "initialize", clock, "actor.initialize (parameters from scratch, optimizer state)")
    after(weights, "seeded_params", clock, "weights drawn (seeded_params)")
    after(kind_train.Traffic, "train_batch", clock, "a batch drawn")
    # compute_logp ends in a host read of its result; a ppo_step in its stats'
    after(JaxPPOActor, "compute_logp", clock,
          lambda *_a, **_k: f"compute_logp #{next(n['logp'])} (the first of a batch compiles "
                            "jit_fwd_step at its shapes)")
    after(kind_train, "check_trainer", clock, "check_trainer (the float32 reference)")
    after(JaxPPOActor, "ppo_update", clock,
          lambda *_a, **_k: f"ppo_update #{next(n['step'])} (a whole step; warm-up steps compile "
                            "jit_grad_step, jit_apply_update)")
    after(JaxPPOActor, "destroy", clock, "destroy()")
    # harness.reseed_actor imports seeded_params by name at call time
    code = bench_run.main(rest)
    clock.mark("result printed")
    return code


if __name__ == "__main__":
    try:
        rc = main()
    except BaseException:  # noqa: BLE001 — report, then leave at once
        import traceback

        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
