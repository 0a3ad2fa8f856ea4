"""Kind `grpo`: the recipe's loop on one chip. `JaxPPOActor` and
`JaxDecodeEngine` in one process, colocated as `examples/configs/gsm8k_grpo.yaml`
ships; the loop below is `examples/gsm8k_grpo.py:main`'s, call for call, without
saver, evaluator and recover dump. Episodes go through the program's
`WorkflowExecutor` (staleness gate, two batches kept in the pipeline) with a
workflow of the benchmark's own that issues a group's requests at pinned
lengths; nothing else submits.

The window opens at the end of the last warm-up step, the engine busy, and
closes `--seconds` later on a clock thread of the benchmark's own (the main
thread is inside the loop); in a traced run it closes with the traced
sub-window, and the loop ends there. What is read comes from inside the program: both
`get_metrics()` dicts as deltas over the window (`ctx["counters"]`), and in a
traced run `perf_tracer`'s record placed on the trace's clock
(`lib/program_spans.py`)."""

from __future__ import annotations

import asyncio
import os
import threading
import time

import numpy as np

from . import harness, kind_rollout, kind_train, metrics, program_spans, xplane
from .traffic import Traffic, batch_lengths, longest_sequence

DECODE_MODULES = r"^jit_(chunk|prefill)"
TRAIN_MODULES = r"^jit_(fwd_step|grad_step|apply_update|zero_grads)"
WAIT_SPANS = ("train/wait_device", "train/read_stats")
CHECK_GROUP = 1 << 20  # the group generated for the check, outside the window


class GroupLoader:
    """The dataloader `prepare_batch` draws from: batch j is the groups
    j*G .. j*G+G-1, one cycle of the traffic's prompt lengths; endless."""

    def __init__(self, groups_per_batch: int):
        self.batch_size = int(groups_per_batch)
        self._next = 0

    def __iter__(self):
        while True:
            first = self._next
            self._next += self.batch_size
            yield [{"group": i} for i in range(first, first + self.batch_size)]


class PinnedGroups:
    """The benchmark's workflow: one episode is one group, its `n_samples`
    requests issued together at the traffic's pinned lengths, returned as
    `RLVRWorkflow` returns a group (per-token `logprobs` and `versions`,
    `rewards` drawn from the seed).

    A batch is a whole cycle of prompt lengths, as the trainer-only cell's:
    groups return in the order of their batches (a group of the next batch
    that finishes in the same chunk as the last of this one waits for it),
    so every step packs into the shapes the warm-up compiled."""

    def __init__(self, traffic: Traffic, groups_per_batch: int, temperature: float):
        self.traffic = traffic
        self.G = int(groups_per_batch)
        self.temperature = temperature
        self.done: list[dict] = []  # every response, with the length it was to have
        self._returned: dict[int, int] = {}
        self._whole: dict[int, asyncio.Event] = {}

    def _batch_whole(self, j: int) -> asyncio.Event:
        return self._whole.setdefault(j, asyncio.Event())

    async def generate(self, engine, i: int) -> list:
        """Group i's requests, issued together; every response is kept."""
        grp = self.traffic.group(i)
        prompt = grp.prompt.tolist()
        resps = await asyncio.gather(*[
            engine.agenerate(kind_rollout._request(prompt, n, self.temperature, f"g{i}s{k}"))
            for k, n in enumerate(grp.output_lens)])
        self.done += [{"want": n, "resp": r, "group": i}
                      for n, r in zip(grp.output_lens, resps)]
        return resps

    async def arun_episode(self, engine, data):
        from areal_tpu.utils.data import pad_sequences_to_tensors

        i = int(data["group"])
        resps = await self.generate(engine, i)
        rewards = self.traffic._rng(6, i).integers(0, 2, len(resps))
        traj = pad_sequences_to_tensors([dict(
            input_ids=np.array(r.input_tokens + r.output_tokens, np.int32),
            loss_mask=np.array([0] * r.input_len + [1] * r.output_len, np.int32),
            logprobs=np.array([0.0] * r.input_len + r.output_logprobs, np.float32),
            versions=np.array([-1] * r.input_len + r.output_versions, np.int32),
            rewards=np.float32(x),
            begin_of_answer=np.int32(r.input_len),
        ) for r, x in zip(resps, rewards)])
        j = i // self.G
        if j > 0:
            await self._batch_whole(j - 1).wait()
        self._returned[j] = self._returned.get(j, 0) + 1
        if self._returned[j] == self.G:
            self._batch_whole(j).set()
        return traj


def grpo_step(actor, rollout, loader, workflow, meta, global_step: int):
    """One step in `examples/gsm8k_grpo.py:main`'s order, each part under the
    `stats_tracker.record_timing` key `main` gives it (a `step/<key>` span)."""
    from areal_tpu.utils import stats_tracker

    with stats_tracker.record_timing("rollout"):
        batch = rollout.prepare_batch(loader, workflow=workflow)
    with stats_tracker.record_timing("recompute_logp"):
        batch["prox_logp"] = actor.compute_logp(batch)
    with stats_tracker.record_timing("compute_advantage"):
        actor.compute_advantages(batch)
    with stats_tracker.record_timing("train_step"), stats_tracker.scope("grpo_actor"):
        stats = actor.ppo_update(batch)
    rollout.pause()
    with stats_tracker.record_timing("update_weights"):
        actor.set_version(global_step + 1)
        actor.update_weights(meta)
        rollout.set_version(global_step + 1)
    stats[0].update(stats_tracker.export_all())
    rollout.resume()
    return batch, stats


def settle(rollout, timeout: float = 120.0) -> None:
    """Wait until the decode engine holds no request. The loop has more than
    one stable rhythm: a step that begins while the last batch still
    generates lets the gate start the next beside it, 128 requests over 64
    slots, and the loop then alternates steps of 4.5 and 7.1 s for good
    (3,244 tokens/s where the even rhythm gives 3,932: two of eight runs,
    `PERF.md` section 6). Which one a run falls into is decided in the first
    steps, by what they compile. So each warm-up step ends with the engine
    idle, as every step of the even rhythm does by itself."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        m = rollout.get_metrics()
        if m["running_requests"] == 0 and m["queued_requests"] == 0:
            return
        time.sleep(0.02)
    raise TimeoutError("the decode engine did not go idle after a warm-up step")


def build(rt, config):
    """Trainer and colocated decode engine, as `main` and `build_rollout`."""
    import jax

    from areal_tpu.api.io_struct import WeightUpdateMeta
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.platforms import enable_compilation_cache

    enable_compilation_cache()
    actor, alloc = kind_train.build_actor(rt, config)
    rollout = JaxDecodeEngine(config.decode, config.rollout)
    rollout.set_model(actor.params, actor.model_config)
    rollout.initialize()
    jax.block_until_ready(rollout.params)
    meta = WeightUpdateMeta.from_memory(alloc)
    actor.connect_engine(rollout, meta)
    return actor, rollout, meta


def window_counters(m0: dict, m1: dict, window_secs: float, decode_config) -> dict:
    """Every numeric key of the two `get_metrics()` dicts: a `*_total` as its
    delta over the window, anything else as it stood at the close; with the
    window's length and the two engine settings the decode ratios need."""
    out = {}
    for k, v in m1.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        out[k] = v - m0.get(k, 0) if k.endswith("_total") else v
    out["window_secs"] = window_secs
    out["new_tokens_per_chunk"] = decode_config.new_tokens_per_chunk
    out["max_running_requests"] = decode_config.max_running_requests
    return out


def read_metrics(rollout) -> dict:
    """Both dicts; a program from before the loop's counters has the engine's
    alone (the metrics that read the others are then left out)."""
    loop = getattr(rollout, "get_loop_metrics", None)
    return {**rollout.get_metrics(), **(loop() if loop else {})}


def traced_fields(trace_ctx: dict, spans: list[dict], host: tuple[float, float],
                  step_ms: float | None) -> tuple[dict, dict, list]:
    """What the traced sub-window adds: (fields, note, idle gaps). `spans` is
    the program's record (host `monotonic_ns`), `host` the window's start and
    stop on the host's clock in seconds, `step_ms` a whole step's length."""
    trace, (lo, hi) = trace_ctx["trace"], trace_ctx["trace_window"]
    clock = program_spans.clock_offset((host[0] * 1e9, host[1] * 1e9), (lo, hi))
    spans = program_spans.shifted(spans, clock["offset_ns"])
    window_s = (hi - lo) / 1e9
    fields = {}
    for name, pattern in (("device_decode_share_pct", DECODE_MODULES),
                          ("device_train_share_pct", TRAIN_MODULES)):
        fields[name] = 100.0 * xplane.module_time(trace, pattern, lo, hi)["seconds"] / window_s
    if not spans:  # a program without the record: the harness's own gaps stand
        return fields, {}, trace_ctx["breakdown"]["idle_gaps"]
    if step_ms:
        # the trainer's share of the traced window in these spans, of a step
        fields["train_wait_device_ms"] = (
            step_ms * program_spans.seconds_inside(spans, WAIT_SPANS, lo, hi) / window_s)
    threads = {"trainer": program_spans.thread_of(spans, "step/"),
               "decode": program_spans.thread_of(spans, "decode/")}
    gaps = program_spans.name_gaps(
        program_spans.device_gaps(trace, lo, hi), spans, threads, k=5,
        # the scheduler's state clock calls its time outside every span `other`
        unmarked={"decode": "sched_other"})
    note = {"record_spans": len(spans), "clock_skew_ms": clock["skew_ns"] / 1e6,
            "record_open_at_stop": sorted({s["name"] for s in spans if s["open"]})}
    return fields, note, gaps


def run(rt) -> dict:
    from contextlib import nullcontext

    from areal_tpu.utils import perf_tracer

    # the record runs from before the engines exist, so that a span open at
    # the profiler's start (a pause, an idle wait) is in it; a program from
    # before `recording()` has none
    recording = getattr(perf_tracer, "recording", None)
    with recording() if rt.trace and recording else nullcontext() as rec:
        return _run(rt, rec)


def _run(rt, rec) -> dict:
    from areal_tpu.utils import perf_tracer

    cell, tfile = rt.cell, rt.cell["traffic_file"]
    config = harness.experiment_config(rt)
    actor, rollout, meta = build(rt, config)
    kind_rollout.warm_engine(rt, rollout, tfile)
    traffic = Traffic(tfile, actor.model_config.vocab_size, rt.seed)
    G = int(tfile["groups_per_batch"])
    workflow = PinnedGroups(traffic, G, float(tfile.get("temperature", 1.0)))
    loader = GroupLoader(G)
    tracer = harness.TraceWindow(rt) if rt.trace else None
    state: dict = {}
    stop = threading.Event()

    def sleep_until(t: float) -> None:
        time.sleep(max(0.0, t - time.monotonic()))

    def close_window() -> None:
        state.update(t_close=time.monotonic(), m1=read_metrics(rollout),
                     cache1=rt.cache.snapshot())
        stop.set()

    def clock_thread(t_open: float):
        """Closes the window on time. A traced run's window closes with its
        traced sub-window: the profiler's stop takes minutes while the loop
        runs on (380-424 s for 20 s traced, the loop running or not, the
        profiler's Python tracer on or off: the cell's file traces 8 s), and
        its work is not the loop's. So the loop ends there."""
        t_stop = t_open + rt.seconds
        try:
            if not tracer:
                sleep_until(t_stop)
                return close_window()
            t_a = min(t_open + float(cell.get("trace_after_seconds", 15.0)), t_stop)
            sleep_until(t_a)
            tracer.start()
            sleep_until(min(t_a + float(cell.get("trace_seconds", 20.0)), t_stop))
            close_window()
            tracer.stop()
            state["profiler_stop_s"] = time.monotonic() - tracer.host[1]
            if rec is not None:
                state["spans"] = rec.snapshot(int(tracer.host[1] * 1e9))
                # beside the trace, for `tools/trace_report.py <trace> --spans`:
                # the window on the host's clock is what places the record
                perf_tracer.record(program_spans.WINDOW_SPAN, *tracer.host)
                state["record"] = rec.save(os.path.join(rt.workdir, "record.json"))
        except BaseException as e:  # noqa: BLE001 — raised on the main thread
            state["error"] = e
        finally:
            stop.set()

    step_ends, step_tokens, step_stats = [], [], []
    batch, clock, step = None, None, 0
    warmup = int(cell.get("warmup_steps", 3))
    while not stop.is_set():
        batch, stats = grpo_step(actor, rollout, loader, workflow, meta, step)
        step += 1
        if step <= warmup:
            settle(rollout)
        step_ends.append(time.monotonic())
        step_tokens.append(float(sum(batch_lengths(batch))))
        step_stats.append(stats)
        if step == warmup:
            state.update(cache0=rt.cache.snapshot(), m0=read_metrics(rollout),
                         t_open=step_ends[-1], setup_s=step_ends[-1] - rt.t_start)
            clock = threading.Thread(target=clock_thread, args=(step_ends[-1],),
                                     name="bench-window-clock", daemon=True)
            clock.start()
    clock.join()
    if "error" in state:
        raise state["error"]
    versions = {"steps": step, "trainer": actor.get_version(), "engine": rollout.get_version()}

    # -- outside the window: stop, then both halves against the reference at
    # the weights the last push left in both
    rollout.pause()
    rollout.pause_generation()
    rollout.abort_all()
    rollout.continue_generation()
    time.sleep(1.0)  # the flushed episodes return to the runner
    loop_done = list(workflow.done)
    n_check = int(cell.get("check_samples", 6))
    pad_to = longest_sequence(tfile)
    checker = PinnedGroups(traffic, G, workflow.temperature)
    asyncio.run(checker.generate(rollout, CHECK_GROUP))
    checks = kind_rollout.check_decode(rt, rollout, checker.done, n_check, pad_to)
    checks += kind_train.check_trainer(rt, actor, batch, actor.compute_logp(batch),
                                       int(cell.get("check_trainer_samples", 2)), pad_to)

    t_open, t_close = state["t_open"], state["t_close"]
    window_s = t_close - t_open
    in_window = harness.CacheWatch.delta(state["cache0"], state["cache1"])
    counters = window_counters(state["m0"], state["m1"], window_s, config.decode)
    try:
        rate = metrics.whole_step_rate(step_ends, step_tokens, t_open, t_close)
    except ValueError:  # no whole step ended inside the window
        rate = {"steps": 0}
    short = [r for r in loop_done if r["resp"].output_len != r["want"]
             and r["resp"].stop_reason != "interrupt"]
    bad = harness.finite_steps(step_stats)
    why = list(bad)
    if short:
        why.append(f"{len(short)} request(s) returned short of their length")
    if rate["steps"] < int(cell.get("min_whole_steps", 4)):
        why.append(f"only {rate['steps']} whole step(s) in the window")
    if len(set(versions.values())) != 1:
        why.append(f"weight versions differ at the close: {versions}")
    if in_window["misses"]:
        why.append(f"{in_window['misses']} compile-cache miss(es) in the window")
    failed = sum(1 for c in checks if not c["ok"]) + len(short)
    fields = {"loop_step_ms": 1e3 * rate["seconds"] / rate["steps"],
              "loop_trained_tokens_per_s": rate["rate"] / int(cell["chips"])} if rate["steps"] else {}
    rt.note(whole_steps=rate["steps"], versions=versions, window_s=window_s,
            step_seconds=[round(b - a, 3) for a, b in zip(step_ends, step_ends[1:])],
            tokens_per_step=step_tokens[-1], compile_requests_in_window=in_window,
            checks=checks, counters=counters,
            kv_pool_tokens_total=state["m1"].get("kv_pool_tokens_total"))
    ctx = {"window": (t_open, t_close), "counters": counters, "fields": fields,
           "model_config": actor.model_config}
    if tracer and "profiler_stop_s" in state:
        ctx.update(tracer.reduce())
        more, note, gaps = traced_fields(ctx, state.get("spans", []), tracer.host,
                                         fields.get("loop_step_ms"))
        fields.update(more)
        ctx["breakdown"]["idle_gaps"] = gaps
        if "record" in state:
            note["record_bytes"] = os.path.getsize(state["record"])
        rt.note(**note, profiler_stop_s=state["profiler_stop_s"])
    result = {
        "correct": failed == 0 and not why and bool(checks),
        "attempted": len(checks) + len(short), "failed": failed,
        "end_to_end": {
            "rollout_tokens_per_s":
                counters["generated_tokens_total"] / window_s / int(cell["chips"]),
            "setup_s": state["setup_s"],
        },
        "ctx": ctx,
        "why_not": why,
    }
    rollout.destroy()
    actor.destroy()
    return result
