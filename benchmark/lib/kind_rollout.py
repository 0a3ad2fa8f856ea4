"""Kind `rollout`: one decode chip of a decoupled fleet. `JaxDecodeEngine`
alone, driven through `agenerate` by a closed loop of the benchmark's own: a
fixed number of groups in flight, the next submitted when one returns."""

from __future__ import annotations

import asyncio
import time

import numpy as np

from . import harness, metrics
from .traffic import Traffic, longest_sequence

COUNTERS = ("generated_tokens_total", "chunks_dispatched_total", "prefills_total",
            "prefix_forks_total", "prefix_inplace_total", "suffix_prefills_total",
            "queue_secs_total", "preemptions_total", "runahead_discarded_tokens_total")


def build_engine(rt, config):
    import jax

    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import ModelConfig
    from areal_tpu.platforms import enable_compilation_cache

    from .weights import seeded_params

    enable_compilation_cache()
    mc = ModelConfig.from_hf_config(
        config.decode.model_path, dtype=config.decode.dtype,
        param_dtype=config.decode.dtype)
    params = seeded_params(mc, rt.seed)
    engine = JaxDecodeEngine(config.decode, config.rollout)
    engine.set_model(params, mc)
    del params
    engine.initialize()
    jax.block_until_ready(engine.params)
    return engine


WAVE_SIZES = (8, 4, 2, 1)  # the engine's batched-prefill variants
WARM_BASE = 1 << 20  # index of the first warm-up group


def _request(prompt, n_out: int, temperature: float, rid: str = ""):
    from areal_tpu.api.cli_args import GenerationHyperparameters
    from areal_tpu.api.io_struct import ModelRequest

    g = GenerationHyperparameters(n_samples=1, max_new_tokens=n_out, min_new_tokens=n_out,
                                  temperature=temperature)
    return ModelRequest(rid=rid, input_ids=list(prompt), gconfig=g)


def prefill_waves(buckets: dict[int, int], budget: int, slots: int) -> list[list[int]]:
    """Which prompt lengths to queue together. `buckets` maps each prefill
    bucket of the traffic to a prompt length that falls into it. The engine
    prefills what is queued when generation resumes in batches of 8, 4, 2 and
    1 per bucket, so 15 distinct prompts of one bucket compile all four
    variants at once; a wave takes as many buckets as fit the engine's
    prefill budget for one pass (`max_prefill_tokens`) and its slots, so that
    no wave is split."""
    waves, room, free = [], 0, 0
    for bucket in sorted(buckets, reverse=True):
        sizes = [w for w in WAVE_SIZES if w <= slots and w * bucket <= budget]
        n = sum(sizes) or 1
        if not waves or n * bucket > room or n + 1 > free:
            waves.append([])
            room, free = budget, slots
        waves[-1] += [buckets[bucket]] * n
        room -= n * bucket
        free -= n
    return waves


def warm_engine(rt, engine, tfile: dict) -> None:
    """Every program this traffic can reach, before the window. The batched
    prefills at every prompt bucket and the duplicate-prompt fork: requests
    of one new token queued while generation is paused, then resumed, which
    is how the engine's own `prewarm` forces a wave (public calls only); it
    would take five waves of whole chunks for each bucket, this takes one or
    two for all. Then the decode chunk at every depth a request grows
    through."""
    from .traffic import prompt_lengths

    plens = prompt_lengths(tfile["prompt_len"], int(tfile.get("prompt_strata", 8)))
    buckets = {max(-(-(p - 1) // 64) * 64, 64): p for p in sorted(plens)}
    temperature = float(tfile.get("temperature", 1.0))
    rng = np.random.default_rng(0xC0FFEE)
    vocab = engine.model_config.vocab_size

    async def wave(lengths: list[int]):
        prompts = [rng.integers(1, vocab, n).tolist() for n in lengths]
        prompts.append(prompts[0])  # a duplicate in the wave forks its primary
        engine.pause_generation()
        try:
            tasks = [asyncio.ensure_future(engine.agenerate(_request(p, 1, temperature)))
                     for p in prompts]
            await asyncio.sleep(0)  # each runs to its first await: all are queued
        finally:
            engine.continue_generation()
        await asyncio.gather(*tasks)

    async def waves():
        for lengths in prefill_waves(buckets, int(engine.config.max_prefill_tokens),
                                     int(engine.config.max_running_requests)):
            await wave(lengths)

    asyncio.run(waves())
    shortest, deepest = min(plens), int(tfile["prompt_len"]["hi"]) + int(tfile["output_len"]["hi"])
    ghost = getattr(engine, "_prewarm_chunk_variants", None)
    if ghost is not None:
        # compiles each depth without generating; private, so optional
        ghost(shortest, deepest - shortest, (1.0,))
    else:
        engine.generate(_request([1] * shortest, deepest - shortest, temperature), 600.0)


class ClosedLoop:
    """`inflight` groups outstanding; each group is `n_samples` requests with
    one prompt and pinned output lengths. Records every response with the
    host time it arrived."""

    def __init__(self, rt, engine, traffic: Traffic, inflight: int, temperature: float):
        self.rt, self.engine, self.traffic = rt, engine, traffic
        self.inflight = inflight
        self.temperature = temperature
        self.done: list[dict] = []  # completed or flushed requests
        self.samples: list[tuple[float, int, int]] = []  # (t, running, live tokens)
        self.next_group = 0
        self._scales: dict[int, float] = {}
        self._pending: set = set()

    async def _request(self, group, k: int, n_out: int):
        req = _request(group.prompt.tolist(), n_out, self.temperature, f"g{group.index}s{k}")
        t0 = time.monotonic()
        resp = await self.engine.agenerate(req)
        self.done.append({"t_sub": t0, "t_done": time.monotonic(), "want": n_out,
                          "resp": resp, "group": group.index})

    async def _group(self, i: int):
        grp = self.traffic.group(i, scale=self._scales.get(i, 1.0))
        await asyncio.gather(*[self._request(grp, k, n)
                               for k, n in enumerate(grp.output_lens)])

    async def warm(self, n_groups: int, scale: float):
        """`n_groups` groups with shortened outputs, run to completion: the
        engine is warm and idle afterwards, and nothing is left in flight.
        They come from far along the sequence of groups, so that the window
        starts at group 0, the head of an epoch."""
        ids = range(WARM_BASE, WARM_BASE + n_groups)
        self._scales.update({i: scale for i in ids})
        await asyncio.gather(*[self._group(i) for i in ids])
        self.done.clear()

    def start_cohort(self):
        """Scale the groups the loop is about to start at once."""
        scales = self.traffic.cohort_scales(self.inflight)
        self._scales.update({self.next_group + k: s for k, s in enumerate(scales)})

    async def run_until(self, t_end: float, sample_every: float = 0.1):
        pending = self._pending
        next_sample = time.monotonic()
        while time.monotonic() < t_end:
            while len(pending) < self.inflight:
                pending.add(asyncio.ensure_future(self._group(self.next_group)))
                self.next_group += 1
            timeout = max(0.0, min(t_end, next_sample) - time.monotonic())
            done, pending = await asyncio.wait(
                pending, timeout=timeout, return_when=asyncio.FIRST_COMPLETED)
            for d in done:
                d.result()  # a failed request fails the run
            if time.monotonic() >= next_sample:
                m = self.engine.get_metrics()
                self.samples.append((time.monotonic(), m["running_requests"],
                                     m["active_tokens"]))
                next_sample = time.monotonic() + sample_every
        self._pending = pending

    async def flush(self):
        """Stop: the engine's own interrupt finishes the chunks it has
        dispatched and returns every request in flight with the tokens it has
        so far."""
        self.engine.pause_generation()
        self.engine.abort_all()
        if self._pending:
            await asyncio.wait(self._pending)
        self._pending = set()


def check_sample(done: list[dict], n: int) -> list[dict]:
    """Which completed requests meet the reference: the two longest (the
    deepest contexts, most chunks and pages) and the rest spread evenly over
    the others by output length. A function of the run alone."""
    whole = sorted((r for r in done if r["resp"].output_len == r["want"]),
                   key=lambda r: (r["want"], r["resp"].input_len, r["group"]))
    if len(whole) <= n:
        return whole
    rest, top = whole[:-2], whole[-2:]
    k = n - len(top)
    return [rest[int((i + 0.5) / k * len(rest))] for i in range(k)] + top


def check_decode(rt, engine, done: list[dict], n: int, pad_to: int) -> list[dict]:
    """The engine's returned log-probabilities of `check_sample`'s requests
    against the reference's full forward over prompt + completion."""
    from ..reference import qwen2_ref

    out = []
    for r in check_sample(done, n):
        resp = r["resp"]
        seq = list(resp.input_tokens) + list(resp.output_tokens)
        ref = qwen2_ref.token_logprobs(engine.params, engine.model_config, seq,
                                       temperature=1.0, pad_to=pad_to)
        # ref[t] scores token t + 1: completion token j is entry input_len + j - 1
        out.append(harness.compare_with_reference(
            f"decode logprobs group {r['group']}: {resp.input_len} + {resp.output_len} tokens",
            np.asarray(resp.output_logprobs), ref[resp.input_len - 1:]))
    return out


def run(rt) -> dict:
    cell, tfile = rt.cell, rt.cell["traffic_file"]
    config = harness.experiment_config(rt)
    engine = build_engine(rt, config)
    warm_engine(rt, engine, tfile)
    traffic = Traffic(tfile, engine.model_config.vocab_size, rt.seed)
    loop = ClosedLoop(rt, engine, traffic, int(tfile["inflight_groups"]),
                      float(tfile.get("temperature", 1.0)))
    tracer = harness.TraceWindow(rt) if rt.trace else None
    state: dict = {}

    async def drive():
        await loop.warm(int(cell["warmup_groups"]), float(cell["warmup_scale"]))
        state["cache0"] = rt.cache.snapshot()
        state["m0"] = engine.get_metrics()
        t_open = state["t_open"] = time.monotonic()
        state["setup_s"] = t_open - rt.t_start
        t_stop = t_open + rt.seconds
        loop.start_cohort()
        if tracer:
            t_a = min(t_open + float(cell.get("trace_after_seconds", 5.0)), t_stop)
            t_b = min(t_a + float(cell.get("trace_seconds", 4.0)), t_stop)
            await loop.run_until(t_a)
            tracer.start()
            await loop.run_until(t_b)
            tracer.stop()
        await loop.run_until(t_stop)
        await loop.flush()
        # the window closes when the last dispatched chunk has been consumed:
        # every token generated since the opening has been returned by then
        state["t_close"] = time.monotonic()
        state["m1"] = engine.get_metrics()
        state["cache1"] = rt.cache.snapshot()

    asyncio.run(drive())
    t_open, t_close = state["t_open"], state["t_close"]
    in_window = harness.CacheWatch.delta(state["cache0"], state["cache1"])
    # the engine was idle at the opening and is flushed at the close, so the
    # tokens of every response, whole or flushed, were generated in the window
    completed = [r for r in loop.done if r["resp"].output_len == r["want"]]
    flushed = [r for r in loop.done if r["resp"].stop_reason == "interrupt"]
    short = [r for r in loop.done
             if r["resp"].output_len != r["want"] and r["resp"].stop_reason != "interrupt"]
    tokens = float(sum(r["resp"].output_len for r in loop.done))
    tpot = [1e3 * (r["t_done"] - r["t_sub"]) / r["want"] for r in completed]
    p95, p50 = metrics.percentile(tpot, 95), metrics.percentile(tpot, 50)
    counters = harness.engine_counters(state["m0"], state["m1"], COUNTERS, config.decode)
    checks = check_decode(rt, engine, loop.done, int(cell.get("check_samples", 4)),
                          longest_sequence(tfile))
    rt.note(requests_completed_in_window=len(completed), flushed_at_close=len(flushed),
            tpot_p50_ms=p50["value"], tpot_p95=p95, generated_tokens=tokens,
            engine_counter_tokens=counters["generated_tokens_total"],
            window_s=t_close - t_open, compile_requests_in_window=in_window,
            counters=counters, checks=checks,
            # how much of the pool the traffic holds: tokens cached for live requests
            live_kv_tokens_mean=float(np.mean([x[2] for x in loop.samples])),
            live_kv_tokens_peak=float(max(x[2] for x in loop.samples)),
            kv_pool_tokens_total=state["m1"].get("kv_pool_tokens_total"))
    failed = sum(1 for c in checks if not c["ok"]) + len(short)
    ctx = {"window": (t_open, t_close), "counters": counters,
           "model_config": engine.model_config,
           "fields": {"tpot_p95_ms": p95["value"], "tpot_p50_ms": p50["value"]}}
    if tracer:
        lo, hi = tracer.host
        inside = [s for s in loop.samples if lo <= s[0] <= hi] or loop.samples[-1:]
        ctx["work"] = {
            "tokens_per_chunk": config.decode.new_tokens_per_chunk,
            "running": float(np.mean([s[1] for s in inside])),
            "live_tokens": float(np.mean([s[2] for s in inside])),
        }
        rt.note(traced_work=ctx["work"])
        ctx.update(tracer.reduce())
    result = {
        "correct": failed == 0 and in_window["misses"] == 0 and bool(checks),
        # the requests compared with the reference, and any that came back short
        "attempted": len(checks) + len(short), "failed": failed,
        "end_to_end": {
            "rollout_tokens_per_s": tokens / (t_close - t_open) / int(cell["chips"]),
            "setup_s": state["setup_s"],
        },
        "ctx": ctx,
        "why_not": [f"{len(short)} request(s) returned short of their length"] if short else [],
    }
    engine.destroy()
    return result
