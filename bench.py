"""Benchmark harness: one JSON line for the driver.

Two measurements on whatever accelerator is attached:

1. TRAIN (primary metric): GSPMD trainer packed-SFT step on the flagship
   Qwen2.5-0.5B geometry (bf16, remat, scan-over-layers, Pallas flash
   attention) at a realistic 64k tokens/step. MFU uses the explicit
   per-token matmul FLOPs model (areal_tpu/utils/flops.py) — embedding
   *lookup* excluded, lm_head matmul + causal attention term included —
   against the chip's bf16 peak.
2. DECODE (detail): in-process continuous-batching engine
   (areal_tpu/engine/jax_decode.py) serving concurrent requests; reports
   steady-state generated tokens/sec/chip — the rollout half of the
   async-RL throughput story (BASELINE.md "rollout tokens/sec").

`vs_baseline` compares trainer MFU to 0.20 — the ballpark dense-model
train-step MFU of the reference's Megatron/FSDP GPU trainer in the
published boba² runs (BASELINE.md; AReaL does not publish MFU directly,
0.20 is the standard H800 Megatron figure for this class of run).
"""

from __future__ import annotations

import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BASELINE_TRAINER_MFU = 0.20

# The no-remat trainer attempt falls back to remat on this error (and only
# this one): which of the two fits depends on what else holds the chip's HBM.
_OOM_MARKER = "RESOURCE_EXHAUSTED: Attempting to reserve"


def bench_train(model, tokens_per_step, seq_len, mb_tokens, warmup, iters):
    from areal_tpu.api.alloc_mode import ParallelStrategy
    from areal_tpu.api.cli_args import (
        MicroBatchSpec,
        OptimizerConfig,
        TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.sft.lm_engine import JaxLMEngine
    from areal_tpu.utils.data import pad_sequences_to_tensors

    cfg = TrainEngineConfig(
        experiment_name="bench",
        trial_name="b",
        path="",
        init_from_scratch=True,
        dtype=model.dtype,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=mb_tokens),
        optimizer=OptimizerConfig(
            lr=1e-4,
            warmup_steps_proportion=0.0,
            lr_scheduler_type="constant",
            gradient_clipping=1.0,
        ),
        gradient_checkpointing=model.remat,
    )
    eng = JaxLMEngine(cfg)
    eng.model_config = model
    eng.create_process_group(ParallelStrategy())
    eng.initialize(None, FinetuneSpec(1, 1000, 1))

    rng = np.random.RandomState(0)
    seqs = [
        dict(
            input_ids=rng.randint(1, model.vocab_size, (seq_len,)),
            loss_mask=np.ones(seq_len, dtype=np.int32),
        )
        for _ in range(tokens_per_step // seq_len)
    ]
    batch = pad_sequences_to_tensors(seqs)

    for _ in range(warmup):
        eng.train_lm(batch)
    stats = []
    t0 = time.perf_counter()
    for _ in range(iters):
        stats.append(eng.train_lm(batch))
    dt = (time.perf_counter() - t0) / iters
    eng.destroy()
    tps = float(np.mean([s["tokens_per_sec_per_chip"] for s in stats]))
    out = dict(
        tokens_per_sec_per_chip=tps,
        step_time_s=dt,
        tokens_per_step=tokens_per_step,
    )
    if all("mfu" in s for s in stats):
        # engine-reported MFU (same flops model), averaged over timed
        # iters; the engine reports none on a device with no known peak
        out["mfu"] = float(np.mean([s["mfu"] for s in stats]))
    return out


def _wait_for_running(eng, timeout_s: float, poll_s: float = 0.01) -> bool:
    """Poll the engine until at least one request is actively decoding.
    Returns False on deadline — callers must NOT then measure pause latency
    against the idle engine (it would masquerade as an under-load number)."""
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        if eng.get_metrics()["running_requests"] > 0:
            return True
        time.sleep(poll_s)
    return False


def bench_decode(model, n_requests, prompt_len, new_tokens, max_running,
                 runahead=1, chunk=None):
    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import init_params

    import jax

    dcfg = JaxDecodeConfig(
        context_length=prompt_len + new_tokens + 128,
        max_running_requests=max_running,
        new_tokens_per_chunk=chunk or min(128, new_tokens),
        decode_runahead_chunks=runahead,
        dtype=model.dtype,
        kv_cache_dtype=model.dtype,
    )
    eng = JaxDecodeEngine(dcfg, InferenceEngineConfig(max_concurrent_rollouts=n_requests))
    eng.set_model(init_params(model, jax.random.PRNGKey(0)), model)
    eng.initialize()

    rng = np.random.RandomState(1)
    g = GenerationHyperparameters(
        max_new_tokens=new_tokens, temperature=1.0, top_p=1.0
    )
    n_warm = max(2, max_running)
    # pre-generated on one thread: RandomState is not thread-safe under the
    # pool.map fan-out below
    prompts = [
        rng.randint(1, model.vocab_size, (prompt_len,)).tolist()
        for _ in range(n_warm + n_requests)
    ]

    def one(i):
        req = ModelRequest(input_ids=prompts[i], gconfig=g)
        return eng.generate(req, timeout=1800)

    interrupt_latency = {}

    def measure_interrupt():
        # Weight-update pause window under load: pause_generation blocks
        # through the in-flight chunk (VERDICT weak #7 asks for this number
        # — the reference aborts mid-request; we land on chunk boundaries).
        # Wait until requests are actually decoding (a fixed sleep misses
        # the whole load window on a fast backend), then pause.
        if not _wait_for_running(eng, 30.0):
            # Pausing anyway would time an IDLE-engine pause and report it
            # as the under-load latency — record the sentinel instead.
            print(
                "[bench] pause probe: no running requests within 30s; "
                "recording pause_s=-1 (not measured) instead of an "
                "idle-engine pause",
                file=sys.stderr,
                flush=True,
            )
            interrupt_latency["pause_s"] = -1.0
            return
        t0 = time.perf_counter()
        eng.pause_generation()
        interrupt_latency["pause_s"] = time.perf_counter() - t0
        eng.continue_generation()

    # Deterministic compile warmup (the same class of fix the prefix bench
    # needed, r05 notes): every batched-prefill wave size and the chunk fn
    # at every KV bucket the context growth reaches — compiled here, not
    # inside the timed window. gconfig=g warms exactly the sampler variant
    # the timed region uses; the fork path is skipped (unique prompts
    # below never fork).
    eng.prewarm(prompt_len=prompt_len, gconfig=g, include_fork=False)
    with ThreadPoolExecutor(max_workers=n_requests + 1) as pool:
        # UNTIMED load pass: covers live-traffic interleavings prewarm's
        # idle-engine waves don't (retire-then-admit while decoding), and
        # hosts the pause-latency probe — a real under-load pause window
        # measured on a warm engine, without eating ~4 s of the timed
        # throughput region.
        stopper = pool.submit(measure_interrupt)
        list(pool.map(one, range(n_warm)))
        stopper.result()
        m0 = eng.get_metrics()  # timed-window deltas, not since-init totals
        t0 = time.perf_counter()
        results = list(pool.map(one, range(n_warm, n_warm + n_requests)))
        dt = time.perf_counter() - t0
        m1 = eng.get_metrics()
    eng.destroy()
    gen_tokens = sum(len(r.output_tokens) for r in results)
    # device-idle split over the timed window: the host gap between a
    # chunk's results landing and the next dispatch — the time the
    # run-ahead scheduler exists to hide
    busy = m1["device_busy_s"] - m0["device_busy_s"]
    idle = m1["device_idle_s"] - m0["device_idle_s"]
    # honest ITL: per-token dispatch->ready device time only (host work is
    # reported separately as the idle fraction)
    itl_ms = np.concatenate(
        [np.asarray(r.itl, dtype=np.float64) for r in results if r.itl]
    ) * 1000.0
    return dict(
        decode_tokens_per_sec_per_chip=gen_tokens / dt,
        decode_requests=n_requests,
        decode_new_tokens=new_tokens,
        decode_runahead_chunks=runahead,
        decode_device_idle_frac=(
            idle / (busy + idle) if (busy + idle) > 0 else 0.0
        ),
        decode_itl_p50_ms=float(np.percentile(itl_ms, 50)) if itl_ms.size else 0.0,
        decode_itl_p99_ms=float(np.percentile(itl_ms, 99)) if itl_ms.size else 0.0,
        interrupt_pause_latency_s=interrupt_latency.get("pause_s", -1.0),
    )


def bench_decode_compare(model, n_requests, prompt_len, new_tokens,
                         max_running, chunk=None):
    """Run-ahead (the default) vs legacy synchronous scheduling at the same
    wave config. Headline numbers come from the run-ahead engine; the sync
    run's throughput and device-idle fraction land under `decode_sync_*` so
    the overlap win (idle fraction strictly down, tokens/s no worse) is a
    single-report read. The run-ahead engine runs FIRST: the second engine
    in a process inherits warm XLA/persistent-cache state, so the
    advantaged position goes to the sync baseline — any reported win is a
    conservative one."""
    out = bench_decode(
        model, n_requests, prompt_len, new_tokens, max_running, runahead=1,
        chunk=chunk,
    )
    sync = bench_decode(
        model, n_requests, prompt_len, new_tokens, max_running, runahead=0,
        chunk=chunk,
    )
    out["decode_sync_tokens_per_sec_per_chip"] = sync[
        "decode_tokens_per_sec_per_chip"
    ]
    out["decode_sync_device_idle_frac"] = sync["decode_device_idle_frac"]
    out["decode_sync_itl_p50_ms"] = sync["decode_itl_p50_ms"]
    out["decode_sync_itl_p99_ms"] = sync["decode_itl_p99_ms"]
    return out


def bench_spec_compare(model, n_requests, prompt_len, new_tokens, max_running,
                       chunk=None, spec_k=7, echo_vocab=64):
    """n-gram speculative decoding (spec_decode="ngram") vs the
    non-speculative oracle on a prompt-echoing workload.

    Untrained random weights never repeat under greedy decoding (no
    induction behavior), so the workload makes the model itself echo:
    the residual-mixing kernels (attn o_kernel, mlp down_kernel) are
    zeroed, which reduces greedy decoding to a deterministic
    last-token -> next-token map over a small vocab (`echo_vocab`) — it
    must enter a cycle within O(sqrt(vocab)) steps, the repetition regime
    prompt-lookup exploits in trained math/code rollouts that quote their
    prompts. BOTH engines serve the same echo model, so the comparison
    isolates the engine cost: one W-wide verify forward per up-to-W
    emitted tokens versus `chunk` sequential decode steps per chunk.

    Reports end-to-end tok/s for both engines, the speedup, and the
    acceptance telemetry (mean accepted-per-chunk, draft hit rate,
    rejected waste). The spec engine runs FIRST so the warm-XLA-process
    advantage goes to the baseline (same conservative ordering as
    bench_decode_compare)."""
    import dataclasses as _dc

    import jax

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import init_params

    echo_model = _dc.replace(model, vocab_size=min(model.vocab_size, echo_vocab))
    params = init_params(echo_model, jax.random.PRNGKey(0))
    zero = lambda a: a * 0.0  # noqa: E731

    def echoify(layer):
        return {
            **layer,
            "attn": {**layer["attn"], "o_kernel": zero(layer["attn"]["o_kernel"])},
            "mlp": {**layer["mlp"], "down_kernel": zero(layer["mlp"]["down_kernel"])},
        }

    if "layers" in params:
        params["layers"] = echoify(params["layers"])
    else:
        for name in list(params):
            if name.startswith("layers_"):
                params[name] = echoify(params[name])

    g = GenerationHyperparameters(max_new_tokens=new_tokens, greedy=True)
    rng = np.random.RandomState(5)
    n_warm = max(2, max_running)
    prompts = [
        rng.randint(1, echo_model.vocab_size, (prompt_len,)).tolist()
        for _ in range(n_warm + n_requests)
    ]

    def run(spec: bool):
        dcfg = JaxDecodeConfig(
            context_length=prompt_len + new_tokens + 128,
            max_running_requests=max_running,
            new_tokens_per_chunk=chunk or min(128, new_tokens),
            spec_decode="ngram" if spec else "off",
            spec_k=spec_k,
            dtype=model.dtype,
            kv_cache_dtype=model.dtype,
        )
        eng = JaxDecodeEngine(
            dcfg, InferenceEngineConfig(max_concurrent_rollouts=n_requests)
        )
        eng.set_model(params, echo_model)
        eng.initialize()
        try:
            eng.prewarm(prompt_len=prompt_len, gconfig=g, include_fork=False)

            def one(i):
                return eng.generate(
                    ModelRequest(input_ids=prompts[i], gconfig=g), timeout=1800
                )

            with ThreadPoolExecutor(max_workers=n_requests) as pool:
                # untimed load pass: live-traffic interleavings + the spec
                # path's first drafted dispatches land outside the clock
                list(pool.map(one, range(n_warm)))
                m0 = eng.get_metrics()
                t0 = time.perf_counter()
                results = list(
                    pool.map(one, range(n_warm, n_warm + n_requests))
                )
                dt = time.perf_counter() - t0
                m1 = eng.get_metrics()
            gen = sum(len(r.output_tokens) for r in results)
            out = dict(tok_s=gen / dt, m0=m0, m1=m1, results=results)
            return out
        finally:
            eng.destroy()

    spec = run(True)
    base = run(False)
    # greedy streams must agree between the engines — a speedup bought
    # with different tokens would be a correctness bug, not a win
    for a, b in zip(spec["results"], base["results"]):
        assert a.output_tokens == b.output_tokens, "spec stream diverged"
    m0, m1 = spec["m0"], spec["m1"]
    d_chunks = m1["spec_chunks_total"] - m0["spec_chunks_total"]
    d_drafted = (
        m1["spec_drafted_tokens_total"] - m0["spec_drafted_tokens_total"]
    )
    d_rejected = (
        m1["spec_rejected_tokens_total"] - m0["spec_rejected_tokens_total"]
    )
    d_accept = d_drafted - d_rejected  # accepted = drafted - rejected
    return dict(
        spec_tokens_per_sec_per_chip=spec["tok_s"],
        spec_off_tokens_per_sec_per_chip=base["tok_s"],
        spec_over_off_speedup=(
            spec["tok_s"] / base["tok_s"] if base["tok_s"] > 0 else 0.0
        ),
        spec_accepted_per_chunk_mean=(
            d_accept / d_chunks if d_chunks else 0.0
        ),
        spec_draft_hit_rate=(
            (d_drafted - d_rejected) / d_drafted if d_drafted else 0.0
        ),
        spec_rejected_tokens=d_rejected,
        spec_verify_chunks=d_chunks,
        spec_k=spec_k,
        spec_itl_p50_ms=m1["itl_p50_ms"],
        spec_new_tokens=new_tokens,
    )


def bench_kvoffload(model, n_sessions, prompt_len, new_tokens, max_running,
                    host_mb=256.0, chunk=None):
    """Tiered KV cache under oversubscription: host-RAM offload
    (`kv_host_pool_mb`) vs today's drop-and-reprefill, on a session-reuse
    trace whose working set exceeds the device slots.

    Trace (identical for both engines): `n_sessions` > `max_running`
    sessions start concurrently and are interrupted mid-stream
    (pause+abort — the weight-update flush every async-RL step performs);
    the sessions that never got a slot run to completion first, which
    forces the LRU eviction of every parked session's KV; then the
    interrupted sessions RESUME (prompt + partial tokens, same rid). With
    the host tier the eviction offloaded their KV and the resume promotes
    it back (fresh blocks + async upload); without it the resume re-runs
    prefill over the whole conversation. Reported: resume TTFT for both
    engines (the number long-context session reuse lives or dies on),
    re-prefill tokens avoided, and the swap traffic that bought it. The
    offload engine runs FIRST so the warm-XLA-process advantage goes to
    the re-prefill baseline (same conservative ordering as
    bench_decode_compare)."""
    import asyncio
    import threading
    import uuid as _uuid
    from dataclasses import replace as _dc_replace

    import jax

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import init_params

    params = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.RandomState(11)
    prompts = [
        rng.randint(1, model.vocab_size, (prompt_len,)).tolist()
        for _ in range(n_sessions)
    ]
    g = GenerationHyperparameters(
        max_new_tokens=new_tokens, temperature=1.0, top_p=1.0
    )

    def run(mb: float) -> dict:
        dcfg = JaxDecodeConfig(
            context_length=prompt_len + new_tokens + 128,
            max_running_requests=max_running,
            new_tokens_per_chunk=chunk or min(128, new_tokens),
            kv_host_pool_mb=mb,
            dtype=model.dtype,
            kv_cache_dtype=model.dtype,
        )
        eng = JaxDecodeEngine(
            dcfg, InferenceEngineConfig(max_concurrent_rollouts=n_sessions)
        )
        eng.set_model(params, model)
        eng.initialize()
        try:
            eng.prewarm(prompt_len=prompt_len, gconfig=g, include_fork=False)
            # phase 1: all sessions start; interrupt them mid-stream
            first = [None] * n_sessions
            rids = [f"sess-{i}-{_uuid.uuid4()}" for i in range(n_sessions)]

            def one_first(i):
                first[i] = eng.generate(
                    ModelRequest(
                        rid=rids[i], input_ids=prompts[i], gconfig=g
                    ),
                    timeout=1800,
                )

            threads = [
                threading.Thread(target=one_first, args=(i,), daemon=True)
                for i in range(n_sessions)
            ]
            for t in threads:
                t.start()
            if not _wait_for_running(eng, 60.0):
                raise RuntimeError("kvoffload bench: sessions never started")
            # let the running wave emit some tokens before the flush
            deadline = time.perf_counter() + 60.0
            while (
                eng.get_metrics()["generated_tokens_total"] < max_running
                and time.perf_counter() < deadline
            ):
                time.sleep(0.005)
            eng.pause_generation()
            eng.abort_all()
            eng.continue_generation()
            for t in threads:
                t.join(120)
            interrupted = [
                i for i, r in enumerate(first)
                if r is not None and len(r.output_tokens) > 0
            ]
            fresh = [
                i for i, r in enumerate(first)
                if r is not None and len(r.output_tokens) == 0
            ]
            # phase 2a: the never-ran sessions complete first — their slot
            # demand LRU-evicts every parked session (offload vs drop)
            with ThreadPoolExecutor(max_workers=max(len(fresh), 1)) as pool:
                list(
                    pool.map(
                        lambda i: eng.generate(
                            ModelRequest(input_ids=prompts[i], gconfig=g),
                            timeout=1800,
                        ),
                        fresh,
                    )
                )
            m0 = eng.get_metrics()
            # phase 2b: the interrupted sessions resume (same rid,
            # prompt + partials) — TTFT here is swap-in vs re-prefill
            def resume(i):
                r1 = first[i]
                return eng.generate(
                    ModelRequest(
                        rid=rids[i],  # same rid: the resume-affinity key
                        input_ids=list(prompts[i]) + list(r1.output_tokens),
                        gconfig=_dc_replace(
                            g,
                            max_new_tokens=max(
                                new_tokens - len(r1.output_tokens), 1
                            ),
                        ),
                    ),
                    timeout=1800,
                )

            t0 = time.perf_counter()
            with ThreadPoolExecutor(
                max_workers=max(len(interrupted), 1)
            ) as pool:
                resumed = list(pool.map(resume, interrupted))
            resume_wall = time.perf_counter() - t0
            m1 = eng.get_metrics()
            ttfts = np.asarray([r.ttft for r in resumed], dtype=np.float64)
            return dict(
                ttft_mean_ms=float(ttfts.mean() * 1e3) if ttfts.size else 0.0,
                ttft_p50_ms=(
                    float(np.percentile(ttfts, 50) * 1e3) if ttfts.size else 0.0
                ),
                resume_wall_s=resume_wall,
                n_resumes=len(interrupted),
                avoided=(
                    m1["reprefill_tokens_avoided_total"]
                    - m0["reprefill_tokens_avoided_total"]
                ),
                swap_out=m1["kv_swap_out_bytes_total"],
                swap_in=m1["kv_swap_in_bytes_total"],
                hit_rate=m1["kv_host_hit_rate"],
                prefills=m1["prefills_total"] - m0["prefills_total"],
            )
        finally:
            eng.destroy()

    on = run(host_mb)
    off = run(0.0)
    return dict(
        kvoffload_resume_ttft_ms=on["ttft_mean_ms"],
        kvoffload_resume_ttft_p50_ms=on["ttft_p50_ms"],
        kvoffload_reprefill_resume_ttft_ms=off["ttft_mean_ms"],
        kvoffload_reprefill_resume_ttft_p50_ms=off["ttft_p50_ms"],
        kvoffload_resume_ttft_speedup=(
            off["ttft_mean_ms"] / on["ttft_mean_ms"]
            if on["ttft_mean_ms"] > 0
            else 0.0
        ),
        kvoffload_resumes=on["n_resumes"],
        kvoffload_reprefill_tokens_avoided=on["avoided"],
        kvoffload_baseline_tokens_avoided=off["avoided"],  # must be 0
        kvoffload_swap_out_bytes=on["swap_out"],
        kvoffload_swap_in_bytes=on["swap_in"],
        kvoffload_host_hit_rate=on["hit_rate"],
        kvoffload_resume_prefills=on["prefills"],
        kvoffload_baseline_resume_prefills=off["prefills"],
        kvoffload_host_pool_mb=host_mb,
        kvoffload_sessions=n_sessions,
        kvoffload_prompt_len=prompt_len,
    )


def bench_kvquant(model, n_sessions, prompt_len, new_tokens, max_running,
                  pool_mb=0.5, chunk=None, spec_k=4):
    """Int8 paged KV pool vs fp at FIXED pool MB (ISSUE 11).

    Three legs, every engine paged:

    1. **Capacity + throughput at fixed bytes**: both engines get
       `kv_pool_tokens` derived from the SAME `pool_mb` budget — int8
       fits ~2x the tokens (1 byte/element + one f32 scale per
       (row, head) vs the fp element size), so at a budget sized to
       pressure the fp pool the int8 engine keeps the whole working set
       resident while fp preempts/offloads. Reports pool tokens,
       resident-session capacity, end-to-end tok/s and the
       preemption/swap traffic for both. The int8 engine runs FIRST so
       the warm-XLA-process advantage goes to the fp baseline (same
       conservative ordering as bench_decode_compare).
    2. **Wire bytes**: one session per dtype is prefilled, parked and
       exported — the migration payload (blocks + scales, shipped as-is
       with no requantization) is the /drain and disaggregation unit, so
       its ratio IS the wire saving.
    3. **Drift, measured not assumed**: greedy + sampled streams vs the
       fp oracle (token match fraction, max |logprob delta| over the
       matched prefix) and the speculative accept-rate on an echo
       workload for both dtypes (the accept-rate shift is the honest
       cost speculation pays for quantized verify logits). NOTE the CPU
       smoke runs RANDOM weights, the worst case for drift: near-uniform
       logits flip argmax/categorical under tiny KV perturbations, so
       the match fractions here are a floor — trained checkpoints sit
       far higher (the math-workload reward comparison is the TPU run's
       job).
    """
    import asyncio as _asyncio
    import dataclasses
    import threading as _threading

    import jax

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import init_params

    params = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.RandomState(17)
    prompts = [
        rng.randint(1, model.vocab_size, (prompt_len,)).tolist()
        for _ in range(n_sessions)
    ]
    g = GenerationHyperparameters(
        max_new_tokens=new_tokens, temperature=1.0, top_p=1.0
    )
    L = model.num_hidden_layers
    nkv = model.num_key_value_heads
    hd = model.head_dim_

    def bytes_per_token(dt: str) -> int:
        elem = 1 if dt == "int8" else np.dtype(model.dtype).itemsize
        scale = 4 if dt == "int8" else 0
        return 2 * L * nkv * (hd * elem + scale)

    def mk(dt, *, pool_tokens=None, host_mb=0.0, spec="off",
           R=max_running, role="unified"):
        dcfg = JaxDecodeConfig(
            context_length=prompt_len + new_tokens + 128,
            max_running_requests=R,
            new_tokens_per_chunk=chunk or min(128, new_tokens),
            kv_dtype=dt,
            kv_pool_tokens=pool_tokens,
            kv_host_pool_mb=host_mb,
            spec_decode=spec,
            spec_k=spec_k,
            role=role,
            dtype=model.dtype,
            kv_cache_dtype=model.dtype,
        )
        eng = JaxDecodeEngine(
            dcfg, InferenceEngineConfig(max_concurrent_rollouts=n_sessions)
        )
        eng.set_model(params, model)
        eng.initialize()
        return eng

    sess_len = prompt_len + new_tokens

    def throughput(dt: str) -> dict:
        pool_tokens = int(pool_mb * 1024 * 1024 // bytes_per_token(dt))
        eng = mk(dt, pool_tokens=pool_tokens, host_mb=max(64.0, pool_mb * 4))
        try:
            eng.prewarm(prompt_len=prompt_len, gconfig=g, include_fork=False)
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=n_sessions) as pool:
                rs = list(
                    pool.map(
                        lambda p: eng.generate(
                            ModelRequest(input_ids=p, gconfig=g),
                            timeout=1800,
                        ),
                        prompts,
                    )
                )
            wall = time.perf_counter() - t0
            m = eng.get_metrics()
            toks = sum(len(r.output_tokens) for r in rs)
            return dict(
                pool_tokens=m["kv_pool_tokens_total"],
                resident_sessions=m["kv_pool_tokens_total"] // sess_len,
                tok_s=toks / wall if wall > 0 else 0.0,
                preemptions=m["preemptions_total"],
                swap_out=m["kv_swap_out_bytes_total"],
                swap_in=m["kv_swap_in_bytes_total"],
                block_nbytes=m["kv_block_nbytes"],
            )
        finally:
            eng.destroy()

    def migrate_bytes(dt: str) -> int:
        eng = mk(dt, R=2, role="prefill")
        try:
            out = {}

            def _go():
                out["r"] = _asyncio.run(
                    eng.aprefill(
                        ModelRequest(
                            rid="mig", input_ids=prompts[0], gconfig=g
                        )
                    )
                )

            t = _threading.Thread(target=_go, daemon=True)
            t.start()
            t.join(300)
            sess = eng.export_session("mig")
            assert sess is not None
            return sum(
                sess[x].nbytes
                for x in ("k", "v", "ks", "vs")
                if x in sess
            )
        finally:
            eng.destroy()

    def streams(dt: str, gg, n=4) -> list:
        eng = mk(dt, R=max_running)
        try:
            with ThreadPoolExecutor(max_workers=n) as pool:
                return list(
                    pool.map(
                        lambda p: eng.generate(
                            ModelRequest(input_ids=p, gconfig=gg),
                            timeout=1800,
                        ),
                        prompts[:n],
                    )
                )
        finally:
            eng.destroy()

    # spec leg: the echo model of bench_spec_compare (residual-mixing
    # kernels zeroed -> greedy decoding cycles), so drafts actually
    # accept and the dtype's accept-rate shift is observable. Params are
    # rebuilt per call with the echo surgery applied.
    def spec_accept(dt: str) -> float:
        zero = lambda a: a * 0.0  # noqa: E731

        def echoify(layer):
            return {
                **layer,
                "attn": {
                    **layer["attn"],
                    "o_kernel": zero(layer["attn"]["o_kernel"]),
                },
                "mlp": {
                    **layer["mlp"],
                    "down_kernel": zero(layer["mlp"]["down_kernel"]),
                },
            }

        eparams = dict(params)
        if "layers" in eparams:
            eparams["layers"] = echoify(eparams["layers"])
        else:
            for name in list(eparams):
                if name.startswith("layers_"):
                    eparams[name] = echoify(eparams[name])
        dcfg = JaxDecodeConfig(
            context_length=prompt_len + new_tokens + 128,
            max_running_requests=2,
            new_tokens_per_chunk=chunk or min(128, new_tokens),
            kv_dtype=dt,
            spec_decode="ngram",
            spec_k=spec_k,
            dtype=model.dtype,
            kv_cache_dtype=model.dtype,
        )
        eng = JaxDecodeEngine(
            dcfg, InferenceEngineConfig(max_concurrent_rollouts=4)
        )
        eng.set_model(eparams, model)
        eng.initialize()
        try:
            gg = dataclasses.replace(g, greedy=True)
            with ThreadPoolExecutor(max_workers=2) as pool:
                list(
                    pool.map(
                        lambda p: eng.generate(
                            ModelRequest(input_ids=p, gconfig=gg),
                            timeout=1800,
                        ),
                        prompts[:2],
                    )
                )
            return float(
                eng.get_metrics()["spec_accepted_per_chunk_mean"]
            )
        finally:
            eng.destroy()

    # int8 first: warm-process advantage goes to the fp baseline
    q = throughput("int8")
    f = throughput("fp")
    mig_i8 = migrate_bytes("int8")
    mig_fp = migrate_bytes("fp")

    drift = {}
    for name, gg in (
        ("greedy", dataclasses.replace(g, greedy=True)),
        ("sampled", dataclasses.replace(g, temperature=0.8, top_p=0.9)),
    ):
        fp_rs = streams("fp", gg)
        i8_rs = streams("int8", gg)
        matched = total = 0
        max_dlp = 0.0
        for rf, ri in zip(fp_rs, i8_rs):
            total += max(len(rf.output_tokens), 1)
            for a, b, la, lb in zip(
                rf.output_tokens, ri.output_tokens,
                rf.output_logprobs, ri.output_logprobs,
            ):
                if a != b:
                    break
                matched += 1
                max_dlp = max(max_dlp, abs(la - lb))
        drift[f"kvquant_{name}_token_match_frac"] = (
            round(matched / total, 4) if total else 0.0
        )
        drift[f"kvquant_{name}_max_logprob_delta_matched"] = round(
            max_dlp, 6
        )
    acc_fp = spec_accept("fp")
    acc_i8 = spec_accept("int8")

    return dict(
        kvquant_pool_mb=pool_mb,
        kvquant_fp_pool_tokens=f["pool_tokens"],
        kvquant_int8_pool_tokens=q["pool_tokens"],
        kvquant_fp_resident_sessions=f["resident_sessions"],
        kvquant_int8_resident_sessions=q["resident_sessions"],
        # headline: resident-session (token) capacity at fixed pool MB
        kvquant_capacity_ratio=(
            round(q["pool_tokens"] / f["pool_tokens"], 4)
            if f["pool_tokens"]
            else 0.0
        ),
        kvquant_fp_tok_s=round(f["tok_s"], 2),
        kvquant_int8_tok_s=round(q["tok_s"], 2),
        kvquant_tok_s_ratio=(
            round(q["tok_s"] / f["tok_s"], 4) if f["tok_s"] > 0 else 0.0
        ),
        kvquant_fp_preemptions=f["preemptions"],
        kvquant_int8_preemptions=q["preemptions"],
        kvquant_fp_swap_out_bytes=f["swap_out"],
        kvquant_int8_swap_out_bytes=q["swap_out"],
        kvquant_fp_block_nbytes=f["block_nbytes"],
        kvquant_int8_block_nbytes=q["block_nbytes"],
        # bytes PER BLOCK moved by any swap/migrate hop: the per-unit
        # saving even when absolute swap traffic differs (int8 usually
        # swaps less because more fits resident)
        kvquant_block_bytes_ratio=round(
            f["block_nbytes"] / q["block_nbytes"], 4
        ),
        kvquant_fp_migrate_bytes=mig_fp,
        kvquant_int8_migrate_bytes=mig_i8,
        kvquant_migrate_bytes_ratio=(
            round(mig_fp / mig_i8, 4) if mig_i8 else 0.0
        ),
        kvquant_fp_spec_accept_per_chunk=round(acc_fp, 4),
        kvquant_int8_spec_accept_per_chunk=round(acc_i8, 4),
        kvquant_spec_accept_shift=round(acc_i8 - acc_fp, 4),
        kvquant_sessions=n_sessions,
        kvquant_prompt_len=prompt_len,
        kvquant_new_tokens=new_tokens,
        **drift,
    )


def bench_wquant(model, n_sessions, prompt_len, new_tokens, max_running,
                 pool_mb=0.5, chunk=None, n_push=3):
    """Int8 weight serving vs fp at a FIXED HBM budget (ISSUE 16).

    Three legs, every engine paged, kv_dtype fp throughout so the weight
    knob is the ONLY difference:

    1. **Capacity + throughput at fixed bytes**: both engines get a KV
       pool budget of `pool_mb` PLUS whatever their weight_dtype left of
       the fp weight footprint — int8 kernels (1 byte + one f32 scale per
       output channel) free ~half the dense-kernel bytes, and at a fixed
       HBM budget that headroom IS extra resident KV. Reports pool
       tokens, resident-session capacity, end-to-end tok/s and decode
       ITL for both. The int8 engine runs FIRST so the warm-XLA-process
       advantage goes to the fp baseline. NOTE the decode speedup claim
       (fused dequant-matmul reads half the weight HBM per chunk) is a
       TPU-bandwidth effect; the CPU smoke's XLA fallback pays dequant
       FLOPs instead, so tok_s_ratio here is a floor.
    2. **Wire bytes + commit pause**: the same full tree is framed
       (pack_buckets) as the producer ships it — bf16-cast fp kernels vs
       producer-quantized int8 + f32 scales — and pushed through
       update_weights_from_tensor n_push times per dtype; reports the
       framed wire bytes and the mean install pause, both ~2x smaller
       quantized.
    3. **Drift, measured not assumed**: greedy + sampled streams vs the
       fp oracle (token match fraction, max |logprob delta| over the
       matched prefix). Same random-weights caveat as bench_kvquant: the
       CPU smoke's near-uniform logits are the drift worst case.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.core.weight_transfer import flatten_named, pack_buckets
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import init_params, quantize_weights

    params = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.RandomState(23)
    prompts = [
        rng.randint(1, model.vocab_size, (prompt_len,)).tolist()
        for _ in range(n_sessions)
    ]
    g = GenerationHyperparameters(
        max_new_tokens=new_tokens, temperature=1.0, top_p=1.0
    )
    L = model.num_hidden_layers
    nkv = model.num_key_value_heads
    hd = model.head_dim_
    kv_tok_bytes = 2 * L * nkv * hd * np.dtype(model.dtype).itemsize

    # the wire trees, exactly as the producer ships them: bf16 cast, then
    # (for int8) producer quantization — jax_engine._dcn_payload's order
    bf16 = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        params,
    )
    wire = {
        "fp": flatten_named(bf16),
        "int8": flatten_named(quantize_weights(bf16)),
    }
    weight_bytes = {
        dt: sum(a.nbytes for a in named.values())
        for dt, named in wire.items()
    }
    freed = {
        "fp": 0,
        "int8": weight_bytes["fp"] - weight_bytes["int8"],
    }
    # the ~2x story measured over the kernels that actually quantize
    # (embed/lm_head/norms stay fp and dominate tiny smoke models)
    kern_i8 = kern_fp = 0
    for name, a in wire["int8"].items():
        if name.endswith("/q"):
            base = name[: -len("/q")]
            kern_fp += wire["fp"][base].nbytes
            kern_i8 += a.nbytes + wire["int8"][base + "/scale"].nbytes

    def mk(dt, *, pool_tokens=None, host_mb=0.0, R=max_running):
        dcfg = JaxDecodeConfig(
            context_length=prompt_len + new_tokens + 128,
            max_running_requests=R,
            new_tokens_per_chunk=chunk or min(128, new_tokens),
            weight_dtype=dt,
            kv_pool_tokens=pool_tokens,
            kv_host_pool_mb=host_mb,
            dtype=model.dtype,
            kv_cache_dtype=model.dtype,
        )
        eng = JaxDecodeEngine(
            dcfg, InferenceEngineConfig(max_concurrent_rollouts=n_sessions)
        )
        eng.set_model(params, model)
        eng.initialize()
        return eng

    sess_len = prompt_len + new_tokens

    def throughput(dt: str) -> dict:
        # fixed budget: pool_mb + whatever this dtype freed of the fp
        # weight footprint goes to resident KV
        pool_tokens = int(
            (pool_mb * 1024 * 1024 + freed[dt]) // kv_tok_bytes
        )
        eng = mk(dt, pool_tokens=pool_tokens, host_mb=max(64.0, pool_mb * 4))
        try:
            eng.prewarm(prompt_len=prompt_len, gconfig=g, include_fork=False)
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=n_sessions) as pool:
                rs = list(
                    pool.map(
                        lambda p: eng.generate(
                            ModelRequest(input_ids=p, gconfig=g),
                            timeout=1800,
                        ),
                        prompts,
                    )
                )
            wall = time.perf_counter() - t0
            m = eng.get_metrics()
            toks = sum(len(r.output_tokens) for r in rs)
            return dict(
                pool_tokens=m["kv_pool_tokens_total"],
                resident_sessions=m["kv_pool_tokens_total"] // sess_len,
                tok_s=toks / wall if wall > 0 else 0.0,
                itl_p50_ms=float(m.get("itl_p50_ms", 0.0) or 0.0),
                preemptions=m["preemptions_total"],
            )
        finally:
            eng.destroy()

    def push_pause(dt: str) -> float:
        eng = mk(dt, R=4)
        try:
            # untimed warm push compiles/primes nothing timed below
            eng.update_weights_from_tensor(wire[dt], version=1)
            t0 = time.perf_counter()
            for i in range(n_push):
                eng.update_weights_from_tensor(wire[dt], version=i + 2)
                jax.block_until_ready(eng.params)
            return (time.perf_counter() - t0) / n_push
        finally:
            eng.destroy()

    def streams(dt: str, gg, n=4) -> list:
        eng = mk(dt, R=max_running)
        try:
            with ThreadPoolExecutor(max_workers=n) as pool:
                return list(
                    pool.map(
                        lambda p: eng.generate(
                            ModelRequest(input_ids=p, gconfig=gg),
                            timeout=1800,
                        ),
                        prompts[:n],
                    )
                )
        finally:
            eng.destroy()

    # int8 first: warm-process advantage goes to the fp baseline
    q = throughput("int8")
    f = throughput("fp")
    framed_bytes = {
        dt: sum(len(b) for b in pack_buckets(named, chunk_mb=512))
        for dt, named in wire.items()
    }
    pause_i8 = push_pause("int8")
    pause_fp = push_pause("fp")

    drift = {}
    for name, gg in (
        ("greedy", dataclasses.replace(g, greedy=True)),
        ("sampled", dataclasses.replace(g, temperature=0.8, top_p=0.9)),
    ):
        fp_rs = streams("fp", gg)
        i8_rs = streams("int8", gg)
        matched = total = 0
        max_dlp = 0.0
        for rf, ri in zip(fp_rs, i8_rs):
            total += max(len(rf.output_tokens), 1)
            for a, b, la, lb in zip(
                rf.output_tokens, ri.output_tokens,
                rf.output_logprobs, ri.output_logprobs,
            ):
                if a != b:
                    break
                matched += 1
                max_dlp = max(max_dlp, abs(la - lb))
        drift[f"wquant_{name}_token_match_frac"] = (
            round(matched / total, 4) if total else 0.0
        )
        drift[f"wquant_{name}_max_logprob_delta_matched"] = round(
            max_dlp, 6
        )

    return dict(
        wquant_pool_mb=pool_mb,
        wquant_fp_weight_bytes=weight_bytes["fp"],
        wquant_int8_weight_bytes=weight_bytes["int8"],
        wquant_weight_freed_bytes=freed["int8"],
        wquant_fp_pool_tokens=f["pool_tokens"],
        wquant_int8_pool_tokens=q["pool_tokens"],
        wquant_fp_resident_sessions=f["resident_sessions"],
        wquant_int8_resident_sessions=q["resident_sessions"],
        wquant_capacity_ratio=(
            round(q["pool_tokens"] / f["pool_tokens"], 4)
            if f["pool_tokens"]
            else 0.0
        ),
        wquant_fp_tok_s=round(f["tok_s"], 2),
        wquant_int8_tok_s=round(q["tok_s"], 2),
        wquant_tok_s_ratio=(
            round(q["tok_s"] / f["tok_s"], 4) if f["tok_s"] > 0 else 0.0
        ),
        wquant_fp_itl_p50_ms=round(f["itl_p50_ms"], 3),
        wquant_int8_itl_p50_ms=round(q["itl_p50_ms"], 3),
        wquant_fp_preemptions=f["preemptions"],
        wquant_int8_preemptions=q["preemptions"],
        wquant_fp_wire_bytes=framed_bytes["fp"],
        wquant_int8_wire_bytes=framed_bytes["int8"],
        # headline: framed push bytes, fp over int8 (~2x: int8 data + one
        # f32 scale per output channel vs bf16 kernels)
        wquant_wire_bytes_ratio=(
            round(framed_bytes["fp"] / framed_bytes["int8"], 4)
            if framed_bytes["int8"]
            else 0.0
        ),
        wquant_kernel_wire_bytes_ratio=(
            round(kern_fp / kern_i8, 4) if kern_i8 else 0.0
        ),
        wquant_fp_commit_pause_s=round(pause_fp, 4),
        wquant_int8_commit_pause_s=round(pause_i8, 4),
        wquant_commit_pause_ratio=(
            round(pause_fp / pause_i8, 4) if pause_i8 > 0 else 0.0
        ),
        wquant_sessions=n_sessions,
        wquant_prompt_len=prompt_len,
        wquant_new_tokens=new_tokens,
        **drift,
    )


def bench_fleet(model, n_replicas, n_groups, group_size, prompt_len,
                new_tokens, max_running, chunk=None, turns=2):
    """Fleet router bench (ISSUE 8): prefix-affinity routing vs
    least_requests across in-process decode replicas, plus a mid-trace
    replica kill proving exactly-once failover.

    Trace (identical for both policies, fresh replicas per run): n_groups
    GRPO-style groups of group_size same-prompt members (distinct rids),
    mixed prompt lengths across groups, bursty staggered arrival, and
    `turns` session turns per member (turn k+1 extends turn k's context —
    the multi-turn reuse shape). Prefix affinity should land group members
    and session turns on the replica already holding their donor KV
    (dup-prompt fork / suffix prefill instead of a full prefill), which is
    the mechanism behind the p50 TTFT win; least_requests spreads them
    blindly. The affinity run goes FIRST so any warm-process advantage
    goes to the baseline.

    Failover leg (fresh 2-replica fleet, prefix_affinity): a wave of
    requests starts, one replica is killed mid-trace (HTTP listener down +
    engine aborted), and every request must still complete exactly once —
    the router's health poll requeues the corpse's qids onto the survivor
    and the clients' router-aware retries re-send with the same delivery
    id (xid), which the servers' idempotency table deduplicates. Reported:
    recovery time (kill -> last affected completion), requests lost (must
    be 0), router requeues, and a direct dedup probe (two concurrent
    /generate with one xid -> one generation)."""
    import asyncio
    import threading
    import uuid as _uuid

    import jax

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
        RouterConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.core.remote_inf_engine import RemoteInfEngine
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.launcher.decode_server import DecodeServer
    from areal_tpu.launcher.router import DecodeRouter
    from areal_tpu.utils import name_resolve
    from areal_tpu.utils.http import arequest_with_retry, close_current_session
    from areal_tpu.models.qwen2 import init_params

    name_resolve.reconfigure(name_resolve.NameResolveConfig(type="memory"))
    params = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.RandomState(23)
    plens = [int(prompt_len * f) for f in (1.0, 0.75, 1.25, 0.5)]
    ctx = int(prompt_len * 1.25) + turns * (new_tokens + 8) + 128
    gcfg = GenerationHyperparameters(
        max_new_tokens=new_tokens, temperature=1.0, top_p=1.0
    )
    group_prompts = [
        rng.randint(1, model.vocab_size, (plens[g % len(plens)],)).tolist()
        for g in range(n_groups)
    ]

    def _http_get(addr, ep):
        async def _g():
            try:
                return await arequest_with_retry(
                    addr, ep, method="GET", max_retries=1, timeout=10
                )
            finally:
                await close_current_session()

        return asyncio.run(_g())

    class _Replica:
        """One decode engine + HTTP server on a private loop thread."""

        def __init__(self, warm_plen):
            dcfg = JaxDecodeConfig(
                context_length=ctx,
                max_running_requests=max_running,
                new_tokens_per_chunk=chunk or min(128, new_tokens),
                dtype=model.dtype,
                kv_cache_dtype=model.dtype,
            )
            self.engine = JaxDecodeEngine(dcfg, InferenceEngineConfig())
            self.engine.set_model(params, model)
            self.engine.initialize()
            self.engine.prewarm(prompt_len=warm_plen, gconfig=gcfg)
            self.server = DecodeServer(
                JaxDecodeConfig(), engine=self.engine, shutdown_grace=0.5
            )
            self.addr = None
            self._loop = None
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(60), "fleet replica failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                self.addr = await self.server.start(host="127.0.0.1", port=0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def kill(self):
            """Die like a crashed replica: listener down (in-flight
            handlers cancelled after shutdown_grace), engine aborted."""
            asyncio.run_coroutine_threadsafe(
                self.server.stop(), self._loop
            ).result(30)
            self.engine.pause_generation()
            self.engine.abort_all()

        def stop(self, destroy=True):
            # stop the server first, THEN the loop: a coroutine that stops
            # its own loop strands run_coroutine_threadsafe's completion
            # callback (the future never resolves)
            try:
                asyncio.run_coroutine_threadsafe(
                    self.server.stop(), self._loop
                ).result(30)
            except Exception as e:  # noqa: BLE001 — already killed
                print(f"[fleet] replica stop: {e!r}", file=sys.stderr)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)
            if destroy:
                self.engine.destroy()

    class _RouterThread:
        def __init__(self, policy, servers, exp, trial):
            self.router = DecodeRouter(
                exp,
                trial,
                servers,
                config=RouterConfig(
                    schedule_policy=policy,
                    health_poll_interval=0.25,
                    dead_after_failures=2,
                    queue_timeout_s=30.0,
                ),
            )
            self.addr = None
            self._loop = None
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(30), "fleet router failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                self.addr = await self.router.start("127.0.0.1", 0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def stop(self):
            # two-step (see _Replica.stop): never loop.stop() from inside
            # the awaited coroutine
            asyncio.run_coroutine_threadsafe(
                self.router.stop(), self._loop
            ).result(30)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)

    def _client(exp, trial):
        c = RemoteInfEngine(
            InferenceEngineConfig(
                experiment_name=exp,
                trial_name=trial,
                request_timeout=600,
                request_retries=1,
                fleet_failover_retries=3,
            )
        )
        return c

    def run_policy(policy):
        exp, trial = "benchfleet", f"{policy}-{_uuid.uuid4().hex[:6]}"
        replicas = [_Replica(min(plens)) for _ in range(n_replicas)]
        addrs = [r.addr for r in replicas]
        rt = _RouterThread(policy, addrs, exp, trial)
        client = _client(exp, trial)
        client.addresses = list(addrs)
        ttfts, itls, stats = [], [], {}
        try:
            time.sleep(0.6)  # one poll round: pressure snapshots exist
            # hit-rate baseline AFTER prewarm: its warmup prefills must not
            # dilute the trace's prefix_cache_hit_rate
            m0s = [r.engine.get_metrics() for r in replicas]

            async def member(g, m):
                rid = f"g{g}-m{m}-{_uuid.uuid4().hex[:6]}"
                ids = list(group_prompts[g])
                for _t in range(turns):
                    r = await client.agenerate(
                        ModelRequest(rid=rid, input_ids=ids, gconfig=gcfg)
                    )
                    ttfts.append(r.ttft)
                    if len(r.output_tokens) > 1:
                        itls.append(
                            (r.latency - r.ttft) / (len(r.output_tokens) - 1)
                        )
                    # next turn extends this turn's context (session reuse)
                    ids = ids + list(r.output_tokens) + [7, 11, 13, 17]

            async def group(g):
                # bursty arrival: groups land in waves
                await asyncio.sleep((g % 3) * 0.15)
                await asyncio.gather(
                    *[member(g, m) for m in range(group_size)]
                )

            async def drive():
                try:
                    await asyncio.gather(*[group(g) for g in range(n_groups)])
                finally:
                    await close_current_session()

            t0 = time.perf_counter()
            asyncio.run(drive())
            wall = time.perf_counter() - t0
            hits = tot = 0
            for r, m0 in zip(replicas, m0s):
                m = r.engine.get_metrics()
                h = (
                    m["prefix_forks_total"]
                    - m0["prefix_forks_total"]
                    + m["prefix_inplace_total"]
                    - m0["prefix_inplace_total"]
                    + m["suffix_prefills_total"]
                    - m0["suffix_prefills_total"]
                )
                hits += h
                tot += h + m["prefills_total"] - m0["prefills_total"]
            rm = _http_get(rt.addr, "/metrics")
            tarr = np.asarray(ttfts, dtype=np.float64) * 1e3
            iarr = np.asarray(itls, dtype=np.float64) * 1e3
            stats = dict(
                ttft_p50_ms=float(np.percentile(tarr, 50)),
                ttft_p99_ms=float(np.percentile(tarr, 99)),
                itl_p50_ms=float(np.percentile(iarr, 50)) if iarr.size else 0.0,
                itl_p99_ms=float(np.percentile(iarr, 99)) if iarr.size else 0.0,
                prefix_hit_rate=hits / tot if tot else 0.0,
                router_affinity_hit_rate=rm.get("affinity_hit_rate", 0.0),
                wall_s=wall,
                n_requests=len(ttfts),
            )
        finally:
            rt.stop()
            for r in replicas:
                r.stop()
        return stats

    def run_failover():
        exp, trial = "benchfleet", f"failover-{_uuid.uuid4().hex[:6]}"
        replicas = [_Replica(min(plens)) for _ in range(2)]
        addrs = [r.addr for r in replicas]
        rt = _RouterThread("prefix_affinity", addrs, exp, trial)
        client = _client(exp, trial)
        client.addresses = list(addrs)
        n_reqs = n_groups * group_size
        done_t: dict[str, float] = {}
        results: dict[str, object] = {}
        try:
            time.sleep(0.6)

            async def one(g, m):
                rid = f"fo-g{g}-m{m}"
                r = await client.agenerate(
                    ModelRequest(
                        rid=rid, input_ids=group_prompts[g], gconfig=gcfg
                    )
                )
                results[rid] = r
                done_t[rid] = time.perf_counter()

            kill_box = {}

            async def killer():
                # kill mid-trace: once the fleet has emitted ~20% of the
                # expected tokens (but before everything finishes)
                target = 0.2 * n_reqs * new_tokens
                deadline = time.perf_counter() + 120
                while time.perf_counter() < deadline:
                    emitted = sum(
                        r.engine.get_metrics()["generated_tokens_total"]
                        for r in replicas
                    )
                    # fire mid-trace: enough tokens out, but never wait
                    # past half the wave completing
                    if emitted >= target or len(done_t) >= max(1, n_reqs // 2):
                        break
                    await asyncio.sleep(0.02)
                kill_box["t"] = time.perf_counter()
                await asyncio.get_running_loop().run_in_executor(
                    None, replicas[0].kill
                )

            async def drive():
                try:
                    tasks = [
                        asyncio.create_task(one(g, m))
                        for g in range(n_groups)
                        for m in range(group_size)
                    ]
                    k = asyncio.create_task(killer())
                    await asyncio.gather(*tasks)
                    await k
                finally:
                    await close_current_session()

            asyncio.run(drive())
            lost = sum(
                1
                for r in results.values()
                if len(r.output_tokens) != new_tokens
            ) + (n_reqs - len(results))
            recovery = (
                max(
                    (t for t in done_t.values() if t > kill_box["t"]),
                    default=kill_box["t"],
                )
                - kill_box["t"]
            )
            rm = _http_get(rt.addr, "/metrics")

            # direct rid-dedup probe on the survivor: two concurrent
            # /generate with one xid must produce ONE generation
            sm0 = replicas[1].engine.get_metrics()
            xid = f"dedup-{_uuid.uuid4().hex[:6]}"
            payload = dict(
                rid=xid,
                input_ids=group_prompts[0][:32],
                gconfig=dict(max_new_tokens=4, temperature=1.0),
                xid=xid,
            )

            async def probe():
                try:
                    return await asyncio.gather(
                        *[
                            arequest_with_retry(
                                replicas[1].addr, "/generate",
                                payload=payload, max_retries=1, timeout=120,
                            )
                            for _ in range(2)
                        ]
                    )
                finally:
                    await close_current_session()

            p1, p2 = asyncio.run(probe())
            sm1 = replicas[1].engine.get_metrics()
            dedup_ok = int(
                p1["output_tokens"] == p2["output_tokens"]
                and _http_get(replicas[1].addr, "/metrics")["idem_hits_total"]
                >= 1
            )
            return dict(
                recovery_s=recovery,
                requests=n_reqs,
                completed=len(results),
                lost=lost,
                router_requeues=rm.get("requeues_total", 0),
                router_failovers=rm.get("failovers_total", 0),
                dedup_probe_ok=dedup_ok,
                survivor_prefills=sm1["prefills_total"] - sm0["prefills_total"],
            )
        finally:
            rt.stop()
            replicas[0].stop(destroy=True)
            replicas[1].stop()

    aff = run_policy("prefix_affinity")
    lr = run_policy("least_requests")
    fo = run_failover()
    return dict(
        fleet_replicas=n_replicas,
        fleet_groups=n_groups,
        fleet_group_size=group_size,
        fleet_turns=turns,
        fleet_affinity_ttft_p50_ms=aff["ttft_p50_ms"],
        fleet_affinity_ttft_p99_ms=aff["ttft_p99_ms"],
        fleet_affinity_itl_p50_ms=aff["itl_p50_ms"],
        fleet_affinity_itl_p99_ms=aff["itl_p99_ms"],
        fleet_affinity_prefix_hit_rate=aff["prefix_hit_rate"],
        fleet_affinity_router_hit_rate=aff["router_affinity_hit_rate"],
        fleet_affinity_wall_s=aff["wall_s"],
        fleet_leastreq_ttft_p50_ms=lr["ttft_p50_ms"],
        fleet_leastreq_ttft_p99_ms=lr["ttft_p99_ms"],
        fleet_leastreq_itl_p50_ms=lr["itl_p50_ms"],
        fleet_leastreq_itl_p99_ms=lr["itl_p99_ms"],
        fleet_leastreq_prefix_hit_rate=lr["prefix_hit_rate"],
        fleet_leastreq_wall_s=lr["wall_s"],
        fleet_affinity_ttft_p50_speedup=(
            lr["ttft_p50_ms"] / aff["ttft_p50_ms"]
            if aff["ttft_p50_ms"] > 0
            else 0.0
        ),
        fleet_requests_per_policy=aff["n_requests"],
        fleet_failover_recovery_s=fo["recovery_s"],
        fleet_failover_requests=fo["requests"],
        fleet_failover_completed=fo["completed"],
        fleet_failover_lost=fo["lost"],
        fleet_failover_router_requeues=fo["router_requeues"],
        fleet_failover_router_failovers=fo["router_failovers"],
        fleet_dedup_probe_ok=fo["dedup_probe_ok"],
    )


def bench_disagg(model, n_decode_reqs, n_prefill_reqs, prompt_short,
                 prompt_long, new_tokens, max_running, chunk=None,
                 drain_sessions=4, drain_prompt=96, drain_tokens=48):
    """Disaggregated prefill/decode bench (ISSUE 10).

    Leg 1 — head-of-line ITL: a mixed trace of decode-heavy requests
    (short prompt, long generation) and prefill-heavy requests (long
    prompt, tiny generation) replayed against two equal-size fleets:

      * DISAGG: 1 prefill-role + 1 decode-role replica. The router sends
        every prompt to the prefill replica (prefix affinity), which
        streams the finished KV server->server to the decode replica
        (host-tier import); the decode replica's scheduler NEVER runs a
        transformer prefill between decode chunks.
      * UNIFIED: 2 unified replicas (the same router, classic policy).
        Every long prefill runs inside some replica's scheduler loop,
        stalling every resident decode slot for its duration — the
        head-of-line hit this bench measures.

    Reported: p50/p99 of per-request mean ITL (client-observed wall,
    which includes the stalls the engine's device-only ITL hides) for
    the decode-heavy requests, with the disagg fleet run FIRST so any
    process-warm advantage goes to the unified baseline. Asserted: every
    request completes exactly once with its full token budget on both
    fleets (no lost/duplicated requests).

    Leg 2 — drain migration, with half the sessions greedy and half
    sampled: sessions generate
    mid-stream on replica A, `/drain` parks them (clients see
    stop_reason="interrupt") and streams every parked session to
    replica B, and the resumes run on B. Asserted: B runs ZERO prompt
    prefills (every resume is a host-tier promotion of the migrated
    blocks), and partial+resumed streams are BIT-IDENTICAL to a
    never-interrupted oracle engine (tokens AND logprobs, greedy and
    sampled)."""
    import asyncio
    import threading
    import uuid as _uuid

    import jax

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
        RouterConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.core.remote_inf_engine import RemoteInfEngine
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.launcher.decode_server import DecodeServer
    from areal_tpu.launcher.router import DecodeRouter
    from areal_tpu.utils import name_resolve
    from areal_tpu.utils.http import arequest_with_retry, close_current_session
    from areal_tpu.models.qwen2 import init_params

    name_resolve.reconfigure(name_resolve.NameResolveConfig(type="memory"))
    params = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.RandomState(31)
    n_chunk = chunk or min(128, new_tokens)
    ctx = prompt_long + max(new_tokens, 16) + n_chunk + 128
    decode_prompts = [
        rng.randint(1, model.vocab_size, (prompt_short,)).tolist()
        for _ in range(n_decode_reqs)
    ]
    prefill_prompts = [
        rng.randint(1, model.vocab_size, (prompt_long,)).tolist()
        for _ in range(n_prefill_reqs)
    ]

    def _post(addr, ep, payload, timeout=120):
        async def _p():
            try:
                return await arequest_with_retry(
                    addr, ep, payload=payload, max_retries=1, timeout=timeout
                )
            finally:
                await close_current_session()

        return asyncio.run(_p())

    class _Replica:
        def __init__(self, role="unified", prewarm_plans=(), host_mb=0.0,
                     seed=1):
            dcfg = JaxDecodeConfig(
                context_length=ctx,
                max_running_requests=max_running,
                new_tokens_per_chunk=n_chunk,
                dtype=model.dtype,
                kv_cache_dtype=model.dtype,
                kv_host_pool_mb=host_mb,
                role=role,
                kv_migrate_chunk_mb=8.0,
                random_seed=seed,
            )
            self.engine = JaxDecodeEngine(dcfg, InferenceEngineConfig())
            self.engine.set_model(params, model)
            self.engine.initialize()
            # warm EVERY prompt bucket the trace will hit (short decode
            # prompts AND long prefill prompts) on every replica of both
            # fleets, so the timed window measures scheduling, not
            # first-compiles
            for plen, wcfg in prewarm_plans:
                self.engine.prewarm(prompt_len=plen, gconfig=wcfg)
            # pass the REAL engine config so /health advertises the role
            self.server = DecodeServer(dcfg, engine=self.engine,
                                       shutdown_grace=0.5)
            self.addr = None
            self._loop = None
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(60), "disagg replica failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                self.addr = await self.server.start(host="127.0.0.1", port=0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def stop(self):
            try:
                asyncio.run_coroutine_threadsafe(
                    self.server.stop(), self._loop
                ).result(30)
            except Exception as e:  # noqa: BLE001 — already down
                print(f"[disagg] replica stop: {e!r}", file=sys.stderr)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)
            self.engine.destroy()

    class _RouterThread:
        def __init__(self, servers, exp, trial):
            self.router = DecodeRouter(
                exp,
                trial,
                servers,
                config=RouterConfig(
                    schedule_policy="prefix_affinity",
                    health_poll_interval=0.25,
                    queue_timeout_s=60.0,
                ),
            )
            self.addr = None
            self._loop = None
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(30), "disagg router failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                self.addr = await self.router.start("127.0.0.1", 0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def stop(self):
            asyncio.run_coroutine_threadsafe(
                self.router.stop(), self._loop
            ).result(30)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)

    gcfg_decode = GenerationHyperparameters(
        max_new_tokens=new_tokens, temperature=1.0, top_p=1.0
    )
    gcfg_prefill = GenerationHyperparameters(
        max_new_tokens=8, temperature=1.0, top_p=1.0
    )
    # several sequential long prefills per worker keep prefill pressure on
    # for the WHOLE decode window (one burst would be over before the
    # decode streams finish on small configs)
    prefill_turns = 3

    def run_itl_leg(label, replicas):
        exp, trial = "benchdisagg", f"{label}-{_uuid.uuid4().hex[:6]}"
        addrs = [r.addr for r in replicas]
        rt = _RouterThread(addrs, exp, trial)
        client = RemoteInfEngine(
            InferenceEngineConfig(
                experiment_name=exp,
                trial_name=trial,
                request_timeout=600,
                request_retries=1,
            )
        )
        client.addresses = list(addrs)
        results: dict[str, object] = {}
        try:
            time.sleep(0.8)  # >= one poll round: roles + pressure known
            for r in replicas:
                # percentiles below must describe the TRACE, not prewarm
                r.engine.reset_timing_windows()
            m0s = [r.engine.get_metrics() for r in replicas]

            async def decode_req(i):
                rid = f"d{i}"
                r = await client.agenerate(
                    ModelRequest(
                        rid=rid, input_ids=decode_prompts[i],
                        gconfig=gcfg_decode,
                    )
                )
                assert rid not in results, f"duplicate completion {rid}"
                results[rid] = r

            async def prefill_worker(i):
                # continuous long-prefill pressure landing MID-decode:
                # the head-of-line shape a co-located scheduler serializes
                # in front of every resident decode slot's next chunk
                await asyncio.sleep(0.02 * i)
                for t in range(prefill_turns):
                    rid = f"p{i}-t{t}"
                    r = await client.agenerate(
                        ModelRequest(
                            rid=rid, input_ids=prefill_prompts[i],
                            gconfig=gcfg_prefill,
                        )
                    )
                    assert rid not in results, f"duplicate completion {rid}"
                    results[rid] = r
                    await asyncio.sleep(0.01)

            async def drive():
                try:
                    await asyncio.gather(
                        *[decode_req(i) for i in range(n_decode_reqs)],
                        *[prefill_worker(i) for i in range(n_prefill_reqs)],
                    )
                finally:
                    await close_current_session()

            t0 = time.perf_counter()
            asyncio.run(drive())
            wall = time.perf_counter() - t0
        finally:
            rt.stop()
        # exactly-once: every request completed once with its full budget
        n_expected = n_decode_reqs + n_prefill_reqs * prefill_turns
        assert len(results) == n_expected, f"{label}: lost requests"
        for i in range(n_decode_reqs):
            r = results[f"d{i}"]
            assert len(r.output_tokens) == new_tokens, (
                f"{label}: d{i} truncated ({len(r.output_tokens)})"
            )
        # WALL inter-token latency from the engines that actually decode
        # (ready→ready per emitted token, so the inter-chunk host gap —
        # where a co-located scheduler serializes long prefills — counts;
        # client-side latency/ttft can't see this: the remote protocol is
        # not streaming, so TTFT ≈ latency there). In the disagg fleet
        # every decode chunk runs on the decode-role replica; in the
        # unified fleet both replicas decode, so their windows merge.
        decoding = [
            r for r in replicas
            if r.engine.config.role != "prefill"
        ]
        ms = [r.engine.get_metrics() for r in decoding]
        import itertools as _it

        samples = np.asarray(
            list(
                _it.chain.from_iterable(
                    r.engine._chunk_wall_itl_ms for r in decoding
                )
            ),
            dtype=np.float64,
        )
        return dict(
            itl_p50_ms=(
                float(np.percentile(samples, 50)) if samples.size else 0.0
            ),
            itl_p99_ms=(
                float(np.percentile(samples, 99)) if samples.size else 0.0
            ),
            itl_dev_p99_ms=max(m["itl_p99_ms"] for m in ms),
            wall_s=wall,
            m0s=m0s,
        )

    # -- leg 1: disagg FIRST (warm advantage to the unified baseline) ---
    warm_plans = (
        (prompt_short, gcfg_decode),
        (prompt_long, gcfg_prefill),
    )
    dis_replicas = [
        _Replica(role="prefill", prewarm_plans=warm_plans),
        _Replica(role="decode", prewarm_plans=warm_plans),
    ]
    try:
        disagg = run_itl_leg("disagg", dis_replicas)
        # post-prewarm deltas: what the TRACE did, not the warmup
        dm, d0 = dis_replicas[1].engine.get_metrics(), disagg["m0s"][1]
        pm, p0 = dis_replicas[0].engine.get_metrics(), disagg["m0s"][0]
        decode_trace_prefills = dm["prefills_total"] - d0["prefills_total"]
        disagg_detail = dict(
            decode_replica_prefills=decode_trace_prefills,
            decode_replica_host_hits=(
                dm["kv_host_hits_total"] - d0["kv_host_hits_total"]
            ),
            decode_replica_migrated_in=(
                dm["kv_migrated_in_sessions_total"]
                - d0["kv_migrated_in_sessions_total"]
            ),
            decode_ttft_transfer_p99_ms=dm["ttft_transfer_p99_ms"],
            prefill_replica_prefills=(
                pm["prefills_total"] - p0["prefills_total"]
            ),
            prefill_ttft_prefill_p99_ms=pm["ttft_prefill_p99_ms"],
        )
        # the mechanism itself: the decode replica's scheduler never ran a
        # transformer prompt prefill during the trace — every admission
        # was a host-tier promotion of migrated blocks
        assert decode_trace_prefills == 0, (
            f"decode replica ran {decode_trace_prefills} prefills — "
            "the prefill handoff is not covering the trace"
        )
    finally:
        for r in dis_replicas:
            r.stop()
    uni_replicas = [
        _Replica(role="unified", prewarm_plans=warm_plans) for _ in range(2)
    ]
    try:
        unified = run_itl_leg("unified", uni_replicas)
    finally:
        for r in uni_replicas:
            r.stop()

    # -- leg 2: drain migration, greedy + sampled ----------------------
    def run_drain():
        greedy = GenerationHyperparameters(
            max_new_tokens=drain_tokens, greedy=True
        )
        sampled = GenerationHyperparameters(
            max_new_tokens=drain_tokens, temperature=0.8, top_p=0.9
        )
        gcfgs = [
            greedy if i % 2 == 0 else sampled for i in range(drain_sessions)
        ]
        drng = np.random.RandomState(77)
        prompts = [
            drng.randint(1, model.vocab_size, (drain_prompt,)).tolist()
            for _ in range(drain_sessions)
        ]
        # oracle: never-interrupted runs, same seed + admission order
        oracle_eng = JaxDecodeEngine(
            JaxDecodeConfig(
                context_length=ctx,
                max_running_requests=max_running,
                new_tokens_per_chunk=n_chunk,
                dtype=model.dtype,
                kv_cache_dtype=model.dtype,
                random_seed=7,
            ),
            InferenceEngineConfig(),
        )
        oracle_eng.set_model(params, model)
        oracle_eng.initialize()
        oracle = {}
        try:
            for i in range(drain_sessions):
                r = oracle_eng.generate(
                    ModelRequest(
                        rid=f"s{i}", input_ids=prompts[i], gconfig=gcfgs[i]
                    ),
                    timeout=300,
                )
                oracle[f"s{i}"] = (list(r.output_tokens), list(r.output_logprobs))
        finally:
            oracle_eng.destroy()

        a = _Replica(role="unified", host_mb=256.0, seed=7)
        b = _Replica(role="unified", seed=7)
        try:
            partials: dict[str, dict] = {}
            lock = threading.Lock()

            def submit(i):
                out = _post(
                    a.addr, "/generate",
                    dict(
                        rid=f"s{i}",
                        input_ids=prompts[i],
                        gconfig=dict(
                            max_new_tokens=gcfgs[i].max_new_tokens,
                            greedy=gcfgs[i].greedy,
                            temperature=gcfgs[i].temperature,
                            top_p=gcfgs[i].top_p,
                        ),
                    ),
                    timeout=300,
                )
                with lock:
                    partials[f"s{i}"] = out

            threads = []
            for i in range(drain_sessions):
                t = threading.Thread(target=submit, args=(i,), daemon=True)
                t.start()
                threads.append(t)
                # sequential-enough arrival: admission order (and so the
                # sampling base keys) matches the oracle's
                time.sleep(0.15)
            # drain once every session is admitted and mid-stream
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                m = a.engine.get_metrics()
                if (
                    m["running_requests"] >= drain_sessions
                    and m["generated_tokens_total"] >= drain_sessions
                ):
                    break
                time.sleep(0.02)
            drain_out = _post(
                a.addr, "/drain", {"targets": [b.addr]}, timeout=300
            )
            for t in threads:
                t.join(timeout=120)
            assert len(partials) == drain_sessions, "lost interrupt responses"
            b0 = b.engine.get_metrics()
            full: dict[str, tuple] = {}
            for i in range(drain_sessions):
                rid = f"s{i}"
                part = partials[rid]
                assert part["stop_reason"] == "interrupt", part["stop_reason"]
                resume_ids = prompts[i] + [int(t) for t in part["output_tokens"]]
                left = gcfgs[i].max_new_tokens - len(part["output_tokens"])
                out = _post(
                    b.addr, "/generate",
                    dict(
                        rid=rid,
                        input_ids=resume_ids,
                        gconfig=dict(
                            max_new_tokens=left,
                            greedy=gcfgs[i].greedy,
                            temperature=gcfgs[i].temperature,
                            top_p=gcfgs[i].top_p,
                        ),
                    ),
                    timeout=300,
                )
                full[rid] = (
                    [int(t) for t in part["output_tokens"]]
                    + [int(t) for t in out["output_tokens"]],
                    [float(x) for x in part["output_logprobs"]]
                    + [float(x) for x in out["output_logprobs"]],
                )
            b1 = b.engine.get_metrics()
            mismatched = sum(
                1
                for rid, (toks, lps) in full.items()
                if toks != oracle[rid][0] or lps != oracle[rid][1]
            )
            reprefills = b1["prefills_total"] - b0["prefills_total"]
            assert drain_out["drained"] == drain_sessions, drain_out
            assert drain_out["failed"] == 0, drain_out
            assert reprefills == 0, (
                f"{reprefills} resumes paid a re-prefill"
            )
            assert mismatched == 0, (
                f"{mismatched} drained streams diverged"
            )
            return dict(
                drained=drain_out["drained"],
                kv_bytes=drain_out["bytes"],
                resume_reprefills=reprefills,
                resume_host_hits=(
                    b1["kv_host_hits_total"] - b0["kv_host_hits_total"]
                ),
                reprefill_tokens_avoided=(
                    b1["reprefill_tokens_avoided_total"]
                    - b0["reprefill_tokens_avoided_total"]
                ),
                streams_bitidentical=int(mismatched == 0),
            )
        finally:
            a.stop()
            b.stop()

    drain_paged = run_drain()

    return dict(
        disagg_decode_reqs=n_decode_reqs,
        disagg_prefill_reqs=n_prefill_reqs,
        disagg_itl_p50_ms=disagg["itl_p50_ms"],
        disagg_itl_p99_ms=disagg["itl_p99_ms"],
        disagg_itl_dev_p99_ms=disagg["itl_dev_p99_ms"],
        disagg_wall_s=disagg["wall_s"],
        unified_itl_p50_ms=unified["itl_p50_ms"],
        unified_itl_p99_ms=unified["itl_p99_ms"],
        unified_itl_dev_p99_ms=unified["itl_dev_p99_ms"],
        unified_wall_s=unified["wall_s"],
        disagg_decode_itl_p99_speedup=(
            unified["itl_p99_ms"] / disagg["itl_p99_ms"]
            if disagg["itl_p99_ms"] > 0
            else 0.0
        ),
        disagg_decode_itl_p50_speedup=(
            unified["itl_p50_ms"] / disagg["itl_p50_ms"]
            if disagg["itl_p50_ms"] > 0
            else 0.0
        ),
        **{f"disagg_{k}": v for k, v in disagg_detail.items()},
        **{f"disagg_drain_paged_{k}": v for k, v in drain_paged.items()},
    )


def bench_kvfabric(model, prompt_len, head_len, tail_len, new_tokens,
                   n_dedup, max_running, chunk=8, n_ttft_reps=3,
                   page_size=None, attn_impl=None, seed=47):
    """Fleet KV fabric bench (ISSUE 17).

    Leg 1 — INTRA-REPLICA DEDUP: `n_dedup` requests share a `head_len`
    head but carry DIVERGENT `tail_len` tails, so the rid/tuple-prefix
    donor paths all miss (request i is never a string-prefix of request
    j). The content-addressed block index still satisfies the shared
    head from whichever resident session produced it first. Asserted:
    the fabric engine's streams are token-identical (greedy) to a
    fabric-off oracle that pays `n_dedup` full prefills, with
    `n_dedup-1` local fabric hits and the avoided-token counter covering
    the shared heads.

    Leg 2 — REMOTE FETCH + WARM START: replica A is hot (several
    resident prompts), replica B is cold. One request lands on B with
    the router-style `kv_fabric` hint naming A; B pulls the run over
    /kv_fetch -> /kv_recv -> /kv_commit and serves with a suffix prefill
    (remote attribution, fetched bytes counted as fabric — not
    migration — traffic). B then /warm_start's its pool from A and the
    timed comparison is TTFT (wall of a 1-new-token /generate) of
    warm-started prompts vs same-length fresh prompts: the headline
    `kvfabric_warm_ttft_speedup`. Compile costs are paid by untimed
    warm-up requests on BOTH paths; timed reps report the median.

    Leg 3 — WEIGHT FLIP MID-TRACE: B installs a new weight version
    (parked-prefix invalidation + version bump — the real install
    sequence). A push of A's old-version run is rejected by the
    version-salted content keys (honest miss, 0 stale-block serves) and
    the next /generate on B pays an honest full prefill while staying
    bit-identical to the oracle."""
    import asyncio
    import threading

    import jax

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.core import kv_fabric
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.launcher.decode_server import DecodeServer
    from areal_tpu.utils.http import arequest_with_retry, close_current_session
    from areal_tpu.models.qwen2 import init_params

    params = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.RandomState(seed)
    ctx = prompt_len + new_tokens + chunk + 128
    gcfg = GenerationHyperparameters(max_new_tokens=new_tokens, greedy=True)

    def mk_engine(fabric=True):
        extra = {}
        if page_size is not None:
            extra["page_size"] = page_size
        if attn_impl is not None:
            extra["paged_attn_impl"] = attn_impl
        dcfg = JaxDecodeConfig(
            context_length=ctx,
            max_running_requests=max_running,
            new_tokens_per_chunk=chunk,
            dtype=model.dtype,
            kv_cache_dtype=model.dtype,
            kv_fabric=fabric,
            kv_migrate_chunk_mb=1.0,
            random_seed=1,
            **extra,
        )
        eng = JaxDecodeEngine(dcfg, InferenceEngineConfig())
        eng.set_model(params, model)
        eng.initialize()
        return eng, dcfg

    def _tokens(n):
        return rng.randint(1, model.vocab_size, (n,)).tolist()

    def _chain_of(eng, tokens):
        return kv_fabric.chain_keys(
            tokens,
            eng._alloc.block_size,
            int(eng._version),
            str(eng.config.kv_dtype),
        )

    # ---- leg 1: intra-replica dedup, fabric vs fabric-off oracle ------
    head = _tokens(head_len)
    dedup_prompts = [head + _tokens(tail_len) for _ in range(n_dedup)]

    def run_dedup(fabric):
        eng, _ = mk_engine(fabric=fabric)
        try:
            streams = []
            t0 = time.perf_counter()
            for i, p in enumerate(dedup_prompts):
                r = eng.generate(
                    ModelRequest(rid=f"dd{i}", input_ids=p, gconfig=gcfg),
                    timeout=300,
                )
                streams.append(list(r.output_tokens))
            wall = time.perf_counter() - t0
            return streams, eng.get_metrics(), wall
        finally:
            eng.destroy()

    oracle_streams, oracle_m, oracle_wall = run_dedup(False)
    fabric_streams, fabric_m, fabric_wall = run_dedup(True)
    assert oracle_m["prefills_total"] == n_dedup, (
        "oracle reused the diverging-tail prompts without the fabric: "
        f"{oracle_m['prefills_total']} prefills for {n_dedup} requests"
    )
    assert fabric_streams == oracle_streams, (
        "fabric-deduped streams diverged from the re-prefill oracle"
    )
    dedup_hits = fabric_m["kv_fabric_local_hits_total"]
    dedup_avoided = fabric_m["kv_fabric_local_tokens_avoided_total"]
    assert dedup_hits >= n_dedup - 1, (
        f"only {dedup_hits} local fabric hits for {n_dedup} shared-head "
        "requests"
    )
    assert dedup_avoided >= (n_dedup - 1) * 64, (
        f"local dedup avoided only {dedup_avoided} tokens"
    )

    # ---- legs 2+3: two replicas on the wire ---------------------------
    n_warm = n_ttft_reps + 1  # one untimed warm-up rep per path
    hot_prompts = [_tokens(prompt_len) for _ in range(n_warm)]
    fetch_prompt = _tokens(prompt_len)
    flip_prompt = _tokens(prompt_len)
    cold_prompts = [_tokens(prompt_len) for _ in range(n_warm)]
    ttft_gcfg = dict(max_new_tokens=1, greedy=True)

    ora, _ = mk_engine(fabric=False)
    try:
        fetch_oracle = list(
            ora.generate(
                ModelRequest(rid="fo", input_ids=fetch_prompt, gconfig=gcfg),
                timeout=300,
            ).output_tokens
        )
        flip_oracle = list(
            ora.generate(
                ModelRequest(rid="po", input_ids=flip_prompt, gconfig=gcfg),
                timeout=300,
            ).output_tokens
        )
    finally:
        ora.destroy()

    a_eng, a_cfg = mk_engine()
    b_eng, b_cfg = mk_engine()

    async def _post(addr, ep, payload, timeout=300):
        return await arequest_with_retry(
            addr, ep, payload=payload, max_retries=1, timeout=timeout
        )

    async def _mget(addr):
        return await arequest_with_retry(
            addr, "/metrics", method="GET", max_retries=1, timeout=30
        )

    async def scenario():
        sa = DecodeServer(a_cfg, engine=a_eng, shutdown_grace=0.2)
        sb = DecodeServer(b_cfg, engine=b_eng, shutdown_grace=0.2)
        aa = await sa.start(host="127.0.0.1", port=0)
        ba = await sb.start(host="127.0.0.1", port=0)
        out: dict[str, object] = {}
        try:
            # populate A: the warm-start donors, the fetch run, the
            # flip-leg run. CONCURRENTLY, so each session occupies its
            # own slot — sequential requests would all reuse the lowest
            # free slot and each admission would retire the previous
            # donor's block registration
            await asyncio.gather(
                *[
                    _post(aa, "/generate", dict(
                        rid=f"hot{i}", input_ids=p, gconfig=ttft_gcfg,
                    ))
                    for i, p in enumerate(hot_prompts)
                ],
                _post(aa, "/generate", dict(
                    rid="hotf", input_ids=fetch_prompt,
                    gconfig=dict(max_new_tokens=new_tokens, greedy=True),
                )),
                _post(aa, "/generate", dict(
                    rid="hotp", input_ids=flip_prompt, gconfig=ttft_gcfg,
                )),
            )

            # remote fetch: B serves the request after pulling A's run
            chain = _chain_of(a_eng, fetch_prompt[:-1])
            r = await _post(ba, "/generate", dict(
                rid="rf", input_ids=fetch_prompt,
                gconfig=dict(max_new_tokens=new_tokens, greedy=True),
                kv_fabric=dict(peer=aa, keys=kv_fabric.encode_digest(chain)),
            ))
            out["fetch_stream"] = list(r["output_tokens"])
            out["m_fetch"] = await _mget(ba)

            # cold TTFT: fresh same-length prompts, first rep untimed
            # (pays the prefill compile), median of the rest
            cold_ms = []
            for i, p in enumerate(cold_prompts):
                t0 = time.perf_counter()
                await _post(ba, "/generate", dict(
                    rid=f"cold{i}", input_ids=p, gconfig=ttft_gcfg,
                ))
                if i > 0:
                    cold_ms.append((time.perf_counter() - t0) * 1e3)

            # warm start B's pool from A, then TTFT over the warm-started
            # prompts (first rep untimed: pays the suffix-prefill compile)
            ws = await _post(ba, "/warm_start", dict(
                peers=[aa], max_sessions=max_running,
            ))
            out["warm_start"] = ws
            warm_ms = []
            for i, p in enumerate(hot_prompts):
                t0 = time.perf_counter()
                await _post(ba, "/generate", dict(
                    rid=f"warm{i}", input_ids=p, gconfig=ttft_gcfg,
                ))
                if i > 0:
                    warm_ms.append((time.perf_counter() - t0) * 1e3)
            out["cold_ms"] = cold_ms
            out["warm_ms"] = warm_ms
            out["m_warm"] = await _mget(ba)

            # weight flip mid-trace on B: the real install sequence
            # (parked-prefix invalidation, then the version bump)
            b_eng.pause_generation()
            with b_eng._sched_lock:
                b_eng._invalidate_parked()
            b_eng.continue_generation()
            b_eng.set_version(int(b_eng._version) + 1)
            # A pushes its old-version run: every block must be rejected
            # by the version-salted keys, never committed
            push = await _post(aa, "/kv_fetch", dict(
                keys=kv_fabric.encode_digest(_chain_of(a_eng, flip_prompt[:-1])),
                target=ba,
            ))
            out["flip_push"] = push
            m0 = await _mget(ba)
            r = await _post(ba, "/generate", dict(
                rid="flip", input_ids=flip_prompt,
                gconfig=dict(max_new_tokens=new_tokens, greedy=True),
            ))
            out["flip_stream"] = list(r["output_tokens"])
            m1 = await _mget(ba)
            out["flip_delta"] = {
                k: m1[k] - m0[k]
                for k in (
                    "kv_fabric_local_hits_total",
                    "kv_fabric_remote_hits_total",
                    "kv_fabric_sessions_in_total",
                    "prefills_total",
                )
            }
            out["m_a"] = await _mget(aa)
            out["m_b"] = m1
            return out
        finally:
            await sa.stop()
            await sb.stop()
            await close_current_session()

    def _run_async(coro, timeout=600):
        result: dict[str, object] = {}

        def go():
            try:
                result["v"] = asyncio.run(coro)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                result["e"] = e

        t = threading.Thread(target=go, daemon=True)
        t.start()
        t.join(timeout)
        assert not t.is_alive(), "kvfabric wire scenario timed out"
        if "e" in result:
            raise result["e"]
        return result["v"]

    try:
        wire = _run_async(scenario())
    finally:
        a_eng.destroy()
        b_eng.destroy()

    # remote fetch: bit-identity + attribution
    assert wire["fetch_stream"] == fetch_oracle, (
        "remote-fetched stream diverged from the re-prefill oracle"
    )
    mf = wire["m_fetch"]
    assert mf["kv_fabric"]["fetch_sessions"] >= 1, "the fetch never landed"
    assert mf["kv_fabric"]["fetch_failures"] == 0
    assert mf["kv_fabric_remote_hits_total"] >= 1, (
        "the fetched run was never promoted into the request"
    )
    assert mf["kv_fabric_fetch_bytes_total"] > 0
    assert mf["kv_migrated_in_sessions_total"] == 0, (
        "fabric traffic leaked into the migration counters"
    )

    # warm start: sessions landed and the warm reps hit them
    ws = wire["warm_start"]
    assert ws["sessions"] >= 1 and ws["bytes"] > 0 and ws["failures"] == 0, (
        f"warm start failed: {ws}"
    )
    mw = wire["m_warm"]
    assert mw["kv_fabric_remote_hits_total"] >= 1 + n_ttft_reps, (
        "warm-started prompts re-prefilled instead of hitting the pool"
    )
    cold_ttft_ms = float(np.median(wire["cold_ms"]))
    warm_ttft_ms = float(np.median(wire["warm_ms"]))

    # weight flip: zero stale-block serves, honest full prefill
    fd = wire["flip_delta"]
    stale_serves = (
        fd["kv_fabric_local_hits_total"]
        + fd["kv_fabric_remote_hits_total"]
        + fd["kv_fabric_sessions_in_total"]
    )
    assert stale_serves == 0, (
        f"stale blocks served across the weight flip: {fd}"
    )
    assert fd["prefills_total"] == 1, (
        "the post-flip request did not pay an honest full prefill"
    )
    assert wire["flip_stream"] == flip_oracle, (
        "post-flip stream diverged from the oracle"
    )

    # fleet aggregate (what the router's /metrics sums over pressure):
    # remote fetches alone must account for avoided re-prefill tokens
    ma, mb = wire["m_a"], wire["m_b"]
    fleet_remote_avoided = (
        ma["kv_fabric_remote_tokens_avoided_total"]
        + mb["kv_fabric_remote_tokens_avoided_total"]
    )
    fleet_avoided = (
        ma["reprefill_tokens_avoided_total"]
        + mb["reprefill_tokens_avoided_total"]
    )
    assert fleet_remote_avoided > 0, (
        "no re-prefill tokens were avoided by REMOTE fetches fleet-wide"
    )

    return dict(
        kvfabric_dedup_requests=n_dedup,
        kvfabric_dedup_local_hits=dedup_hits,
        kvfabric_dedup_tokens_avoided=dedup_avoided,
        kvfabric_dedup_frac_prompt_avoided=(
            dedup_avoided / float(sum(len(p) for p in dedup_prompts))
        ),
        kvfabric_dedup_bitidentical=float(fabric_streams == oracle_streams),
        kvfabric_dedup_wall_s=fabric_wall,
        kvfabric_dedup_oracle_wall_s=oracle_wall,
        kvfabric_remote_hits=mb["kv_fabric_remote_hits_total"],
        kvfabric_remote_tokens_avoided=(
            mb["kv_fabric_remote_tokens_avoided_total"]
        ),
        kvfabric_fetch_bytes=mb["kv_fabric_fetch_bytes_total"],
        kvfabric_remote_bitidentical=float(
            wire["fetch_stream"] == fetch_oracle
        ),
        kvfabric_warm_sessions=ws["sessions"],
        kvfabric_warm_bytes=ws["bytes"],
        kvfabric_cold_ttft_ms=cold_ttft_ms,
        kvfabric_warm_ttft_ms=warm_ttft_ms,
        kvfabric_warm_ttft_speedup=(
            cold_ttft_ms / warm_ttft_ms if warm_ttft_ms > 0 else 0.0
        ),
        kvfabric_stale_serves_after_flip=stale_serves,
        kvfabric_flip_bitidentical=float(wire["flip_stream"] == flip_oracle),
        kvfabric_fleet_reprefill_tokens_avoided=fleet_avoided,
        kvfabric_fleet_remote_tokens_avoided=fleet_remote_avoided,
    )


def bench_chaos(model, n_replicas, n_groups, group_size, prompt_len,
                new_tokens, max_running, chunk=None, turns=2, seed=123):
    """Chaos bench (ISSUE 9 tentpole proof): replay the fleet session-reuse
    trace under a seeded fault schedule and assert the system DEGRADES
    instead of corrupting data.

    Two runs over the identical trace (greedy sampling, so every stream is
    a pure function of its prompt — independent of replica placement,
    batch composition, and retry interleaving):

      1. ORACLE — fresh replicas, no injector.
      2. CHAOS  — fresh replicas, `core.fault_injection` armed with a
         seeded plan covering four distinct fault modes on the request
         path: pre-effect aborts (client.http.send — the server never saw
         the request), ERROR-AFTER-EFFECT (client.http.recv — the
         generation landed, the response is lost; only the server's xid
         idempotency table keeps the same-xid transport retry from
         double-generating), torn response bodies (client.http.body — a
         2xx whose JSON is truncated mid-flight), fixed+jittered delays
         (server.generate — the SLOW-replica shape, a replica that answers
         late rather than dying), plus a router.schedule abort (the
         router's own handler failing over to the client's transport
         retry).

    The fleet is DISAGGREGATED (ISSUE 10): one prefill-role replica joins
    the `n_replicas` unified ones, so every request's prompt runs on the
    prefill replica and the KV streams to a decode replica before
    generation. The schedule adds `kv.migrate.send` (sender dies
    mid-stream — the full-session replay under the same xid must land the
    handoff exactly once via interval-merged staging + commit dedup) and
    a torn `kv.migrate.recv` frame (rejected by the manifest length-check
    before a byte stages; the frame retry re-covers it).

    Exactly-once is asserted three ways: every (group, member, turn)
    stream completes exactly once client-side (0 lost, no duplicate
    completion key), every accepted token stream is BIT-IDENTICAL to the
    unfaulted oracle, and engine-side admissions exceed the logical
    request count only by fault-recovery re-prefills (an honest miss —
    a resume landing where its KV is not — re-prefills rather than
    wedging), each traceable to an injected fault: the extra-admission
    count is bounded by the faults fired, and a double-imported or
    abandoned migration would break the bound or the bit-identity.
    Reported: distinct fault modes fired, per-mode
    counters, idempotency replays, and recovery latency (worst per-request
    completion-time inflation vs the oracle — what the injected faults
    cost the requests they hit).

    SUPERVISED leg (ISSUE 13): the same trace runs a third time under a
    FleetSupervisor with every `supervisor.*` seam armed — spawn failures
    (twice, then success), a hung drain (injected delay past the drain
    deadline -> rollback), a supervisor death mid-kill (abort; the next
    tick replans), and health flaps — plus a mid-trace replica kill the
    supervisor must notice and replace. The leg asserts the control plane
    CONVERGES: the dead replica is replaced through the backoff machinery
    (no crash-loop), the surplus replica is eventually drained and
    retired (after one rollback), the fleet lands back at the
    min-capacity floor, and the trace itself stays exactly-once and
    bit-identical to the oracle throughout the churn."""
    import asyncio
    import threading
    import uuid as _uuid

    import jax

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
        RouterConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.core import fault_injection
    from areal_tpu.core.fault_injection import FaultPlan, FaultPoint
    from areal_tpu.core.remote_inf_engine import RemoteInfEngine
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.launcher.decode_server import DecodeServer
    from areal_tpu.launcher.router import DecodeRouter
    from areal_tpu.utils import name_resolve
    from areal_tpu.utils.http import arequest_with_retry, close_current_session
    from areal_tpu.models.qwen2 import init_params

    name_resolve.reconfigure(name_resolve.NameResolveConfig(type="memory"))
    params = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.RandomState(seed)
    plens = [int(prompt_len * f) for f in (1.0, 0.75, 1.25, 0.5)]
    ctx = int(prompt_len * 1.25) + turns * (new_tokens + 8) + 128
    # greedy: the oracle contract — streams depend only on the prompt
    gcfg = GenerationHyperparameters(max_new_tokens=new_tokens, greedy=True)
    group_prompts = [
        rng.randint(1, model.vocab_size, (plens[g % len(plens)],)).tolist()
        for g in range(n_groups)
    ]
    n_logical = n_groups * group_size * turns

    def _http_get(addr, ep):
        async def _g():
            try:
                return await arequest_with_retry(
                    addr, ep, method="GET", max_retries=1, timeout=10
                )
            finally:
                await close_current_session()

        return asyncio.run(_g())

    class _Replica:
        def __init__(self, warm_plen, role="unified"):
            dcfg = JaxDecodeConfig(
                context_length=ctx,
                max_running_requests=max_running,
                new_tokens_per_chunk=chunk or min(128, new_tokens),
                dtype=model.dtype,
                kv_cache_dtype=model.dtype,
                role=role,
                kv_migrate_chunk_mb=0.05,  # several frames per session:
                # gives the kv.migrate fault points mid-stream hits
            )
            self.engine = JaxDecodeEngine(dcfg, InferenceEngineConfig())
            self.engine.set_model(params, model)
            self.engine.initialize()
            self.engine.prewarm(prompt_len=warm_plen, gconfig=gcfg)
            # the real dcfg (not a default) so /health advertises the role
            self.server = DecodeServer(
                dcfg, engine=self.engine, shutdown_grace=0.5
            )
            self.addr = None
            self._loop = None
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(60), "chaos replica failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                self.addr = await self.server.start(host="127.0.0.1", port=0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def admissions(self):
            m = self.engine.get_metrics()
            return (
                m["prefills_total"]
                + m["prefix_forks_total"]
                + m["prefix_inplace_total"]
                + m["suffix_prefills_total"]
            )

        def kill(self):
            """Die like a crashed replica. Idempotent: the supervisor's
            replace path re-kills whatever the bench already killed."""
            if getattr(self, "_killed", False):
                return
            self._killed = True
            asyncio.run_coroutine_threadsafe(
                self.server.stop(), self._loop
            ).result(30)
            self.engine.pause_generation()
            self.engine.abort_all()

        def stop(self):
            try:
                asyncio.run_coroutine_threadsafe(
                    self.server.stop(), self._loop
                ).result(30)
            except Exception as e:  # noqa: BLE001 — already down
                print(f"[chaos] replica stop: {e!r}", file=sys.stderr)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)
            self.engine.destroy()

    class _RouterThread:
        def __init__(self, servers, exp, trial):
            self.router = DecodeRouter(
                exp,
                trial,
                servers,
                config=RouterConfig(
                    schedule_policy="prefix_affinity",
                    health_poll_interval=0.25,
                    dead_after_failures=4,
                    queue_timeout_s=60.0,
                ),
            )
            self.addr = None
            self._loop = None
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(30), "chaos router failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                self.addr = await self.router.start("127.0.0.1", 0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def stop(self):
            asyncio.run_coroutine_threadsafe(
                self.router.stop(), self._loop
            ).result(30)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)

    def run_trace(label, plan):
        exp, trial = "benchchaos", f"{label}-{_uuid.uuid4().hex[:6]}"
        # disaggregated fleet: n_replicas unified (decode-capable) + one
        # prefill-role replica every prompt runs on; identical for oracle
        # and chaos runs, so greedy streams stay a pure function of the
        # prompt regardless of which faults fire on the handoff path
        replicas = [_Replica(min(plens)) for _ in range(n_replicas)]
        replicas.append(_Replica(min(plens), role="prefill"))
        addrs = [r.addr for r in replicas]
        rt = _RouterThread(addrs, exp, trial)
        client = RemoteInfEngine(
            InferenceEngineConfig(
                experiment_name=exp,
                trial_name=trial,
                request_timeout=300,
                request_retries=3,
                fleet_failover_retries=2,
            )
        )
        client.addresses = list(addrs)
        streams: dict = {}
        lat: dict = {}
        out: dict = {}
        # arm AFTER replica prewarm/startup so the schedule perturbs the
        # trace, not the fixture setup
        fault_injection.configure(plan)
        try:
            time.sleep(0.6)  # one poll round
            adm0 = sum(r.admissions() for r in replicas)
            _ADM_KEYS = ("prefills_total", "prefix_forks_total",
                         "prefix_inplace_total", "suffix_prefills_total")
            adm_base = [
                {k: r.engine.get_metrics()[k] for k in _ADM_KEYS}
                for r in replicas
            ]

            async def member(g, m):
                rid = f"c{g}-m{m}"
                ids = list(group_prompts[g])
                for t in range(turns):
                    t0 = time.perf_counter()
                    r = await client.agenerate(
                        ModelRequest(rid=rid, input_ids=ids, gconfig=gcfg)
                    )
                    key = (g, m, t)
                    assert key not in streams, f"duplicate completion {key}"
                    streams[key] = tuple(r.output_tokens)
                    lat[key] = time.perf_counter() - t0
                    ids = ids + list(r.output_tokens) + [7, 11, 13, 17]

            async def group(g):
                await asyncio.sleep((g % 3) * 0.1)
                await asyncio.gather(
                    *[member(g, m) for m in range(group_size)]
                )

            async def drive():
                try:
                    await asyncio.gather(*[group(g) for g in range(n_groups)])
                finally:
                    await close_current_session()

            t0 = time.perf_counter()
            asyncio.run(drive())
            out["wall_s"] = time.perf_counter() - t0
            out["streams"] = streams
            out["lat"] = lat
            out["admissions"] = sum(r.admissions() for r in replicas) - adm0
            # per-replica admission-counter deltas: when the exactly-once
            # assert trips, this names the replica and path that
            # over-admitted instead of leaving a bare count
            out["admission_detail"] = [
                {
                    "addr": r.addr,
                    "role": getattr(r.engine.config, "role", "unified"),
                    **{
                        k: r.engine.get_metrics()[k] - adm_base[i][k]
                        for k in _ADM_KEYS
                    },
                }
                for i, r in enumerate(replicas)
            ]
            out["idem_hits"] = sum(
                _http_get(r.addr, "/metrics")["idem_hits_total"]
                for r in replicas
            )
            out["migrated_in"] = sum(
                r.engine.get_metrics()["kv_migrated_in_sessions_total"]
                for r in replicas
            )
            out["migrate_dedups"] = sum(
                _http_get(r.addr, "/metrics")["kv_migrate"]["commit_dedups"]
                for r in replicas
            )
            out["router_metrics"] = _http_get(rt.addr, "/metrics")
            out["fault_counters"] = fault_injection.snapshot()
        finally:
            fault_injection.deactivate()
            rt.stop()
            for r in replicas:
                r.stop()
        return out

    class _SupervisorThread:
        """FleetSupervisor on its own loop thread: it owns spawn / drain /
        kill scheduling while the bench thread only reads get_metrics()."""

        def __init__(self, sup):
            self.sup = sup
            self._loop = None
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(30), "supervisor failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                await self.sup.start(host="127.0.0.1", port=0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def stop(self):
            asyncio.run_coroutine_threadsafe(
                self.sup.stop(), self._loop
            ).result(30)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)

    def run_supervised(plan, kill_delay):
        """Chaos leg 3: the trace under a FleetSupervisor with the
        supervisor.* seams armed plus a mid-trace replica kill. The fleet
        starts one replica ABOVE the floor so the supervisor has a
        legitimate scale-down to attempt (whose first drain hangs and
        rolls back) while the kill forces a replace (whose first spawn
        attempts fail). Membership is discovery-driven: the router seeds
        no servers and follows the supervisor's name_resolve
        registrations, so a retired replica actually leaves rotation."""
        from areal_tpu.api.cli_args import SupervisorConfig
        from areal_tpu.launcher.supervisor import FleetSupervisor

        exp, trial = "benchchaos", f"sup-{_uuid.uuid4().hex[:6]}"
        replicas = [_Replica(min(plens)) for _ in range(n_replicas + 1)]
        spawned: list = []
        spawn_lock = threading.Lock()
        rt = _RouterThread([], exp, trial)

        def spawn_fn(role):
            r = _Replica(min(plens), role=role)
            with spawn_lock:
                spawned.append(r)
            return r

        scfg = SupervisorConfig(
            enabled=True,
            tick_interval_s=0.25,
            min_replicas=n_replicas,
            max_replicas=n_replicas + 1,
            util_inflight_target=max_running,
            scale_up_util=0.9,
            scale_down_util=0.35,
            scale_up_queue_depth=3,
            scale_up_cooldown_s=1.0,
            scale_down_cooldown_s=1.0,
            replace_cooldown_s=0.5,
            rerole_enabled=False,  # unified fleet: topology stays put
            spawn_max_attempts=4,  # 2 injected failures + margin
            spawn_backoff_s=0.2,
            spawn_backoff_max_s=1.0,
            drain_deadline_s=3.0,
            health_fail_threshold=2,
            health_timeout_s=2.0,
        )
        sup = FleetSupervisor(
            rt.addr,
            spawn_fn,
            config=scfg,
            experiment_name=exp,
            trial_name=trial,
        )
        for r in replicas:
            sup.adopt(r, role="unified")
        st = _SupervisorThread(sup)
        client = RemoteInfEngine(
            InferenceEngineConfig(
                experiment_name=exp,
                trial_name=trial,
                request_timeout=300,
                request_retries=3,
                fleet_failover_retries=2,
            )
        )
        client.addresses = [r.addr for r in replicas]
        streams: dict = {}
        fault_injection.configure(plan)
        try:
            time.sleep(0.75)  # discovery + one poll round

            async def member(g, m):
                rid = f"s{g}-m{m}"
                ids = list(group_prompts[g])
                for t in range(turns):
                    r = await client.agenerate(
                        ModelRequest(rid=rid, input_ids=ids, gconfig=gcfg)
                    )
                    key = (g, m, t)
                    assert key not in streams, f"duplicate completion {key}"
                    streams[key] = tuple(r.output_tokens)
                    ids = ids + list(r.output_tokens) + [7, 11, 13, 17]

            async def group(g):
                await asyncio.sleep((g % 3) * 0.1)
                await asyncio.gather(
                    *[member(g, m) for m in range(group_size)]
                )

            async def killer():
                await asyncio.sleep(kill_delay)
                victim = replicas[min(1, len(replicas) - 1)]
                print(
                    f"[chaos] supervised: killing {victim.addr}",
                    file=sys.stderr,
                )
                await asyncio.get_running_loop().run_in_executor(
                    None, victim.kill
                )

            async def drive():
                try:
                    k = asyncio.ensure_future(killer())
                    await asyncio.gather(*[group(g) for g in range(n_groups)])
                    await k
                finally:
                    await close_current_session()

            t0 = time.perf_counter()
            asyncio.run(drive())
            wall = time.perf_counter() - t0

            # convergence: the replace (through 2 spawn failures), the
            # rolled-back-then-committed scale-down, and a fleet back at
            # the floor with nothing in flight
            deadline_t = time.monotonic() + 60
            while time.monotonic() < deadline_t:
                m = sup.get_metrics()
                if (
                    m["replacements_total"] >= 1
                    and m["scale_downs_total"] >= 1
                    and m["drain_rollbacks_total"] >= 1
                    and m["spawn_failures_total"] >= 2
                    and m["fleet_alive"] == n_replicas
                    and m["pending_spawns"] == 0
                    and m["disruptive_inflight"] == 0
                ):
                    break
                time.sleep(0.25)
            sup_metrics = sup.get_metrics()
            sup_body = _http_get(sup.addr, "/supervisor")
            counters = fault_injection.snapshot()
        finally:
            fault_injection.deactivate()
            st.stop()
            rt.stop()
            with spawn_lock:
                fleet = replicas + spawned
            for r in fleet:
                r.stop()
        return dict(
            streams=streams,
            wall_s=wall,
            sup=sup_metrics,
            sup_body=sup_body,
            fault_counters=counters,
        )

    # seeded schedule: >= 4 distinct modes on the request path. Explicit
    # hit indices (`at`) guarantee each mode actually fires on any trace
    # with a handful of requests; `times` bounds repeated firing.
    plan = FaultPlan(
        seed=seed,
        points=[
            FaultPoint(site="client.http.send", mode="abort",
                       at=(1, 6), times=2,
                       match={"endpoint": "/generate"}),
            FaultPoint(site="client.http.recv", mode="error_after_effect",
                       at=(0, 4), times=2,
                       match={"endpoint": "/generate"}),
            FaultPoint(site="client.http.body", mode="torn",
                       at=(2,), times=1,
                       match={"endpoint": "/generate"}),
            FaultPoint(site="server.generate", mode="delay",
                       at=(3, 8), times=2, delay_s=0.2, jitter_s=0.1),
            FaultPoint(site="router.schedule", mode="abort",
                       at=(2,), times=1),
            # the disaggregated handoff path (ISSUE 10): a sender dying
            # mid-KV-stream — the full-session replay under the same xid
            # must land the handoff exactly once — and a torn KV frame the
            # receiver's manifest length-check rejects before staging
            FaultPoint(site="kv.migrate.send", mode="abort",
                       at=(1,), times=1),
            FaultPoint(site="kv.migrate.recv", mode="torn",
                       at=(4,), times=1),
        ],
    )

    oracle = run_trace("oracle", None)
    chaos = run_trace("chaos", plan)

    assert len(oracle["streams"]) == n_logical, "oracle lost requests"
    lost = n_logical - len(chaos["streams"])
    mismatched = sum(
        1
        for k, v in oracle["streams"].items()
        if chaos["streams"].get(k) != v
    )
    extra_admissions = chaos["admissions"] - n_logical
    counters = chaos["fault_counters"]
    modes_fired = {k.split("|")[1] for k in counters}
    faults_total = sum(counters.values())
    # worst per-request completion-time inflation vs the unfaulted oracle:
    # what the injected faults cost the requests they hit (retries, replay
    # round-trips, injected delay)
    recovery_max_s = max(
        chaos["lat"][k] - oracle["lat"][k] for k in oracle["lat"]
    )
    assert lost == 0, f"chaos lost {lost} requests"
    assert mismatched == 0, (
        f"{mismatched} streams diverged from the unfaulted oracle"
    )
    # Engine-side exactly-once, split by kind. Extra admissions beyond
    # the logical count are the HONEST-MISS recovery path (a fault lands
    # a resume where its migrated KV is not — schedule abort before the
    # affinity was recorded, failover off an aborted target — and the
    # replica re-prefills rather than wedging; streams stay bit-identical
    # so it is wasted work, never duplicated output). Each such re-prefill
    # must be traceable to an injected fault: negative (lost work) or
    # more re-prefills than faults means real double-generation.
    assert 0 <= extra_admissions <= faults_total, (
        f"{extra_admissions} extra engine-side admissions with only "
        f"{faults_total} injected faults: {chaos['admission_detail']}"
    )
    assert {"abort", "error_after_effect", "delay", "torn"} <= modes_fired, (
        f"schedule only exercised {sorted(modes_fired)}"
    )
    assert chaos["idem_hits"] >= 1, (
        "error-after-effect never exercised the idempotency replay"
    )
    kv_faults = {
        k: v for k, v in counters.items() if k.startswith("kv.migrate")
    }
    assert kv_faults, "kv.migrate fault points never fired"
    assert chaos["migrated_in"] >= 1, (
        "no KV session ever migrated — the handoff path went untested"
    )

    # leg 3: the control plane under fire (ISSUE 13). Seam indices:
    # spawn 0,1 = the replace's first two attempts; drain 0 = the first
    # scale-down's drain (hung past the 3 s deadline -> rollback); kill 0
    # = the supervisor dying mid-transition (the next tick replans; the
    # /drain in-progress guard + idempotent re-drain make the retry
    # safe); health 2,4 land on different replicas in consecutive ticks
    # (single-probe flaps, below the dead threshold).
    sup_plan = FaultPlan(
        seed=seed + 1,
        points=[
            FaultPoint(site="supervisor.spawn", mode="abort",
                       at=(0, 1), times=2),
            FaultPoint(site="supervisor.drain", mode="delay",
                       at=(0,), times=1, delay_s=8.0),
            FaultPoint(site="supervisor.kill", mode="abort",
                       at=(0,), times=1),
            FaultPoint(site="supervisor.health", mode="abort",
                       at=(2, 4), times=2),
        ],
    )
    kill_delay = min(2.0, max(0.5, 0.4 * oracle["wall_s"]))
    supervised = run_supervised(sup_plan, kill_delay)

    sup_lost = n_logical - len(supervised["streams"])
    sup_mismatched = sum(
        1
        for k, v in oracle["streams"].items()
        if supervised["streams"].get(k) != v
    )
    sup_counters = supervised["fault_counters"]
    sup_sites = {k.split("|")[0] for k in sup_counters}
    sup_m = supervised["sup"]
    assert sup_lost == 0, f"supervised leg lost {sup_lost} requests"
    assert sup_mismatched == 0, (
        f"{sup_mismatched} supervised streams diverged from the oracle"
    )
    assert {
        "supervisor.spawn",
        "supervisor.drain",
        "supervisor.kill",
        "supervisor.health",
    } <= sup_sites, f"supervisor seams unexercised: {sorted(sup_sites)}"
    assert sup_m["replacements_total"] >= 1, (
        "the killed replica was never replaced"
    )
    assert sup_m["spawn_failures_total"] >= 2, (
        "injected spawn failures never hit the backoff machinery"
    )
    assert sup_m["crash_loops_total"] == 0, (
        "the replace crash-looped instead of recovering"
    )
    assert sup_m["drain_rollbacks_total"] >= 1, (
        "the hung drain never rolled an action back"
    )
    assert sup_m["scale_downs_total"] >= 1, (
        "the surplus replica was never retired"
    )
    assert (
        sup_m["fleet_alive"] == n_replicas
        and sup_m["pending_spawns"] == 0
    ), f"fleet failed to converge to the floor: {sup_m}"
    sup_alive_slots = [
        s for s in supervised["sup_body"]["slots"] if s["alive"]
    ]
    assert len(sup_alive_slots) == n_replicas, (
        f"/supervisor reports {len(sup_alive_slots)} alive slots"
    )

    # leg 4: the fabric fetch path under fire (ISSUE 17). Self-contained
    # two-peer scenarios off the trace; the same kv.migrate.* seams that
    # cover session migration cover fabric fetches (shared _stream_kv
    # wire). TORN: the fetch's first /kv_recv frame is torn — the frame
    # retry re-covers it and staging interval-merge + commit dedup land
    # the run EXACTLY ONCE. ABORT: every send attempt dies (past the
    # replay budget) — the serving side abandons the stream and the
    # requesting replica DEGRADES to a local full prefill, bit-identical,
    # with zero fabric sessions imported (no torn half-run ever serves).
    from areal_tpu.core import kv_fabric

    def mk_fabric_engine():
        dcfg = JaxDecodeConfig(
            context_length=ctx,
            max_running_requests=max_running,
            new_tokens_per_chunk=chunk or min(128, new_tokens),
            dtype=model.dtype,
            kv_cache_dtype=model.dtype,
            page_size=16,  # 96-token smoke prompts span >= 5 complete
            # blocks — past the 64-token fabric floor
            paged_attn_impl="xla",
            kv_migrate_chunk_mb=0.05,  # several frames per fetch: the
            # seams land mid-stream
        )
        eng = JaxDecodeEngine(dcfg, InferenceEngineConfig())
        eng.set_model(params, model)
        eng.initialize()
        return eng, dcfg

    fab_prompt = rng.randint(1, model.vocab_size, (prompt_len,)).tolist()
    fa_eng, fa_cfg = mk_fabric_engine()
    fb_eng, fb_cfg = mk_fabric_engine()
    fc_eng, fc_cfg = mk_fabric_engine()

    torn_plan = FaultPlan(
        seed=seed + 2,
        points=[
            FaultPoint(site="kv.migrate.recv", mode="torn",
                       at=(0,), times=1),
        ],
    )
    abort_plan = FaultPlan(
        seed=seed + 3,
        points=[
            # all three send attempts (retries=2) die: past the budget
            FaultPoint(site="kv.migrate.send", mode="abort",
                       at=(0, 1, 2), times=3),
        ],
    )

    async def fabric_scenario():
        sa = DecodeServer(fa_cfg, engine=fa_eng, shutdown_grace=0.2)
        sb = DecodeServer(fb_cfg, engine=fb_eng, shutdown_grace=0.2)
        sc = DecodeServer(fc_cfg, engine=fc_eng, shutdown_grace=0.2)
        aa = await sa.start(host="127.0.0.1", port=0)
        ba = await sb.start(host="127.0.0.1", port=0)
        ca = await sc.start(host="127.0.0.1", port=0)
        out: dict[str, object] = {}
        try:
            gpayload = dict(max_new_tokens=new_tokens, greedy=True)
            # A pays the one full prefill: its stream is the oracle
            r = await arequest_with_retry(
                aa, "/generate",
                payload=dict(rid="fa", input_ids=fab_prompt,
                             gconfig=gpayload),
                max_retries=1, timeout=300,
            )
            out["oracle"] = list(r["output_tokens"])
            hint = dict(
                peer=aa,
                keys=kv_fabric.encode_digest(kv_fabric.chain_keys(
                    fab_prompt[:-1],
                    fa_eng._alloc.block_size,
                    int(fa_eng._version),
                    str(fa_eng.config.kv_dtype),
                )),
            )
            fault_injection.configure(torn_plan)
            try:
                r = await arequest_with_retry(
                    ba, "/generate",
                    payload=dict(rid="fb", input_ids=fab_prompt,
                                 gconfig=gpayload, kv_fabric=hint),
                    max_retries=1, timeout=300,
                )
            finally:
                out["torn_counters"] = fault_injection.snapshot()
                fault_injection.deactivate()
            out["torn_stream"] = list(r["output_tokens"])
            out["m_torn"] = await arequest_with_retry(
                ba, "/metrics", method="GET", max_retries=1, timeout=30
            )
            fault_injection.configure(abort_plan)
            try:
                r = await arequest_with_retry(
                    ca, "/generate",
                    payload=dict(rid="fc", input_ids=fab_prompt,
                                 gconfig=gpayload, kv_fabric=hint),
                    max_retries=1, timeout=300,
                )
            finally:
                out["abort_counters"] = fault_injection.snapshot()
                fault_injection.deactivate()
            out["abort_stream"] = list(r["output_tokens"])
            out["m_abort"] = await arequest_with_retry(
                ca, "/metrics", method="GET", max_retries=1, timeout=30
            )
            out["m_serve"] = await arequest_with_retry(
                aa, "/metrics", method="GET", max_retries=1, timeout=30
            )
            return out
        finally:
            await sa.stop()
            await sb.stop()
            await sc.stop()
            await close_current_session()

    try:
        fab = asyncio.run(fabric_scenario())
    finally:
        fa_eng.destroy()
        fb_eng.destroy()
        fc_eng.destroy()

    torn_faults = {
        k: int(v)
        for k, v in fab["torn_counters"].items()
        if k.startswith("kv.migrate")
    }
    abort_faults = {
        k: int(v)
        for k, v in fab["abort_counters"].items()
        if k.startswith("kv.migrate")
    }
    assert torn_faults, "the torn fabric-fetch fault never fired"
    assert sum(abort_faults.values()) >= 3, (
        f"abort seam fired {abort_faults}: the fetch replay budget was "
        "never exhausted"
    )
    mt, mab, msv = fab["m_torn"], fab["m_abort"], fab["m_serve"]
    # torn frame -> replay -> exactly once: one committed fabric session,
    # one remote hit, the stream bit-identical to the full-prefill oracle
    assert fab["torn_stream"] == fab["oracle"], (
        "torn-then-replayed fabric fetch corrupted the stream"
    )
    assert mt["kv_fabric_sessions_in_total"] == 1, (
        f"torn fetch landed {mt['kv_fabric_sessions_in_total']} sessions "
        "(exactly-once violated)"
    )
    assert mt["kv_fabric_remote_hits_total"] == 1
    assert mt["kv_fabric"]["fetch_failures"] == 0
    # aborted fetch -> degraded to a LOCAL full prefill: zero fabric
    # sessions imported, zero fabric hits, one honest prefill, the
    # stream still bit-identical
    assert fab["abort_stream"] == fab["oracle"], (
        "the degraded (aborted-fetch) request corrupted the stream"
    )
    assert mab["kv_fabric_sessions_in_total"] == 0, (
        "an aborted fetch still imported a fabric session"
    )
    assert mab["kv_fabric_remote_hits_total"] == 0
    assert mab["kv_fabric_local_hits_total"] == 0
    assert mab["prefills_total"] == 1, (
        f"{mab['prefills_total']} prefills on the degraded replica: the "
        "re-prefill ran more (or less) than exactly once"
    )
    assert msv["kv_migrate"]["out_failures"] >= 1, (
        "the serving side never recorded the abandoned fetch stream"
    )

    rm = chaos["router_metrics"]
    return dict(
        chaos_replicas=n_replicas,
        chaos_requests=n_logical,
        chaos_lost=lost,
        chaos_recovery_reprefills=extra_admissions,
        chaos_streams_bitidentical=int(mismatched == 0),
        chaos_exactly_once=float(
            lost == 0
            and mismatched == 0
            and 0 <= extra_admissions <= faults_total
        ),
        chaos_fault_modes_fired=len(modes_fired),
        chaos_faults_injected=faults_total,
        chaos_idem_replays=chaos["idem_hits"],
        chaos_kv_migrated_sessions=chaos["migrated_in"],
        chaos_kv_migrate_commit_dedups=chaos["migrate_dedups"],
        chaos_kv_migrate_faults={k: int(v) for k, v in sorted(kv_faults.items())},
        chaos_recovery_max_s=recovery_max_s,
        chaos_oracle_wall_s=oracle["wall_s"],
        chaos_wall_s=chaos["wall_s"],
        chaos_router_requeues=rm.get("requeues_total", 0),
        chaos_router_queue_sheds=rm.get("queue_sheds_total", 0),
        chaos_fault_counters={k: int(v) for k, v in sorted(counters.items())},
        chaos_supervised_exactly_once=float(
            sup_lost == 0 and sup_mismatched == 0
        ),
        chaos_supervised_wall_s=supervised["wall_s"],
        chaos_supervised_replacements=sup_m["replacements_total"],
        chaos_supervised_spawn_failures=sup_m["spawn_failures_total"],
        chaos_supervised_crash_loops=sup_m["crash_loops_total"],
        chaos_supervised_drain_rollbacks=sup_m["drain_rollbacks_total"],
        chaos_supervised_scale_downs=sup_m["scale_downs_total"],
        chaos_supervised_health_flaps=sup_m["health_flaps_total"],
        chaos_supervised_fleet_alive=sup_m["fleet_alive"],
        chaos_supervisor_faults={
            k: int(v)
            for k, v in sorted(sup_counters.items())
            if k.startswith("supervisor.")
        },
        chaos_fabric_torn_sessions_in=mt["kv_fabric_sessions_in_total"],
        chaos_fabric_torn_remote_hits=mt["kv_fabric_remote_hits_total"],
        chaos_fabric_abort_sessions_in=mab["kv_fabric_sessions_in_total"],
        chaos_fabric_abort_reprefills=mab["prefills_total"],
        chaos_fabric_streams_bitidentical=float(
            fab["torn_stream"] == fab["oracle"]
            and fab["abort_stream"] == fab["oracle"]
        ),
        chaos_fabric_exactly_once=float(
            mt["kv_fabric_sessions_in_total"] == 1
            and mab["kv_fabric_sessions_in_total"] == 0
            and mab["prefills_total"] == 1
        ),
        chaos_fabric_faults={
            **{f"torn:{k}": v for k, v in sorted(torn_faults.items())},
            **{f"abort:{k}": v for k, v in sorted(abort_faults.items())},
        },
    )


def bench_autoscale(model, n_base, n_peak, n_groups, group_size, prompt_len,
                    new_tokens, max_running, chunk=None, lull_gap=0.7,
                    kill_after_s=2.0, slo_band=1.10, itl_grace_ms=0.0,
                    seed=321):
    """Autoscale bench (ISSUE 13 headline): a bursty diurnal trace with a
    mid-trace replica kill, served twice.

      SUPERVISED — the fleet starts at the `n_base` floor under a
        FleetSupervisor (max `n_peak`). The burst builds queue/util
        pressure that scales the fleet up; the killed replica is
        replaced through the spawn machinery; the trailing lull scales
        the surplus back down. Membership is discovery-driven (router
        seeds no servers; the supervisor registers/deregisters replicas
        in name_resolve), so retired capacity actually leaves rotation.
        Spawns come from a WARM POOL (pre-built spares `spawn_fn` pops,
        falling back to a cold build when the pool runs dry) — the
        standard warm-pool autoscaling model: the bench measures the
        control plane's decisions and exactly-once guarantees, not
        engine boot time, and the bill counts only replicas standing IN
        the fleet.
      STATIC — the best static provisioning: `n_peak` replicas from the
        first request. It takes the same mid-burst kill and (having no
        control plane) runs the rest of the trace a replica short,
        surviving on the router's failover.

    The trace is diurnal: a leading lull (groups spaced `lull_gap` s
    apart), a burst (the middle ~40% of groups arriving nearly at once),
    a trailing lull. The kill lands `kill_after_s` into the burst — late
    enough that the supervised fleet has scaled toward the peak, so both
    fleets lose a replica that was doing real work.

    Claim proved by the assertions: the supervised fleet MATCHES the
    static fleet's client-observed p99 TTFT and wall-ITL (within a 10%
    noise band — it typically wins the burst tail, because it ends the
    burst at full peak while the static fleet stays a replica short) at
    MATERIALLY fewer replica-seconds. Billing: the static bill is the
    peak reservation (`n_peak x wall` — a static deployment pays for
    capacity whether or not a crash idles it; its alive-seconds are also
    reported), the supervised bill is the supervisor's integral of
    replicas actually standing. Exactly-once: every request completes
    exactly once in both runs, and the two runs' greedy streams are
    bit-identical to each other (placement- and churn-independent).

    Client-observed SLO decomposition: per request, wall = client
    completion time, decode span = engine latency minus engine TTFT, so
    `wall - decode_span` is the wall TTFT (router queueing, scheduling,
    failover retries included — exactly what a static-vs-elastic fleet
    changes) and decode_span / (tokens - 1) is the wall ITL."""
    import asyncio
    import threading
    import uuid as _uuid

    import jax

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
        RouterConfig,
        SupervisorConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.core.remote_inf_engine import RemoteInfEngine
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.launcher.decode_server import DecodeServer
    from areal_tpu.launcher.router import DecodeRouter
    from areal_tpu.launcher.supervisor import FleetSupervisor
    from areal_tpu.utils import name_resolve
    from areal_tpu.utils.http import close_current_session
    from areal_tpu.models.qwen2 import init_params

    assert 1 <= n_base < n_peak, "need headroom between floor and peak"
    name_resolve.reconfigure(name_resolve.NameResolveConfig(type="memory"))
    params = init_params(model, jax.random.PRNGKey(0))
    rng = np.random.RandomState(seed)
    ctx = prompt_len + new_tokens + 128
    gcfg = GenerationHyperparameters(max_new_tokens=new_tokens, greedy=True)
    group_prompts = [
        rng.randint(1, model.vocab_size, (prompt_len,)).tolist()
        for _ in range(n_groups)
    ]
    n_logical = n_groups * group_size

    # diurnal arrival plan: lull / burst / lull by group index
    burst_lo, burst_hi = int(n_groups * 0.3), int(n_groups * 0.7)
    starts, t = [], 0.0
    for g in range(n_groups):
        starts.append(t)
        t += 0.05 if burst_lo <= g < burst_hi else lull_gap
    t_burst = starts[burst_lo]
    t_kill = t_burst + kill_after_s

    class _Replica:
        def __init__(self):
            dcfg = JaxDecodeConfig(
                context_length=ctx,
                max_running_requests=max_running,
                new_tokens_per_chunk=chunk or min(128, new_tokens),
                dtype=model.dtype,
                kv_cache_dtype=model.dtype,
            )
            self.engine = JaxDecodeEngine(dcfg, InferenceEngineConfig())
            self.engine.set_model(params, model)
            self.engine.initialize()
            self.engine.prewarm(prompt_len=prompt_len, gconfig=gcfg)
            self.server = DecodeServer(
                dcfg, engine=self.engine, shutdown_grace=0.5
            )
            self.addr = None
            self._loop = None
            self._killed = False
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(60), "autoscale replica failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                self.addr = await self.server.start(host="127.0.0.1", port=0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def kill(self):
            if self._killed:
                return
            self._killed = True
            asyncio.run_coroutine_threadsafe(
                self.server.stop(), self._loop
            ).result(30)
            self.engine.pause_generation()
            self.engine.abort_all()

        def stop(self):
            try:
                asyncio.run_coroutine_threadsafe(
                    self.server.stop(), self._loop
                ).result(30)
            except Exception as e:  # noqa: BLE001 — already killed
                print(f"[autoscale] replica stop: {e!r}", file=sys.stderr)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)
            self.engine.destroy()

    class _RouterThread:
        def __init__(self, servers, exp, trial):
            self.router = DecodeRouter(
                exp,
                trial,
                servers,
                config=RouterConfig(
                    schedule_policy="prefix_affinity",
                    health_poll_interval=0.25,
                    dead_after_failures=3,
                    queue_timeout_s=60.0,
                ),
            )
            self.addr = None
            self._loop = None
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(30), "autoscale router failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                self.addr = await self.router.start("127.0.0.1", 0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def stop(self):
            asyncio.run_coroutine_threadsafe(
                self.router.stop(), self._loop
            ).result(30)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)

    class _SupervisorThread:
        def __init__(self, sup):
            self.sup = sup
            self._loop = None
            self._ready = threading.Event()
            self._thread = threading.Thread(target=self._run, daemon=True)
            self._thread.start()
            assert self._ready.wait(30), "supervisor failed to start"

        def _run(self):
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def _start():
                await self.sup.start(host="127.0.0.1", port=0)
                self._ready.set()

            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        def stop(self):
            asyncio.run_coroutine_threadsafe(
                self.sup.stop(), self._loop
            ).result(30)
            self._loop.call_soon_threadsafe(self._loop.stop)
            self._thread.join(timeout=10)

    def run_fleet(label, supervised):
        exp, trial = "benchautoscale", f"{label}-{_uuid.uuid4().hex[:6]}"
        n_start = n_base if supervised else n_peak
        replicas = [_Replica() for _ in range(n_start)]
        spawned: list = []
        spawn_lock = threading.Lock()
        # warm pool: expected spawn demand is (n_peak - n_base) scale-ups
        # plus one replacement; a dry pool falls back to a cold build
        spares: list = (
            [_Replica() for _ in range(n_peak - n_base + 1)]
            if supervised
            else []
        )
        # supervised membership is discovery-only: the supervisor's
        # registrations are the fleet. The static fleet seeds the router.
        rt = _RouterThread(
            [] if supervised else [r.addr for r in replicas], exp, trial
        )
        st = None
        if supervised:
            def spawn_fn(role):
                with spawn_lock:
                    r = spares.pop() if spares else None
                if r is None:
                    r = _Replica()  # cold path: pool ran dry
                with spawn_lock:
                    spawned.append(r)
                return r

            scfg = SupervisorConfig(
                enabled=True,
                tick_interval_s=0.15,
                min_replicas=n_base,
                max_replicas=n_peak,
                util_inflight_target=max_running,
                scale_up_util=0.85,
                scale_down_util=0.25,
                scale_up_queue_depth=2,
                scale_up_cooldown_s=0.5,
                scale_down_cooldown_s=1.5,
                replace_cooldown_s=0.5,
                rerole_enabled=False,
                spawn_max_attempts=3,
                spawn_backoff_s=0.2,
                spawn_backoff_max_s=1.0,
                drain_deadline_s=5.0,
                health_fail_threshold=2,
                health_timeout_s=2.0,
            )
            sup = FleetSupervisor(
                rt.addr,
                spawn_fn,
                config=scfg,
                experiment_name=exp,
                trial_name=trial,
            )
            for r in replicas:
                sup.adopt(r)
            st = _SupervisorThread(sup)
        client = RemoteInfEngine(
            InferenceEngineConfig(
                experiment_name=exp,
                trial_name=trial,
                request_timeout=300,
                # fail over fast: when the mid-trace kill (or a
                # supervisor scale-down) retires an addr, one refused
                # connect should move the request on, not a retry loop
                request_retries=1,
                fleet_failover_retries=3,
            )
        )
        client.addresses = [r.addr for r in replicas]
        done: dict = {}
        ttfts: list = []
        itls: list = []
        killed_at: dict = {}
        try:
            time.sleep(0.75)  # discovery + one poll round

            async def member(g, m):
                rid = f"a{g}-m{m}-{_uuid.uuid4().hex[:6]}"
                t0 = time.perf_counter()
                r = await client.agenerate(
                    ModelRequest(
                        rid=rid,
                        input_ids=list(group_prompts[g]),
                        gconfig=gcfg,
                    )
                )
                wall = time.perf_counter() - t0
                key = (g, m)
                assert key not in done, f"duplicate completion {key}"
                done[key] = tuple(r.output_tokens)
                # client-observed split: wall minus the engine decode span
                # = TTFT as the user sees it (queueing, scheduling, and
                # failover retries included)
                span = max(0.0, r.latency - r.ttft)
                ttfts.append(max(0.0, wall - span))
                if len(r.output_tokens) > 1:
                    itls.append(span / (len(r.output_tokens) - 1))

            async def group(g):
                await asyncio.sleep(starts[g])
                await asyncio.gather(
                    *[member(g, m) for m in range(group_size)]
                )

            async def killer(t_start):
                await asyncio.sleep(t_kill)
                victim = replicas[n_base - 1]  # alive in BOTH fleets
                killed_at["t"] = time.perf_counter() - t_start
                print(
                    f"[autoscale] {label}: killing {victim.addr} at "
                    f"t={killed_at['t']:.2f}s",
                    file=sys.stderr,
                )
                await asyncio.get_running_loop().run_in_executor(
                    None, victim.kill
                )

            async def drive():
                t_start = time.perf_counter()
                try:
                    k = asyncio.ensure_future(killer(t_start))
                    await asyncio.gather(*[group(g) for g in range(n_groups)])
                    await k
                finally:
                    await close_current_session()

            t0 = time.perf_counter()
            asyncio.run(drive())
            wall = time.perf_counter() - t0
            sup_metrics = None
            rs = None
            if supervised:
                # billing snapshot at trace end: capacity actually
                # standing DURING the trace, integrated by the supervisor
                rs = float(st.sup.get_metrics()["replica_seconds"])
                # then let the control loop converge (the replacement
                # spawn may still be in flight) before reading counters
                deadline = time.monotonic() + 45.0
                while time.monotonic() < deadline:
                    m = st.sup.get_metrics()
                    if (
                        m["replacements_total"] >= 1
                        and m["pending_spawns"] == 0
                        and m["disruptive_inflight"] == 0
                    ):
                        break
                    time.sleep(0.25)
                sup_metrics = st.sup.get_metrics()
        finally:
            if st is not None:
                st.stop()
            rt.stop()
            with spawn_lock:
                fleet = replicas + spawned + spares
            for r in fleet:
                r.stop()
        tk = killed_at.get("t", wall)
        if not supervised:
            # a static deployment reserves the peak fleet for the whole
            # trace; the crash does not refund the reservation
            rs = n_peak * wall
        tarr = np.asarray(ttfts, dtype=np.float64)
        iarr = np.asarray(itls, dtype=np.float64) * 1e3
        return dict(
            done=done,
            wall=wall,
            kill_t=tk,
            rs=rs,
            alive_rs=n_start * min(tk, wall)
            + max(0, n_start - 1) * max(0.0, wall - tk),
            ttft_p50=float(np.percentile(tarr, 50)),
            ttft_p99=float(np.percentile(tarr, 99)),
            itl_p50=float(np.percentile(iarr, 50)) if iarr.size else 0.0,
            itl_p99=float(np.percentile(iarr, 99)) if iarr.size else 0.0,
            sup=sup_metrics,
        )

    static = run_fleet("static", supervised=False)
    elastic = run_fleet("elastic", supervised=True)

    assert len(static["done"]) == n_logical, (
        f"static fleet lost {n_logical - len(static['done'])} requests"
    )
    assert len(elastic["done"]) == n_logical, (
        f"supervised fleet lost {n_logical - len(elastic['done'])} requests"
    )
    diverged = sum(
        1 for k, v in static["done"].items() if elastic["done"].get(k) != v
    )
    assert diverged == 0, (
        f"{diverged} greedy streams diverged between the static and "
        f"supervised runs"
    )
    sup_m = elastic["sup"]
    assert sup_m["scale_ups_total"] >= 1, "the burst never scaled the fleet up"
    assert sup_m["replacements_total"] >= 1, (
        "the killed replica was never replaced"
    )
    assert sup_m["crash_loops_total"] == 0, "spawns crash-looped"
    ttft_ratio = elastic["ttft_p99"] / max(1e-9, static["ttft_p99"])
    itl_ratio = elastic["itl_p99"] / max(1e-9, static["itl_p99"])
    rs_ratio = elastic["rs"] / max(1e-9, static["rs"])
    assert ttft_ratio <= slo_band, (
        f"supervised p99 TTFT {elastic['ttft_p99']:.3f}s vs static "
        f"{static['ttft_p99']:.3f}s (ratio {ttft_ratio:.2f} > {slo_band})"
    )
    # the ratio gate OR an absolute grace floor: on the CPU smoke the
    # per-request decode spans are a few ms, so a sub-ms absolute gap
    # can read as a large ratio while meaning nothing for the SLO
    assert (
        itl_ratio <= slo_band
        or (elastic["itl_p99"] - static["itl_p99"]) <= itl_grace_ms
    ), (
        f"supervised p99 wall-ITL {elastic['itl_p99']:.2f}ms vs static "
        f"{static['itl_p99']:.2f}ms (ratio {itl_ratio:.2f} > {slo_band}, "
        f"gap > {itl_grace_ms}ms)"
    )
    assert rs_ratio <= 0.9, (
        f"supervised replica-seconds {elastic['rs']:.1f} not materially "
        f"below the static reservation {static['rs']:.1f} "
        f"(ratio {rs_ratio:.2f} > 0.9)"
    )
    return dict(
        autoscale_requests=n_logical,
        autoscale_lost=0,
        autoscale_duplicates=0,
        autoscale_streams_bitidentical=int(diverged == 0),
        autoscale_replica_seconds_ratio=1.0 / rs_ratio,
        autoscale_supervised_replica_seconds=elastic["rs"],
        autoscale_static_replica_seconds=static["rs"],
        autoscale_static_alive_replica_seconds=static["alive_rs"],
        autoscale_ttft_p99_ratio=ttft_ratio,
        autoscale_itl_p99_ratio=itl_ratio,
        autoscale_supervised_ttft_p50_s=elastic["ttft_p50"],
        autoscale_supervised_ttft_p99_s=elastic["ttft_p99"],
        autoscale_static_ttft_p99_s=static["ttft_p99"],
        autoscale_supervised_itl_p99_ms=elastic["itl_p99"],
        autoscale_static_itl_p99_ms=static["itl_p99"],
        autoscale_scale_ups=sup_m["scale_ups_total"],
        autoscale_scale_downs=sup_m["scale_downs_total"],
        autoscale_replacements=sup_m["replacements_total"],
        autoscale_spawn_failures=sup_m["spawn_failures_total"],
        autoscale_crash_loops=sup_m["crash_loops_total"],
        autoscale_supervised_wall_s=elastic["wall"],
        autoscale_static_wall_s=static["wall"],
        autoscale_kill_t_s=elastic["kill_t"],
    )


def bench_weightsync(model, n_pushes, chunk_mb, prompt_len, new_tokens):
    """Staged weight-sync bench: transfer time vs commit-pause time.

    Spins a real decode server (HTTP, loopback) + RemoteInfEngine client,
    keeps a background stream of generation running, and pushes fresh
    full-tree weights `n_pushes` times through the staged path. Reports the
    two windows the overlapped protocol splits: staging/transfer seconds
    (generation LIVE — tokens keep flowing) and commit-pause seconds (the
    only window generation stops), plus wire throughput and the tokens
    generated during the staging windows as direct overlap evidence.
    """
    import asyncio
    import threading

    import jax

    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.core.remote_inf_engine import RemoteInfEngine
    from areal_tpu.core.weight_transfer import flatten_named
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.launcher.decode_server import DecodeServer
    from areal_tpu.models.qwen2 import init_params

    dcfg = JaxDecodeConfig(
        context_length=prompt_len + new_tokens + 128,
        max_running_requests=8,
        # fine-grained chunks: the commit pause lands on a chunk boundary,
        # so chunk size sets the floor of the measured pause window
        new_tokens_per_chunk=min(8, new_tokens),
        dtype=model.dtype,
        kv_cache_dtype=model.dtype,
    )
    eng = JaxDecodeEngine(dcfg, InferenceEngineConfig())
    params = init_params(model, jax.random.PRNGKey(0))
    eng.set_model(params, model)
    eng.initialize()

    # serve over a private event loop in a daemon thread
    server = DecodeServer(JaxDecodeConfig(), engine=eng)
    loop = asyncio.new_event_loop()
    ready = threading.Event()
    addr_box = {}

    def _serve():
        asyncio.set_event_loop(loop)

        async def _start():
            addr_box["addr"] = await server.start(host="127.0.0.1", port=0)
            ready.set()

        loop.run_until_complete(_start())
        loop.run_forever()

    srv_thread = threading.Thread(target=_serve, daemon=True)
    srv_thread.start()
    assert ready.wait(60), "decode server failed to start"

    client = RemoteInfEngine(
        InferenceEngineConfig(setup_timeout=60, request_timeout=600)
    )
    client.initialize(addr=addr_box["addr"])

    # background generation stream: proves tokens flow through staging
    stop = threading.Event()
    rng = np.random.RandomState(7)
    prompts = [
        rng.randint(1, model.vocab_size, (prompt_len,)).tolist()
        for _ in range(64)
    ]
    g = GenerationHyperparameters(max_new_tokens=new_tokens, temperature=1.0)

    def _gen_loop(j):
        k = j
        while not stop.is_set():
            try:
                eng.generate(
                    ModelRequest(input_ids=prompts[k % len(prompts)], gconfig=g),
                    timeout=600,
                )
            except Exception as e:  # noqa: BLE001 — engine shutting down
                print(f"[weightsync] gen loop exit: {e!r}", file=sys.stderr)
                return
            k += 4
        return

    gen_threads = [
        threading.Thread(target=_gen_loop, args=(j,), daemon=True)
        for j in range(4)
    ]
    for t in gen_threads:
        t.start()

    # let generation reach steady state first: the commit pause waits for
    # the in-flight chunk, so measuring against a cold engine would charge
    # first-compile time to the pause window
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        if eng.get_metrics()["generated_tokens_total"] > 4 * new_tokens:
            break
        time.sleep(0.1)

    named = flatten_named(params)
    wire_bytes = sum(a.nbytes for a in named.values())
    # untimed warm push (compiles nothing, but primes HTTP pools + staging)
    client.update_weights_from_tensor(named, version=1, chunk_mb=chunk_mb)
    base = client.get_metrics()
    tokens_during_staging = 0
    for i in range(n_pushes):
        tok0 = eng.get_metrics()["generated_tokens_total"]
        push_id = client.stage_weights(named, chunk_mb=chunk_mb)
        tok1 = eng.get_metrics()["generated_tokens_total"]
        client.commit_staged(push_id, version=i + 2)
        tokens_during_staging += tok1 - tok0
    m = client.get_metrics()
    stop.set()
    for t in gen_threads:
        t.join(timeout=30)
    client.destroy()

    async def _stop():
        await server.stop()
        loop.stop()

    asyncio.run_coroutine_threadsafe(_stop(), loop)
    srv_thread.join(timeout=30)
    eng.destroy()

    transfer_s = (m["staging_secs"] - base["staging_secs"]) / n_pushes
    commit_s = (m["commit_pause_secs"] - base["commit_pause_secs"]) / n_pushes
    return dict(
        weightsync_transfer_s=transfer_s,
        weightsync_commit_pause_s=commit_s,
        weightsync_pause_share=commit_s / max(transfer_s + commit_s, 1e-9),
        weightsync_wire_mb=wire_bytes / 1024 / 1024,
        weightsync_mb_per_s=wire_bytes / 1024 / 1024 / max(transfer_s, 1e-9),
        weightsync_tokens_during_staging=float(tokens_during_staging)
        / n_pushes,
        # raw(bf16-equivalent)/sent over the staged frames: 1.0 for fp
        # pushes, ~2x once the producer ships int8 + f32 scales (ISSUE 16)
        weightsync_wire_compression=m.get("weight_sync_compression", 1.0),
    )


def _pp_bubble_sim(pp, v, n_mbs, t_f, t_b, schedule="1f1b"):
    """Event-driven earliest-start execution of a pipeline timetable on pp
    independent ranks — the MPMD rendering the hybrid ICI/DCN mesh deploys
    (each slice runs its own stage stream; only activation/cotangent hops
    cross the DCN boundary). Jobs run in the schedule's per-rank order but
    start as soon as their cross-rank dependencies land, so the returned
    idle fraction is the timetable's intrinsic bubble. The lockstep SPMD
    scan that renders the same timetable inside ONE slice pads every round
    to the global round clock (its wall time is reported separately as
    `pp_*_step_s`); the simulated bubble is what the interleaving buys on
    the multi-slice deployment: ~(pp-1)/(v*M + pp-1) vs (pp-1)/(M + pp-1).

    t_f / t_b are per-CHUNK forward/backward costs (a chunk is 1/v of a
    rank's layers); the returned fraction is scale-invariant in them.
    """
    C = pp * v
    delta = C - 1
    rounds = (
        delta
        + ((n_mbs - 1) // pp) * C
        + (v - 1) * pp
        + (n_mbs - 1) % pp
        + pp
    )
    free = [0.0] * pp
    done_f: dict = {}
    done_b: dict = {}

    def run_f(s, m, vc):
        c = vc * pp + s
        dep = done_f[(m, c - 1)] if c else 0.0
        end = max(free[s], dep) + t_f
        free[s] = done_f[(m, c)] = end

    def run_b(s, m, vc, barrier=0.0):
        c = vc * pp + s
        dep = done_b[(m, c + 1)] if c < C - 1 else done_f[(m, C - 1)]
        end = max(free[s], dep, done_f[(m, c)], barrier) + t_b
        free[s] = done_b[(m, c)] = end

    if schedule == "gpipe":
        # all forwards in microbatch order, then all backwards in reverse
        # microbatch order, after a global barrier (the autodiff of the
        # round scan replays residuals only once every forward is done)
        for r in range(n_mbs + C - 1):
            for s in range(pp):
                for vc in range(v):
                    n = r - (vc * pp + s)
                    if 0 <= n < n_mbs:
                        run_f(s, n, vc)
        barrier = max(done_f.values())
        for r in range(n_mbs + C - 1):
            for s in reversed(range(pp)):
                for vc in reversed(range(v)):
                    n = r - ((C - 1) - (vc * pp + s))
                    if 0 <= n < n_mbs:
                        run_b(s, n_mbs - 1 - n, vc, barrier)
    else:  # the (interleaved) 1F1B timetable, same n-counter decode as
        # parallel/pipeline.py's round scan
        for r in range(rounds):
            for s in range(pp):
                n = r - s
                if n >= 0:
                    m = (n // C) * pp + n % pp
                    if m < n_mbs:
                        run_f(s, m, (n // pp) % v)
            for s in range(pp):
                nb = r - delta - (pp - 1 - s)
                if nb >= 0:
                    m = (nb // C) * pp + nb % pp
                    if m < n_mbs:
                        run_b(s, m, v - 1 - ((nb // pp) % v))
    makespan = max(done_b.values())
    busy = n_mbs * C * (t_f + t_b)
    return 1.0 - busy / (pp * makespan)


def bench_pp_schedules(model, pp, n_mbs, seq_len, warmup, iters):
    """Pipeline-schedule micro-bench: the SAME stacked micro-batch stream
    through the pp>1 trunk under "gpipe" vs "1f1b" vs "1f1b_interleaved"
    (v=1 and v=2), reporting per-leg wall time, the compiled program's
    temp (activation) memory, and the timetable's bubble fraction
    (`_pp_bubble_sim` with the leg's measured per-chunk cost). Two deltas
    matter: gpipe-vs-1f1b is the stash bound (gpipe residuals grow with M;
    1f1b is capped at 2·pp-1 stage inputs), and v=2-vs-v=1 is the
    interleaving trade — bubble shrinks ~1/v AND the per-round backward
    touches half the layers, so the transient vjp residual footprint
    (the temp-memory term that dominates past a few layers per stage)
    drops even as the stash grows to v·(2·pp-1) chunk inputs."""
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from areal_tpu.api.alloc_mode import ParallelStrategy
    from areal_tpu.api.cli_args import (
        MicroBatchSpec,
        OptimizerConfig,
        TrainEngineConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.jax_engine import _memory_analysis_dict
    from areal_tpu.engine.sft.lm_engine import (
        JaxLMEngine,
        compute_packed_sft_loss,
    )

    ndev = jax.device_count()
    if ndev < pp or ndev % pp:
        return {"ppsched_skipped": f"{ndev} devices incompatible with pp={pp}"}

    # every leg needs L divisible by pp*v (v up to 2) and enough depth per
    # virtual chunk that the residual-vs-stash trade is visible
    v_max = 2
    L = model.num_hidden_layers
    if L < 2 * pp * v_max or L % (pp * v_max):
        L = max(L, 2 * pp * v_max)
        L += -L % (pp * v_max)
        model = _dc.replace(model, num_hidden_layers=L)

    rng = np.random.RandomState(0)
    stacked = {
        "input_ids": np.asarray(
            rng.randint(1, model.vocab_size, (n_mbs, seq_len)), np.int32
        ),
        "position_ids": np.tile(
            np.arange(seq_len, dtype=np.int32), (n_mbs, 1)
        ),
        "segment_ids": np.zeros((n_mbs, seq_len), np.int32),
        "loss_mask": np.ones((n_mbs, seq_len), np.int32),
    }
    stacked = {k: jnp.asarray(v) for k, v in stacked.items()}
    weights = jnp.ones((n_mbs,), jnp.float32)

    out = {"pp_size": pp, "pp_n_mbs": n_mbs, "pp_seq_len": seq_len}
    legs = (
        ("gpipe", "gpipe", 1),
        ("1f1b", "1f1b", 1),
        ("1f1b_interleaved_v1", "1f1b_interleaved", 1),
        ("1f1b_interleaved_v2", "1f1b_interleaved", 2),
    )
    for tag, sched, virt in legs:
        cfg = TrainEngineConfig(
            experiment_name="bench",
            trial_name="ppsched",
            path="",
            init_from_scratch=True,
            dtype=model.dtype,
            mb_spec=MicroBatchSpec(max_tokens_per_mb=seq_len),
            optimizer=OptimizerConfig(lr=1e-4),
            gradient_checkpointing=model.remat,
        )
        cfg.jax.pipeline_schedule = sched
        cfg.jax.virtual_pp_size = virt
        # the interleaved engine stores layers chunk-major, so each leg
        # gets a fresh engine (params re-initialized in its own layout)
        eng = JaxLMEngine(cfg)
        eng.model_config = model
        eng.create_process_group(
            ParallelStrategy(
                pipeline_parallel_size=pp, data_parallel_size=ndev // pp
            )
        )
        eng.initialize(None, FinetuneSpec(1, 1000, 1))
        fn = eng._get_pipelined_grad_step(compute_packed_sft_loss)
        compiled = fn.lower(eng.params, stacked, weights).compile()
        mem = _memory_analysis_dict(compiled)
        for _ in range(warmup):
            jax.block_until_ready(fn(eng.params, stacked, weights))
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(fn(eng.params, stacked, weights))
        step_s = (time.perf_counter() - t0) / iters
        eng.destroy()
        out[f"pp_{tag}_step_s"] = step_s
        out[f"pp_{tag}_temp_bytes"] = mem.get("temp_size_in_bytes", 0)
        t_chunk = step_s / (2 * n_mbs * virt)  # measured per-chunk cost
        out[f"pp_{tag}_bubble_frac"] = _pp_bubble_sim(
            pp, virt, n_mbs, t_chunk, t_chunk, schedule=sched
        )
    if out.get("pp_gpipe_temp_bytes"):
        out["pp_temp_ratio_gpipe_over_1f1b"] = out["pp_gpipe_temp_bytes"] / max(
            out["pp_1f1b_temp_bytes"], 1
        )
    v1, v2 = "pp_1f1b_interleaved_v1", "pp_1f1b_interleaved_v2"
    out["pp_bubble_ratio_v1_over_v2"] = out[f"{v1}_bubble_frac"] / max(
        out[f"{v2}_bubble_frac"], 1e-9
    )
    out["pp_temp_ratio_v1_over_v2"] = out[f"{v1}_temp_bytes"] / max(
        out[f"{v2}_temp_bytes"], 1
    )
    return out


def bench_prefix_decode(model, n_groups, group_size, prompt_len, new_tokens):
    """Prefill-heavy decode, grouped vs ungrouped prompts.

    GRPO issues group_size samples of the SAME prompt; the engine prefills
    each unique prompt once and forks the KV for the rest (jax_decode.py
    prefix registry). This measures that win directly: identical token
    volume, (a) every prompt unique (one prefill per request) vs (b)
    n_groups unique prompts shared group_size ways (one prefill per group).
    """
    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
    )
    from areal_tpu.api.io_struct import ModelRequest
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import init_params

    import jax

    n_requests = n_groups * group_size
    dcfg = JaxDecodeConfig(
        context_length=prompt_len + new_tokens + 128,
        max_running_requests=n_requests,
        new_tokens_per_chunk=min(32, new_tokens),
        dtype=model.dtype,
        kv_cache_dtype=model.dtype,
    )
    g = GenerationHyperparameters(
        max_new_tokens=new_tokens, temperature=1.0, top_p=1.0
    )
    rng = np.random.RandomState(7)
    params = init_params(model, jax.random.PRNGKey(0))

    def run(prompts: list[list[int]]) -> float:
        eng = JaxDecodeEngine(
            dcfg, InferenceEngineConfig(max_concurrent_rollouts=n_requests)
        )
        eng.set_model(params, model)
        eng.initialize()

        def batch(ps, timed: bool) -> float:
            eng.pause_generation()  # line up all requests, then go
            with ThreadPoolExecutor(max_workers=n_requests) as pool:
                futs = [
                    pool.submit(
                        eng.generate,
                        ModelRequest(input_ids=list(p), gconfig=g),
                        1800,
                    )
                    for p in ps
                ]
                while eng._request_q.qsize() < len(ps):
                    time.sleep(0.01)
                t0 = time.perf_counter()
                eng.continue_generation()
                results = [f.result() for f in futs]
                dt = time.perf_counter() - t0
            gen = sum(len(r.output_tokens) for r in results)
            return gen / dt if timed else 0.0

        try:
            # Shape-representative warm pass: a full UNTIMED batch with the
            # same duplication pattern but fresh random tokens, so every
            # program the timed pass needs — batched-prefill B∈{1,2,4,8}
            # per bucket, the fork path, and the chunk-fn active-row
            # buckets hit while the batch drains — is compiled before the
            # clock starts. (A 2-request warmup once left the B=8 wave and
            # drain buckets compiling INSIDE the timing; measured "speedup"
            # was mostly compile noise: 1.4x where steady state is ~6x.)
            # Warm prompts share no prefix with the timed ones, so the
            # prefix registry cannot leak warm KV into the measurement.
            warm = [
                rng.randint(1, model.vocab_size, (prompt_len,)).tolist()
                for _ in range(len(set(map(tuple, prompts))))
            ]
            pattern = {}
            warm_prompts = []
            for p in prompts:
                key = tuple(p)
                if key not in pattern:
                    pattern[key] = warm[len(pattern)]
                warm_prompts.append(list(pattern[key]))
            batch(warm_prompts, timed=False)
            return batch(prompts, timed=True)
        finally:
            eng.destroy()

    unique = [
        rng.randint(1, model.vocab_size, (prompt_len,)).tolist()
        for _ in range(n_requests)
    ]
    grouped = []
    for i in range(n_groups):
        grouped.extend([list(unique[i])] * group_size)
    tps_unique = run(unique)
    tps_grouped = run(grouped)
    return dict(
        prefix_ungrouped_tok_s=tps_unique,
        prefix_grouped_tok_s=tps_grouped,
        prefix_share_speedup=tps_grouped / max(tps_unique, 1e-9),
        prefix_groups=n_groups,
        prefix_group_size=group_size,
        prefix_prompt_len=prompt_len,
    )


def bench_grpo(
    model,
    n_prompts,
    group_size,
    prompt_len,
    new_tokens,
    warmup_steps,
    steps,
    mb_tokens,
):
    """The real thing: async GRPO end-to-end — decode-engine rollouts
    through the RLVR workflow (staleness-gated, >=2 batches in flight),
    decoupled-loss PPO update, weight push back into the decode engine.

    Accounting matches the reference's benchmark README
    (benchmark/verl_v0_3_0_post1_76084d3/README.md:33-43): throughput =
    total effective tokens / end-to-end wall time over the timed steps;
    additionally samples/sec/chip (BASELINE.json's primary metric) and
    rollout generated-tokens/sec.
    """
    from areal_tpu.api.alloc_mode import ParallelStrategy
    from areal_tpu.api.cli_args import (
        InferenceEngineConfig,
        JaxDecodeConfig,
        MicroBatchSpec,
        NormConfig,
        OptimizerConfig,
        PPOActorConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec, WeightUpdateMeta
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.engine.ppo.actor import JaxPPOActor

    samples_per_step = n_prompts * group_size
    actor_cfg = PPOActorConfig(
        experiment_name="bench",
        trial_name="grpo",
        path="",
        init_from_scratch=True,
        dtype=model.dtype,
        mb_spec=MicroBatchSpec(max_tokens_per_mb=mb_tokens),
        optimizer=OptimizerConfig(
            lr=1e-5,
            warmup_steps_proportion=0.0,
            lr_scheduler_type="constant",
            gradient_clipping=1.0,
        ),
        gradient_checkpointing=model.remat,
        group_size=group_size,
        ppo_n_minibatches=1,
        eps_clip=0.2,
        kl_ctl=0.0,
        adv_norm=NormConfig(
            mean_level="group", std_level="group", group_size=group_size
        ),
        use_decoupled_loss=True,
        temperature=1.0,
    )
    actor = JaxPPOActor(actor_cfg)
    actor.model_config = model
    actor.create_process_group(ParallelStrategy())
    actor.initialize(None, FinetuneSpec(1, 100_000, samples_per_step))

    rollout = JaxDecodeEngine(
        JaxDecodeConfig(
            context_length=prompt_len + new_tokens + 128,
            max_running_requests=64,
            new_tokens_per_chunk=min(128, new_tokens),
            dtype=model.dtype,
            kv_cache_dtype=model.dtype,
        ),
        InferenceEngineConfig(
            max_concurrent_rollouts=samples_per_step * 2,
            consumer_batch_size=samples_per_step,
            max_head_offpolicyness=2,
            request_timeout=3600,
        ),
    )
    rollout.set_model(actor.params, model)
    rollout.initialize()
    actor.connect_engine(rollout, WeightUpdateMeta.from_memory())
    try:
        return _bench_grpo_run(
            actor, rollout, model, n_prompts, group_size, prompt_len,
            new_tokens, warmup_steps, steps,
        )
    finally:
        # leaked engines would hold their KV caches + optimizer state into
        # the next mode of the same process
        rollout.destroy()
        actor.destroy()


def _bench_grpo_run(
    actor, rollout, model, n_prompts, group_size, prompt_len,
    new_tokens, warmup_steps, steps,
):
    import jax

    from areal_tpu.api.cli_args import GenerationHyperparameters
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    samples_per_step = n_prompts * group_size
    rng = np.random.RandomState(3)

    class CycleLoader:
        """prepare_batch keeps >=2 batches in flight; never run dry."""

        batch_size = n_prompts  # prompts per training batch

        def __iter__(self):
            while True:
                yield [
                    dict(
                        input_ids=rng.randint(
                            1, model.vocab_size, (prompt_len,)
                        ).tolist()
                    )
                    for _ in range(n_prompts)
                ]

    def reward(prompt, completion, prompt_ids, completion_ids, **kw):
        # synthetic verifiable reward: cheap, deterministic, nonzero spread
        return float(sum(completion_ids[:8]) % 7) / 7.0

    workflow = RLVRWorkflow(
        reward,
        GenerationHyperparameters(
            n_samples=group_size,
            max_new_tokens=new_tokens,
            temperature=1.0,
            top_p=1.0,
        ),
    )
    loader = CycleLoader()

    loader_it = iter(loader)

    def one_step(version: int, sync: bool = False):
        # time_perf breakdown (reference accounting,
        # benchmark/verl_v0_3_0_post1_76084d3/README.md:33-43): e2e =
        # rollout-wait + train + weight-push. Rollout-wait is what the
        # trainer BLOCKS on — async generation overlaps ≥2 batches deep;
        # sync mode submits THIS step's prompts and waits for them (the
        # reference's synchronous-RL baseline, blog/AReaL_v0_3.md:10).
        t0 = time.perf_counter()
        if sync:
            batch = rollout.rollout_batch(next(loader_it), workflow=workflow)
        else:
            batch = rollout.prepare_batch(loader, workflow=workflow)
        rollout_wait_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        batch["prox_logp"] = actor.compute_logp(batch)
        actor.compute_advantages(batch)
        stats = actor.ppo_update(batch)
        train_s = time.perf_counter() - t1
        actor.set_version(version)
        t_push = time.perf_counter()
        rollout.pause()
        actor.update_weights(None)
        rollout.set_version(version)
        rollout.resume()
        push_s = time.perf_counter() - t_push
        gen_tokens = int((batch["versions"] >= 0).sum())
        total_tokens = int(batch["attention_mask"].sum())
        return gen_tokens, total_tokens, rollout_wait_s, train_s, push_s, stats

    version = 0
    for _ in range(warmup_steps):
        version += 1
        one_step(version, sync=True)  # sync warmup compiles every program

    # Sync baseline FIRST (an async phase leaves >=2 batches in flight,
    # which would subsidize a later sync measurement).
    sync_steps = max(2, steps - 1)
    t0 = time.perf_counter()
    for _ in range(sync_steps):
        version += 1
        one_step(version, sync=True)
    sync_e2e = time.perf_counter() - t0

    version += 1
    one_step(version)  # untimed: fill the async pipeline

    gen_tot = tok_tot = 0
    wait_tot = train_tot = push_tot = 0.0
    t0 = time.perf_counter()
    for _ in range(steps):
        version += 1
        gen_tokens, total_tokens, wait_s, train_s, push_s, _ = one_step(
            version
        )
        gen_tot += gen_tokens
        tok_tot += total_tokens
        wait_tot += wait_s
        train_tot += train_s
        push_tot += push_s
    e2e = time.perf_counter() - t0
    n_chips = max(jax.device_count(), 1)
    return dict(
        grpo_sync_step_time_s=sync_e2e / sync_steps,
        grpo_async_vs_sync_speedup=(sync_e2e / sync_steps) / (e2e / steps),
        grpo_samples_per_sec_per_chip=samples_per_step * steps / e2e / n_chips,
        grpo_rollout_tokens_per_sec_per_chip=gen_tot / e2e / n_chips,
        grpo_effective_tokens_per_sec_per_chip=tok_tot / e2e / n_chips,
        grpo_step_time_s=e2e / steps,
        grpo_time_rollout_wait_s=wait_tot / steps,
        grpo_time_train_s=train_tot / steps,
        grpo_weight_push_s=push_tot / steps,
        grpo_prompts_per_step=n_prompts,
        grpo_group_size=group_size,
        grpo_new_tokens=new_tokens,
        grpo_steps=steps,
    )


def bench_chaostrain(
    model,
    n_prompts,
    group_size,
    prompt_len,
    new_tokens,
    steps,
    mb_tokens,
    kill_step=2,
):
    """Trainer-side chaos: a small deterministic GRPO loop killed at seeded
    fault points (mid engine.save, the save-vs-marker gap, the
    consume-vs-dump gap, mid weight-push), resumed from the committed
    recovery point, and checked against an unfaulted oracle — plus a leg
    where the NEWEST committed checkpoint is deliberately torn and recovery
    must fall back to its predecessor.

    Proof obligations per leg (the headline is the AND of all of them):
    - exactly-once: the sample-ledger WAL ends with one entry per training
      step, rid union == every generated trajectory, 0 lost / 0 duplicated
      (the wait()-to-dump window is rolled back and replayed, never
      double-journaled);
    - monotone weight versions: the resumed engine version equals the
      committed version, WAL entry versions never regress;
    - bit-determinism: post-resume per-step losses and the final weight
      fingerprint match the oracle (greedy decoding + shuffle-free loader +
      rollout_id-sorted batches + fixed init keys make the loop replayable).
    """
    import shutil
    import tempfile

    from areal_tpu.api.alloc_mode import ParallelStrategy
    from areal_tpu.api.cli_args import (
        GenerationHyperparameters,
        InferenceEngineConfig,
        JaxDecodeConfig,
        MicroBatchSpec,
        NormConfig,
        OptimizerConfig,
        PPOActorConfig,
        RecoverConfig,
    )
    from areal_tpu.api.io_struct import FinetuneSpec, StepInfo, WeightUpdateMeta
    from areal_tpu.core import fault_injection
    from areal_tpu.core.fault_injection import (
        FaultPlan,
        FaultPoint,
        InjectedFault,
    )
    from areal_tpu.core.sample_ledger import SampleWAL
    from areal_tpu.dataset import SimpleDataLoader
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.engine.ppo.actor import JaxPPOActor
    from areal_tpu.utils import recover as recover_mod
    from areal_tpu.utils.recover import RecoverHandler, ledger_wal_path
    from areal_tpu.workflow.rlvr import RLVRWorkflow

    samples_per_step = n_prompts * group_size
    rng = np.random.RandomState(11)
    # fixed dataset, one epoch == `steps` batches: every leg sees the same
    # prompts in the same order (loader position is checkpointed state)
    dataset = [
        dict(input_ids=rng.randint(1, model.vocab_size, (prompt_len,)).tolist())
        for _ in range(n_prompts * steps)
    ]
    ft_spec = FinetuneSpec(1, len(dataset), samples_per_step)

    def reward(prompt, completion, prompt_ids, completion_ids, **kw):
        return float(sum(completion_ids[:8]) % 7) / 7.0

    class Env:
        pass

    def build(fileroot):
        env = Env()
        env.rcfg = RecoverConfig(
            experiment_name="bench", trial_name="chaostrain",
            fileroot=fileroot, mode="fault", freq_steps=1, keep_last=2,
        )
        actor_cfg = PPOActorConfig(
            experiment_name="bench",
            trial_name="chaostrain",
            path="",
            init_from_scratch=True,  # fixed PRNG keys: identical across legs
            dtype=model.dtype,
            mb_spec=MicroBatchSpec(max_tokens_per_mb=mb_tokens),
            optimizer=OptimizerConfig(
                lr=1e-3,
                warmup_steps_proportion=0.0,
                lr_scheduler_type="constant",
                gradient_clipping=1.0,
            ),
            gradient_checkpointing=model.remat,
            group_size=group_size,
            ppo_n_minibatches=1,
            eps_clip=0.2,
            kl_ctl=0.0,
            # batch-level normalization: greedy decoding makes group members
            # identical, so group-level norm would zero every advantage and
            # the oracle would be a trivially-flat loop
            adv_norm=NormConfig(
                mean_level="batch", std_level="batch", group_size=group_size
            ),
            use_decoupled_loss=True,
            temperature=1.0,
        )
        env.actor = JaxPPOActor(actor_cfg)
        env.actor.model_config = model
        env.actor.create_process_group(ParallelStrategy())
        env.actor.initialize(None, ft_spec)
        env.rollout = JaxDecodeEngine(
            JaxDecodeConfig(
                context_length=prompt_len + new_tokens + 128,
                max_running_requests=64,
                new_tokens_per_chunk=min(128, new_tokens),
                dtype=model.dtype,
                kv_cache_dtype=model.dtype,
            ),
            InferenceEngineConfig(
                max_concurrent_rollouts=samples_per_step * 2,
                consumer_batch_size=samples_per_step,
                max_head_offpolicyness=steps + 2,
                request_timeout=3600,
            ),
        )
        env.rollout.set_model(env.actor.params, model)
        env.rollout.initialize()
        env.actor.connect_engine(env.rollout, WeightUpdateMeta.from_memory())
        env.rollout.attach_ledger_wal(ledger_wal_path(env.rcfg))
        env.workflow = RLVRWorkflow(
            reward,
            GenerationHyperparameters(
                n_samples=group_size, max_new_tokens=new_tokens,
                temperature=1.0, top_p=1.0, greedy=True,
            ),
        )
        env.loader = SimpleDataLoader(
            dataset, batch_size=n_prompts, shuffle=False
        )
        env.handler = RecoverHandler(env.rcfg, ft_spec)
        return env

    def destroy(env):
        env.rollout.destroy()
        env.actor.destroy()

    def _si(g):
        return StepInfo(
            epoch=0, epoch_step=g, global_step=g, steps_per_epoch=steps
        )

    def _loss_of(stats):
        s = stats[0]
        for k in ("loss", "actor/loss"):
            if k in s:
                return float(s[k])
        for k in sorted(s):
            if k.endswith("loss"):
                return float(s[k])
        return float("nan")

    def _fingerprint(actor):
        import jax

        return float(
            sum(
                float(np.abs(np.asarray(x)).sum())
                for x in jax.tree_util.tree_leaves(actor.params)
            )
        )

    def one_step(env, g):
        batch = env.rollout.rollout_batch(
            next(env.data_iter), workflow=env.workflow
        )
        # wait() shuffles result order; re-sort by the ledger's rollout_id
        # stamp so the training batch is identical across crash/resume legs
        order = np.argsort(np.asarray(batch["rollout_id"]), kind="stable")
        batch = {k: np.asarray(v)[order] for k, v in batch.items()}
        batch["prox_logp"] = env.actor.compute_logp(batch)
        env.actor.compute_advantages(batch)
        stats = env.actor.ppo_update(batch)
        env.actor.set_version(g + 1)
        env.rollout.pause()
        env.actor.update_weights(None)
        env.rollout.set_version(g + 1)
        env.rollout.resume()
        return _loss_of(stats)

    def dump(env, g):
        """Returns True when a dump-internal fault seam fired (the injector
        aborts mid-dump, RecoverHandler degrades — the on-disk state is
        exactly a process that died there, so the leg abandons the loop)."""
        before = fault_injection.snapshot()
        env.handler.dump(
            env.actor, _si(g), dataloader=env.loader, rollout=env.rollout
        )
        after = fault_injection.snapshot()
        return any(after.get(k, 0) > before.get(k, 0) for k in after)

    def run_leg(fileroot, plan):
        """Run to completion or the seeded kill; returns (committed per-step
        losses, crashed step or None, final fingerprint or None)."""
        env = build(fileroot)
        env.data_iter = iter(env.loader)
        if plan is not None:
            fault_injection.configure(plan)
        losses, crashed_at, fp = {}, None, None
        try:
            for g in range(steps):
                try:
                    loss = one_step(env, g)
                except InjectedFault:
                    crashed_at = g
                    break
                if dump(env, g):
                    crashed_at = g
                    break
                losses[g] = loss
            if crashed_at is None:
                fp = _fingerprint(env.actor)
        finally:
            fault_injection.deactivate()
            destroy(env)
        return losses, crashed_at, fp

    def resume_leg(fileroot, committed_losses):
        """Fresh env (a restarted trainer), recover, replay to completion."""
        env = build(fileroot)
        try:
            info = env.handler.load(
                env.actor,
                dataloader=env.loader,
                inference_engine=env.rollout,
                weight_update_meta=WeightUpdateMeta.from_memory(),
            )
            assert info is not None, "no recoverable state after crash"
            start = info.last_step_info.next().global_step
            resumed_version = env.actor.get_version()
            env.data_iter = iter(env.loader)
            losses = dict(committed_losses)
            for g in range(start, steps):
                losses[g] = one_step(env, g)
                dump(env, g)
            return dict(
                start=start,
                resumed_version=resumed_version,
                losses=losses,
                fp=_fingerprint(env.actor),
                wal=SampleWAL(ledger_wal_path(env.rcfg)).replay(),
            )
        finally:
            destroy(env)

    def check_wal(wal):
        versions = [e["version"] for e in wal]
        rids = [r for e in wal for r in e["rids"]]
        lost = steps * n_prompts - len(set(rids))
        dup = len(rids) - len(set(rids))
        exactly_once = (
            versions == list(range(steps)) and lost == 0 and dup == 0
        )
        monotonic = versions == sorted(versions)
        return exactly_once, monotonic, lost, dup

    KILL_SITES = (
        "recover.dump.save",     # mid engine.save: torn tmp dir left behind
        "recover.dump.marker",   # save-vs-marker gap: sealed but uncommitted
        "train.step",            # consume-vs-dump gap: batch journaled, not committed
        "train.weights.push",    # mid push: update applied in memory, lost
    )
    tmp_roots = []

    def mkroot(tag):
        d = tempfile.mkdtemp(prefix=f"chaostrain-{tag}-")
        tmp_roots.append(d)
        return d

    try:
        # -- oracle: the unfaulted run every leg must reproduce ----------
        oracle_root = mkroot("oracle")
        oracle_losses, crashed, oracle_fp = run_leg(oracle_root, None)
        assert crashed is None and len(oracle_losses) == steps
        ora_wal = SampleWAL(
            ledger_wal_path(
                RecoverConfig(
                    experiment_name="bench", trial_name="chaostrain",
                    fileroot=oracle_root, mode="fault",
                )
            )
        ).replay()
        ora_once, ora_mono, _, _ = check_wal(ora_wal)

        legs = []
        loss_diffs, fp_diffs = [], []
        all_once, all_mono = ora_once, ora_mono
        lost_total = dup_total = 0

        # -- seeded kill legs -------------------------------------------
        for site in KILL_SITES:
            root = mkroot(site.replace(".", "-"))
            plan = FaultPlan(
                seed=5,
                points=(
                    FaultPoint(
                        site=site, mode="abort", at=(kill_step,), times=1
                    ),
                ),
            )
            committed, crashed_at, _ = run_leg(root, plan)
            assert crashed_at == kill_step, (site, crashed_at)
            res = resume_leg(root, committed)
            assert res["start"] == kill_step, (site, res["start"])
            once, mono, lost, dup = check_wal(res["wal"])
            mono = mono and res["resumed_version"] == res["start"]
            diff = max(
                abs(res["losses"][g] - oracle_losses[g]) for g in range(steps)
            )
            fpd = abs(res["fp"] - oracle_fp)
            legs.append(
                dict(site=site, crashed_at=crashed_at, resume=res["start"],
                     once=once, loss_diff=diff)
            )
            loss_diffs.append(diff)
            fp_diffs.append(fpd)
            all_once &= once
            all_mono &= mono
            lost_total += lost
            dup_total += dup

        # -- torn-newest leg: bit-rot the newest COMMITTED checkpoint ----
        torn_root = mkroot("torn")
        full_losses, crashed, _ = run_leg(torn_root, None)
        assert crashed is None
        rcfg_t = RecoverConfig(
            experiment_name="bench", trial_name="chaostrain",
            fileroot=torn_root, mode="fault", keep_last=2,
        )
        newest = os.path.join(
            recover_mod.recover_root(rcfg_t), f"step-{steps - 1}"
        )
        with open(os.path.join(newest, "recover_info.pkl"), "ab") as f:
            f.write(b"\x00bitrot")  # size+checksum mismatch vs manifest
        recover_mod.reset_metrics()
        # drop the committed final step's losses: the torn checkpoint means
        # step steps-1 must be REPLAYED from the predecessor, not trusted
        res = resume_leg(torn_root, {g: full_losses[g] for g in range(steps - 1)})
        torn_skipped = recover_mod.get_metrics()["recover_torn_skipped_total"]
        assert res["start"] == steps - 1, res["start"]
        once, mono, lost, dup = check_wal(res["wal"])
        torn_diff = max(
            abs(res["losses"][g] - oracle_losses[g]) for g in range(steps)
        )
        loss_diffs.append(torn_diff)
        fp_diffs.append(abs(res["fp"] - oracle_fp))
        all_once &= once
        all_mono &= mono
        lost_total += lost
        dup_total += dup
        legs.append(
            dict(site="torn-newest", crashed_at=None, resume=res["start"],
                 once=once, loss_diff=torn_diff)
        )

        max_loss_diff = max(loss_diffs)
        max_fp_diff = max(fp_diffs)
        ok = (
            all_once
            and all_mono
            and lost_total == 0
            and dup_total == 0
            and torn_skipped >= 1
            and max_loss_diff < 1e-6
            and max_fp_diff < 1e-4
        )
        return dict(
            chaostrain_exactly_once=ok,
            chaostrain_kill_legs=len(KILL_SITES),
            chaostrain_lost_samples=lost_total,
            chaostrain_double_trained=dup_total,
            chaostrain_versions_monotonic=all_mono,
            chaostrain_loss_max_abs_diff=max_loss_diff,
            chaostrain_fingerprint_max_abs_diff=max_fp_diff,
            chaostrain_torn_skipped=int(torn_skipped),
            chaostrain_steps=steps,
            chaostrain_kill_step=kill_step,
            chaostrain_legs=[
                f"{leg['site']}@{leg['crashed_at']}→resume{leg['resume']}"
                f" once={leg['once']} Δloss={leg['loss_diff']:.2e}"
                for leg in legs
            ],
        )
    finally:
        for d in tmp_roots:
            shutil.rmtree(d, ignore_errors=True)


# --mode choice -> bench entry point. The argparse choices are derived from
# this table and the dev-mode headline metrics live beside it, so a new mode
# cannot ship half-wired; tests/test_bench_modes.py pins the sync.
BENCH_MODE_FNS = {
    "train": bench_train,
    "decode": bench_decode_compare,
    "prefix": bench_prefix_decode,
    "grpo": bench_grpo,
    "ppsched": bench_pp_schedules,
    "weightsync": bench_weightsync,
    "specdecode": bench_spec_compare,
    "kvoffload": bench_kvoffload,
    "kvquant": bench_kvquant,
    "wquant": bench_wquant,
    "fleet": bench_fleet,
    "chaos": bench_chaos,
    "chaostrain": bench_chaostrain,
    "disagg": bench_disagg,
    "kvfabric": bench_kvfabric,
    "autoscale": bench_autoscale,
}
BENCH_MODES = ("all", *BENCH_MODE_FNS)
# headline metric per dev mode (modes that skip the trainer MFU line)
MODE_HEADLINES = {
    "decode": ("decode_tokens_per_sec_per_chip", "tok/s/chip"),
    "prefix": ("prefix_share_speedup", "x"),
    "grpo": ("grpo_samples_per_sec_per_chip", "samples/s/chip"),
    "ppsched": ("pp_bubble_ratio_v1_over_v2", "x"),
    "weightsync": ("weightsync_commit_pause_s", "s"),
    "specdecode": ("spec_over_off_speedup", "x"),
    "kvoffload": ("kvoffload_resume_ttft_speedup", "x"),
    "kvquant": ("kvquant_capacity_ratio", "x"),
    "wquant": ("wquant_wire_bytes_ratio", "x"),
    "fleet": ("fleet_affinity_ttft_p50_speedup", "x"),
    "chaos": ("chaos_exactly_once", "bool"),
    "chaostrain": ("chaostrain_exactly_once", "bool"),
    "disagg": ("disagg_decode_itl_p99_speedup", "x"),
    "kvfabric": ("kvfabric_warm_ttft_speedup", "x"),
    "autoscale": ("autoscale_replica_seconds_ratio", "x"),
}


def _emit(metric: str, value: float, detail: dict) -> None:
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(value, 4),
                "unit": "fraction_of_peak",
                "vs_baseline": round(value / BASELINE_TRAINER_MFU, 3),
                "detail": detail,
            }
        ),
        flush=True,
    )


def main(mode: str = "all") -> None:
    from areal_tpu.platforms import enable_compilation_cache

    enable_compilation_cache()

    import jax

    from areal_tpu.models.qwen2 import ModelConfig

    dev = jax.devices()[0]
    on_accel = dev.platform == "tpu"
    if not on_accel and not os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        # the small CPU sizes below are a plumbing check, run only when
        # asked for by name; they never stand in for a chip that is missing
        raise SystemExit(
            f"bench: found no TPU (JAX reports platform {dev.platform!r}); "
            "set JAX_PLATFORMS=cpu to run the CPU plumbing check on purpose"
        )

    def want(m: str) -> bool:
        return mode in ("all", m)

    if on_accel:
        # The fused vocab-chunked LM loss (ops/fused_xent.py) removes the
        # f32 [T, vocab] logits from HBM, which frees enough memory to run
        # WITHOUT remat at the 4096-token micro-batch — measured 0.312 MFU
        # vs 0.274 with remat (v5e). Keep remat=True as the OOM fallback so
        # a busier chip still produces a number instead of a crash.
        def flagship(remat: bool) -> ModelConfig:
            return ModelConfig(
                vocab_size=151936,
                hidden_size=896,
                intermediate_size=4864,
                num_hidden_layers=24,
                num_attention_heads=14,
                num_key_value_heads=2,
                tie_word_embeddings=True,
                dtype="bfloat16",
                param_dtype="bfloat16",
                remat=remat,
                scan_layers=True,
            )

        def train_attempt(remat: bool):
            return bench_train(
                flagship(remat),
                tokens_per_step=65536,
                seq_len=1024,
                mb_tokens=4096,
                warmup=2,
                iters=5,
            )

        model = flagship(False)
        train = {"mfu": 0.0}
        decode = {}
        if want("train"):
            try:
                train = train_attempt(False)
            except Exception as e:  # noqa: BLE001 — fall back on OOM only
                if _OOM_MARKER not in f"{type(e).__name__}: {e}":
                    raise
                print(
                    "[bench] no-remat step OOMed; retrying with remat",
                    file=sys.stderr,
                    flush=True,
                )
                model = flagship(True)
                train = train_attempt(True)
        if want("decode"):
            decode = bench_decode_compare(
                model, n_requests=128, prompt_len=128, new_tokens=256,
                max_running=64,
            )
        if want("prefix"):
            decode.update(
                bench_prefix_decode(
                    model, n_groups=4, group_size=8, prompt_len=512,
                    new_tokens=32,
                )
            )
        if want("ppsched"):
            decode.update(
                bench_pp_schedules(
                    flagship(True), pp=2, n_mbs=8, seq_len=1024,
                    warmup=1, iters=3,
                )
            )
        if want("weightsync"):
            decode.update(
                bench_weightsync(
                    model, n_pushes=3, chunk_mb=64, prompt_len=128,
                    new_tokens=128,
                )
            )
        if want("specdecode"):
            decode.update(
                bench_spec_compare(
                    model, n_requests=64, prompt_len=128, new_tokens=256,
                    max_running=64, spec_k=7,
                )
            )
        if want("kvoffload"):
            decode.update(
                bench_kvoffload(
                    model, n_sessions=96, prompt_len=512, new_tokens=256,
                    max_running=64, host_mb=2048.0,
                )
            )
        if want("kvquant"):
            decode.update(
                bench_kvquant(
                    model, n_sessions=96, prompt_len=512,
                    new_tokens=256, max_running=64, pool_mb=300.0,
                )
            )
        if want("wquant"):
            decode.update(
                bench_wquant(
                    model, n_sessions=96, prompt_len=512,
                    new_tokens=256, max_running=64, pool_mb=300.0,
                )
            )
        if want("fleet"):
            decode.update(
                bench_fleet(
                    model, n_replicas=3, n_groups=8, group_size=8,
                    prompt_len=512, new_tokens=128, max_running=32,
                )
            )
        if want("chaos"):
            decode.update(
                bench_chaos(
                    model, n_replicas=2, n_groups=4, group_size=4,
                    prompt_len=256, new_tokens=64, max_running=16,
                )
            )
        if want("disagg"):
            decode.update(
                bench_disagg(
                    model, n_decode_reqs=16, n_prefill_reqs=8,
                    prompt_short=64, prompt_long=2048, new_tokens=256,
                    max_running=32, drain_sessions=8, drain_prompt=512,
                    drain_tokens=128,
                )
            )
        if want("kvfabric"):
            decode.update(
                bench_kvfabric(
                    model, prompt_len=1024, head_len=512, tail_len=128,
                    new_tokens=64, n_dedup=8, max_running=24,
                    chunk=8, n_ttft_reps=3,
                )
            )
        if want("autoscale"):
            decode.update(
                bench_autoscale(
                    # chunked decode (32 scheduler round trips per
                    # request) keeps the burst backlog standing for
                    # several supervisor ticks; elastic pays the
                    # scale-up lag in the burst tail, so the SLO band
                    # is looser than parity — the headline is the
                    # replica-seconds bill
                    model, n_base=2, n_peak=4, n_groups=16,
                    group_size=8, prompt_len=256, new_tokens=128,
                    max_running=16, chunk=4, kill_after_s=1.0,
                    slo_band=1.25,
                )
            )
        if want("grpo"):
            # GRPO co-locates trainer (fwd+bwd+opt) and decode engine on
            # one chip: run the actor with remat on to leave HBM headroom
            # for the decode param copy + KV cache.
            def grpo_attempt():
                return bench_grpo(
                    flagship(True),
                    n_prompts=16,
                    group_size=8,
                    prompt_len=128,
                    new_tokens=256,
                    warmup_steps=1,
                    steps=3,
                    mb_tokens=4096,
                )

            decode.update(
                grpo_attempt()
            )
        if want("train"):
            # Scale evidence: the largest model one v5e chip fits per the
            # HBM estimator (utils/hbm.py) — Qwen2.5-3B geometry with LoRA
            # (bf16 base 6.2 GiB, adamw state only on adapters; full-FT
            # 1.5B needs 18.6 GiB and does NOT fit). Bonus metric: failure
            # must not cost the primary line.
            def lora3b():
                m = ModelConfig(
                    vocab_size=151936,
                    hidden_size=2048,
                    intermediate_size=11008,
                    num_hidden_layers=36,
                    num_attention_heads=16,
                    num_key_value_heads=2,
                    tie_word_embeddings=True,
                    dtype="bfloat16",
                    param_dtype="bfloat16",
                    remat=True,
                    scan_layers=True,
                    lora_rank=32,
                    lora_alpha=64.0,
                )
                return bench_train(
                    m, tokens_per_step=16384, seq_len=1024, mb_tokens=4096,
                    warmup=1, iters=3,
                )

            try:
                r = lora3b()
                train.update({f"lora3b_{k}": v for k, v in r.items()})
            except Exception as e:  # noqa: BLE001
                print(f"[bench] 3B-LoRA bonus phase failed: {e}", file=sys.stderr)
        metric = "trainer_mfu_qwen2.5-0.5b_bf16_packed_sft"
    else:  # JAX_PLATFORMS=cpu: small sizes that check the plumbing, no speeds
        model = ModelConfig(
            vocab_size=1024,
            hidden_size=128,
            intermediate_size=256,
            num_hidden_layers=2,
            num_attention_heads=4,
            num_key_value_heads=2,
            dtype="float32",
            param_dtype="float32",
        )
        train = {}
        decode = {}
        if want("train"):
            train = bench_train(
                model, tokens_per_step=512, seq_len=128, mb_tokens=640,
                warmup=1, iters=3,
            )
        if want("decode"):
            # enough CHUNKS per request that the steady-state decode loop
            # dominates admission/prefill transients — the run-ahead vs
            # sync comparison is meaningless on a one-chunk-per-request
            # window, so chunk=8 gives an 8-deep stream per request
            decode = bench_decode_compare(
                model, n_requests=8, prompt_len=16, new_tokens=64,
                max_running=4, chunk=8,
            )
        if want("prefix"):
            decode.update(
                bench_prefix_decode(
                    model, n_groups=2, group_size=2, prompt_len=32,
                    new_tokens=8,
                )
            )
        if want("ppsched"):
            decode.update(
                bench_pp_schedules(
                    model, pp=2, n_mbs=8, seq_len=128, warmup=1, iters=2
                )
            )
        if want("weightsync"):
            decode.update(
                bench_weightsync(
                    model, n_pushes=2, chunk_mb=0.01, prompt_len=16,
                    new_tokens=32,
                )
            )
        if want("specdecode"):
            # long enough generation that the greedy echo cycle locks in
            # and most verify chunks ride at full acceptance (the ramp-in
            # chunks before the cycle establishes accept little)
            decode.update(
                bench_spec_compare(
                    model, n_requests=8, prompt_len=16, new_tokens=192,
                    max_running=4, chunk=8, spec_k=7,
                )
            )
        if want("kvoffload"):
            # pool slots (4) well below the 8-session working set, long
            # prompts so the avoided re-prefill dominates the resume TTFT
            decode.update(
                bench_kvoffload(
                    model, n_sessions=8, prompt_len=256, new_tokens=64,
                    max_running=4, host_mb=64.0, chunk=8,
                )
            )
        if want("kvquant"):
            # pool_mb sized so the f32 pool pressures the 4-slot working
            # set (8 sessions x 320 tokens) while int8 holds it resident
            decode.update(
                bench_kvquant(
                    model, n_sessions=8, prompt_len=256, new_tokens=64,
                    max_running=4, pool_mb=0.7, chunk=8,
                )
            )
        if want("wquant"):
            # tiny-model weights are small vs the pool, so the smoke
            # mostly proves mechanics (wire ratio, commit pause, drift);
            # the capacity headroom story is the TPU leg's job
            decode.update(
                bench_wquant(
                    model, n_sessions=8, prompt_len=256, new_tokens=64,
                    max_running=4, pool_mb=0.7, chunk=8, n_push=2,
                )
            )
        if want("fleet"):
            # prompts long enough (>= 64-token affinity block AND the
            # engine's 64-token min shared prefix) that affinity routing
            # can turn group members into dup-prompt forks / session
            # turns into suffix prefills on the affine replica
            decode.update(
                bench_fleet(
                    model, n_replicas=2, n_groups=4, group_size=4,
                    prompt_len=128, new_tokens=16, max_running=4, chunk=8,
                )
            )
        if want("chaos"):
            # greedy streams + a seeded 7-point schedule over 2 decode
            # replicas + 1 prefill replica; prompts past the 64-token
            # affinity block so the chaos trace exercises the same
            # fork/suffix reuse paths the fleet smoke does while faults
            # land mid-stream AND mid-KV-handoff
            decode.update(
                bench_chaos(
                    model, n_replicas=2, n_groups=3, group_size=2,
                    prompt_len=96, new_tokens=16, max_running=4, chunk=8,
                )
            )
        if want("disagg"):
            # long prefills (256 tok on the tiny model) landing mid-trace
            # against 8-token decode chunks: the co-located baseline
            # serializes each prefill ahead of the next decode chunk, the
            # disaggregated fleet never does — that gap is the p99 ITL
            # headline. Drain leg: 4 sessions (greedy+sampled alternating)
            # per kv layout, migrated mid-stream and resumed bit-identically
            decode.update(
                bench_disagg(
                    model, n_decode_reqs=8, n_prefill_reqs=4,
                    prompt_short=48, prompt_long=1024, new_tokens=256,
                    max_running=16, chunk=4, drain_sessions=4,
                    drain_prompt=96, drain_tokens=48,
                )
            )
        if want("kvfabric"):
            # 32-token blocks (xla attention) and 1k prompts: the
            # warm-started replica's suffix prefill runs 32 tokens where
            # the cold one runs 1024 — long enough that the avoided
            # prefill clears the scheduler-tick noise floor on CPU.
            # Dedup leg: 4 requests sharing a 128-token head (4 complete
            # blocks) with 32-token divergent tails
            decode.update(
                bench_kvfabric(
                    model, prompt_len=1024, head_len=128, tail_len=32,
                    new_tokens=16, n_dedup=4, max_running=16, chunk=8,
                    n_ttft_reps=3, page_size=32, attn_impl="xla",
                )
            )
        if want("autoscale"):
            # diurnal lull -> burst -> lull with a mid-burst replica kill:
            # the supervised fleet starts at the 2-replica floor, rides
            # the burst up toward the 3-replica peak, replaces the killed
            # replica, and sheds the surplus in the trailing lull, while
            # the static comparator reserves the peak fleet throughout
            decode.update(
                bench_autoscale(
                    # sized so the burst (7 groups x 4 members, ~0.5s per
                    # 64-token request at chunk 2) holds in-flight demand
                    # well above the 2-replica capacity for ~1.5s — several
                    # supervisor ticks — with the kill landing mid-burst.
                    # The smoke's SLO band is wide: single-process CPU
                    # percentiles are GIL/compile-cache noise — the
                    # machinery and the exactly-once claims are what this
                    # smoke pins
                    model, n_base=2, n_peak=3, n_groups=24, group_size=4,
                    prompt_len=64, new_tokens=64, max_running=4, chunk=2,
                    # the kill lands after the supervised fleet has reached
                    # peak, so BOTH fleets lose a working replica mid-burst
                    kill_after_s=1.25, slo_band=2.5, itl_grace_ms=2.0,
                )
            )
        if want("grpo"):
            decode.update(
                bench_grpo(
                    model, n_prompts=2, group_size=2, prompt_len=16,
                    new_tokens=16, warmup_steps=1, steps=2, mb_tokens=256,
                )
            )
        if want("chaostrain"):
            # 4-step deterministic GRPO loop (greedy decode, shuffle-free
            # loader, batch-level adv norm) killed at each seeded trainer
            # seam at step 2, resumed from the committed recovery point and
            # checked against the unfaulted oracle; plus the torn-newest
            # checkpoint leg recovering from the predecessor
            decode.update(
                bench_chaostrain(
                    model, n_prompts=2, group_size=2, prompt_len=16,
                    new_tokens=16, steps=4, mb_tokens=256,
                )
            )

    detail = {
        "platform": dev.platform,
        "device": dev.device_kind,
        "device_count": len(jax.devices()),
        "mode": mode,
        **{k: round(v, 4) if isinstance(v, float) else v for k, v in train.items()},
        **{k: round(v, 4) if isinstance(v, float) else v for k, v in decode.items()},
    }
    if "step_time_s" in train:
        detail["step_time_s"] = round(train["step_time_s"], 3)
    if on_accel and mode in ("all", "train"):
        _emit(metric, train["mfu"], detail)
    else:
        # dev modes skip the trainer (emitting the MFU metric as 0.0 would
        # read as a catastrophic regression) and a CPU run has no
        # utilization: headline the mode's own number, if it has one.
        name, unit = MODE_HEADLINES.get(mode, ("", "none"))
        print(
            json.dumps(
                {
                    "metric": f"bench_{mode}_{'tpu' if on_accel else 'cpu_smoke'}",
                    "value": round(float(decode.get(name, 0.0)), 4),
                    "unit": unit,
                    "vs_baseline": 0.0,
                    "detail": detail,
                }
            ),
            flush=True,
        )


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser()
    p.add_argument(
        "--mode",
        default="all",
        choices=list(BENCH_MODES),
        help="which measurements to run (default: all)",
    )
    main(p.parse_args().mode)
