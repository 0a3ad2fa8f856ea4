"""Kind `rollout_ssm`: the `rollout` kind (one decode chip of a decoupled
fleet, `JaxDecodeEngine` alone under a closed loop) for a dense model whose
layers are Mamba-1 state-space mixers with an attention layer in every few
(Jamba-class: a float32 recurrent state a slot, constant in the context,
BESIDE a paged pool of one kv head for the attention layers, in one slot
cache; the whole model on the chip). The engine, the loop, the window and the
choice of compared requests are `kind_rollout`'s and `kind_rollout_kda`'s own
(`run` is a copy of the latter's frame; PERF.md section 7 lists the opening
that would fold the copies); what differs is here: the mixer's special leaves
redrawn as Mamba publishes its initialisation, a warm-up that makes a
one-prompt wave in every bucket the traffic's prompts meet before the batched
ones, the reference (`reference/jamba_ref.py`, under
`harness.compare_with_reference`'s limits), the byte and FLOP counts
(`flops_ssm.py`, fed the traced stretch's own counters), and the checks on the
caches themselves: the state's float32 (log-probabilities do not see a state
rounded to bf16) and the attention rows' bf16."""

from __future__ import annotations

import asyncio
import math
import time

import numpy as np

from . import flops_ssm, harness, metrics, xplane
from .kind_rollout import COUNTERS as ROLLOUT_COUNTERS
from .kind_rollout import ClosedLoop, _request, check_sample
from .kind_rollout_kda import state_storage_check
from .traffic import Traffic, longest_sequence, prompt_lengths

# live slots only, summed over layers and token steps (engine/jax_decode.py)
COUNTERS = ROLLOUT_COUNTERS + (
    "chunks_consumed_token_steps_total",
    "kv_full_rows_read_total", "kv_full_bytes_read_total",
    "gdn_state_updates_total", "gdn_state_bytes_total")
CHUNK_MODULE = "^jit_chunk"
# the decode step's state update, one a state-space layer a step (ops/ssm_step.py)
SSM_STEP_OP = "^%ssm_step[. ]"
DT_MIN, DT_MAX = 1e-3, 1e-1  # Mamba draws its initial step log-uniform here
STATE_STEPS = 32  # token steps of the state check's replay


def require_ssm(model_path: str, config_file: dict):
    """Before anything is built: a program that does not know this model
    type, or reads it as another model, fails here, in seconds, and not
    after a window of the wrong model. Returns the model's config."""
    from areal_tpu.models.qwen2 import ModelConfig

    mc = ModelConfig.from_hf_config(model_path)
    L = config_file["num_hidden_layers"]
    period, offset = config_file["attn_layer_period"], config_file["attn_layer_offset"]
    types = tuple("full_attention" if i % period == offset else "mamba" for i in range(L))
    want = (types, config_file["mamba_d_state"], config_file["mamba_expand"],
            config_file["mamba_dt_rank"], config_file["mamba_d_conv"],
            config_file["mamba_conv_bias"], config_file["num_key_value_heads"], "none", 0,
            config_file["tie_word_embeddings"])
    got = (getattr(mc, "layer_types", None), getattr(mc, "ssm_state_size", None),
           getattr(mc, "ssm_expand", None), getattr(mc, "ssm_dt_rank", None),
           mc.linear_conv_kernel_dim, getattr(mc, "ssm_conv_bias", None),
           mc.num_key_value_heads, getattr(mc, "pos_embed", None), mc.num_experts,
           mc.tie_word_embeddings)
    if got != want:
        raise RuntimeError(
            f"the program read {config_file.get('model_type')!r} as (layer types, state "
            f"lanes, expansion, dt rank, convolution width, its bias, kv heads, positions, "
            f"experts, tied head) = {got}; the configuration says {want}")
    return mc


def redraw_mixer_leaves(params, seed: int):
    """`weights.py` knows projections, norms and biases. Some leaves of the
    state-space mixer are none of these (it would give `ssm_A_log` N(0, 1/16),
    `D` N(0, 1), `dt_bias` and `conv_bias` N(0, 0.5^2), `dt_kernel` N(0,
    1/160) and the convolution N(0, 1/channels)). They are drawn here as
    Mamba publishes its initialisation, a pure function of the seed and the
    leaf's place in the tree: `A_log[n, c] = log(n + 1)`, `D = 1`, `dt_bias =
    softplus^-1(dt0)` with dt0 log-uniform in [1e-3, 1e-1], `dt_kernel ~
    U(-r^-0.5, r^-0.5)` at the step's rank r, the convolution and its bias
    U(-1/2, 1/2) (torch's Conv1d default at a fan-in of its width, 4)."""
    import jax
    import jax.numpy as jnp

    # as weights.py folds a seed of more than 31 bits, then this draw's own stream
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF), 0x55A1)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name, k = str(getattr(path[-1], "key", "")), jax.random.fold_in(key, i)
        if name == "ssm_A_log":  # [..., state lanes, channels]
            lanes = jnp.log(jnp.arange(1, leaf.shape[-2] + 1, dtype=jnp.float32))
            leaf = jnp.broadcast_to(lanes[:, None], leaf.shape).astype(leaf.dtype)
        elif name == "D":
            leaf = jnp.ones_like(leaf)
        elif name == "dt_bias":
            dt0 = jnp.exp(jax.random.uniform(k, leaf.shape, jnp.float32,
                                             math.log(DT_MIN), math.log(DT_MAX)))
            leaf = (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(leaf.dtype)
        elif name == "dt_kernel":  # [..., rank, channels]
            r = leaf.shape[-2] ** -0.5
            leaf = jax.random.uniform(k, leaf.shape, jnp.float32, -r, r).astype(leaf.dtype)
        elif name in ("conv_kernel", "conv_bias"):
            leaf = jax.random.uniform(k, leaf.shape, jnp.float32, -0.5, 0.5).astype(leaf.dtype)
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def build_engine(rt, config):
    """`kind_rollout.build_engine`, with the mixer's own leaves redrawn."""
    import jax

    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from areal_tpu.models.qwen2 import ModelConfig
    from areal_tpu.platforms import enable_compilation_cache

    from .weights import seeded_params

    enable_compilation_cache()
    mc = ModelConfig.from_hf_config(
        config.decode.model_path, dtype=config.decode.dtype,
        param_dtype=config.decode.dtype)
    params = redraw_mixer_leaves(seeded_params(mc, rt.seed), rt.seed)
    engine = JaxDecodeEngine(config.decode, config.rollout)
    engine.set_model(params, mc)
    del params
    engine.initialize()
    jax.block_until_ready(engine.params)
    return engine


def warm_buckets(tfile: dict) -> dict[int, int]:
    """{prefill bucket: a prompt length of the traffic's that falls into it}
    for every length the traffic's prompts take (`prompt_strata` of them): a
    prompt of p tokens prefills p - 1, in buckets of 64 (the engine's)."""
    plens = prompt_lengths(tfile["prompt_len"], int(tfile.get("prompt_strata", 8)))
    return {max(-(-(p - 1) // 64) * 64, 64): p for p in sorted(plens)}


def prefill_waves(buckets: dict[int, int], budget: int, slots: int) -> list[list[int]]:
    """Which prompt lengths to queue together so that EVERY batched-prefill
    variant of every bucket is made, whatever the budget splits: a wave holds
    exactly B distinct prompts of a bucket, for B = 1, 2, 4, 8 in turn (the
    engine batches what one pass admits of a bucket in 8s, 4s, 2s and 1s),
    and as many buckets as fit the pass's budget (`max_prefill_tokens`) and
    the slots, so that no wave is split between two passes."""
    waves = []
    for size in (1, 2, 4, 8):
        room, free = 0, 0
        for bucket in sorted(buckets, reverse=True):
            if size * bucket > budget or size > slots:
                continue
            if size * bucket > room or size > free:
                waves.append([])
                room, free = budget, slots
            waves[-1] += [buckets[bucket]] * size
            room -= size * bucket
            free -= size
    return waves


def warm_engine(rt, engine, tfile: dict) -> None:
    """Every program this traffic can reach, before the window: a wave of
    exactly B distinct prompts at every bucket the traffic's prompts meet,
    for each B the engine batches (`prefill_waves`: the one-prompt waves
    first, from which a lone late group member's prefill takes its program:
    what PERF.md section 7 records the SDAR kind's warm-up never made), the
    first wave with a duplicate that forks its primary; then the decode chunk
    at every depth a request grows through, as `kind_rollout.warm_engine`
    makes it. The batched-prefill programs made are noted by (bucket, B)."""
    buckets = warm_buckets(tfile)
    temperature = float(tfile.get("temperature", 1.0))
    rng = np.random.default_rng(0x55A1)
    vocab = engine.model_config.vocab_size

    async def wave(lengths: list[int], fork: bool):
        prompts = [rng.integers(1, vocab, n).tolist() for n in lengths]
        if fork:
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
        plan = prefill_waves(buckets, int(engine.config.max_prefill_tokens),
                             int(engine.config.max_running_requests))
        for i, lengths in enumerate(plan):
            await wave(lengths, fork=i == 0)

    asyncio.run(waves())
    made = sorted(getattr(engine, "_batched_prefill_fns", {}))
    want = [(b, w) for b in sorted(buckets) for w in (1, 2, 4, 8)
            if w * b <= int(engine.config.max_prefill_tokens)]
    rt.note(prefill_programs_warmed=made, prefill_programs_missing=sorted(set(want) - set(made)))
    shortest = min(buckets.values())
    deepest = int(tfile["prompt_len"]["hi"]) + int(tfile["output_len"]["hi"])
    ghost = getattr(engine, "_prewarm_chunk_variants", None)
    if ghost is not None:
        # compiles each depth without generating; private, so optional
        ghost(shortest, deepest - shortest, (1.0,))
    else:
        engine.generate(_request([1] * shortest, deepest - shortest, temperature), 600.0)


def check_decode(rt, engine, done: list[dict], n: int, pad_to: int,
                 state_bits: int | None = None) -> list[dict]:
    """The engine's returned log-probabilities of `check_sample`'s requests
    (prefill through the chunked scan and the dense attention, then the state
    kernel and the paged read a token at a time) against the reference's full
    forward over prompt + completion, whose recurrence is token by token."""
    from ..reference import jamba_ref

    out = []
    for r in check_sample(done, n):
        resp = r["resp"]
        seq = list(resp.input_tokens) + list(resp.output_tokens)
        ref = jamba_ref.token_logprobs(engine.params, engine.model_config, seq,
                                       temperature=1.0, pad_to=pad_to, state_bits=state_bits)
        # ref[t] scores token t + 1: completion token j is entry input_len + j - 1
        out.append(compare_with_reference(
            f"decode logprobs group {r['group']}: {resp.input_len} + {resp.output_len} tokens",
            np.asarray(resp.output_logprobs), ref[resp.input_len - 1:]))
    return out


def compare_with_reference(name: str, got: np.ndarray, ref: np.ndarray) -> dict:
    """One sample: the program's log-probabilities against `jamba_ref`'s, in
    `harness.compare_with_reference`'s form (a sequence's mean and its
    largest |difference|) under `jamba_ref`'s limits: this model's bf16
    arithmetic sits at a mean of 0.08 nat where the dense Qwen2.5's sits at
    0.02, so the harness's 0.06 / 0.3 would refuse the program as it should be."""
    from ..reference.jamba_ref import MAX_ABS_TOL, MEAN_ABS_TOL

    d = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    ok = bool(np.isfinite(d).all() and d.mean() <= MEAN_ABS_TOL and d.max() <= MAX_ABS_TOL)
    return {"what": name, "ok": ok, "tokens": int(d.size), "mean_abs": float(d.mean()),
            "p90_abs": float(np.quantile(d, 0.9)), "max_abs": float(d.max())}


def state_step_check(S, seed: int, step=None, steps: int = STATE_STEPS) -> dict:
    """`steps` token steps of the program's state update (`step`, by default
    `ops/ssm_step.py`'s, the op the decode chunk calls) for every slot of the
    pool's last state-space layer, from the pool's own rows and seeded
    inputs, against the reference's recurrence on the same inputs in float32.
    The pool itself is left as it was."""
    import jax
    import jax.numpy as jnp

    from ..reference.jamba_ref import STATE_STEP_REL_TOL
    from ..reference.jamba_ref import ssm_step as ref_step

    if step is None:
        from areal_tpu.ops.ssm_step import ssm_step as step
    n_layers, rows, N, Di = S.shape
    R, layer = rows - 1, n_layers - 1
    ks = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), 0x5A7E), 4)
    # small steps, so that a step's rounding is still there many steps on
    xs = (jax.random.uniform(ks[0], (steps, R, Di), jnp.float32, 1e-3, 2e-2),
          jax.random.normal(ks[1], (steps, R, Di), jnp.float32),
          jax.random.normal(ks[2], (steps, R, N), jnp.float32),
          jax.random.normal(ks[3], (steps, R, N), jnp.float32))
    A = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, Di))
    D = jnp.ones((Di,), jnp.float32)

    @jax.jit
    def program(S, xs):
        def one(S, x):
            y, S = step(S, *x, A, D, layer)
            return S, y

        S, y = jax.lax.scan(one, S, xs)
        return S[layer, 1:], y

    @jax.jit
    def reference(S, xs):
        def one(h, x):  # every slot a sequence of its own
            return jax.vmap(lambda h, dt, u, B, C: ref_step(h, (dt, u, B, C, A, D)))(h, *x)

        return jax.lax.scan(one, S[layer, 1:].astype(jnp.float32), xs)

    def rel(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)) / jnp.max(jnp.abs(b)))

    (S_got, y_got), (S_ref, y_ref) = program(S, xs), reference(S, xs)
    d_state, d_out = rel(S_got, S_ref), rel(y_got, y_ref)
    return {"what": f"state update, {steps} steps of {R} slots from the pool's rows",
            "ok": bool(np.isfinite([d_state, d_out]).all()
                       and max(d_state, d_out) <= STATE_STEP_REL_TOL),
            "state_rel": d_state, "out_rel": d_out}


def check_state(rt, engine) -> list[dict]:
    """What the log-probabilities' bounds cannot see (jamba_ref.py): the
    precision of the recurrent state, read on the state itself, and its
    storage: float32 beyond bf16, the null row (0) untouched, every entry
    finite (a free slot's row is a finished request's leftovers that no
    request reads: a slot is written from scratch when it is taken); noted
    beside them, the norm the slots' states hold at the window's end."""
    import jax.numpy as jnp

    S = engine.state_pool()["S"]
    storage = state_storage_check(S)
    null = float(jnp.max(jnp.abs(S[:, 0])))
    norms = jnp.sqrt(jnp.sum(S[:, 1:] ** 2, axis=(2, 3)))  # [layers, slots]
    storage.update(null_row_max_abs=null, state_norm_mean=float(norms.mean()),
                   state_norm_max=float(norms.max()))
    storage["ok"] = bool(storage["ok"] and null == 0.0 and bool(jnp.isfinite(S).all()))
    return [storage, state_step_check(S, rt.seed)]


def check_attention_rows(engine) -> list[dict]:
    """What the log-probabilities' bounds cannot see either (two attention
    layers of 28): the precision of the paged pool's rows, read on the pool
    itself. Of its non-zero entries, the share float8 (e4m3) cannot hold: a
    bf16 pool leaves fifteen mantissas of sixteen there, rows rounded to
    float8 as they are written none."""
    import jax
    import jax.numpy as jnp

    from ..reference.jamba_ref import ROWS_BEYOND_F8_SHARE_MIN

    kq, vq = engine._kv_operands()

    @jax.jit
    def count(pool):
        beyond = pool != jax.lax.reduce_precision(pool, exponent_bits=5, mantissa_bits=3)
        return jnp.sum(pool != 0, dtype=jnp.float32), jnp.sum(beyond, dtype=jnp.float32)

    nonzero, beyond = (sum(float(x) for x in pair)
                       for pair in zip(count(kq["full"]), count(vq["full"])))
    share = beyond / nonzero if nonzero else 0.0
    pool = kq["full"]
    return [{"what": f"attention pools 2 x {tuple(pool.shape)} {pool.dtype}: entries beyond float8",
             "ok": bool(share >= ROWS_BEYOND_F8_SHARE_MIN), "nonzero": nonzero,
             "beyond_f8_share": share}]


def traced_work(trace: dict, trace_window, tokens_per_chunk: int, running: float,
                counters: dict, model_config, device_kind: str) -> tuple[dict, dict]:
    """(`work`, `fields`) of the traced sub-window: the token steps its chunks
    computed, and each roofline share (least time over the trace's time), as
    `kind_rollout_kda.traced_work` builds them: `counters` are the engine's
    over the TRACED sub-window itself, the live state updates and the live
    attention rows a token step counts over the token steps those counters
    cover (`chunks_consumed_token_steps_total`), no expectation; `running`
    the mean number of occupied slots sampled inside it."""
    lo, hi = trace_window
    chunk = xplane.module_time(trace, CHUNK_MODULE, lo, hi)
    steps = chunk["calls"] * tokens_per_chunk
    kinds = flops_ssm.layer_kinds(model_config)
    counted_steps = max(counters["chunks_consumed_token_steps_total"], 1)
    updates = counters["gdn_state_updates_total"] / counted_steps
    rows = counters["kv_full_rows_read_total"] / counted_steps
    work = {"tokens_per_chunk": tokens_per_chunk, "running": running, "steps": steps,
            "counted_steps": counted_steps, "state_updates_per_step": updates,
            "live_slots_per_step": updates / max(kinds["ssm"], 1),
            "attention_rows_per_step": rows}
    fields = {}
    if steps and chunk["seconds"] > 0 and updates > 0:
        live = updates / kinds["ssm"]
        step = flops_ssm.decode_step_needed_seconds(model_config, live, updates, rows,
                                                    device_kind)
        fields["chunk_roofline_ssm"] = 100.0 * steps * step["seconds"] / chunk["seconds"]
        work["needed_step"] = step
        ssm_s = xplane.op_time(trace, SSM_STEP_OP, lo, hi)
        if ssm_s > 0:
            ssm = flops_ssm.ssm_step_needed_seconds(model_config, updates, device_kind,
                                                    calls=kinds["ssm"])
            fields["ssm_step_roofline"] = 100.0 * steps * ssm["seconds"] / ssm_s
            work["needed_ssm_step"] = ssm
    return work, fields


def run(rt, state_bits: int | None = None) -> dict:
    import jax

    cell, tfile = rt.cell, rt.cell["traffic_file"]
    config = harness.experiment_config(rt)
    require_ssm(config.decode.model_path, cell["config_file"])
    engine = build_engine(rt, config)
    # where the peak comes from: drawing the weights, the engine at work, or
    # the float32 reference after the window (the device line has the last)
    state_peaks = {"weights_and_pool": harness.device_line()["memory_peak_bytes"]}
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
            state["m_a"] = engine.get_metrics()
            tracer.start()
            await loop.run_until(t_b)
            state["m_b"] = engine.get_metrics()  # (before the stop, which holds the loop)
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
    completed = [r for r in loop.done if r["resp"].output_len == r["want"]]
    flushed = [r for r in loop.done if r["resp"].stop_reason == "interrupt"]
    short = [r for r in loop.done
             if r["resp"].output_len != r["want"] and r["resp"].stop_reason != "interrupt"]
    tokens = float(sum(r["resp"].output_len for r in loop.done))
    tpot = [1e3 * (r["t_done"] - r["t_sub"]) / r["want"] for r in completed]
    p95, p50 = metrics.percentile(tpot, 95), metrics.percentile(tpot, 50)
    counters = harness.engine_counters(state["m0"], state["m1"], COUNTERS, config.decode)
    state_peaks["window_closed"] = harness.device_line()["memory_peak_bytes"]
    checks = check_decode(rt, engine, loop.done, int(cell.get("check_samples", 4)),
                          longest_sequence(tfile), state_bits) + check_state(rt, engine) + (
        check_attention_rows(engine))
    rt.note(requests_completed_in_window=len(completed), flushed_at_close=len(flushed),
            tpot_p50_ms=p50["value"], tpot_p95=p95, generated_tokens=tokens,
            engine_counter_tokens=counters["generated_tokens_total"],
            window_s=t_close - t_open, compile_requests_in_window=in_window,
            counters=counters, checks=checks,
            parameters=flops_ssm.param_count(engine.model_config),
            memory_peak_bytes_by_stage=state_peaks,
            live_kv_tokens_mean=float(np.mean([x[2] for x in loop.samples])),
            live_kv_tokens_peak=float(max(x[2] for x in loop.samples)),
            running_mean=float(np.mean([x[1] for x in loop.samples])),
            kv_pool_tokens_total=state["m1"].get("kv_pool_tokens_total"))
    failed = sum(1 for c in checks if not c["ok"]) + len(short)
    ctx = {"window": (t_open, t_close), "counters": counters,
           "model_config": engine.model_config,
           "fields": {"tpot_p95_ms": p95["value"], "tpot_p50_ms": p50["value"]}}
    if tracer:
        lo, hi = tracer.host
        inside = [s for s in loop.samples if lo <= s[0] <= hi] or loop.samples[-1:]
        ctx.update(tracer.reduce())
        traced_counters = harness.engine_counters(state["m_a"], state["m_b"], COUNTERS,
                                                  config.decode)
        work, fields = traced_work(
            ctx["trace"], ctx["trace_window"], config.decode.new_tokens_per_chunk,
            float(np.mean([s[1] for s in inside])), traced_counters, engine.model_config,
            jax.devices()[0].device_kind)
        ctx["work"] = work
        ctx["fields"].update(fields)
        rt.note(traced_work=work, traced_fields=fields)
    result = {
        "correct": failed == 0 and in_window["misses"] == 0 and bool(checks),
        # the requests and the caches compared with the reference, and any
        # request that came back short
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
