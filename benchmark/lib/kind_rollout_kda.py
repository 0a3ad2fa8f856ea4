"""Kind `rollout_kda`: the `rollout` kind (one decode chip of a decoupled
fleet, `JaxDecodeEngine` alone under a closed loop) for a sparse model whose
layers are Kimi Delta Attention mixers with a latent-attention layer in every
few (Kimi-Linear-class: a recurrent state a slot whose decay is a vector a
head BESIDE latent rows in a paged pool, in one slot cache; a share of 256
sigmoid-routed experts held here). The engine, its warm-up, the loop and the
choice of compared requests are `kind_rollout`'s own, as its five siblings
take them; what differs is here: the mixer's own leaves redrawn as the
published module starts them, the reference
(`reference/kimi_linear_ref.py`, with its tolerances), the byte and FLOP
counts (`flops_kda.py`, fed the window's counters), and BOTH checks: the
log-probabilities of completed requests and the caches' precision on the
caches themselves: the state's float32 (`kind_rollout_linear`'s finding
stands here: log-probabilities do not see a state rounded to bf16) and the
latent rows' bf16 (two latent layers of eight: they do not see rows at float8
either).

(PERF.md section 7 lists the opening that would fold this file and its five
siblings back into `kind_rollout.py`: the reference and the counts named by
the configuration's file. This is the sixth copy of `run`.)"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from . import flops_kda, harness, metrics, xplane
from .kind_rollout import COUNTERS as ROLLOUT_COUNTERS
from .kind_rollout import ClosedLoop, check_sample, warm_engine
from .traffic import Traffic, longest_sequence

# live slots only, summed over layers and token steps (engine/jax_decode.py)
COUNTERS = ROLLOUT_COUNTERS + (
    "chunks_consumed_token_steps_total",
    "moe_pairs_total", "moe_hot_expert_pairs_total", "moe_absent_pairs_total",
    "moe_group_tokens_here_total", "moe_group_experts_touched_total",
    "kv_latent_rows_read_total", "kv_latent_bytes_read_total",
    "gdn_state_updates_total", "gdn_state_bytes_total")
CHUNK_MODULE = "^jit_chunk"
# XLA's Mosaic grouped matmul for `jax.lax.ragged_dot`, three a layer a step
EXPERT_MATMUL_OP = "^%ragged-dot-none[. ]"
# the decode step's state update under a vector decay, one a KDA layer a step
# (ops/gdn_step.py names the call apart from the Gated DeltaNet's)
KDA_STEP_OP = "^%kda_step[. ]"
# the absorbed attention's read of the latent pool, one a latent layer a step
LATENT_ATTENTION_OP = "^%paged_attention_latent[. ]"
A_MAX = 16.0  # the published module draws A ~ U(0, 16) and keeps log A


def require_kda(model_path: str, config_file: dict):
    """Before anything is built: a program that does not know this model
    type, or reads it as another model, fails here, in seconds, and not
    after a window of the wrong model. Returns the model's config."""
    from areal_tpu.models.qwen2 import ModelConfig

    mc = ModelConfig.from_hf_config(model_path)
    L, lin = config_file["num_hidden_layers"], config_file["linear_attn_config"]
    types = tuple("linear_attention" if i in lin["kda_layers"] else "full_attention"
                  for i in range(1, L + 1))
    want = (config_file["num_experts"], config_file["num_experts_published"],
            config_file["num_experts_per_token"], config_file["moe_intermediate_size"], types,
            lin["num_heads"], True, config_file["kv_lora_rank"], 0, "none")
    got = (mc.num_experts, getattr(mc, "num_experts_published", None), mc.num_experts_per_tok,
           mc.moe_intermediate_size, getattr(mc, "layer_types", None),
           getattr(mc, "linear_num_value_heads", None), getattr(mc, "linear_decay_lanes", None),
           getattr(mc, "kv_lora_rank", None), getattr(mc, "q_lora_rank", None),
           getattr(mc, "pos_embed", None))
    if got != want:
        raise RuntimeError(
            f"the program read {config_file.get('model_type')!r} as (experts held, published, "
            f"per token, expert width, layer types, KDA heads, a decay a lane, latent rank, "
            f"query rank, positions) = {got}; the configuration says {want}")
    return mc


BIAS_STD = 0.01  # the selection bias: small beside sigmoid scores near 1/2


def redraw_mixer_leaves(params, seed: int):
    """`weights.py` knows projections, norms and biases. Some leaves of the
    Kimi Delta Attention mixer are none of these, and it would give `dt_bias`
    N(0, 0.5^2), `A_log` N(0, 1/1) and a convolution N(0, 1/channels). They
    are drawn here as `kind_rollout_linear` draws Qwen3-Next's, a pure
    function of the seed and the leaf's place in the tree: `A_log = log U(0,
    16)` (floored at log 1e-3), `dt_bias = 1`, and the three depthwise
    convolutions U(-1/2, 1/2) (torch's Conv1d default at a fan-in of its
    width, 4). The router's selection bias, which `weights.py` would draw
    like any bias at N(0, 0.5^2) (it would decide every token's choice), is
    N(0, 0.01^2), as `kind_rollout_hybrid` draws K-EXAONE's."""
    import jax
    import jax.numpy as jnp

    # as weights.py folds a seed of more than 31 bits, then this draw's own stream
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF), 0x6B1D)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name, k = str(getattr(path[-1], "key", "")), jax.random.fold_in(key, i)
        if name == "A_log":
            a = jax.random.uniform(k, leaf.shape, jnp.float32, 1e-3, A_MAX)
            leaf = jnp.log(a).astype(leaf.dtype)
        elif name == "dt_bias":
            leaf = jnp.ones_like(leaf)
        elif name.endswith("conv_kernel"):
            leaf = jax.random.uniform(k, leaf.shape, jnp.float32, -0.5, 0.5).astype(leaf.dtype)
        elif name == "router_bias":
            leaf = (BIAS_STD * jax.random.normal(k, leaf.shape, jnp.float32)).astype(leaf.dtype)
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


def compare_with_reference(name: str, got: np.ndarray, ref: np.ndarray,
                           margin: np.ndarray) -> dict:
    """One sample: the program's log-probabilities against `kimi_linear_ref`'s,
    under `kimi_linear_ref`'s tolerances: the mean and the 90th percentile of
    |delta| over the sequence's tokens. Reported beside them, deciding
    nothing: the largest delta, and the largest over the tokens whose own
    routing is no near-tie (`margin`, the reference's, per token)."""
    from ..reference.kimi_linear_ref import MEAN_ABS_TOL, NEAR_TIE_MARGIN, P90_ABS_TOL

    d = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    p90 = float(np.quantile(d, 0.9))
    ok = bool(np.isfinite(d).all() and d.mean() <= MEAN_ABS_TOL and p90 <= P90_ABS_TOL)
    clear = np.asarray(margin) >= NEAR_TIE_MARGIN
    return {"what": name, "ok": ok, "tokens": int(d.size), "mean_abs": float(d.mean()),
            "p90_abs": p90, "max_abs": float(d.max()),
            "max_abs_clear": float(d[clear].max()) if clear.any() else 0.0,
            "clear_share": float(clear.mean())}


def check_decode(rt, engine, done: list[dict], n: int, pad_to: int) -> list[dict]:
    """The engine's returned log-probabilities of `check_sample`'s requests
    (prefill through the chunk scan and the expanded attention, then the state
    kernel and the absorbed attention over the latent pool a token at a time)
    against the reference's full forward over prompt + completion, whose
    delta rule is the token-by-token recurrence and whose attention is the
    expanded form."""
    from ..reference import kimi_linear_ref

    out = []
    for r in check_sample(done, n):
        resp = r["resp"]
        seq = list(resp.input_tokens) + list(resp.output_tokens)
        ref, margin = kimi_linear_ref.token_logprobs(
            engine.params, engine.model_config, seq, temperature=1.0, pad_to=pad_to,
            with_margins=True)
        # ref[t] scores token t + 1: completion token j is entry input_len + j - 1
        first = resp.input_len - 1
        out.append(compare_with_reference(
            f"decode logprobs group {r['group']}: {resp.input_len} + {resp.output_len} tokens",
            np.asarray(resp.output_logprobs), ref[first:], margin[first:]))
    return out


STATE_STEPS = 32  # token steps of the state check's replay


def state_storage_check(S) -> dict:
    """The pool's `S` as the window left it: of its slots' non-zero entries,
    the share bf16 cannot hold (`reduce_precision`: XLA keeps it where it
    drops an `astype` round trip). float32 arithmetic leaves nearly all of
    them there; a bf16 pool, or an update that rounds what it writes, none."""
    import jax
    import jax.numpy as jnp

    from ..reference.kimi_linear_ref import STATE_F32_SHARE_MIN

    @jax.jit
    def count(S):
        rows = S[:, 1:]
        beyond = rows != jax.lax.reduce_precision(rows, exponent_bits=8, mantissa_bits=7)
        return jnp.sum(rows != 0, dtype=jnp.float32), jnp.sum(beyond, dtype=jnp.float32)

    nonzero, beyond = (float(x) for x in count(S))
    share = beyond / nonzero if nonzero else 0.0
    return {"what": f"state pool {tuple(S.shape)} {S.dtype}: entries beyond bf16",
            "ok": bool(str(S.dtype) == "float32" and share >= STATE_F32_SHARE_MIN),
            "nonzero": nonzero, "beyond_bf16_share": share}


def state_step_check(S, seed: int, step=None, steps: int = STATE_STEPS) -> dict:
    """`steps` token steps of the program's state update (`step`, by default
    `ops/gdn_step.py`'s under a vector decay, the op the decode chunk calls)
    for every slot of the pool's last KDA layer, from the pool's own rows and
    seeded inputs, against the reference's recurrence on the same inputs in
    float32. The pool itself is left as it was."""
    import jax
    import jax.numpy as jnp

    from ..reference.kimi_linear_ref import STATE_STEP_REL_TOL, delta_rule_step

    if step is None:
        from areal_tpu.ops.gdn_step import gdn_step as step
    n_lin, rows, Hv, dk, dv = S.shape
    R, layer = rows - 1, n_lin - 1
    ks = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), 0x5A7E), 5)

    def unit(key):
        t = jax.random.normal(key, (steps, R, Hv, dk), jnp.float32)
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    # slow decays, so that a step's rounding is still there many steps on
    xs = (unit(ks[0]) * dk ** -0.5, unit(ks[1]),
          jax.random.normal(ks[2], (steps, R, Hv, dv), jnp.float32),
          -jax.random.uniform(ks[3], (steps, R, Hv, dk), jnp.float32, 0.005, 0.5),
          jax.nn.sigmoid(jax.random.normal(ks[4], (steps, R, Hv), jnp.float32)))

    @jax.jit
    def program(S, xs):
        def one(S, x):
            o, S = step(S, *x, layer)
            return S, o

        S, o = jax.lax.scan(one, S, xs)
        return S[layer, 1:], o

    @jax.jit
    def reference(S, xs):
        with jax.default_matmul_precision("highest"):
            return jax.lax.scan(jax.vmap(delta_rule_step), S[layer, 1:].astype(jnp.float32), xs)

    def rel(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)) / jnp.max(jnp.abs(b)))

    (S_got, o_got), (S_ref, o_ref) = program(S, xs), reference(S, xs)
    d_state, d_out = rel(S_got, S_ref), rel(o_got, o_ref)
    return {"what": f"state update, {steps} steps of {R} slots from the pool's rows",
            "ok": bool(np.isfinite([d_state, d_out]).all()
                       and max(d_state, d_out) <= STATE_STEP_REL_TOL),
            "state_rel": d_state, "out_rel": d_out}


def check_state(rt, engine) -> list[dict]:
    """What the log-probabilities' bounds cannot see (kimi_linear_ref.py): the
    precision of the recurrent state, read on the state itself."""
    S = engine.state_pool()["S"]
    return [state_storage_check(S), state_step_check(S, rt.seed)]


def check_latent_rows(engine) -> list[dict]:
    """What the log-probabilities' bounds cannot see either (kimi_linear_ref.py:
    two layers of eight are latent): the precision of the latent pool's rows,
    read on the pool itself. Of its non-zero entries, the share float8 (e4m3)
    cannot hold: a bf16 pool leaves fifteen mantissas of sixteen there, rows
    rounded to float8 as they are written none."""
    import jax
    import jax.numpy as jnp

    from ..reference.kimi_linear_ref import LATENT_BEYOND_F8_SHARE_MIN

    pool = engine._kv_operands()[0]["latent"]

    @jax.jit
    def count(pool):
        # (`reduce_precision`: XLA:TPU drops an `astype` round trip through
        # float8 and the share then reads 0.0 for any pool, call k5; 3 mantissa
        # bits at 5 exponent bits hold every e4m3 value, its subnormals too)
        beyond = pool != jax.lax.reduce_precision(pool, exponent_bits=5, mantissa_bits=3)
        return jnp.sum(pool != 0, dtype=jnp.float32), jnp.sum(beyond, dtype=jnp.float32)

    nonzero, beyond = (float(x) for x in count(pool))
    share = beyond / nonzero if nonzero else 0.0
    return [{"what": f"latent pool {tuple(pool.shape)} {pool.dtype}: entries beyond float8",
             "ok": bool(share >= LATENT_BEYOND_F8_SHARE_MIN), "nonzero": nonzero,
             "beyond_f8_share": share}]


def traced_work(trace: dict, trace_window, tokens_per_chunk: int, running: float,
                counters: dict, model_config, device_kind: str) -> tuple[dict, dict]:
    """(`work`, `fields`) of the traced sub-window: the token steps its chunks
    computed, and each roofline share (least time over the trace's time).
    `counters` are the engine's over the TRACED sub-window itself (`run` reads
    them as the profiler starts and before it stops: the window's own would
    be diluted by its close, when the loop drains and few slots are live);
    `running` the mean number of running requests sampled inside it (it
    counts requests admitted while a chunk was in flight: more than the
    chunk's live slots). The live state updates, the live latent rows, the
    held pairs and the held experts they touch a token step are counts over
    the token steps those counters cover (`chunks_consumed_token_steps_total`: all
    are added when a chunk is consumed), no expectation."""
    lo, hi = trace_window
    chunk = xplane.module_time(trace, CHUNK_MODULE, lo, hi)
    steps = chunk["calls"] * tokens_per_chunk
    kinds = flops_kda.layer_kinds(model_config)
    counted_steps = max(counters["chunks_consumed_token_steps_total"], 1)
    updates = counters["gdn_state_updates_total"] / counted_steps
    rows = counters["kv_latent_rows_read_total"] / counted_steps
    pairs = counters["moe_pairs_total"] / counted_steps
    touched = counters["moe_group_experts_touched_total"] / counted_steps
    work = {"tokens_per_chunk": tokens_per_chunk, "running": running, "steps": steps,
            "counted_steps": counted_steps, "state_updates_per_step": updates,
            "latent_rows_per_step": rows, "held_pairs_per_step": pairs,
            "held_experts_touched_per_step": touched}
    fields = {}
    if steps and chunk["seconds"] > 0 and updates > 0:
        step = flops_kda.decode_step_needed_seconds(model_config, running, updates, rows, pairs,
                                                    touched, device_kind)
        fields["chunk_roofline_kda"] = 100.0 * steps * step["seconds"] / chunk["seconds"]
        work["needed_step"] = step
        kda_s = xplane.op_time(trace, KDA_STEP_OP, lo, hi)
        if kda_s > 0:
            kda = flops_kda.kda_step_needed_seconds(model_config, updates, device_kind)
            fields["kda_step_roofline"] = 100.0 * steps * kda["seconds"] / kda_s
        attn_s = xplane.op_time(trace, LATENT_ATTENTION_OP, lo, hi)
        if attn_s > 0:
            attn = flops_kda.latent_attention_needed_seconds(model_config, rows, device_kind)
            fields["nope_latent_attention_roofline"] = 100.0 * steps * attn["seconds"] / attn_s
        experts_s = xplane.op_time(trace, EXPERT_MATMUL_OP, lo, hi)
        if experts_s > 0:
            layer = flops_kda.expert_matmuls_needed_seconds(
                model_config, pairs / kinds["sparse"], touched / kinds["sparse"], device_kind)
            fields["routed_expert_matmul_roofline"] = (
                100.0 * steps * kinds["sparse"] * layer["seconds"] / experts_s)
    return work, fields


def run(rt) -> dict:
    import jax

    cell, tfile = rt.cell, rt.cell["traffic_file"]
    config = harness.experiment_config(rt)
    require_kda(config.decode.model_path, cell["config_file"])
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
                          longest_sequence(tfile)) + check_state(rt, engine) + (
        check_latent_rows(engine))
    rt.note(requests_completed_in_window=len(completed), flushed_at_close=len(flushed),
            tpot_p50_ms=p50["value"], tpot_p95=p95, generated_tokens=tokens,
            engine_counter_tokens=counters["generated_tokens_total"],
            window_s=t_close - t_open, compile_requests_in_window=in_window,
            counters=counters, checks=checks,
            parameters=flops_kda.param_count(engine.model_config),
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
        # the requests and the state compared with the reference, and any request
        # that came back short
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
