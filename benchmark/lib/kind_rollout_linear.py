"""Kind `rollout_linear`: the `rollout` kind (one decode chip of a decoupled
fleet, `JaxDecodeEngine` alone under a closed loop) for a sparse model whose
layers are Gated DeltaNet mixers with a gated full-attention layer in every
few (Qwen3-Next-class: a recurrent state a slot beside the paged pool, a share
of many small experts held here). The engine, its warm-up, the loop and the
choice of compared requests are `kind_rollout`'s own, as `kind_rollout_hybrid`
takes them; what differs is here: the mixer's own leaves redrawn as the
published module starts them, the reference (`reference/qwen3next_ref.py`,
with its tolerances), the byte and FLOP counts (`flops_linear.py`), and the
counters of the held experts, of the state and of the paged rows.

(PERF.md section 7 lists the opening that would fold this file,
`kind_rollout_hybrid.py` and `kind_rollout_moe.py` back into
`kind_rollout.py`: the reference and the counts named by the configuration's
file. This is the third copy of `run`.)"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from . import flops_linear, harness, metrics, xplane
from .kind_rollout import COUNTERS as ROLLOUT_COUNTERS
from .kind_rollout import ClosedLoop, check_sample, warm_engine
from .traffic import Traffic, longest_sequence

# live slots only, summed over layers and token steps (engine/jax_decode.py)
COUNTERS = ROLLOUT_COUNTERS + (
    "moe_pairs_total", "moe_hot_expert_pairs_total", "moe_absent_pairs_total",
    "kv_full_rows_read_total", "kv_full_bytes_read_total",
    "gdn_state_updates_total", "gdn_state_bytes_total")
CHUNK_MODULE = "^jit_chunk"
# XLA's Mosaic grouped matmul for `jax.lax.ragged_dot`, three a layer a step
EXPERT_MATMUL_OP = "^%ragged-dot-none[. ]"
# the decode step's state update, one a linear layer a step (ops/gdn_step.py)
GDN_STEP_OP = "^%gdn_step[. ]"
A_MAX = 16.0  # the published module draws A ~ U(0, 16) and keeps log A


def require_linear_stack(model_path: str, config_file: dict):
    """Before anything is built: a program that does not know this model
    type, or reads it as another model, fails here, in seconds, and not
    after a window of the wrong model. Returns the model's config."""
    from areal_tpu.models.qwen2 import ModelConfig

    mc = ModelConfig.from_hf_config(model_path)
    L = config_file["num_hidden_layers"]
    every = config_file["full_attention_interval"]
    types = tuple("full_attention" if (i + 1) % every == 0 else "linear_attention"
                  for i in range(L))
    want = (config_file["num_experts"], config_file["num_experts_published"],
            config_file["num_experts_per_tok"], config_file["moe_intermediate_size"], types,
            config_file["linear_num_value_heads"], config_file["partial_rotary_factor"])
    got = (mc.num_experts, getattr(mc, "num_experts_published", None), mc.num_experts_per_tok,
           mc.moe_intermediate_size, getattr(mc, "layer_types", None),
           getattr(mc, "linear_num_value_heads", None),
           getattr(mc, "partial_rotary_factor", None))
    if got != want:
        raise RuntimeError(
            f"the program read {config_file.get('model_type')!r} as (experts held, published, "
            f"per token, expert width, layer types, value heads, rotary share) = {got}; the "
            f"configuration says {want}")
    return mc


def redraw_mixer_leaves(params, seed: int):
    """`weights.py` knows projections, norms and biases. Three leaves of the
    Gated DeltaNet mixer are none of these, and it would give `dt_bias`
    N(0, 0.5^2), `A_log` N(0, 1/1) and the convolution N(0, 1/channels).
    They are drawn here as the published module starts them, a pure function
    of the seed and the leaf's place in the tree: `A_log = log U(0, 16)`
    (floored at log 1e-3), `dt_bias = 1`, and the depthwise convolution
    U(-1/2, 1/2) (torch's Conv1d default at a fan-in of its width, 4)."""
    import jax
    import jax.numpy as jnp

    # as weights.py folds a seed of more than 31 bits, then this draw's own stream
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF), 0x6D17)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for i, (path, leaf) in enumerate(leaves):
        name, k = str(getattr(path[-1], "key", "")), jax.random.fold_in(key, i)
        if name == "A_log":
            a = jax.random.uniform(k, leaf.shape, jnp.float32, 1e-3, A_MAX)
            leaf = jnp.log(a).astype(leaf.dtype)
        elif name == "dt_bias":
            leaf = jnp.ones_like(leaf)
        elif name == "conv_kernel":
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


def compare_with_reference(name: str, got: np.ndarray, ref: np.ndarray,
                           margin: np.ndarray) -> dict:
    """One sample: the program's log-probabilities against `qwen3next_ref`'s,
    under `qwen3next_ref`'s tolerances: the mean and the 90th percentile of
    |delta| over the sequence's tokens. Reported beside them, deciding
    nothing: the largest delta, and the largest over the tokens whose own
    routing is no near-tie (`margin`, the reference's, per token)."""
    from ..reference.qwen3next_ref import MEAN_ABS_TOL, NEAR_TIE_MARGIN, P90_ABS_TOL

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
    (prefill through the chunked scan, then the state kernel and the paged
    cache a token at a time) against the reference's full forward over
    prompt + completion, whose delta rule is the token-by-token recurrence."""
    from ..reference import qwen3next_ref

    out = []
    for r in check_sample(done, n):
        resp = r["resp"]
        seq = list(resp.input_tokens) + list(resp.output_tokens)
        ref, margin = qwen3next_ref.token_logprobs(
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

    from ..reference.qwen3next_ref import STATE_F32_SHARE_MIN

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
    `ops/gdn_step.py`'s, the op the decode chunk calls) for every slot of the
    pool's last linear layer, from the pool's own rows and seeded inputs,
    against the reference's recurrence on the same inputs in float32. The
    pool itself is left as it was."""
    import jax
    import jax.numpy as jnp

    from ..reference.qwen3next_ref import STATE_STEP_REL_TOL, delta_rule_step

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
          -jax.random.uniform(ks[3], (steps, R, Hv), jnp.float32, 0.005, 0.5),
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
    """What the log-probabilities' bounds cannot see (qwen3next_ref.py): the
    precision of the recurrent state, read on the state itself."""
    S = engine.state_pool()["S"]
    return [state_storage_check(S), state_step_check(S, rt.seed)]


def traced_work(trace: dict, trace_window, tokens_per_chunk: int, running: float,
                live_tokens: float, model_config, device_kind: str) -> tuple[dict, dict]:
    """(`work`, `fields`) of the traced sub-window: the token steps its chunks
    computed, and each roofline share (least time over the trace's time)."""
    lo, hi = trace_window
    chunk = xplane.module_time(trace, CHUNK_MODULE, lo, hi)
    steps = chunk["calls"] * tokens_per_chunk
    work = {"tokens_per_chunk": tokens_per_chunk, "running": running,
            "live_tokens": live_tokens, "steps": steps}
    fields = {}
    if steps and chunk["seconds"] > 0:
        kinds = flops_linear.layer_kinds(model_config)
        step = flops_linear.decode_step_needed_seconds(model_config, running, live_tokens,
                                                       device_kind)
        fields["chunk_roofline_linear"] = 100.0 * steps * step["seconds"] / chunk["seconds"]
        work["needed_step"] = step
        experts_s = xplane.op_time(trace, EXPERT_MATMUL_OP, lo, hi)
        if experts_s > 0:
            layer = flops_linear.expert_matmuls_needed_seconds(model_config, running, device_kind)
            fields["small_expert_matmul_roofline"] = (
                100.0 * steps * kinds["sparse"] * layer["seconds"] / experts_s)
        gdn_s = xplane.op_time(trace, GDN_STEP_OP, lo, hi)
        if gdn_s > 0:
            layer = flops_linear.gdn_step_needed_seconds(model_config, running, device_kind)
            fields["gdn_step_roofline"] = (
                100.0 * steps * kinds["linear"] * layer["seconds"] / gdn_s)
    return work, fields


def run(rt) -> dict:
    import jax

    cell, tfile = rt.cell, rt.cell["traffic_file"]
    config = harness.experiment_config(rt)
    require_linear_stack(config.decode.model_path, cell["config_file"])
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
                          longest_sequence(tfile)) + check_state(rt, engine)
    rt.note(requests_completed_in_window=len(completed), flushed_at_close=len(flushed),
            tpot_p50_ms=p50["value"], tpot_p95=p95, generated_tokens=tokens,
            engine_counter_tokens=counters["generated_tokens_total"],
            window_s=t_close - t_open, compile_requests_in_window=in_window,
            counters=counters, checks=checks,
            parameters=flops_linear.param_count(engine.model_config),
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
        work, fields = traced_work(
            ctx["trace"], ctx["trace_window"], config.decode.new_tokens_per_chunk,
            float(np.mean([s[1] for s in inside])), float(np.mean([s[2] for s in inside])),
            engine.model_config, jax.devices()[0].device_kind)
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
