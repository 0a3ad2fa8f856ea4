"""Kind `rollout_latent`: the `rollout` kind (one decode chip of a decoupled
fleet, `JaxDecodeEngine` alone under a closed loop) for a latent-attention
sparse model (DeepSeek-V2 class: one cached row a token and layer, a whole
routing group of the experts held here). The engine, its warm-up, the loop
and the choice of compared requests are `kind_rollout`'s own, as
`kind_rollout_hybrid` takes them; what differs is here: the reference
(`reference/deepseek_v2_ref.py`, with its tolerances; the engine's pool is
freed before it runs, for the float32 forward over 16,384 tokens does not fit
beside it), the byte and FLOP counts (`flops_latent.py`, fed the rows and
pairs the engine counted and no expectation), and the counters of the latent
pool and of the held group.

(PERF.md section 7 lists the opening that would fold this file and its
siblings back into `kind_rollout.py`: the reference and the counts named by
the configuration's file.)"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from . import flops_latent, harness, metrics, xplane
from .kind_rollout import COUNTERS as ROLLOUT_COUNTERS
from .kind_rollout import ClosedLoop, build_engine, check_sample, warm_engine
from .traffic import Traffic, longest_sequence

# live slots only, summed over layers and token steps (engine/jax_decode.py)
COUNTERS = ROLLOUT_COUNTERS + (
    "moe_pairs_total", "moe_hot_expert_pairs_total", "moe_absent_pairs_total",
    "moe_group_tokens_here_total", "moe_group_experts_touched_total",
    "kv_latent_rows_read_total", "kv_latent_bytes_read_total")
CHUNK_MODULE = "^jit_chunk"
# XLA's Mosaic grouped matmul for `jax.lax.ragged_dot`, three a sparse layer a step
EXPERT_MATMUL_OP = "^%ragged-dot-none[. ]"
LATENT_ATTENTION_OP = "^%paged_attention_latent[. ]"


def require_latent(model_path: str, config_file: dict):
    """Before anything is built: a program that does not know this model
    type, or reads it as another model, fails here, in seconds, and not
    after a window of the wrong model. Returns the model's config."""
    from areal_tpu.models.qwen2 import ModelConfig

    mc = ModelConfig.from_hf_config(model_path)
    f = config_file
    want = (f["kv_lora_rank"], f["q_lora_rank"], f["qk_nope_head_dim"], f["qk_rope_head_dim"],
            f["v_head_dim"], f["n_routed_experts"], f["num_experts_published"],
            f["num_experts_per_tok"], f["n_group"], f["topk_group"], f["first_k_dense_replace"],
            f["rope_scaling"]["type"])
    got = tuple(getattr(mc, k, None) for k in (
        "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "num_experts", "num_experts_published", "num_experts_per_tok", "moe_n_group",
        "moe_topk_group", "first_k_dense", "rope_scaling_type"))
    if got != want:
        raise RuntimeError(
            f"the program read {f.get('model_type')!r} as (latent rank, query rank, nope, rope, "
            f"v widths, experts held, published, per token, groups, groups kept, dense layers, "
            f"rope scaling) = {got}; the configuration says {want}")
    return mc


def _deltas(got, ref) -> np.ndarray:
    return np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))


def compare_with_reference(name: str, got: np.ndarray, ref: np.ndarray,
                           margin: np.ndarray) -> dict:
    """One sample: the program's log-probabilities against
    `deepseek_v2_ref`'s, under its tolerance on the mean of |delta| over the
    sequence's tokens. Reported beside it, deciding nothing: the 90th
    percentile (bounded over the run's compared tokens together,
    `compare_all`), the largest delta, and the largest over the tokens whose
    own routing is no near-tie (`margin`, the reference's)."""
    from ..reference.deepseek_v2_ref import MEAN_ABS_TOL, NEAR_TIE_MARGIN

    d = _deltas(got, ref)
    ok = bool(np.isfinite(d).all() and d.mean() <= MEAN_ABS_TOL)
    clear = np.asarray(margin) >= NEAR_TIE_MARGIN
    return {"what": name, "ok": ok, "tokens": int(d.size), "mean_abs": float(d.mean()),
            "p90_abs": float(np.quantile(d, 0.9)), "max_abs": float(d.max()),
            "max_abs_clear": float(d[clear].max()) if clear.any() else 0.0,
            "clear_share": float(clear.mean())}


def compare_all(pairs: list[tuple]) -> dict:
    """The run's compared tokens taken together, (got, ref) a sample: the
    90th percentile of |delta| under `deepseek_v2_ref`'s tolerance. A short
    sample's own 90th percentile is its sixth or seventh largest delta, a
    count of near-tie flips; over a few thousand tokens it is the body."""
    from ..reference.deepseek_v2_ref import P90_ABS_TOL

    d = np.concatenate([_deltas(got, ref) for got, ref in pairs])
    p90 = float(np.quantile(d, 0.9))
    return {"what": f"all {len(pairs)} compared samples together", "tokens": int(d.size),
            "ok": bool(np.isfinite(d).all() and p90 <= P90_ABS_TOL),
            "mean_abs": float(d.mean()), "p90_abs": p90, "max_abs": float(d.max())}


def free_pools(engine) -> None:
    """The engine's pools off the device: its loop has been flushed, every
    response is on the host, and nothing generates again."""
    import jax

    for leaf in jax.tree.leaves((engine._k_cache, engine._v_cache)):
        leaf.delete()
    engine._k_cache = engine._v_cache = None


def check_decode(rt, engine, done: list[dict], n: int, pad_to: int) -> list[dict]:
    """The engine's returned log-probabilities of `check_sample`'s requests
    (prefill through the expanded form, then decode through the latent pool
    in the absorbed form) against the reference's full forward over prompt +
    completion: a check a request, and one over their tokens together."""
    from ..reference import deepseek_v2_ref

    out, pairs = [], []
    for r in check_sample(done, n):
        resp = r["resp"]
        seq = list(resp.input_tokens) + list(resp.output_tokens)
        ref, margin = deepseek_v2_ref.token_logprobs(
            engine.params, engine.model_config, seq, temperature=1.0, pad_to=pad_to,
            with_margins=True)
        # ref[t] scores token t + 1: completion token j is entry input_len + j - 1
        first = resp.input_len - 1
        pairs.append((np.asarray(resp.output_logprobs), ref[first:]))
        out.append(compare_with_reference(
            f"decode logprobs group {r['group']}: {resp.input_len} + {resp.output_len} tokens",
            *pairs[-1], margin[first:]))
    return out + [compare_all(pairs)] if pairs else out


def traced_work(trace: dict, trace_window, tokens_per_chunk: int, running: float,
                counters: dict, model_config, device_kind: str) -> tuple[dict, dict]:
    """(`work`, `fields`) of the traced sub-window: the token steps its chunks
    computed, and each roofline share (least time over the trace's time). The
    live rows, the held pairs and the held experts they touch a token step are
    the window's own, from the engine's counters over the steps its chunks
    computed."""
    lo, hi = trace_window
    chunk = xplane.module_time(trace, CHUNK_MODULE, lo, hi)
    steps = chunk["calls"] * tokens_per_chunk
    window_steps = counters["chunks_dispatched_total"] * tokens_per_chunk
    rows = counters["kv_latent_rows_read_total"] / max(window_steps, 1)
    pairs = counters["moe_pairs_total"] / max(window_steps, 1)
    touched = counters["moe_group_experts_touched_total"] / max(window_steps, 1)
    work = {"tokens_per_chunk": tokens_per_chunk, "running": running, "steps": steps,
            "latent_rows_per_step": rows, "held_pairs_per_step": pairs,
            "held_experts_touched_per_step": touched}
    fields = {}
    if steps and chunk["seconds"] > 0:
        step = flops_latent.decode_step_needed_seconds(model_config, running, rows, pairs,
                                                       touched, device_kind)
        fields["chunk_roofline_latent"] = 100.0 * steps * step["seconds"] / chunk["seconds"]
        work["needed_step"] = step
        attn_s = xplane.op_time(trace, LATENT_ATTENTION_OP, lo, hi)
        if attn_s > 0:
            attn = flops_latent.latent_attention_needed_seconds(model_config, rows, device_kind)
            fields["latent_attention_roofline"] = 100.0 * steps * attn["seconds"] / attn_s
            work["needed_latent_attention"] = attn
        experts_s = xplane.op_time(trace, EXPERT_MATMUL_OP, lo, hi)
        if experts_s > 0:
            sparse = flops_latent.layer_kinds(model_config)["sparse"]
            layer = flops_latent.expert_matmuls_needed_seconds(
                model_config, pairs / sparse, touched / sparse, device_kind)
            fields["group_expert_matmul_roofline"] = (
                100.0 * steps * sparse * layer["seconds"] / experts_s)
    return work, fields


def run(rt) -> dict:
    import jax

    cell, tfile = rt.cell, rt.cell["traffic_file"]
    config = harness.experiment_config(rt)
    require_latent(config.decode.model_path, cell["config_file"])
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
    pool_tokens = state["m1"].get("kv_pool_tokens_total")
    block_nbytes = state["m1"].get("kv_block_nbytes")
    free_pools(engine)
    checks = check_decode(rt, engine, loop.done, int(cell.get("check_samples", 4)),
                          longest_sequence(tfile))
    rt.note(requests_completed_in_window=len(completed), flushed_at_close=len(flushed),
            tpot_p50_ms=p50["value"], tpot_p95=p95, generated_tokens=tokens,
            engine_counter_tokens=counters["generated_tokens_total"],
            window_s=t_close - t_open, compile_requests_in_window=in_window,
            counters=counters, checks=checks,
            parameters=flops_latent.param_count(engine.model_config),
            memory_peak_bytes_by_stage=state_peaks,
            live_kv_tokens_mean=float(np.mean([x[2] for x in loop.samples])),
            live_kv_tokens_peak=float(max(x[2] for x in loop.samples)),
            running_mean=float(np.mean([x[1] for x in loop.samples])),
            kv_pool_tokens_total=pool_tokens, kv_block_nbytes=block_nbytes)
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
            float(np.mean([s[1] for s in inside])), counters, engine.model_config,
            jax.devices()[0].device_kind)
        ctx["work"] = work
        ctx["fields"].update(fields)
        rt.note(traced_work=work, traced_fields=fields)
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
