"""Kind `rollout_diffusion`: the `rollout` kind (one decode chip of a decoupled
fleet, `JaxDecodeEngine` alone under a closed loop) for a sparse model that
generates by diffusion over blocks (SDAR-MoE class: a decode forward denoises
a block of `block_length` positions a slot under a block-causal mask, a commit
forward writes the clean block's rows, and a chunk returns whole blocks). The
engine build, its warm-up, the loop and the choice of compared requests are
`kind_rollout`'s own, as the other rollout kinds take them; what differs is
here: the reference (`reference/sdar_ref.py`, with its tolerances) and WHAT is
compared (every denoise state of chosen blocks, rebuilt from the response's
reveal steps), the byte and FLOP counts (`flops_diffusion.py`, a forward and
not a token step), and the counters of the forwards.

(PERF.md section 7 lists the opening that would fold the rollout kinds back
into `kind_rollout.py`: the reference and the counts named by the
configuration's file. This is the fourth copy of `run`.)"""

from __future__ import annotations

import asyncio
import time

import numpy as np

from . import flops_diffusion, harness, metrics, xplane
from .kind_rollout import COUNTERS as ROLLOUT_COUNTERS
from .kind_rollout import ClosedLoop, build_engine, check_sample, warm_engine
from .traffic import Traffic

# live slots only, summed over layers and forwards (engine/jax_decode.py)
COUNTERS = ROLLOUT_COUNTERS + (
    "moe_pairs_total", "moe_hot_expert_pairs_total",
    "diffusion_slot_forwards_total", "diffusion_commit_forwards_total",
    "diffusion_blocks_committed_total", "diffusion_block_tokens_discarded_total",
    "kv_block_rows_read_total")
CHUNK_MODULE = "^jit_chunk"
# XLA's Mosaic grouped matmul for `jax.lax.ragged_dot`, three a layer a forward
EXPERT_MATMUL_OP = "^%ragged-dot-none[. ]"
CHECK_BLOCKS = 12  # of a compared request: the first, the last whole, ten between
PAD_STEP = 256  # reference sequences share compiled shapes at multiples of this


def require_block_diffusion(model_path: str, config_file: dict):
    """Before anything is built: a program that does not know this model
    type (it raises), or reads it as a causal model, fails here, in seconds,
    and not after a window of the wrong model. Returns the model's config."""
    from areal_tpu.models.qwen2 import ModelConfig

    mc = ModelConfig.from_hf_config(model_path)
    want = (config_file["num_experts"], config_file["num_experts_per_tok"],
            config_file["moe_intermediate_size"], config_file["block_length"],
            config_file["mask_token_id"])
    got = (mc.num_experts, mc.num_experts_per_tok, mc.moe_intermediate_size,
           getattr(mc, "block_length", None), getattr(mc, "mask_token_id", None))
    if got != want or not mc.qk_norm or getattr(mc, "qk_norm_full", False):
        raise RuntimeError(
            f"the program read {config_file.get('model_type')!r} as (experts, per token, "
            f"expert width, block length, mask token) = {got}, per-head q/k norm = "
            f"{mc.qk_norm and not getattr(mc, 'qk_norm_full', False)}; the configuration says "
            f"{want} with a per-head q/k norm")
    return mc


def block_states(resp, block_length: int, mask_token_id: int) -> list[dict]:
    """Every denoise state of every WHOLE generated block of one response,
    rebuilt from its tokens and reveal steps. A block: {"base": its first
    position, "context": the clean tokens before it, "states": [{"step",
    "input": the B tokens the forward at that step saw (the mask token where
    a position was not yet revealed), "revealed": [(j, token, index of the
    token in the response's output)]}]}. A last block cut by `max_new_tokens`
    is left out: what it held beyond the cut was discarded, and the states
    cannot be rebuilt without it."""
    B = block_length
    P, n = resp.input_len, resp.output_len
    seq = list(resp.input_tokens) + list(resp.output_tokens)
    steps = [-1] * P + list(resp.output_reveal_steps)
    if len(steps) != len(seq):
        raise ValueError(f"{len(resp.output_reveal_steps)} reveal steps for {n} tokens")
    out = []
    for base in range((P // B) * B, P + n - B + 1, B):
        at = steps[base:base + B]
        states = []
        for s in sorted({a for a in at if a >= 0}):
            states.append({
                "step": s,
                "input": [seq[base + j] if at[j] < s else mask_token_id for j in range(B)],
                "revealed": [(j, seq[base + j], base + j - P) for j in range(B) if at[j] == s],
            })
        out.append({"base": base, "context": seq[:base], "states": states})
    return out


def chosen_blocks(blocks: list[dict], n: int = CHECK_BLOCKS) -> list[dict]:
    """The first, the last and `n - 2` evenly spaced between."""
    if len(blocks) <= n:
        return blocks
    idx = sorted({round(i * (len(blocks) - 1) / (n - 1)) for i in range(n)})
    return [blocks[i] for i in idx]


def compare_with_reference(name: str, got: np.ndarray, ref: np.ndarray, states: int) -> dict:
    """One request: the program's log-probabilities of the tokens revealed at
    the rebuilt states against `sdar_ref`'s, under `sdar_ref`'s tolerance (on
    the mean; the 90th percentile and the largest are reported beside it)."""
    from ..reference.sdar_ref import MEAN_ABS_TOL

    d = np.abs(np.asarray(got, np.float64) - np.asarray(ref, np.float64))
    ok = bool(d.size and np.isfinite(d).all() and d.mean() <= MEAN_ABS_TOL)
    return {"what": name, "ok": ok, "tokens": int(d.size), "states": states,
            "mean_abs": float(d.mean()) if d.size else float("nan"),
            "p90_abs": float(np.quantile(d, 0.9)) if d.size else float("nan"),
            "max_abs": float(d.max()) if d.size else float("nan")}


def check_request(params, model_config, resp, name: str = "", reference=None) -> dict:
    """Every denoise state of `chosen_blocks` of one response: the engine's
    returned log-probability of each token revealed at that state against the
    reference's full forward over the whole sequence up to the block's end.
    The last block's states read every earlier block's committed rows, so a
    cache left with denoise-time rows fails."""
    from ..reference import sdar_ref

    state_logprobs = reference or sdar_ref.state_logprobs
    B = int(model_config.block_length)
    got, ref, states = [], [], 0
    for blk in chosen_blocks(block_states(resp, B, int(model_config.mask_token_id))):
        pad_to = -(-(blk["base"] + B) // PAD_STEP) * PAD_STEP
        for st in blk["states"]:
            lp = state_logprobs(params, model_config, blk["context"], st["input"], pad_to=pad_to)
            states += 1
            for j, token, k in st["revealed"]:
                got.append(resp.output_logprobs[k])
                ref.append(float(lp[j, token]))
    return compare_with_reference(
        name or f"{resp.input_len} + {resp.output_len} tokens", np.asarray(got),
        np.asarray(ref), states)


def check_decode(rt, engine, done: list[dict], n: int) -> list[dict]:
    """`check_request` for `check_sample`'s requests (the two longest and the
    rest spread over the others)."""
    return [
        check_request(
            engine.params, engine.model_config, r["resp"],
            f"denoise states group {r['group']}: {r['resp'].input_len} + "
            f"{r['resp'].output_len} tokens")
        for r in check_sample(done, n)
    ]


def forwards_per_chunk(decode_config, block_length: int) -> int:
    """Forwards of one chunk program: its `new_tokens_per_chunk //
    block_length` blocks, each its denoise steps and a commit."""
    steps = min(int(decode_config.diffusion_steps), block_length)
    return (int(decode_config.new_tokens_per_chunk) // block_length) * (steps + 1)


def traced_work(trace: dict, trace_window, forwards_per_chunk: int, running: float,
                live_tokens: float, denoise_share: float, model_config,
                device_kind: str) -> tuple[dict, dict]:
    """(`work`, `fields`) of the traced sub-window: the forwards its chunks
    computed (`steps`: a kernel's time reads per forward), and each roofline
    share (least time over the trace's time)."""
    lo, hi = trace_window
    chunk = xplane.module_time(trace, CHUNK_MODULE, lo, hi)
    steps = chunk["calls"] * forwards_per_chunk
    work = {"forwards_per_chunk": forwards_per_chunk, "running": running,
            "live_tokens": live_tokens, "denoise_share": denoise_share, "steps": steps}
    fields = {}
    if steps and chunk["seconds"] > 0:
        fwd = flops_diffusion.forward_needed_seconds(
            model_config, running, live_tokens, device_kind, denoise_share)
        fields["chunk_roofline_diffusion"] = 100.0 * steps * fwd["seconds"] / chunk["seconds"]
        work["needed_forward"] = fwd
        experts_s = xplane.op_time(trace, EXPERT_MATMUL_OP, lo, hi)
        if experts_s > 0:
            layer = flops_diffusion.expert_matmuls_needed_seconds(
                model_config, running, device_kind)
            fields["block_expert_matmul_roofline"] = (
                100.0 * steps * model_config.num_hidden_layers * layer["seconds"] / experts_s)
    return work, fields


def run(rt) -> dict:
    import jax

    cell, tfile = rt.cell, rt.cell["traffic_file"]
    config = harness.experiment_config(rt)
    require_block_diffusion(config.decode.model_path, cell["config_file"])
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
    t_check = time.monotonic()
    checks = check_decode(rt, engine, loop.done, int(cell.get("check_samples", 4)))
    rt.note(requests_completed_in_window=len(completed), flushed_at_close=len(flushed),
            tpot_p50_ms=p50["value"], tpot_p95=p95, generated_tokens=tokens,
            engine_counter_tokens=counters["generated_tokens_total"],
            window_s=t_close - t_open, compile_requests_in_window=in_window,
            counters=counters, checks=checks, check_s=time.monotonic() - t_check,
            parameters=flops_diffusion.param_count(engine.model_config),
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
        slot_forwards = counters["diffusion_slot_forwards_total"]
        denoise_share = (
            1.0 - counters["diffusion_commit_forwards_total"] / slot_forwards
            if slot_forwards else 1.0)
        work, fields = traced_work(
            ctx["trace"], ctx["trace_window"],
            forwards_per_chunk(config.decode, int(engine.model_config.block_length)),
            float(np.mean([s[1] for s in inside])), float(np.mean([s[2] for s in inside])),
            denoise_share, engine.model_config, jax.devices()[0].device_kind)
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
