"""Kind `train`: `JaxPPOActor` alone, one whole PPO step after another
(`compute_logp` + `compute_advantages` + `ppo_update`) on batches the traffic
generator packs. No decode engine exists in the process."""

from __future__ import annotations

import time

import numpy as np

from . import harness, metrics
from .traffic import Traffic, batch_lengths, longest_sequence


def build_actor(rt, config):
    """The trainer as `examples/gsm8k_grpo.py:main` builds it."""
    from areal_tpu.api.alloc_mode import AllocationMode
    from areal_tpu.api.io_struct import FinetuneSpec
    from areal_tpu.engine.ppo.actor import JaxPPOActor
    from areal_tpu.utils import name_resolve

    name_resolve.reconfigure(config.cluster.name_resolve)
    alloc = AllocationMode.from_str(config.allocation_mode)
    actor = JaxPPOActor(config.actor)
    actor.create_process_group(alloc.train)
    bs = config.train_dataset.batch_size
    actor.initialize(None, FinetuneSpec(1, 1000 * bs, bs))
    harness.reseed_actor(actor, rt.seed)
    return actor, alloc


def ppo_step(rt, actor, batch: dict) -> list:
    """One whole step in `main`'s order, with the benchmark's spans."""
    from areal_tpu.utils import stats_tracker

    with rt.spans.span("compute_logp"):
        batch["prox_logp"] = actor.compute_logp(batch)
    with rt.spans.span("compute_advantages"):
        actor.compute_advantages(batch)
    with rt.spans.span("ppo_update"), stats_tracker.scope("grpo_actor"):
        return actor.ppo_update(batch)


def check_trainer(rt, actor, batch: dict, prox: np.ndarray, n: int, pad_to: int) -> list[dict]:
    """The trainer's `compute_logp` against the float32 reference on the
    first `n` sequences of the batch (seeded, so a seeded sample)."""
    from ..reference import qwen2_ref

    lens = batch_lengths(batch)
    out = []
    for i in range(n):
        T = lens[i]
        ref = qwen2_ref.token_logprobs(
            actor.params, actor.model_config, batch["input_ids"][i, :T],
            temperature=float(actor.config.temperature), pad_to=pad_to)
        # prox[t] scores token t + 1, as the reference's entry t does
        out.append(harness.compare_with_reference(
            f"trainer.compute_logp seq {i}", prox[i, : T - 1], ref))
    return out


def run(rt) -> dict:
    cell, tfile = rt.cell, rt.cell["traffic_file"]
    config = harness.experiment_config(rt)
    actor, _ = build_actor(rt, config)
    traffic = Traffic(tfile, actor.model_config.vocab_size, rt.seed)
    G = int(tfile["groups_per_batch"])
    batches = [traffic.train_batch(j, G) for j in range(int(tfile.get("distinct_batches", 2)))]

    # -- warm-up: every distinct batch once (compiles every padded shape),
    # behaviour log-probabilities from the policy itself, and the check
    checks, step_stats = [], []
    for j, b in enumerate(batches):
        prox = actor.compute_logp(b)
        b["logprobs"] = np.roll(prox, 1, axis=-1) * (np.asarray(b["loss_mask"]) > 0)
        if j == 0:
            checks = check_trainer(rt, actor, b, prox, int(cell.get("check_samples", 4)),
                                   longest_sequence(tfile))
    for _ in range(int(cell.get("warmup_steps", 2))):
        for b in batches:
            step_stats.append(ppo_step(rt, actor, {k: np.copy(v) for k, v in b.items()}))

    step_ends, step_tokens = [time.monotonic()], [0.0]
    cache0 = rt.cache.snapshot()
    t_open = time.monotonic()
    setup_s = t_open - rt.t_start
    t_close = t_open + rt.seconds
    trace = harness.StepTrace(rt)
    i = 0
    while time.monotonic() < t_close:
        trace.before_step(i)
        b = batches[i % len(batches)]
        step_stats.append(ppo_step(rt, actor, {k: np.copy(v) for k, v in b.items()}))
        step_ends.append(time.monotonic())
        lens = batch_lengths(b)
        step_tokens.append(float(sum(lens)))
        trace.after_step(i, lens)
        i += 1
    trace.close()
    in_window = harness.CacheWatch.delta(cache0, rt.cache.snapshot())

    chips = int(cell["chips"])
    rate = metrics.whole_step_rate(step_ends, step_tokens, t_open, t_close)
    # each step's seconds go on the line too: a stalled step names itself
    rt.note(whole_steps=rate["steps"], whole_step_seconds=rate["seconds"],
            step_seconds=[round(b - a, 4) for a, b in zip(step_ends, step_ends[1:])],
            tokens_per_step=step_tokens[-1], compile_requests_in_window=in_window,
            checks=checks)
    bad = harness.finite_steps(step_stats)
    failed = sum(1 for c in checks if not c["ok"])
    result = {
        "correct": failed == 0 and not bad and in_window["misses"] == 0 and bool(checks),
        "attempted": len(checks), "failed": failed,
        "end_to_end": {"train_tokens_per_s": rate["rate"] / chips, "setup_s": setup_s},
        "ctx": {"window": (t_open, t_close), "model_config": actor.model_config,
                "work": {"lengths": trace.lengths, "steps": trace.steps}, **trace.reduce()},
        "why_not": bad,
    }
    actor.destroy()
    return result
