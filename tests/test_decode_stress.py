"""Randomized decode-scheduler stress test against a naive oracle.

The scheduler in `engine/jax_decode.py` interleaves admission budgeting,
wave-batched prefill with same-wave dup forking, partial-prefix suffix
prefill, covering-donor reuse, parked-KV resume, LRU eviction, and
pool-pressure preemption. The scenario tests pin each feature alone; this
test drives them all CONCURRENTLY with seeded randomness and checks every
completed request against a naive re-prefill oracle (step-by-step greedy
forward) — the property that makes RL rollouts trustworthy: no scheduling
interleaving may change a single emitted token.

Chaos ops (pause → weight re-install → version bump → resume, and
pause → abort_all → resume) run from a separate thread while clients use
the reference's interrupt-accumulate-resubmit protocol
(areal/engine/remote_inf_engine.py:428-478), so parked-KV resume and
post-swap re-prefill are exercised under pool pressure, not in isolation.

Weights are re-installed with IDENTICAL values, so greedy outputs are
deterministic regardless of interleaving; version stamps still bump, which
lets us assert the stamping invariants without racing the swap clock.
"""

import asyncio
import threading
import uuid
from dataclasses import replace

import numpy as np
import pytest

import jax

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.models.qwen2 import ModelConfig, forward, init_params

TINY = ModelConfig(
    vocab_size=64,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    dtype="float32",
    param_dtype="float32",
)

SEED = 1234
N_JOBS = 48
N_CHAOS_ROUNDS = 8


class DigitTok:
    eos_token_id = None

    def decode(self, ids):
        return "".join(str(i % 10) for i in ids)


_ORACLE_PAD = 80  # >= max prompt + max_new of any job here, ONE compile


def _make_oracle(params):
    """Step-by-step greedy continuation via the training forward pass,
    jitted ONCE at a padded length (pad rows carry a different segment id so
    the packed-attention mask isolates them); the eager per-shape version
    costs minutes across 48 jobs x 12 steps on CPU."""

    @jax.jit
    def step(ids, true_len):
        positions = np.arange(_ORACLE_PAD, dtype=np.int32)
        seg = (positions >= true_len).astype(np.int32)  # pads in segment 1
        logits = forward(params, ids, positions, seg, TINY)
        return jax.numpy.argmax(logits[true_len - 1])

    def greedy_reference(prompt, n_new):
        seq = list(prompt)
        for _ in range(n_new):
            ids = np.zeros(_ORACLE_PAD, dtype=np.int32)
            ids[: len(seq)] = seq
            # the oracle IS a per-token host sync: each step feeds the
            # emitted token back into the next python-built input
            seq.append(int(step(ids, len(seq))))  # areal-lint: disable=AR201
        return seq[len(prompt):]

    return greedy_reference


def oracle_truncate(full, gconfig):
    """Pure-python model of the engine's stop semantics: walk the greedy
    continuation token by token; stop-token ids halt inclusively at first
    occurrence; stop STRINGS halt at the earliest token boundary whose
    decoded output contains the string (cf. test_stop_strings)."""
    tok = DigitTok()
    out = []
    for t in full[: gconfig.max_new_tokens]:
        out.append(t)
        if gconfig.stop_token_ids and t in gconfig.stop_token_ids:
            return out, "stop"
        if gconfig.stop and any(s in tok.decode(out) for s in gconfig.stop):
            return out, "stop"
    return out, "length"


def _make_jobs(rng, greedy_reference):
    """Prompt families engineered to hit the sharing machinery: exact
    duplicates (same-wave dup fork / covering donor), extensions
    (partial-prefix suffix prefill), and fresh prompts, with a mix of
    stop-token / stop-string / plain termination."""
    bases = [
        [1, 5, 9, 13, 2],
        [3, 7, 11],
        [2, 4, 6, 8, 10, 12],
        [9, 9, 1, 4],
    ]
    jobs = []
    for i in range(N_JOBS):
        kind = rng.integers(0, 4)
        if kind == 0:  # exact duplicate of a base
            prompt = list(bases[rng.integers(0, len(bases))])
        elif kind == 1:  # extension of a base (partial-prefix candidate)
            b = bases[rng.integers(0, len(bases))]
            prompt = list(b) + [int(x) for x in rng.integers(1, 60, rng.integers(1, 5))]
        else:  # fresh
            prompt = [int(x) for x in rng.integers(1, 60, rng.integers(2, 8))]
        max_new = int(rng.integers(4, 13))
        full = greedy_reference(prompt, max_new)
        stop_ids, stop_strs = [], []
        style = rng.random()
        if style < 0.25:
            # a stop id guaranteed to occur (some position in the oracle)
            stop_ids = [int(full[rng.integers(1, len(full))])]
        elif style < 0.35:
            stop_ids = [63]  # vocab edge, very unlikely to occur
        elif style < 0.5:
            text = DigitTok().decode(full)
            k = int(rng.integers(1, max(2, len(text) - 1)))
            stop_strs = [text[k : k + 2]]
        g = GenerationHyperparameters(
            greedy=True,
            max_new_tokens=max_new,
            stop_token_ids=stop_ids,
            stop=stop_strs,
        )
        jobs.append(
            {
                "prompt": prompt,
                "gconfig": g,
                "full": full,
                "delay": float(rng.random() * 1.5),
            }
        )
    return jobs


async def _run_job(eng, job):
    """Client protocol: on "interrupt", accumulate partials and resubmit
    prompt+tokens under the SAME rid (parked-KV resume path). Stop-string
    jobs do not resubmit: once partial output is folded into the prompt the
    engine (by design) only scans NEW tokens for the string, so the
    cross-interrupt oracle is not defined — prefix parity is still checked.
    """
    g = job["gconfig"]
    rid = str(uuid.uuid4())
    cur_prompt = list(job["prompt"])
    remaining = g.max_new_tokens
    acc_t, acc_lp, acc_v = [], [], []
    n_interrupts = 0
    while True:
        resp = await eng.agenerate(
            ModelRequest(
                rid=rid,
                input_ids=cur_prompt,
                gconfig=replace(g, max_new_tokens=remaining),
            )
        )
        acc_t += list(resp.output_tokens)
        acc_lp += list(resp.output_logprobs)
        acc_v += list(resp.output_versions)
        if resp.stop_reason != "interrupt":
            return dict(job, tokens=acc_t, logprobs=acc_lp, versions=acc_v,
                        reason=resp.stop_reason, interrupts=n_interrupts)
        n_interrupts += 1
        if g.stop:
            return dict(job, tokens=acc_t, logprobs=acc_lp, versions=acc_v,
                        reason="interrupt", interrupts=n_interrupts)
        remaining -= resp.output_len
        cur_prompt += list(resp.output_tokens)
        if remaining <= 0:
            return dict(job, tokens=acc_t, logprobs=acc_lp, versions=acc_v,
                        reason="length", interrupts=n_interrupts)


def test_pool_pressure_preemption_runahead_paged(cpu_devices):
    """Pool-pressure preemption x run-ahead x the paged KV layout.

    Geometry: 3 distinct 8-token prompts admit together, each reserving
    the 64-token prefill bucket (8 blocks at page_size=8) — exactly the
    pool's 24 usable blocks, zero slack. Every generation runs past 64
    total tokens, so each slot eventually needs a 9th block; with no
    parked KV and no free-slot donors to reclaim, `_dispatch_chunk`'s
    ensure loop MUST go through `_preempt_slot` while
    `decode_runahead_chunks=1` keeps a speculative chunk in flight on
    the in-pool attention path. The preempted request requeues
    invisibly and re-admits with its generated tokens as coverage
    prompt — every completed stream must still match the naive greedy
    oracle token for token. CPU-sized (tiny model, 3 requests): tier-1,
    not slow."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    cfg = JaxDecodeConfig(
        context_length=128,
        max_running_requests=3,
        new_tokens_per_chunk=4,
        page_size=8,
        # 24 usable blocks: 3 x 8-block admissions fit exactly; the first
        # slot to cross 64 tokens finds the pool dry and must preempt
        kv_pool_tokens=192,
        decode_runahead_chunks=1,
        paged_attn_impl="xla",
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig(), tokenizer=DigitTok())
    eng.set_model(params, TINY)
    eng.initialize()
    greedy_reference = _make_oracle(params)
    rng = np.random.default_rng(SEED + 7)
    jobs = []
    for _ in range(3):
        prompt = [int(x) for x in rng.integers(1, 60, 8)]
        jobs.append(
            {
                "prompt": prompt,
                "full": greedy_reference(prompt, 60),
                "gconfig": GenerationHyperparameters(
                    greedy=True, max_new_tokens=60
                ),
            }
        )

    async def main():
        return await asyncio.gather(
            *[
                eng.agenerate(
                    ModelRequest(input_ids=j["prompt"], gconfig=j["gconfig"])
                )
                for j in jobs
            ]
        )

    try:
        results = asyncio.run(main())
        m = eng.get_metrics()
    finally:
        eng.destroy()
    for i, (j, r) in enumerate(zip(jobs, results)):
        assert r.output_tokens == j["full"], (
            f"job {i}: preemption+requeue broke greedy parity on the paged "
            f"path: {r.output_tokens} != {j['full']}"
        )
        assert r.stop_reason == "length", (i, r.stop_reason)
        assert len(r.output_logprobs) == len(r.output_tokens), i
    # the pool pressure must actually have bitten
    assert m["preemptions_total"] > 0, m


def test_pool_pressure_offload_swapback_runahead_spec_paged(cpu_devices):
    """Zero-slack pool + HOST TIER x run-ahead x speculation x paged.

    Same 24-usable-block geometry as the preemption test above, but with
    `kv_host_pool_mb` enabled and `spec_decode="ngram"` on: the forced
    `_preempt_slot` now OFFLOADS the victim's KV to host RAM, and the
    invisible re-admission promotes it back (fresh blocks + async
    upload) instead of re-prefilling — while runahead=1 keeps a chunk in
    flight and the drafter/verify path is live. Every completed stream
    must still match the naive greedy oracle token for token, and the
    metrics must prove the preempt -> offload -> swap-back cycle
    actually ran (nonzero swap traffic + avoided re-prefill tokens)."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    cfg = JaxDecodeConfig(
        context_length=128,
        max_running_requests=3,
        new_tokens_per_chunk=4,
        page_size=8,
        kv_pool_tokens=192,
        kv_host_pool_mb=64,
        decode_runahead_chunks=1,
        paged_attn_impl="xla",
        spec_decode="ngram",
        spec_k=3,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig(), tokenizer=DigitTok())
    eng.set_model(params, TINY)
    eng.initialize()
    greedy_reference = _make_oracle(params)
    rng = np.random.default_rng(SEED + 7)
    jobs = []
    for _ in range(3):
        prompt = [int(x) for x in rng.integers(1, 60, 8)]
        jobs.append(
            {
                "prompt": prompt,
                "full": greedy_reference(prompt, 60),
                "gconfig": GenerationHyperparameters(
                    greedy=True, max_new_tokens=60
                ),
            }
        )

    async def main():
        return await asyncio.gather(
            *[
                eng.agenerate(
                    ModelRequest(input_ids=j["prompt"], gconfig=j["gconfig"])
                )
                for j in jobs
            ]
        )

    try:
        results = asyncio.run(main())
        m = eng.get_metrics()
    finally:
        eng.destroy()
    for i, (j, r) in enumerate(zip(jobs, results)):
        assert r.output_tokens == j["full"], (
            f"job {i}: preempt->offload->swap-back broke greedy parity: "
            f"{r.output_tokens} != {j['full']}"
        )
        assert r.stop_reason == "length", (i, r.stop_reason)
        assert len(r.output_logprobs) == len(r.output_tokens), i
    # the whole tiered lifecycle must actually have run
    assert m["preemptions_total"] > 0, m
    assert m["kv_swap_out_bytes_total"] > 0, m
    assert m["kv_swap_in_bytes_total"] > 0, m
    assert m["kv_host_hits_total"] > 0, m
    assert m["reprefill_tokens_avoided_total"] > 0, m
    assert m["spec_chunks_total"] > 0, m  # speculation was live throughout


@pytest.mark.slow
def test_randomized_scheduler_greedy_parity(cpu_devices):
    rng = np.random.default_rng(SEED)
    params = init_params(TINY, jax.random.PRNGKey(0))
    cfg = JaxDecodeConfig(
        context_length=96,
        max_running_requests=3,
        new_tokens_per_chunk=4,
        page_size=16,
        # ~2 full slots' worth of blocks for 3 running slots + parked KV:
        # admission must preempt/evict under load
        kv_pool_tokens=160,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig(), tokenizer=DigitTok())
    eng.set_model(params, TINY)
    eng.initialize()

    jobs = _make_jobs(rng, _make_oracle(params))
    results = []
    job_err = []
    versions_set = [0]
    done = threading.Event()

    async def _main():
        async def delayed(j):
            await asyncio.sleep(j["delay"])
            return await _run_job(eng, j)

        return await asyncio.gather(*[delayed(j) for j in jobs])

    def loop_thread():
        try:
            results.extend(asyncio.run(_main()))
        except BaseException as e:  # noqa: BLE001
            job_err.append(e)
        finally:
            done.set()

    t = threading.Thread(target=loop_thread, daemon=True)
    try:
        t.start()
        # chaos: interleave weight re-installs (identical values, version
        # bump) and abort_all storms while jobs are in flight
        chaos_rng = np.random.default_rng(SEED + 1)
        for round_i in range(N_CHAOS_ROUNDS):
            if done.wait(0.35 + float(chaos_rng.random()) * 0.4):
                break
            eng.pause_generation()
            try:
                if round_i % 2 == 0:
                    eng.abort_all()
                else:
                    eng.update_weights_from_distributed(None, params=params)
                    v = versions_set[-1] + 1
                    eng.set_version(v)
                    versions_set.append(v)
            finally:
                eng.continue_generation()
        assert done.wait(600), "stress jobs did not finish in 600s"
        if job_err:
            raise job_err[0]
    finally:
        done.wait(5)
        eng.destroy()

    assert len(results) == N_JOBS
    n_interrupted = sum(r["interrupts"] > 0 for r in results)
    for i, r in enumerate(results):
        exp_tokens, exp_reason = oracle_truncate(r["full"], r["gconfig"])
        if r["reason"] == "interrupt":
            # stop-string job cut short: oracle prefix parity only
            assert r["tokens"] == exp_tokens[: len(r["tokens"])], i
        else:
            assert r["tokens"] == exp_tokens, (
                f"job {i}: greedy parity broken under scheduling chaos: "
                f"{r['tokens']} != {exp_tokens}"
            )
            assert r["reason"] == exp_reason, (i, r["reason"], exp_reason)
        # stamping invariants: one version+logprob per token, versions
        # non-decreasing across interrupt resumes, all from set_version
        assert len(r["versions"]) == len(r["tokens"]), i
        assert len(r["logprobs"]) == len(r["tokens"]), i
        assert all(v in versions_set for v in r["versions"]), i
        assert r["versions"] == sorted(r["versions"]), i
        assert all(np.isfinite(lp) and lp <= 1e-6 for lp in r["logprobs"]), i
    # the chaos must have actually bitten: some jobs interrupted, some
    # preemptions or parked evictions occurred under the tiny pool
    m = eng.get_metrics()
    assert n_interrupted > 0, "abort storms never interrupted a job"
    assert m["preemptions_total"] + m["prefix_forks_total"] > 0, m
