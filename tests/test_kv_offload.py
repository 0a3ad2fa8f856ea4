"""Tiered KV cache: host-RAM offload under the paged pool (ISSUE 7).

Three layers of coverage:

1. `HostKVStore` unit contracts (pure host accounting, no engine): byte
   budget + LRU eviction with tombstones, exact-resume matching with
   stale-entry drop, pending-copy materialisation window, counter
   bookkeeping incl. the take/restore promotion dance.
2. The engine invariant the tier is FOR: a stream that was interrupted,
   EVICTED to host RAM and promoted back is bit-identical — tokens AND
   logprobs — to the never-evicted oracle, greedy and sampled, at
   `decode_runahead_chunks=1` with `spec_decode="ngram"` on (the
   acceptance matrix of the issue). The restored bytes ARE the
   original KV and the slot's sampling base key travels with the entry,
   so fold_in(original_key, position) sampling makes the whole stream a
   pure function of token index again.
3. Degradation contracts: a host-tier MISS (budget-evicted entry) falls
   back to the pre-tier re-prefill and still matches the greedy oracle;
   `kv_host_pool_mb=0` reproduces today's drop-and-reprefill behavior
   exactly (all host metrics stay zero); weight installs flush the tier.
"""

import asyncio
import threading
import time
import uuid
from dataclasses import replace

import numpy as np
import pytest

from _numerics import assert_logprobs_close

import jax

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.engine.kv_pool import HostKVEntry, HostKVStore
from areal_tpu.models.qwen2 import ModelConfig, init_params

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

# K+V bytes per pool block for TINY at page_size=8, float32:
# 2 sides * L=2 * bs=8 * nKV=2 * hd=8 * 4B = 2048
_TINY_BLOCK_NBYTES = 2 * 2 * 8 * 2 * 8 * 4


# -- 1. HostKVStore unit contracts -------------------------------------


def _entry(rid, nb=2, covered=None, tokens=None, pending=False):
    covered = covered if covered is not None else nb * 4
    tokens = tokens if tokens is not None else list(range(covered))
    return HostKVEntry(
        rid=rid,
        k=np.zeros((1, nb, 4, 1, 2), np.float32),
        v=np.zeros((1, nb, 4, 1, 2), np.float32),
        nb=nb,
        covered=covered,
        tokens=tokens,
        rope_delta=0,
        base_key=np.zeros(2, np.uint32),
        ts=time.monotonic(),
        pending=pending,
    )


def test_store_budget_lru_and_tombstones():
    # budget: 2 blocks' worth; each entry below is 1 block
    st = HostKVStore(budget_bytes=200, block_nbytes=100, block_size=4)
    assert st.put(_entry("a", nb=1))
    assert st.put(_entry("b", nb=1))
    assert st.bytes_used == 200 and len(st) == 2
    # third entry LRU-evicts "a" (oldest) and tombstones it
    assert st.put(_entry("c", nb=1))
    assert len(st) == 2 and st.evictions == 1
    assert not st.match("a", 4, list(range(4)))  # tombstone -> counted miss
    assert st.misses == 1
    # the tombstone is consumed: a second lookup is silent
    assert not st.match("a", 4, list(range(4)))
    assert st.misses == 1
    # an entry bigger than the whole budget is rejected outright — and
    # tombstoned, so the dropped KV's resume counts as a miss
    assert not st.put(_entry("huge", nb=3))
    assert st.rejected_puts == 1
    assert not st.match("huge", 12, list(range(12)))
    assert st.misses == 2
    # match-hit keeps the entry; take pops it; note_hit counts the swap-in
    assert st.match("b", 4, list(range(4)))
    e = st.take("b")
    assert e is not None and st.bytes_used == 100
    st.note_hit(e)
    assert st.hits == 1 and st.swap_in_bytes_total == 100
    assert st.reprefill_tokens_avoided == e.covered


def test_store_stale_entry_drops_and_counts_miss():
    st = HostKVStore(budget_bytes=1000, block_nbytes=100, block_size=4)
    st.put(_entry("a", nb=1, covered=4, tokens=[1, 2, 3, 4]))
    # same rid, diverged tokens (edited prompt): stale -> dropped + miss
    assert not st.match("a", 4, [1, 2, 3, 9])
    assert st.misses == 1 and len(st) == 0
    # coverage-length mismatch is stale too
    st.put(_entry("b", nb=1, covered=4, tokens=[1, 2, 3, 4]))
    assert not st.match("b", 3, [1, 2, 3])
    assert st.misses == 2 and len(st) == 0


def test_store_take_restore_roundtrip():
    st = HostKVStore(budget_bytes=1000, block_nbytes=100, block_size=4)
    st.put(_entry("a", nb=2))
    e = st.take("a")
    assert len(st) == 0 and st.bytes_used == 0
    st.restore(e)  # promotion failed (device pool dry): entry comes back
    assert len(st) == 1 and st.bytes_used == 200
    assert st.hits == 0 and st.swap_in_bytes_total == 0
    assert st.match("a", e.covered, e.tokens)


def test_store_clear_tombstones_everything():
    st = HostKVStore(budget_bytes=1000, block_nbytes=100, block_size=4)
    st.put(_entry("a", nb=1))
    st.put(_entry("b", nb=1))
    assert st.clear() == 2
    assert len(st) == 0 and st.bytes_used == 0
    # weight-install invalidation: later resumes are honest misses
    assert not st.match("a", 4, list(range(4)))
    assert not st.match("b", 4, list(range(4)))
    assert st.misses == 2


class _CountingArray:
    """Stand-in device array: np.asarray(x) goes through __array__, so the
    store's materialisation points are observable."""

    def __init__(self, arr):
        self.arr = arr
        self.materialized = 0

    def __array__(self, dtype=None, copy=None):
        self.materialized += 1
        return self.arr

    def copy_to_host_async(self):
        pass


def test_store_pending_window_materializes_like_iter_prefetched():
    st = HostKVStore(
        budget_bytes=10_000, block_nbytes=100, block_size=4, pending_window=2
    )
    arrays = []
    for rid in ("a", "b", "c", "d"):
        e = _entry(rid, nb=1, pending=True)
        e.k = _CountingArray(np.asarray(e.k))
        e.v = _CountingArray(np.asarray(e.v))
        arrays.append((e.k, e.v))
        st.put(e)
    # window=2: entries beyond the two most recent have been materialised
    # (device refs dropped), the last two are still in flight
    assert arrays[0][0].materialized == 1 and arrays[1][0].materialized == 1
    assert arrays[2][0].materialized == 0 and arrays[3][0].materialized == 0
    # take() of a still-pending entry materialises on the spot
    e = st.take("d")
    assert arrays[3][0].materialized == 1 and not e.pending
    st.flush_pending()
    assert arrays[2][0].materialized == 1


# -- engine-level helpers ----------------------------------------------


class DigitTok:
    eos_token_id = None

    def decode(self, ids):
        return "".join(str(i % 10) for i in ids)


def _engine(params, host_mb, *, R=2, spec="ngram", pool_tokens=None,
            context=256, page=8, chunk=4, runahead=1):
    cfg = JaxDecodeConfig(
        context_length=context,
        max_running_requests=R,
        new_tokens_per_chunk=chunk,
        page_size=page,
        kv_pool_tokens=pool_tokens,
        kv_host_pool_mb=host_mb,
        decode_runahead_chunks=runahead,
        paged_attn_impl="xla",
        spec_decode=spec,
        spec_k=3,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig(), tokenizer=DigitTok())
    eng.set_model(params, TINY)
    eng.initialize()
    return eng


def _wait_tokens(eng, n, timeout=120.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if eng.get_metrics()["generated_tokens_total"] >= n:
            return True
        time.sleep(0.002)
    return False


def _generate(eng, req, timeout=180.0):
    out = {}

    def _go():
        async def _r():
            return await eng.agenerate(req)

        try:
            out["r"] = asyncio.run(_r())
        except BaseException as e:  # noqa: BLE001
            out["e"] = e

    t = threading.Thread(target=_go, daemon=True)
    t.start()
    t.join(timeout)
    if "e" in out:
        raise out["e"]
    assert "r" in out, "generate timed out"
    return out["r"]


def _interrupt_first_segment(eng, rid, prompt, g, min_new_tokens=1):
    """Submit one request, let it emit a few tokens, then pause+abort:
    returns the interrupted partial response (the request is now PARKED
    server-side). Deterministic: nothing else is in flight, and the
    resume is NOT yet queued when this returns."""
    out = {}

    def _go():
        async def _r():
            return await eng.agenerate(
                ModelRequest(rid=rid, input_ids=prompt, gconfig=g)
            )

        out["r"] = asyncio.run(_r())

    base = eng.get_metrics()["generated_tokens_total"]
    t = threading.Thread(target=_go, daemon=True)
    t.start()
    assert _wait_tokens(eng, base + min_new_tokens), "no tokens emitted"
    eng.pause_generation()
    eng.abort_all()
    eng.continue_generation()
    t.join(120)
    resp = out["r"]
    assert resp.stop_reason == "interrupt", resp.stop_reason
    assert len(resp.output_tokens) >= min_new_tokens
    return resp


def _resume_segment(eng, rid, prompt, partial, g):
    """Client interrupt protocol: resubmit prompt + partial under the same
    rid with the remaining token budget."""
    return _generate(
        eng,
        ModelRequest(
            rid=rid,
            input_ids=list(prompt) + list(partial),
            gconfig=replace(
                g, max_new_tokens=g.max_new_tokens - len(partial)
            ),
        ),
    )


def _run_fillers(eng, prompts, g):
    async def _main():
        return await asyncio.gather(
            *[
                eng.agenerate(ModelRequest(input_ids=p, gconfig=g))
                for p in prompts
            ]
        )

    out = {}

    def _go():
        out["r"] = asyncio.run(_main())

    t = threading.Thread(target=_go, daemon=True)
    t.start()
    t.join(180)
    assert "r" in out, "fillers did not finish"
    return out["r"]


def _oracle_streams(params, prompts, gconfigs, spec):
    """Never-evicted reference: same engine settings but enough slots (and
    the dense full-provisioned pool) that nothing is ever parked-out or
    preempted — every request runs straight through. Per-slot sampling
    purity makes slot geometry irrelevant to the streams."""
    eng = _engine(params, 0, R=len(prompts) + 1, spec=spec)
    try:

        async def _main():
            return await asyncio.gather(
                *[
                    eng.agenerate(ModelRequest(input_ids=p, gconfig=g))
                    for p, g in zip(prompts, gconfigs)
                ]
            )

        out = {}

        def _go():
            out["r"] = asyncio.run(_main())

        t = threading.Thread(target=_go, daemon=True)
        t.start()
        t.join(180)
        assert "r" in out
        res = out["r"]
    finally:
        eng.destroy()
    return {
        tuple(p): (list(r.output_tokens), list(r.output_logprobs))
        for p, r in zip(prompts, res)
    }


# -- 2. bit-identity vs the never-evicted oracle ------------------------


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_evicted_resume_bit_identical_to_oracle(cpu_devices, greedy):
    """park -> LRU-evict -> host offload -> promote: the resumed stream's
    tokens AND logprobs equal the never-evicted oracle's, greedy and
    sampled, at runahead=1 with spec_decode="ngram" on. Sampled identity is what the traveling base key buys: every
    position samples with fold_in(original_key, position) regardless of
    where the interrupt/eviction landed."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    prompts = [[int(x) for x in rng.integers(1, 60, 8)] for _ in range(3)]
    g = GenerationHyperparameters(
        greedy=greedy, temperature=1.0, top_p=1.0, max_new_tokens=48
    )
    g_fill = replace(g, max_new_tokens=12)
    oracle = _oracle_streams(params, prompts, [g, g_fill, g_fill], "ngram")

    eng = _engine(params, 64, R=2, spec="ngram")
    try:
        rid = str(uuid.uuid4())
        seg1 = _interrupt_first_segment(eng, rid, prompts[0], g)
        # fillers admit while A's resume is NOT queued: their slot demand
        # LRU-evicts A's parked KV -> offloaded to the host tier
        fillers = _run_fillers(eng, prompts[1:], g_fill)
        assert eng.get_metrics()["kv_swap_out_bytes_total"] > 0, (
            "fillers never evicted the parked slot"
        )
        # A resumes: exact host-tier match -> promotion, no prefill
        seg2 = _resume_segment(eng, rid, prompts[0], seg1.output_tokens, g)
        m = eng.get_metrics()
    finally:
        eng.destroy()
    assert m["kv_host_hits_total"] >= 1, m
    assert m["kv_swap_in_bytes_total"] > 0, m
    assert m["reprefill_tokens_avoided_total"] > 0, m
    a_tokens = list(seg1.output_tokens) + list(seg2.output_tokens)
    a_logps = list(seg1.output_logprobs) + list(seg2.output_logprobs)
    oa_tokens, oa_logps = oracle[tuple(prompts[0])]
    tag = f"[{'greedy' if greedy else 'sampled'}]"
    assert a_tokens == oa_tokens, (
        f"{tag} evicted resume diverged from the never-evicted oracle:\n"
        f"{a_tokens}\n{oa_tokens}"
    )
    assert_logprobs_close(a_logps, oa_logps, f"{tag} logprobs diverged")
    for p, r in zip(prompts[1:], fillers):
        assert list(r.output_tokens) == oracle[tuple(p)][0], "filler diverged"
        assert_logprobs_close(r.output_logprobs, oracle[tuple(p)][1])


def test_preempt_offload_swapback_bit_identical(cpu_devices):
    """Pool-pressure preemption (the internal requeue, invisible to the
    client) with the host tier: the preempted slot's KV is offloaded and
    promoted back at re-admission — SAMPLED stream bit-identical to a
    run with a pool big enough to never preempt (the base key rides on
    the _Slot across the requeue)."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [[int(x) for x in rng.integers(1, 60, 8)] for _ in range(3)]
    g = GenerationHyperparameters(
        greedy=False, temperature=1.0, top_p=1.0, max_new_tokens=60
    )

    def run(pool_tokens, host_mb):
        eng = _engine(
            params, host_mb, R=3, pool_tokens=pool_tokens, context=128,
            spec="ngram",
        )
        try:

            async def _main():
                return await asyncio.gather(
                    *[
                        eng.agenerate(ModelRequest(input_ids=p, gconfig=g))
                        for p in prompts
                    ]
                )

            out = {}

            def _go():
                out["r"] = asyncio.run(_main())

            t = threading.Thread(target=_go, daemon=True)
            t.start()
            t.join(180)
            assert "r" in out
            m = eng.get_metrics()
        finally:
            eng.destroy()
        return out["r"], m

    oracle, om = run(None, 0)  # full provisioning: no preemption possible
    assert om["preemptions_total"] == 0
    # zero-slack pool (24 usable blocks = 3 x 8-block admissions, exactly):
    # crossing 64 tokens forces _preempt_slot; the host tier catches it
    got, m = run(192, 64)
    assert m["preemptions_total"] > 0, m
    assert m["kv_host_hits_total"] > 0, m
    for i, (a, b) in enumerate(zip(got, oracle)):
        assert a.output_tokens == b.output_tokens, (
            f"job {i}: preempt+offload+swap-back changed the sampled stream"
        )
        assert_logprobs_close(a.output_logprobs, b.output_logprobs, i)


# -- 3. degradation contracts ------------------------------------------


def test_host_miss_falls_back_to_reprefill(cpu_devices):
    """A host-tier MISS (the entry was budget-evicted from host RAM) must
    fall back to the pre-tier re-prefill and still produce the greedy
    oracle stream.

    Geometry: two 30-token-prompt sessions — each offload entry is 4-6
    blocks (coverage 30..48 even with run-ahead overshoot at chunk=2) —
    against a 6-block host budget: session 0's entry fits alone, but
    session 1's offload must LRU-evict it (two entries are >= 8 blocks).
    Session 0's resume is then a tombstoned MISS that re-prefills;
    session 1's resume is a HIT that promotes."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(23)
    prompts = [
        [int(x) for x in rng.integers(1, 60, 30)],  # session 0 (miss)
        [int(x) for x in rng.integers(1, 60, 30)],  # session 1 (hit)
        [int(x) for x in rng.integers(1, 60, 8)],  # fillers
        [int(x) for x in rng.integers(1, 60, 8)],
    ]
    g = GenerationHyperparameters(greedy=True, max_new_tokens=48)
    g_fill = replace(g, max_new_tokens=12)
    oracle = _oracle_streams(params, prompts, [g, g, g_fill, g_fill], "ngram")

    host_mb = (6 * _TINY_BLOCK_NBYTES) / (1024 * 1024)
    eng = _engine(params, host_mb, R=2, spec="ngram", chunk=2)
    try:
        rids = [str(uuid.uuid4()), str(uuid.uuid4())]
        seg1 = [
            _interrupt_first_segment(eng, rids[i], prompts[i], g)
            for i in range(2)
        ]
        # both sessions parked; fillers evict BOTH (LRU: session 0 first),
        # and session 1's offload LRU-evicts session 0's host entry
        _run_fillers(eng, prompts[2:], g_fill)
        m_mid = eng.get_metrics()
        assert m_mid["kv_host_evictions_total"] >= 1, m_mid
        assert m_mid["kv_host_pool_entries"] == 1, m_mid
        # session 0 resumes -> tombstoned MISS -> re-prefill fallback
        seg2_0 = _resume_segment(
            eng, rids[0], prompts[0], seg1[0].output_tokens, g
        )
        # session 1 resumes -> host HIT -> promotion
        seg2_1 = _resume_segment(
            eng, rids[1], prompts[1], seg1[1].output_tokens, g
        )
        m = eng.get_metrics()
    finally:
        eng.destroy()
    assert m["kv_host_misses_total"] >= 1, m
    assert m["kv_host_hits_total"] >= 1, m
    assert 0.0 < m["kv_host_hit_rate"] < 1.0, m
    for i, seg2 in enumerate((seg2_0, seg2_1)):
        toks = list(seg1[i].output_tokens) + list(seg2.output_tokens)
        assert toks == oracle[tuple(prompts[i])][0], (
            f"session {i}: fallback/promotion broke the greedy stream"
        )


def test_disabled_host_tier_reproduces_todays_behavior(cpu_devices):
    """kv_host_pool_mb=0 (the default): eviction drops KV, resumes
    re-prefill, every host metric stays zero — the pre-tier engine
    exactly (the acceptance criterion's no-regression clause)."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(31)
    prompts = [[int(x) for x in rng.integers(1, 60, 8)] for _ in range(3)]
    g = GenerationHyperparameters(greedy=True, max_new_tokens=48)
    g_fill = replace(g, max_new_tokens=12)
    oracle = _oracle_streams(params, prompts, [g, g_fill, g_fill], "ngram")

    eng = _engine(params, 0, R=2, spec="ngram")
    try:
        assert eng._host_store is None
        rid = str(uuid.uuid4())
        seg1 = _interrupt_first_segment(eng, rid, prompts[0], g)
        _run_fillers(eng, prompts[1:], g_fill)
        seg2 = _resume_segment(eng, rid, prompts[0], seg1.output_tokens, g)
        m = eng.get_metrics()
    finally:
        eng.destroy()
    assert not m["kv_host_pool_enabled"]
    for k in (
        "kv_host_pool_tokens",
        "kv_host_pool_entries",
        "kv_swap_out_bytes_total",
        "kv_swap_in_bytes_total",
        "kv_host_hits_total",
        "kv_host_misses_total",
        "reprefill_tokens_avoided_total",
    ):
        assert m[k] == 0, (k, m[k])
    assert m["kv_host_hit_rate"] == 0.0
    # greedy parity still holds through the drop-and-reprefill path
    toks = list(seg1.output_tokens) + list(seg2.output_tokens)
    assert toks == oracle[tuple(prompts[0])][0]


def test_weight_update_invalidates_host_tier(cpu_devices):
    """Weight installs must clear the host tier (offloaded KV was computed
    by the OLD weights) — the resume after the install re-prefills, and
    the drop is visible as a tombstoned miss."""
    params = init_params(TINY, jax.random.PRNGKey(0))
    rng = np.random.default_rng(41)
    prompts = [[int(x) for x in rng.integers(1, 60, 8)] for _ in range(3)]
    g = GenerationHyperparameters(greedy=True, max_new_tokens=48)
    g_fill = replace(g, max_new_tokens=12)
    oracle = _oracle_streams(params, prompts, [g, g_fill, g_fill], "off")

    eng = _engine(params, 64, R=2, spec="off")
    try:
        rid = str(uuid.uuid4())
        seg1 = _interrupt_first_segment(eng, rid, prompts[0], g)
        _run_fillers(eng, prompts[1:], g_fill)
        assert eng.get_metrics()["kv_swap_out_bytes_total"] > 0
        # identical weights, so the greedy oracle is unchanged — but the
        # install must still flush the tier
        eng.update_weights_from_distributed(None, params=params)
        assert eng.get_metrics()["kv_host_pool_entries"] == 0
        seg2 = _resume_segment(eng, rid, prompts[0], seg1.output_tokens, g)
        m = eng.get_metrics()
    finally:
        eng.destroy()
    assert m["kv_host_hits_total"] == 0, m
    assert m["kv_host_misses_total"] >= 1, m  # tombstoned resume
    toks = list(seg1.output_tokens) + list(seg2.output_tokens)
    assert toks == oracle[tuple(prompts[0])][0]
