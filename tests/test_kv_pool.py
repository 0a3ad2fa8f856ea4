"""Paged KV cache: allocator accounting, memory bounds, fork aliasing,
and pool-pressure preemption parity (parity target: the paged/radix KV
the reference inherits from SGLang, areal/engine/sglang_remote.py:22)."""

import jax
import numpy as np
import pytest

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.io_struct import ModelRequest
from areal_tpu.engine.jax_decode import JaxDecodeEngine
from areal_tpu.engine.kv_pool import KVBlockAllocator, PoolDry
from areal_tpu.models.qwen2 import ModelConfig, forward, init_params

TINY = ModelConfig(
    vocab_size=48,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    dtype="float32",
    param_dtype="float32",
)


def greedy_reference(params, prompt, n_new):
    seq = list(prompt)
    for _ in range(n_new):
        T = len(seq)
        logits = forward(
            params,
            np.array(seq, dtype=np.int32),
            np.arange(T, dtype=np.int32),
            np.zeros(T, dtype=np.int32),
            TINY,
        )
        seq.append(int(np.argmax(np.asarray(logits[-1]))))
    return seq[len(prompt):]


# -- allocator unit tests ----------------------------------------------


def test_allocator_ensure_and_free():
    a = KVBlockAllocator(n_slots=4, n_blocks=9, block_size=128,
                         max_blocks_per_slot=8)
    assert a.free_blocks == 8  # block 0 is the pinned null block
    assert a.ensure(0, 200)  # 2 blocks
    assert a.nblocks[0] == 2 and a.free_blocks == 6
    assert a.ensure(0, 200)  # idempotent
    assert a.free_blocks == 6
    assert a.ensure(0, 500)  # grow to 4
    assert a.nblocks[0] == 4 and a.free_blocks == 4
    assert a.allocated_tokens() == 4 * 128
    a.free_slot(0)
    assert a.free_blocks == 8 and a.nblocks[0] == 0
    assert (a.tables[0] == 0).all()


def test_allocator_pool_dry_and_guard():
    a = KVBlockAllocator(4, 9, 128, 8)
    assert a.ensure(0, 8 * 128)
    assert not a.ensure(1, 1)  # dry
    with pytest.raises(AssertionError):
        KVBlockAllocator(4, 8, 128, 8)  # pool smaller than one full slot


def test_allocator_fork_aliases_full_blocks():
    a = KVBlockAllocator(4, 17, 128, 8)
    assert a.ensure(0, 300)  # 3 blocks: 2 full + 1 partial under covered=300
    free_before = a.free_blocks
    cp = a.fork(0, 1, covered=300)
    # 2 aliased + 1 fresh partial: only ONE new block consumed
    assert a.free_blocks == free_before - 1
    assert cp is not None and cp[0] == a.tables[0, 2] and cp[1] == a.tables[1, 2]
    assert (a.tables[1, :2] == a.tables[0, :2]).all()
    assert a.refcount[a.tables[0, 0]] == 2
    # aliased blocks survive one holder's free
    a.free_slot(0)
    assert a.refcount[a.tables[1, 0]] == 1
    # block-aligned boundary: no copy needed
    assert a.ensure(2, 256)
    assert a.fork(2, 3, covered=256) is None
    assert (a.tables[3, :2] == a.tables[2, :2]).all()


def test_allocator_fork_rolls_back_on_dry():
    a = KVBlockAllocator(3, 9, 128, 8)
    assert a.ensure(0, 300)  # 3 blocks
    assert a.ensure(2, 5 * 128)  # hog the remaining 5; free now 0
    with pytest.raises(PoolDry):
        a.fork(0, 1, covered=300)  # needs 1 block for the boundary copy
    # rollback: slot 1 empty, slot 0's refcounts back to 1
    assert a.nblocks[1] == 0
    assert a.refcount[a.tables[0, 0]] == 1


# -- engine integration -------------------------------------------------


@pytest.mark.slow
def test_pool_reserves_far_less_than_dense(cpu_devices):
    """The headline paging property: 8 slots x 2048 context reserves a
    17-block pool (2176 tokens), not 8 x 2048 = 16384 rows — and short
    concurrent requests all serve correctly out of it."""
    cfg = JaxDecodeConfig(
        context_length=2048,
        max_running_requests=8,
        new_tokens_per_chunk=8,
        kv_pool_tokens=1024,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    params = init_params(TINY, jax.random.PRNGKey(0))
    eng.set_model(params, TINY)
    eng.initialize()
    try:
        n_blocks = eng._k_cache.shape[1]
        assert n_blocks == 17, n_blocks  # max(8, 16) + 1
        assert n_blocks * eng._k_cache.shape[2] < 8 * 2048 / 4
        prompts = [[i + 1, 5, 9, 2] for i in range(6)]
        import threading

        results = [None] * len(prompts)

        def run(i):
            results[i] = eng.generate(
                ModelRequest(
                    input_ids=list(prompts[i]),
                    gconfig=GenerationHyperparameters(
                        greedy=True, max_new_tokens=6
                    ),
                ),
                timeout=600,
            )

        ts = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(600)
        for i, p in enumerate(prompts):
            assert results[i] is not None
            assert results[i].output_tokens == greedy_reference(params, p, 6)
        m = eng.get_metrics()
        assert m["kv_blocks_total"] == 16
        assert m["kv_tokens_allocated"] <= 16 * 128
    finally:
        eng.destroy()


@pytest.mark.slow
def test_pool_pressure_preempts_and_stays_exact(cpu_devices):
    """When concurrent long generations outgrow the pool, the engine
    preempts (frees blocks, requeues internally) and every request still
    returns the exact greedy continuation — the client never sees the
    preemption."""
    cfg = JaxDecodeConfig(
        context_length=2048,
        max_running_requests=4,
        new_tokens_per_chunk=8,
        kv_pool_tokens=128,  # floor: 16 usable blocks (one full slot)
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    params = init_params(TINY, jax.random.PRNGKey(0))
    eng.set_model(params, TINY)
    eng.initialize()
    try:
        # 4 x 450-token prompts prefill into 4 blocks each (16 = the
        # whole pool); once a generation crosses the 512-row boundary the
        # chunk needs a 5th block and must preempt a peer
        rng = np.random.RandomState(0)
        prompts = [
            [int(t) for t in rng.randint(1, 40, size=450)] for _ in range(4)
        ]
        import threading

        results = [None] * 4

        def run(i):
            results[i] = eng.generate(
                ModelRequest(
                    input_ids=list(prompts[i]),
                    gconfig=GenerationHyperparameters(
                        greedy=True, max_new_tokens=72
                    ),
                ),
                timeout=900,
            )

        ts = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(900)
        for i in range(4):
            assert results[i] is not None, f"request {i} did not finish"
            assert results[i].output_tokens == greedy_reference(
                params, prompts[i], 72
            ), f"request {i} diverged"
        assert eng.get_metrics()["preemptions_total"] > 0
    finally:
        eng.destroy()


@pytest.mark.slow
def test_group_fork_shares_blocks(cpu_devices):
    """A GRPO group's shared prompt is stored ONCE: later group members
    alias the donor's full blocks and own only the boundary block plus
    their generation tail."""
    cfg = JaxDecodeConfig(
        context_length=2048,
        max_running_requests=8,
        new_tokens_per_chunk=8,
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    params = init_params(TINY, jax.random.PRNGKey(0))
    eng.set_model(params, TINY)
    eng.initialize()
    try:
        prompt = [1 + (i % 40) for i in range(300)]  # covered=299: 2 full + 1
        import threading

        results = [None] * 4

        def run(i):
            results[i] = eng.generate(
                ModelRequest(
                    input_ids=list(prompt),
                    gconfig=GenerationHyperparameters(
                        greedy=True, max_new_tokens=4
                    ),
                ),
                timeout=600,
            )

        ts = [threading.Thread(target=run, args=(i,)) for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(600)
        expected = greedy_reference(params, prompt, 4)
        for i in range(4):
            assert results[i] is not None
            assert results[i].output_tokens == expected
        m = eng.get_metrics()
        assert m["prefix_forks_total"] >= 3, m
        # dense would hold 4 x 3 = 12+ blocks of prompt KV; aliasing holds
        # the 2 full blocks once + one boundary/tail block per request
        assert m["kv_tokens_allocated"] <= (2 + 4 * 1 + 2) * 128, m
    finally:
        eng.destroy()


@pytest.mark.slow
def test_reclaim_never_eats_inflight_donor(cpu_devices):
    """Regression (round-5 review): a fork that hits PoolDry must not
    reclaim its own DONOR — here a PARKED slot whose admission-time
    registration makes it the prefix donor. Pre-fix, _reclaim_blocks
    evicted that parked slot, zeroed its block table, and the retried
    fork aliased null-block garbage and REGISTERED it as a valid shared
    prefix (silent rollout corruption). Post-fix the fork defers, the
    donor survives, and the deferred request later decodes exactly."""
    from areal_tpu.engine.jax_decode import _Slot

    cfg = JaxDecodeConfig(
        context_length=2048,
        max_running_requests=4,
        new_tokens_per_chunk=8,
        kv_pool_tokens=128,  # floor: 16 usable blocks
        dtype="float32",
        kv_cache_dtype="float32",
    )
    eng = JaxDecodeEngine(cfg, InferenceEngineConfig())
    params = init_params(TINY, jax.random.PRNGKey(0))
    eng.set_model(params, TINY)
    eng.initialize()
    try:
        eng.pause_generation()  # drive the scheduler by hand
        # A prefills (registers its prompt prefix), decodes one chunk,
        # then is interrupted -> parked in slot 0, registration intact
        prompt_a = [1 + (i % 40) for i in range(300)]  # 3 blocks
        a = _Slot(rid="a", prompt=list(prompt_a),
                  gconfig=GenerationHyperparameters(greedy=True,
                                                    max_new_tokens=64),
                  future=None, loop=None)
        eng._request_q.put(a)
        with eng._sched_lock:
            eng._admit()
            eng._run_chunk(eng._active_mask())
        eng.abort_all()
        (donor_slot, _, _) = eng._parked["a"]
        assert tuple(prompt_a[:-1]) in eng._prefix_lookup
        donor_blocks = list(eng._alloc.tables[donor_slot, :3])

        # hog exactly the remaining 13 blocks with a long active request
        hog = _Slot(rid="hog",
                    prompt=[2 + (i % 30) for i in range(1657)],
                    gconfig=GenerationHyperparameters(greedy=True,
                                                      max_new_tokens=120),
                    future=None, loop=None)
        eng._request_q.put(hog)
        with eng._sched_lock:
            eng._admit()
        assert eng._alloc.free_blocks == 0, eng._alloc.free_blocks

        # same-prompt request: donor fork needs a boundary block -> dry.
        # The reclaim scan must NOT evict the parked donor.
        c = _Slot(rid="c", prompt=list(prompt_a),
                  gconfig=GenerationHyperparameters(greedy=True,
                                                    max_new_tokens=4),
                  future=None, loop=None)
        eng._request_q.put(c)
        with eng._sched_lock:
            eng._admit()
        assert "a" in eng._parked, "reclaim evicted the in-flight donor"
        assert list(eng._alloc.tables[donor_slot, :3]) == donor_blocks
        assert all(b != 0 for b in donor_blocks)
        assert tuple(prompt_a[:-1]) in eng._prefix_lookup

        # drive to completion: the hog finishes (pool pressure may evict
        # the parked donor NOW - legal, c is no longer mid-fork), then c
        # admits and must decode the exact greedy continuation
        for _ in range(60):
            with eng._sched_lock:
                eng._admit()
                act = eng._active_mask()
                if act.any():
                    eng._run_chunk(act)
            if c.stop_reason is not None and hog.stop_reason is not None:
                break
        assert c.stop_reason is not None, "c never completed"
        assert c.tokens == greedy_reference(params, prompt_a, 4)
    finally:
        eng.destroy()


# -- what a slot's cache is: `SlotCache`'s contract, a case a kind of cache ----
# (no engine and no device program; `new_pools` alone touches the device)

import json  # noqa: E402
import os  # noqa: E402

from test_deepseek_v2 import FULL as LATENT  # noqa: E402
from test_jamba import CFG as STATE_SPACE  # noqa: E402 — a state-space state a slot, runs stacked
from test_kexaone import FULL as RING  # noqa: E402 — window 8: 3 pages of 4, slack 4
from test_kimi_linear import FULL as STATE_LATENT  # noqa: E402 — a state AND latent rows a slot
from test_qwen3next import FULL as STATE  # noqa: E402
from test_sdar import CFG as BLOCK  # noqa: E402 — blocks of 4

from areal_tpu.engine import kv_pool  # noqa: E402
from areal_tpu.engine.kv_pool import SlotCache  # noqa: E402

CACHES = {"uniform": TINY, "ring": RING, "state": STATE, "latent": LATENT, "block": BLOCK,
          "state_latent": STATE_LATENT, "state_space": STATE_SPACE}
KINDS = sorted(CACHES)
HAS_STATE = ("state", "state_latent", "state_space")  # one recurrent state a slot: good for one length
R, BS, NB = 4, 4, 65


def _cache(kind, **over):
    kw = dict(slots=R, block_size=BS, n_blocks=NB, max_blocks_per_slot=16, kv_dtype="float32")
    return SlotCache(CACHES[kind], **{**kw, **over})


def _published(name, **over):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmark/configs", name + ".json")) as f:
        return SlotCache(ModelConfig.from_hf_config(json.load(f)), slots=2, block_size=128,
                         n_blocks=11, max_blocks_per_slot=10, kv_dtype="bfloat16", **over)


@pytest.mark.parametrize("kind", KINDS)
def test_cache_kinds_and_what_is_cached_at_admission(kind):
    cache = _cache(kind)
    assert cache.kinds == {"uniform": (), "ring": ("pools", "window"), "state": ("pools", "state"),
                           "latent": ("pools", "latent"), "block": ("block",),
                           "state_latent": ("pools", "state", "latent"),
                           "state_space": ("pools", "state")}[kind]
    if kind == "block":
        # whole blocks; a slot's rows are its tokens
        assert [cache.cover(n) for n in (3, 4, 9, 16)] == [0, 4, 8, 16]
        assert cache.generated(16, 9) == 7
    else:
        # all but the last token; rows lag tokens by one
        assert [cache.cover(n) for n in (1, 9, 16)] == [0, 8, 15]
        assert cache.generated(15, 9) == 7
    assert cache.shares_partial_prefix == cache.content_addressed == (kind == "uniform")


@pytest.mark.parametrize("kind", KINDS)
def test_what_a_prefill_scatters_through(kind):
    cache = _cache(kind)
    assert cache.alloc.ensure(2, 10)
    tables = cache.tables(2, 3)
    row = tables[0] if isinstance(tables, tuple) else tables
    np.testing.assert_array_equal(row, cache.alloc.row(2, 3))
    if kind == "ring":
        assert len(tables) == 2
        np.testing.assert_array_equal(tables[1], cache.ring.blocks(2))
    elif kind in HAS_STATE:
        assert len(tables) == 2 and tables[1] == cache.state.row(2) == 3
        assert tables[1].dtype == np.int32
    else:
        assert not isinstance(tables, tuple)


@pytest.mark.parametrize("kind", KINDS)
def test_whether_a_slot_still_holds_a_prefix(kind):
    cache = _cache(kind)
    cache.rewritten(1, 20)
    assert cache.holds(1, 20)
    # a chunk of 4 dispatched: the state has absorbed 24 tokens and is good for
    # 24 alone; the ring (window 8, 12 rows) still has rows 13..19
    lengths = np.array([0, 24, 0, 0])
    active = np.arange(R) == 1
    cache.written(active, lengths)
    assert cache.holds(1, 20) == (kind not in HAS_STATE)
    assert cache.holds(1, 24)
    # written past the window's room: rows 13.. are gone from the ring
    cache.written(active, np.array([0, 40, 0, 0]))
    assert cache.holds(1, 20) == (kind not in (*HAS_STATE, "ring"))
    # the slot starts over with no prefill
    copies = cache.zero(1)
    assert [fn.__name__ for fn, *_ in copies] == (["zero"] if kind in HAS_STATE else [])
    assert cache.holds(1, 0) and cache.holds(1, 20) == (kind in ("uniform", "latent", "block"))


@pytest.mark.parametrize("kind", KINDS)
def test_what_a_fork_aliases_and_what_it_copies(kind, monkeypatch):
    made = []
    monkeypatch.setattr(jax, "jit", lambda fn, **kw: made.append((fn.__name__, kw)) or fn)
    cache = _cache(kind)
    assert cache.alloc.ensure(0, 12)
    cache.rewritten(0, 10)
    # (a state beside latent rows: the blocks aliased and the state copied, in one fork)
    extra = {"ring": ["fork_ring"], "state": ["fork_state"],
             "state_latent": ["fork_state"], "state_space": ["fork_state"]}.get(kind, [])
    # 10 rows: two blocks aliased, the third (2 rows) copied
    copies = cache.fork(0, 1, 10)
    assert [fn.__name__ for fn, *_ in copies] == ["fork_block"] + extra
    assert all(isinstance(x, (np.ndarray, np.generic)) for _, *ops in copies for x in ops)
    np.testing.assert_array_equal(cache.alloc.tables[1, :2], cache.alloc.tables[0, :2])
    assert copies[0][1] == cache.alloc.tables[0, 2] and copies[0][2] == cache.alloc.tables[1, 2]
    # the copy holds what the donor held: a state its one length
    assert cache.holds(1, 10) and cache.holds(1, 8) == (kind not in HAS_STATE)
    if kind == "ring":
        np.testing.assert_array_equal(copies[1][1], cache.ring.blocks(0))
        np.testing.assert_array_equal(copies[1][2], cache.ring.blocks(1))
    if kind in HAS_STATE:
        assert copies[1][1:] == (1, 2)
    # a block-aligned boundary copies no block; a slot onto itself nothing at all
    assert [fn.__name__ for fn, *_ in cache.fork(0, 2, 8)] == extra
    assert cache.fork(0, 0, 10) == []
    # each program jitted once, at its first use, the pools donated
    assert made == [(name, {"donate_argnums": (0, 1)}) for name in ["fork_block"] + extra]
    # no block for the boundary: PoolDry, and the accounts of dst untouched
    small = _cache(kind, n_blocks=17)
    assert small.alloc.ensure(0, 64)
    small.rewritten(0, 10), small.rewritten(3, 5)
    with pytest.raises(PoolDry):
        small.fork(0, 3, 10)
    assert small.holds(3, 5)


@pytest.mark.parametrize("kind", KINDS)
def test_the_pools_are_the_trees_the_programs_take(kind):
    cfg = CACHES[kind]
    cache = _cache(kind)
    k, v, ks, vs = cache.new_pools()
    assert ks is None and vs is None
    shapes = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)  # noqa: E731
    row = (BS, cfg.num_key_value_heads * cfg.head_dim_)
    L = cfg.num_hidden_layers
    if kind in ("uniform", "block"):
        assert shapes(k) == shapes(v) == ((L, NB, *row), "float32")
    elif kind == "ring":
        want = {"full": ((2, NB, *row), "float32"), "window": ((7, 1 + R * 3, *row), "float32")}
        assert shapes(k) == shapes(v) == want
    elif kind == "state":
        assert shapes(v) == {"full": ((2, NB, *row), "float32")}
        assert shapes(k) == {**shapes(v), "state": {
            "S": ((6, 1 + R, 8, 16, 16), "float32"), "conv": ((6, 1 + R, 3, 256), "float32")}}
    elif kind == "state_space":
        # the shapes are the model's to say (`ModelConfig.slot_state_shapes`):
        # [state lanes, channels] a slot, ONE kv head a row of the paged pool
        assert row == (BS, 12) and cfg.slot_state_shapes == {"S": (16, 96), "conv": (3, 96)}
        assert shapes(v) == {"full": ((2, NB, *row), "float32")}
        assert shapes(k) == {**shapes(v), "state": {
            "S": ((10, 1 + R, 16, 96), "float32"), "conv": ((10, 1 + R, 3, 96), "float32")}}
    elif kind == "state_latent":
        # the K-side dict holds the latent pool (the attention layers alone) and
        # the state; nothing on the V side
        assert v == {} and shapes(k) == {
            "latent": ((1, NB, BS, cfg.latent_row_lanes), "float32"),
            "state": {"S": ((3, 1 + R, 4, 16, 16), "float32"),
                      "conv": ((3, 1 + R, 3, 192), "float32")}}
    else:
        assert shapes(k) == {"latent": ((L, NB, BS, cfg.latent_row_lanes), "float32")} and v == {}
    assert all(not np.asarray(a).any() for a in jax.tree.leaves((k, v)))
    # what the counter walks is what the kernel is handed: the group of pages
    # from the paged pool's shapes, the live columns of each slot
    from areal_tpu.ops import paged_attention, paged_attention_latent

    live, pages = cache.walk(np.array([1, 9, 64]), 16)
    np.testing.assert_array_equal(live, [1, 3, 16])
    if kind in ("latent", "state_latent"):
        assert pages == paged_attention_latent.PAGES_PER_GROUP
    else:
        pool = k["full"] if isinstance(k, dict) else k
        assert pages == paged_attention.pool_group_pages(pool, 1, 16)
        assert cache.walk(np.array([9]), 16, W=4)[1] == paged_attention.pool_group_pages(pool, 4, 16)


def test_an_int8_pool_has_its_scale_pools_and_a_uniform_window_its_columns():
    k, v, ks, vs = _cache("uniform", quant=True).new_pools()
    assert k.dtype == v.dtype == np.int8 and k.shape == (2, NB, BS, 16)
    assert ks.shape == vs.shape == (2, NB, 2, BS) and ks.dtype == np.float32
    # a uniform stack under a window keeps no ring: the columns wholly before
    # the window are not walked
    import dataclasses

    windowed = SlotCache(dataclasses.replace(TINY, sliding_window=8), slots=R, block_size=BS,
                         n_blocks=NB, max_blocks_per_slot=16, kv_dtype="float32")
    assert windowed.kinds == () and windowed.ring is None
    np.testing.assert_array_equal(windowed.walk(np.array([1, 9, 64]), 16)[0], [1, 3, 2])


@pytest.mark.parametrize("kind,tail,want", [
    ("ring", [5, 7], dict(full=5, window=7, latent=0, state=0)),
    # ONE entry of state updates: all `models/qwen2.py:decode_load_len` emits
    ("state", [5, 0, 7], dict(full=5, window=0, latent=0, state=7)),
    ("state_space", [5, 0, 7], dict(full=5, window=0, latent=0, state=7)),
    ("latent", [0, 0, 9], dict(full=0, window=0, latent=9, state=0)),
    # state updates, then latent rows (models/qwen2.py: decode_step_paged)
    ("state_latent", [0, 0, 3, 9], dict(full=0, window=0, latent=9, state=3)),
])
def test_a_chunks_counters_of_rows_read_by_kind(kind, tail, want):
    assert _cache(kind).rows_read(tail) == want


@pytest.mark.parametrize("name,over,row,block,state_update", [
    # K and V of 2 kv heads of 128 in bf16, 28 layers; int8 with an f32 scale a head
    ("qwen2.5-1.5b", {}, 1024, 28 * 128 * 1024, 0),
    ("qwen2.5-1.5b", dict(quant=True), 2 * 2 * (128 + 4), 28 * 128 * 528, 0),
    # PERF.md section 3: 576 lanes stored as 640, one row and no V side
    ("deepseek-v2", {}, 1280, None, 0),
    # PERF.md section 3: a linear layer's state update a slot, in and out
    ("qwen3-next-80b-a3b", {}, 2 * 2 * 256 * 2, None, 4_292_608),
    # the same 640-lane row over the 2 latent layers, and a KDA layer's update:
    # 2 MiB of state and 72 KiB of convolution rows, in and out
    ("kimi-linear-48b-a3b", {}, 1280, None, 4_341_760),
    # ONE kv head of 128 a side over the 2 attention layers, and a state-space
    # layer's update: 327,680 B of float32 state and 30,720 B of rows, in and out
    ("ai21-jamba2-3b", {}, 2 * 1 * 128 * 2, None, 716_800),
    ("k-exaone-236b-a23b", {}, 2 * 8 * 128 * 2, None, 0),
    ("sdar-30b-a3b-chat", {}, 2 * 4 * 128 * 2, None, 0),
])
def test_bytes_at_published_widths(name, over, row, block, state_update):
    cache = _published(name, **over)
    cfg = cache.cfg
    assert cache.row_nbytes == row and cache.state_update_nbytes == state_update
    paged = (cfg.cache_layers["latent" if cfg.latent else "full"] if cfg.mixed
             else range(cfg.num_hidden_layers))
    assert cache.block_nbytes == (block or len(paged) * 128 * row)


# What the parent's three hand-written refusal functions refused (R) and served
# (.) at `initialize()`, by kind of cache; the ring's slack is 4 rows, so a
# verify chunk of spec_k=2 fits it and one of spec_k=8 does not.
MECHANISMS = {
    "kv_dtype": dict(kv_dtype="int8"),
    "host tier": dict(kv_host_pool_mb=16.0),
    "migration": dict(role="prefill"),
    "spec_k=2": dict(spec_decode="ngram", spec_k=2),
    "spec_k=8": dict(spec_decode="ngram", spec_k=8),
    "tensor_parallel": dict(tensor_parallel_size=2),
    "weight_dtype": dict(weight_dtype="int8"),
    "vision": {},
}
REFUSED = {
    #           kv_dtype host migr spec2 spec8 tp   w8   vision
    "uniform": ". . . . . . . .",
    "ring":    "R R R . R . . .",
    "state":   "R R R R R . . .",
    "latent":  "R R R R R R R R",
    "block":   "R R R R R . . R",
    # the union of the `state` and the `latent` rows
    "state_latent": "R R R R R R R R",
    # the `state` row and nothing new
    "state_space": "R R R R R . . .",
}


@pytest.mark.parametrize("mechanism", list(MECHANISMS))
@pytest.mark.parametrize("kind", KINDS)
def test_what_each_cache_cannot_serve(kind, mechanism):
    config = JaxDecodeConfig(**MECHANISMS[mechanism])
    asked = dict(vision=mechanism == "vision", weight_quant=mechanism == "weight_dtype")
    refused = REFUSED[kind].split()[list(MECHANISMS).index(mechanism)] == "R"
    cache = _cache(kind)
    if not refused:
        cache.unserved(config, **asked)
        return
    with pytest.raises(NotImplementedError, match="is not served with: .* needs .*: ") as e:
        cache.unserved(config, **asked)
    # the reason names the setting, and the default config is served
    setting = "a vision tower" if mechanism == "vision" else next(iter(MECHANISMS[mechanism]))
    assert setting in str(e.value) and CACHES[kind].model_type in str(e.value)
    cache.unserved(JaxDecodeConfig())


@pytest.mark.parametrize("kind", KINDS)
def test_migration_calls_are_refused_whatever_the_role(kind):
    cache = _cache(kind)
    if kind == "uniform":
        cache.unserved_call("export_session", "migration")
        return
    with pytest.raises(NotImplementedError, match=r"export_session \(migration\) needs"):
        cache.unserved_call("export_session", "migration")


def test_a_block_mask_over_any_other_kind_is_refused_and_every_need_has_its_words():
    import dataclasses

    for cfg in (dataclasses.replace(BLOCK, sliding_window=8),
                dataclasses.replace(RING, block_length=4, mask_token_id=3)):
        cache = SlotCache(cfg, slots=R, block_size=BS, n_blocks=NB, max_blocks_per_slot=16,
                          kv_dtype="float32")
        with pytest.raises(NotImplementedError, match="one paged pool under the block-causal"):
            cache.unserved(JaxDecodeConfig())
    needs = {n for _, ns in kv_pool.MECHANISMS.values() for n in ns}
    assert needs == set(kv_pool._NEEDS)
    assert {n for lacks in kv_pool.KINDS.values() for n in lacks} - {"is"} <= needs
