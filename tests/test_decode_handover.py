"""A slot passes to the next queued request when its occupant's last chunk is
DISPATCHED, not when that chunk is read back (`_hand_over_spent_slot`).

Held here, on the CPU at tiny widths: with more requests than slots every
request's tokens, log-probabilities and versions are the synchronous
(`decode_runahead_chunks=0`) engine's to the bit, for each kind of slot cache,
greedy and sampled, while the same work takes fewer chunks than with the
hand-over never taken; a stop inside the last chunk of a request whose slot
has gone truncates it as before; a pause with a weight swap, `abort_all` and a
scheduler exception between the hand-over and the read-back leave no request
without its tokens or its error; and the mechanism is never reached under
spec decode, block diffusion, run-ahead 0 or while a slot is free, and comes
before a parked request's cache is evicted.
"""

import asyncio
import concurrent.futures
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from areal_tpu.api.cli_args import (  # noqa: E402
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.engine_api import EngineDeadError  # noqa: E402
from areal_tpu.api.io_struct import ModelRequest  # noqa: E402
from areal_tpu.engine.jax_decode import JaxDecodeEngine, _Slot  # noqa: E402

CHUNK = 16
SLOTS = 2
# (prompt's seed, pinned length, greedy): more requests than slots, ends at
# every place of a chunk, a whole chunk, one token; 0 and 1 share a prompt (a
# same-pass fork), 5 repeats 2's after 2 has finished
WORK = [(0, 20, True), (0, 9, False), (1, 33, False), (2, 16, True), (3, 40, True),
        (1, 5, False), (4, 26, False), (5, 1, True), (6, 17, False)]
TIMEOUT = 120.0  # every wait below is bounded by it


def _uniform():
    from test_decode_runahead import TINY

    from areal_tpu.models.qwen2 import init_params

    return TINY, init_params(TINY, jax.random.PRNGKey(0))


def _sparse():
    from benchmark.lib import weights
    from test_olmoe import SEED, TINY

    return TINY, weights.seeded_params(TINY, SEED)


def _mixed():
    from test_kexaone import FULL, seeded

    return FULL, seeded(FULL)


def _state():
    from benchmark.lib import kind_rollout_linear, weights
    from test_qwen3next import FULL, SEED

    return FULL, kind_rollout_linear.redraw_mixer_leaves(weights.seeded_params(FULL, SEED), SEED)


def _latent():
    from test_deepseek_v2 import FULL, seeded

    return FULL, seeded(FULL)


# what a slot's cache is: one paged pool; the same under routed experts; a
# ring beside the pool (window + full layers); a recurrent state beside it;
# a latent pool
MODELS = {"uniform": _uniform, "sparse": _sparse, "mixed": _mixed, "state": _state,
          "latent": _latent}


def _engine(cfg, params, runahead=1, **over):
    kw = dict(context_length=256, max_running_requests=SLOTS, new_tokens_per_chunk=CHUNK,
              page_size=4, dtype="float32", kv_cache_dtype="float32", random_seed=5,
              decode_runahead_chunks=runahead)
    kw.update(over)
    engine = JaxDecodeEngine(JaxDecodeConfig(**kw), InferenceEngineConfig())
    engine.set_model(params, cfg)
    engine.initialize()
    return engine


def _request(vocab, seed, n, greedy=False, plen=11, **over):
    prompt = np.random.default_rng(1000 + seed).integers(1, vocab, plen).tolist()
    g = GenerationHyperparameters(n_samples=1, max_new_tokens=n, greedy=greedy,
                                  temperature=1.0, **over)
    return ModelRequest(input_ids=prompt, gconfig=g)


def _serve(engine, reqs):
    """The requests queued while paused, so that the first pass finds them all
    and the schedule is a function of their lengths alone; returns the
    responses, the chunks it took, the slots handed over and the largest
    `running_requests` sampled meanwhile."""
    m0 = engine.get_metrics()
    running = []

    async def go():
        engine.pause_generation()
        tasks = [asyncio.ensure_future(engine.agenerate(r)) for r in reqs]
        await asyncio.sleep(0)
        engine.continue_generation()
        deadline = time.monotonic() + TIMEOUT
        while not all(t.done() for t in tasks):
            assert time.monotonic() < deadline, "requests not served in time"
            running.append(engine.get_metrics()["running_requests"])
            await asyncio.sleep(0.001)
        return await asyncio.gather(*tasks)

    out = asyncio.run(go())
    m1 = engine.get_metrics()
    return dict(out=out, running=max(running, default=0),
                **{k: m1[k] - m0[k] for k in ("chunks_dispatched_total", "slots_handed_over_total",
                                              "runahead_discarded_tokens_total")})


@pytest.fixture(scope="module", params=sorted(MODELS))
def served(request, cpu_devices):
    """{"sync", "ahead", "never"}: WORK through the synchronous engine, through
    the run-ahead engine, and through that engine again with the hand-over
    never taken (its chunks alone are read: its keys are later admissions')."""
    cfg, params = MODELS[request.param]()
    reqs = [_request(cfg.vocab_size, s, n, g) for s, n, g in WORK]
    runs = {}
    for name, runahead in (("sync", 0), ("ahead", 1)):
        engine = _engine(cfg, params, runahead)
        try:
            runs[name] = _serve(engine, reqs)
            if name == "ahead":
                engine._hand_over_spent_slot = lambda: None
                runs["never"] = _serve(engine, reqs)
        finally:
            engine.destroy()
    return runs


@pytest.mark.parametrize("greedy", [True, False], ids=["greedy", "sampled"])
def test_streams_are_the_synchronous_engines_to_the_bit(served, greedy):
    picked = [i for i, w in enumerate(WORK) if w[2] == greedy]
    assert picked
    for i in picked:
        a, b = served["sync"]["out"][i], served["ahead"]["out"][i]
        assert len(b.output_tokens) == WORK[i][1] and b.stop_reason == "length", i
        assert a.output_tokens == b.output_tokens, i
        assert a.output_logprobs == b.output_logprobs, i
        assert a.output_versions == b.output_versions, i
        assert a.stop_reason == b.stop_reason, i


def test_the_same_work_takes_fewer_chunks(served):
    """The count is the mechanism: a request costs its slot `ceil(L / 16)`
    chunks, where it cost one more (the chunk its slot stood masked in)."""
    sync, ahead, never = served["sync"], served["ahead"], served["never"]
    assert sync["slots_handed_over_total"] == 0 == never["slots_handed_over_total"]
    assert ahead["slots_handed_over_total"] == 7  # every admission after the first two
    # two slots, FIFO, lengths in chunks 2 1 3 1 3 1 2 1 2 (16 in all, 40 tokens
    # the longest): nine passes where every request's slot is filled in the
    # pass that spends it, twelve where it stands masked for a chunk first
    assert ahead["chunks_dispatched_total"] == 9 == sync["chunks_dispatched_total"]
    assert never["chunks_dispatched_total"] == 12
    assert ahead["runahead_discarded_tokens_total"] == 0


def test_running_requests_never_exceed_the_slots(served):
    """A request that waits for its last chunk is in no slot and not counted."""
    for name in ("sync", "ahead", "never"):
        assert 1 <= served[name]["running"] <= SLOTS, name


# -- hand-driven: the scheduler thread parked, its passes made here ------------


class _Now:
    """A request's loop for an engine driven by hand: callbacks run at once."""

    @staticmethod
    def call_soon_threadsafe(fn, *args):
        fn(*args)


def _queue(engine, req, rid):
    item = _Slot(rid=rid, prompt=list(req.input_ids), gconfig=req.gconfig,
                 future=concurrent.futures.Future(), loop=_Now())
    engine._request_q.put(item)
    return item


def _park(engine):
    """Pause, and wait until the scheduler thread has made its pass under the
    flag (it drains what is in flight there) and sleeps through the pause:
    from here on the passes are this thread's alone."""
    engine.pause_generation()
    deadline = time.monotonic() + TIMEOUT
    while engine._sched_clock.read().get("paused", 0.0) == 0.0:
        assert time.monotonic() < deadline, "the scheduler thread never parked"
        time.sleep(0.002)


def _until(engine, *items, passes=64):
    """Passes until the items' futures are done: a bounded wait."""
    for _ in range(passes):
        if all(item.future.done() for item in items):
            return
        _pass(engine)
    raise AssertionError(f"not done after {passes} passes: "
                         f"{[i.rid for i in items if not i.future.done()]}")


def _pass(engine, consume=True):
    """One scheduler pass at run-ahead 1: admit, dispatch, then (`consume`) the
    read-back of the chunk before. Without it the pass stops BETWEEN a
    hand-over and the read-back of the chunk the old request waits for."""
    with engine._sched_lock:
        engine._admit()
        rec = engine._dispatch_chunk(engine._active_mask())
        if rec is not None:
            engine._inflight.append(rec)
        # (with nothing new dispatched the scheduler drains the stragglers)
        while consume and len(engine._inflight) > (rec is not None):
            engine._consume_chunk(engine._inflight.popleft())


@pytest.fixture
def parked_engine(cpu_devices):
    """(engine, cfg) of one slot, chunks of 4, the scheduler thread parked."""
    cfg, params = _uniform()
    engine = _engine(cfg, params, max_running_requests=1, new_tokens_per_chunk=4)
    _park(engine)
    yield engine, cfg
    engine.destroy()


def _handed_over_and_unread(engine, cfg, a_len=8, **a_over):
    """A (two chunks) in the one slot, B queued behind it; stops after the pass
    that handed A's slot to B and dispatched B's first chunk: A's last chunk
    and B's first are in flight."""
    a = _queue(engine, _request(cfg.vocab_size, 1, a_len, **a_over), "a")
    b = _queue(engine, _request(cfg.vocab_size, 2, 12), "b")
    _pass(engine)  # A admitted, chunk 1
    _pass(engine)  # no slot for B, A not spent; chunk 2, chunk 1 read
    assert len(a.tokens) == 4 and engine._slots[0] is a
    _pass(engine, consume=False)
    assert engine._slots[0] is b and engine.get_metrics()["slots_handed_over_total"] == 1
    assert [sorted(r.handed) for r in engine._inflight] == [[0], []]
    assert len(a.tokens) == 4 and not a.future.done()
    return a, b


def _alone(cfg, n, seed=1, **over):
    """The stream of a request served alone by a synchronous engine of the
    same seed: the first admission's key."""
    engine = _engine(cfg, _uniform()[1], 0, max_running_requests=1, new_tokens_per_chunk=4)
    try:
        return engine.generate(_request(cfg.vocab_size, seed, n, **over), timeout=TIMEOUT)
    finally:
        engine.destroy()


def test_a_pause_and_weight_swap_between_hand_over_and_read_back(parked_engine):
    """The swap's drain reads A's last chunk back: A has all its tokens under
    the version they were generated under; B goes on under the new one."""
    engine, cfg = parked_engine
    a, b = _handed_over_and_unread(engine, cfg)
    with engine._weight_swap():
        engine.params = jax.tree.map(lambda x: x * 1.05, engine.params)
        engine._version += 1
    assert not engine._inflight
    resp = a.future.result(timeout=0)
    ref = _alone(cfg, 8)
    assert resp.stop_reason == "length" and resp.output_versions == [0] * 8
    assert resp.output_tokens == ref.output_tokens
    assert resp.output_logprobs == ref.output_logprobs
    assert len(resp.itl) == 8 and resp.ttft < float("inf")
    assert len(b.tokens) == 4 and engine._slots[0] is b
    _until(engine, b)
    assert b.future.result(timeout=0).output_versions == [0] * 4 + [1] * 8


def test_abort_all_between_hand_over_and_read_back(parked_engine):
    """A is complete (its chunk was dispatched whole), B is interrupted with
    the tokens it has and resumes from its parked cache."""
    engine, cfg = parked_engine
    a, b = _handed_over_and_unread(engine, cfg)
    engine.pause_generation()
    assert engine.abort_all() == 1
    ra, rb = a.future.result(timeout=0), b.future.result(timeout=0)
    assert ra.stop_reason == "length" and len(ra.output_tokens) == 8
    assert ra.output_tokens == _alone(cfg, 8).output_tokens
    assert rb.stop_reason == "interrupt" and len(rb.output_tokens) == 4
    assert "b" in engine._parked and engine.get_metrics()["running_requests"] == 0
    before = engine._n_prefills
    again = _queue(engine, ModelRequest(
        input_ids=rb.input_tokens + rb.output_tokens,
        gconfig=GenerationHyperparameters(max_new_tokens=8, temperature=1.0)), "b")
    _until(engine, again)
    assert engine._n_prefills == before and len(again.tokens) == 8


def test_a_stop_in_the_last_chunk_after_the_slot_has_gone(parked_engine):
    """A's stop id falls inside its last chunk, read back when its slot is
    B's already: A returns truncated there, as it would from its slot, and B's
    cache and stream are not touched by it."""
    engine, cfg = parked_engine
    full = _alone(cfg, 8).output_tokens
    cut = next(i for i in range(4, 8) if full[i] not in full[:i]) + 1
    a, b = _handed_over_and_unread(engine, cfg, stop_token_ids=[full[cut - 1]])
    m0 = engine.get_metrics()["generated_tokens_total"]
    _until(engine, a, b)
    ra = a.future.result(timeout=0)
    assert ra.stop_reason == "stop" and ra.output_tokens == full[:cut]
    assert len(ra.output_logprobs) == len(ra.output_versions) == len(ra.itl) == cut
    # tokens past the stop were never generated, as far as any counter says
    assert engine.get_metrics()["generated_tokens_total"] - m0 == (cut - 4) + 12
    # B alone would have drawn the second key: serve it second on a fresh engine
    ref = _engine(cfg, _uniform()[1], 0, max_running_requests=1, new_tokens_per_chunk=4)
    try:
        ref.generate(_request(cfg.vocab_size, 1, 8), timeout=TIMEOUT)
        want = ref.generate(_request(cfg.vocab_size, 2, 12), timeout=TIMEOUT)
    finally:
        ref.destroy()
    rb = b.future.result(timeout=0)
    assert rb.output_tokens == want.output_tokens and rb.output_logprobs == want.output_logprobs


def test_a_scheduler_exception_between_hand_over_and_read_back(cpu_devices):
    """The scheduler dies with A in no slot: A, B (in A's old slot) and C
    (queued) all get the engine's dead error, and nothing hangs."""
    cfg, params = _uniform()
    engine = _engine(cfg, params, max_running_requests=1, new_tokens_per_chunk=4)
    dispatch = engine._dispatch_chunk

    def dies_after_a_hand_over(active):
        if engine._n_handed_over:
            raise RuntimeError("made to fail")
        return dispatch(active)

    engine._dispatch_chunk = dies_after_a_hand_over
    try:
        async def go():
            engine.pause_generation()
            tasks = [asyncio.ensure_future(engine.agenerate(_request(cfg.vocab_size, s, 8)))
                     for s in (1, 2, 3)]
            await asyncio.sleep(0)
            engine.continue_generation()
            return await asyncio.wait_for(
                asyncio.gather(*tasks, return_exceptions=True), TIMEOUT)

        out = asyncio.run(go())
        assert all(isinstance(e, EngineDeadError) for e in out), out
        assert engine.get_metrics()["slots_handed_over_total"] == 1
    finally:
        engine.destroy()


def test_run_ahead_two_drains_a_handed_over_record_at_a_pause(cpu_devices):
    """At depth 2 a pass ends with the old request's last chunk still in
    flight, so the pause's own drain (not a pass) completes it."""
    cfg, params = _uniform()
    reqs = [_request(cfg.vocab_size, s, n) for s, n in ((1, 8), (2, 8), (3, 4))]
    ref = _engine(cfg, params, 0, max_running_requests=1, new_tokens_per_chunk=4)
    try:
        want = _serve(ref, reqs)["out"]
    finally:
        ref.destroy()
    engine = _engine(cfg, params, 2, max_running_requests=1, new_tokens_per_chunk=4)
    seen = threading.Event()
    consume = engine._consume_chunk

    def consume_then_pause(rec):
        consume(rec)
        if any(r.handed for r in engine._inflight) and not seen.is_set():
            seen.set()
            engine._gen_paused.set()  # the next pass drains

    engine._consume_chunk = consume_then_pause
    try:
        async def go():
            tasks = [asyncio.ensure_future(engine.agenerate(r)) for r in reqs]
            deadline = time.monotonic() + TIMEOUT
            while not seen.is_set():
                assert time.monotonic() < deadline, "no hand-over seen in time"
                await asyncio.sleep(0.001)
            first = await asyncio.wait_for(tasks[0], TIMEOUT)  # completed by the drain
            engine.continue_generation()
            return [first] + list(await asyncio.wait_for(asyncio.gather(*tasks[1:]), TIMEOUT))

        got = asyncio.run(go())
        assert engine.get_metrics()["slots_handed_over_total"] == 2
        for a, b in zip(want, got):
            assert a.output_tokens == b.output_tokens and a.output_logprobs == b.output_logprobs
    finally:
        engine.destroy()


# -- where the mechanism is never reached ---------------------------------------


def test_never_while_a_slot_is_free_and_before_a_parked_cache(cpu_devices):
    """Free slots first, then spent slots, then the parked request's cache
    (whose resume would re-prefill)."""
    cfg, params = _uniform()
    engine = _engine(cfg, params, max_running_requests=3, new_tokens_per_chunk=4)
    try:
        _park(engine)
        p = _queue(engine, _request(cfg.vocab_size, 9, 12), "p")
        _pass(engine)
        _pass(engine)
        engine.pause_generation()  # (drains)
        assert engine.abort_all() == 1 and "p" in engine._parked  # slot 0 is parked
        a = _queue(engine, _request(cfg.vocab_size, 1, 4), "a")
        _pass(engine)  # A in slot 1: one chunk is all of it
        b = _queue(engine, _request(cfg.vocab_size, 2, 40), "b")
        _pass(engine)  # A spent and unread, but slot 2 is free: B takes that
        assert engine.get_metrics()["slots_handed_over_total"] == 0
        assert engine._slots[2] is b and a.future.done() and engine._slots[1] is None
        # A retired at its read-back, as ever: its conversation is a donor
        assert engine._slot_prefix[1] is not None
        c = _queue(engine, _request(cfg.vocab_size, 3, 4), "c")
        _pass(engine)  # C takes free slot 1; one chunk is all of it
        assert engine._slots[1] is c
        d = _queue(engine, _request(cfg.vocab_size, 4, 4), "d")
        _pass(engine)  # no slot free: C's (spent, unread) before P's parked cache
        assert engine.get_metrics()["slots_handed_over_total"] == 1
        assert engine._slots[1] is d and "p" in engine._parked and c.future.done()
        e = _queue(engine, _request(cfg.vocab_size, 5, 12), "e")
        _pass(engine, consume=False)  # and again: D's, spent by the chunk in flight
        assert engine.get_metrics()["slots_handed_over_total"] == 2 and "p" in engine._parked
        assert engine._slots[1] is e
        with engine._sched_lock:
            engine._drain_inflight_locked()
            assert d.future.done() and len(e.tokens) == 4
            f = _queue(engine, _request(cfg.vocab_size, 6, 4), "f")
            engine._admit()  # nothing in flight, so nothing spent: now the parked cache goes
        assert "p" not in engine._parked and engine._slots[0] is f
        assert engine.get_metrics()["slots_handed_over_total"] == 2
        _until(engine, b, e, f)
        assert p.future.result(timeout=0).stop_reason == "interrupt"
        for item, n in ((a, 4), (b, 40), (c, 4), (d, 4), (e, 12), (f, 4)):
            assert len(item.future.result(timeout=0).output_tokens) == n, item.rid
    finally:
        engine.destroy()


@pytest.mark.parametrize("drafts", ["its own", "always"])
def test_never_under_spec_decode(cpu_devices, monkeypatch, drafts):
    """A scheduler that drafts never hands a slot over, whether its chunks in
    flight are verify chunks ("always": every pass has a draft) or the plain
    chunks of draftless passes; its streams stay the synchronous engine's."""
    from areal_tpu.engine import jax_decode

    if drafts == "always":
        monkeypatch.setattr(jax_decode, "_ngram_draft", lambda context, k, n: [0] * min(k, 2))
    cfg, params = _uniform()
    reqs = [_request(cfg.vocab_size, s, n, g) for s, n, g in WORK]
    runs = []
    for runahead in (0, 1):
        engine = _engine(cfg, params, runahead, spec_decode="ngram", spec_k=4, spec_ngram_max=3)
        try:
            runs.append(_serve(engine, reqs))
        finally:
            engine.destroy()
    assert runs[1]["slots_handed_over_total"] == 0
    for a, b in zip(runs[0]["out"], runs[1]["out"]):
        assert a.output_tokens == b.output_tokens
        # (a verify chunk scores a position in another program than a plain
        # chunk does, and which of the two a token met follows the drafts'
        # timing: tests/test_spec_decode.py holds the two to each other)
        np.testing.assert_allclose(a.output_logprobs, b.output_logprobs, atol=1e-5)


def test_never_under_block_diffusion(cpu_devices):
    """A diffusion chunk projects its depth and takes back what it did not
    commit: "covered by dispatched chunks" is an upper bound there."""
    from benchmark.lib.weights import seeded_params
    from test_sdar import CFG

    engine = _engine(CFG, seeded_params(CFG, 7), context_length=128, new_tokens_per_chunk=8,
                     page_size=16)
    try:
        reqs = [_request(CFG.vocab_size - 1, s, n, plen=10) for s, n in
                ((1, 8), (2, 14), (3, 6), (4, 12), (5, 8))]
        run = _serve(engine, reqs)
        assert run["slots_handed_over_total"] == 0
        assert [len(r.output_tokens) for r in run["out"]] == [8, 14, 6, 12, 8]
    finally:
        engine.destroy()
