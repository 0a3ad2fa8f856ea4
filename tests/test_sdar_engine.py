"""SDAR-MoE through `JaxDecodeEngine`'s normal path at a tiny width on the
CPU: prefill of the prompt's whole blocks, then the diffusion chunk through
the paged pool. Greedy generation token for token and reveal step for reveal
step against `sdar_ref.generate` (static and dynamic); every denoise state of
every block of sampled requests against `sdar_ref.state_logprobs`; what fails
(a skipped commit pass, one precision lower); streams equal across chunk
lengths, run-ahead and batch composition; a group's fork off a block
boundary; a stop inside a block; an interrupt inside a block; a weight swap
inside a block; what `initialize()` refuses. The model and its weights are
tests/test_sdar.py's."""

import asyncio

import numpy as np
import pytest

from test_sdar import CFG, MASK, _ids, params, tiny  # noqa: F401 — `params` is a fixture

LOGP_TOL = 5e-5  # float32 program against float32 reference, sampled tokens


def _engine(params, cfg=CFG, **over):
    from areal_tpu.api.cli_args import InferenceEngineConfig, JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    kw = dict(context_length=128, max_running_requests=4, new_tokens_per_chunk=8, page_size=16,
              dtype="float32", kv_cache_dtype="float32")
    kw.update(over)
    engine = JaxDecodeEngine(JaxDecodeConfig(**kw), InferenceEngineConfig())
    engine.set_model(params, cfg)
    return engine


def _request(prompt, n, greedy=False, rid="", **over):
    from areal_tpu.api.cli_args import GenerationHyperparameters
    from areal_tpu.api.io_struct import ModelRequest

    g = GenerationHyperparameters(n_samples=1, max_new_tokens=n, greedy=greedy,
                                  temperature=1.0, **over)
    return ModelRequest(rid=rid, input_ids=list(prompt), gconfig=g)


def _together(engine, reqs):
    """Queued while paused, so that one admission pass takes them all."""
    async def go():
        engine.pause_generation()
        tasks = [asyncio.ensure_future(engine.agenerate(r)) for r in reqs]
        await asyncio.sleep(0)
        engine.continue_generation()
        return await asyncio.gather(*tasks)

    return asyncio.run(go())


def _check(params, resp, reference=None, cfg=CFG):
    from benchmark.lib import kind_rollout_diffusion as kind

    return kind.check_request(params, cfg, resp, reference=reference)


# -- against the reference ------------------------------------------------------------

@pytest.mark.parametrize("strategy,threshold", [
    ("low_confidence_static", 0.9), ("low_confidence_dynamic", 0.08)])
def test_greedy_generation_is_the_references(params, strategy, threshold):
    """Prompts that end on and off a block boundary, shorter than a block
    (nothing to prefill), longer than a prefill bucket; `max_new_tokens` on
    and inside a block."""
    from benchmark.reference import sdar_ref

    engine = _engine(params, diffusion_strategy=strategy,
                     diffusion_threshold=threshold).initialize()
    steps_seen = set()
    try:
        for P, n in ((9, 14), (8, 5), (3, 9), (70, 8)):
            prompt = _ids(P, P).tolist()
            resp = engine.generate(_request(prompt, n, greedy=True), timeout=600)
            toks, logps, steps = sdar_ref.generate(params, CFG, prompt, n, strategy=strategy,
                                                   threshold=threshold)
            assert resp.output_tokens == toks and resp.stop_reason == "length"
            assert resp.output_reveal_steps == steps
            np.testing.assert_allclose(resp.output_logprobs, logps, atol=LOGP_TOL)
            assert MASK not in resp.output_tokens
            steps_seen.update(steps)
        m = engine.get_metrics()
    finally:
        engine.destroy()
    if strategy == "low_confidence_dynamic":
        # the threshold was low enough to reveal more than the quota
        assert m["diffusion_slot_forwards_total"] > 0 and max(steps_seen) < 3
    else:
        assert steps_seen == {0, 1, 2, 3}
    assert m["diffusion_blocks_committed_total"] == m["diffusion_commit_forwards_total"] > 0
    assert m["kv_block_rows_read_total"] > 0 and m["moe_pairs_total"] > 0


@pytest.fixture(scope="module")
def sampled(params):
    """A group of three off a block boundary (one prefill, two forks) and a
    lone request, sampled at temperature 1 through chunks of two blocks."""
    engine = _engine(params).initialize()
    try:
        prompt = _ids(21, 70).tolist()
        group = _together(engine, [_request(prompt, n) for n in (22, 9, 16)])
        lone = engine.generate(_request(_ids(22, 12).tolist(), 13), timeout=600)
        m = engine.get_metrics()
    finally:
        engine.destroy()
    return group, lone, m


def test_every_state_of_every_block_meets_the_reference(params, sampled):
    from benchmark.lib import kind_rollout_diffusion as kind

    group, lone, m = sampled
    assert m["prefills_total"] == 2 and m["prefix_forks_total"] == 2
    for resp in (*group, lone):
        assert len(resp.output_reveal_steps) == resp.output_len
        blocks = kind.block_states(resp, 4, MASK)
        # every whole block, the first with the prompt's tail as its head
        assert len(blocks) == (resp.input_len + resp.output_len) // 4 - resp.input_len // 4
        assert len(kind.chosen_blocks(blocks)) == min(len(blocks), 12)
        c = kind.check_request(params, CFG, resp)
        assert c["ok"] and c["max_abs"] < LOGP_TOL, c
        assert c["tokens"] == 4 * len(blocks) - resp.input_len % 4
    # the group shares its prompt and differs in what it sampled
    assert group[0].output_tokens[:9] != group[1].output_tokens


def test_one_precision_lower_fails(params, sampled):
    from benchmark.reference import sdar_ref

    low = sdar_ref.round_mantissa(params, 3)
    c = _check(params, sampled[0][0],
               reference=lambda p, *a, **k: sdar_ref.state_logprobs(low, *a, **k))
    assert not c["ok"], c


def test_a_skipped_commit_pass_fails(params, monkeypatch):
    """The commit forward switched off (a clean block's forward writes to the
    null block): the pool keeps the rows of the last denoise forward, whose
    input still held masks, and the next block's states miss the reference."""
    from areal_tpu.engine import jax_decode

    real = jax_decode.diffusion_step_paged

    def no_commit(p, tokens, positions, kp, vp, bt, cfg, active=None, **kw):
        return real(p, tokens, positions, kp, vp, bt, cfg,
                    active=active & (tokens == MASK).any(axis=1), **kw)

    monkeypatch.setattr(jax_decode, "diffusion_step_paged", no_commit)
    engine = _engine(params).initialize()
    try:
        resp = engine.generate(_request(_ids(31, 10).tolist(), 18), timeout=600)
    finally:
        engine.destroy()
    c = _check(params, resp)
    # (float32 here: the engine as it is meets the reference to LOGP_TOL)
    assert c["mean_abs"] > 1000 * LOGP_TOL and c["max_abs"] > 0.1, c


# -- streams do not depend on the schedule ---------------------------------------------

@pytest.mark.parametrize("over", [
    dict(new_tokens_per_chunk=16), dict(decode_runahead_chunks=0),
    dict(new_tokens_per_chunk=4, decode_runahead_chunks=2),
])
def test_streams_are_equal_across_chunks_runahead_and_batch(params, sampled, over):
    """The first request admitted has the same key in every engine: its
    stream is the same alone or in a group, at every chunk length and depth
    of run-ahead."""
    first = sampled[0][0]
    engine = _engine(params, **over).initialize()
    try:
        alone = engine.generate(_request(first.input_tokens, first.output_len), timeout=600)
    finally:
        engine.destroy()
    assert alone.output_tokens == first.output_tokens
    assert alone.output_reveal_steps == first.output_reveal_steps
    np.testing.assert_allclose(alone.output_logprobs, first.output_logprobs, atol=LOGP_TOL)


# -- stops, interrupts, weight swaps -----------------------------------------------------

def test_a_stop_token_inside_a_block_ends_the_request_there(params):
    engine = _engine(params).initialize()
    try:
        prompt = _ids(41, 9).tolist()
        whole = engine.generate(_request(prompt, 14, greedy=True), timeout=600)
        # the 6th token: position 14, the third of its block
        stop = whole.output_tokens[5]
        cut = whole.output_tokens.index(stop) + 1
        m0 = engine.get_metrics()
        resp = engine.generate(_request(prompt, 14, greedy=True, stop_token_ids=[stop]),
                               timeout=600)
        m1 = engine.get_metrics()
    finally:
        engine.destroy()
    assert resp.stop_reason == "stop" and resp.output_tokens == whole.output_tokens[:cut]
    assert resp.output_reveal_steps == whole.output_reveal_steps[:cut]
    assert len(resp.output_logprobs) == len(resp.output_versions) == cut
    assert (m1["diffusion_block_tokens_discarded_total"]
            > m0["diffusion_block_tokens_discarded_total"])


KEY = np.array([7, 11], dtype=np.uint32)  # the hand-driven requests' base key


def _hand_driven(engine):
    from areal_tpu.api.cli_args import GenerationHyperparameters
    from areal_tpu.engine.jax_decode import _Slot

    def run(rid, prompt, chunks, n=14):
        g = GenerationHyperparameters(max_new_tokens=n, temperature=1.0)
        item = _Slot(rid=rid, prompt=list(prompt), gconfig=g, future=None, loop=None,
                     base_key=KEY.copy())
        engine._request_q.put(item)
        with engine._sched_lock:
            engine._admit()
            for _ in range(chunks):
                engine._run_chunk(engine._active_mask())
        return item

    return run


def test_an_interrupt_inside_a_block_returns_committed_blocks(params):
    """A chunk of two blocks' forwards (10) ends inside the third block of a
    request whose first block the prompt half fills (3 forwards, then 5, then
    2 of the third's): the interrupt returns the two committed blocks, the
    slot is parked at their end, and the resume denoises the third block
    again, to the stream of the request never interrupted."""
    engine = _engine(params).initialize()
    try:
        engine.pause_generation()
        run = _hand_driven(engine)
        prompt = _ids(51, 10).tolist()
        ref = run("r0", prompt, 6)
        assert ref.stop_reason == "length" and len(ref.tokens) == 14
        item = run("r1", prompt, 1)
        block = [np.asarray(a) for a in engine._dev_block]
        slot = engine._slots.index(item)
        assert block[1][slot].sum() == 2 and block[4][slot] == 2  # two revealed, two steps in
        assert engine.abort_all() == 1 and item.stop_reason == "interrupt"
        assert item.tokens == ref.tokens[:6] and len(item.reveal_steps) == 6
        slot, covered, _ = engine._parked["r1"]
        assert covered == 16 == engine._slot_cache.cover(len(prompt) + len(item.tokens))
        before = engine._n_prefills
        rest = run("r1", prompt + item.tokens, 3, n=8)
        assert engine._n_prefills == before  # resumed in place
        assert rest.stop_reason == "length"
        assert item.tokens + rest.tokens == ref.tokens
        assert item.reveal_steps + rest.reveal_steps == ref.reveal_steps
        np.testing.assert_allclose(item.logprobs + rest.logprobs, ref.logprobs, atol=LOGP_TOL)
    finally:
        engine.destroy()


def test_an_interrupted_stream_is_the_uninterrupted_one(params):
    """Through the public calls: pause, abort, resubmit prompt + tokens under
    the same rid; the pieces make the stream of one request."""
    engine = _engine(params, new_tokens_per_chunk=4, context_length=256).initialize()
    try:
        prompt = _ids(52, 10).tolist()

        async def go():
            task = asyncio.ensure_future(engine.agenerate(_request(prompt, 200, rid="a")))
            while engine.get_metrics()["generated_tokens_total"] < 6:
                await asyncio.sleep(0.001)
            engine.pause_generation()
            engine.abort_all()
            first = await task
            engine.continue_generation()
            second = await engine.agenerate(
                _request(prompt + first.output_tokens, 200 - first.output_len, rid="a"))
            return first, second

        first, second = asyncio.run(go())
        m = engine.get_metrics()
    finally:
        engine.destroy()
    assert first.stop_reason == "interrupt" and 0 < first.output_len < 200
    assert (len(prompt) + first.output_len) % 4 == 0  # whole blocks
    assert m["prefills_total"] == 1  # the resume prefilled nothing
    whole = _engine(params, context_length=256).initialize()
    try:
        ref = whole.generate(_request(prompt, 200), timeout=600)
    finally:
        whole.destroy()
    assert first.output_tokens + second.output_tokens == ref.output_tokens
    assert first.output_reveal_steps + second.output_reveal_steps == ref.output_reveal_steps


def test_a_weight_swap_drops_the_block_in_flight(params):
    """New weights between two chunks, inside a block: what the slot was
    denoising is dropped and denoised again from all masks under the new
    weights, so one block never mixes weight versions; committed blocks stay."""
    import jax

    from benchmark.lib import kind_rollout_diffusion as kind
    from benchmark.reference import sdar_ref

    engine = _engine(params).initialize()
    try:
        engine.pause_generation()
        run = _hand_driven(engine)
        prompt = _ids(61, 10).tolist()
        item = run("w", prompt, 1)
        slot = engine._slots.index(item)
        assert len(item.tokens) == 6  # two blocks committed, the third in flight
        assert np.asarray(engine._dev_block[1])[slot].sum() == 2
        new = jax.tree.map(lambda x: x * 1.05, params)
        with engine._weight_swap():
            engine.params = jax.tree.map(jax.device_put, new, engine._param_shardings)
            engine._version += 1
        with engine._sched_lock:
            assert slot in engine._patch_slots
            engine._patch_diffusion_state()
            block = [np.asarray(a) for a in engine._dev_block]
            assert not block[1][slot].any() and block[4][slot] == 0  # all masks, step 0
            assert (block[0][slot] == MASK).all()
            assert int(np.asarray(engine._dev_lengths)[slot]) == 16  # committed rows stay
            for _ in range(5):
                engine._run_chunk(engine._active_mask())
    finally:
        engine.destroy()
    assert item.stop_reason == "length" and item.versions == [0] * 6 + [1] * 8
    assert sorted(item.reveal_steps[6:10]) == [0, 1, 2, 3]
    from areal_tpu.api.io_struct import ModelResponse

    resp = ModelResponse(input_tokens=prompt, output_tokens=item.tokens,
                         output_logprobs=item.logprobs, output_reveal_steps=item.reveal_steps)
    blocks = kind.block_states(resp, 4, MASK)
    assert len(blocks) == 4
    for blk in blocks[:2]:  # committed under the old weights
        for st in blk["states"]:
            lp = sdar_ref.state_logprobs(params, CFG, blk["context"], st["input"])
            for j, token, k in st["revealed"]:
                assert abs(lp[j, token] - item.logprobs[k]) < LOGP_TOL


# -- what is not served ------------------------------------------------------------------

@pytest.mark.parametrize("over,why", [
    (dict(spec_decode="ngram", spec_k=2), "verify chunk"),
    (dict(kv_host_pool_mb=16.0), "host tier"),
    (dict(role="prefill"), "migration"),
    (dict(kv_dtype="int8"), "int8 pool"),
    (dict(context_length=126), "whole numbers of blocks"),
    (dict(new_tokens_per_chunk=6), "whole numbers of blocks"),
    (dict(diffusion_steps=0), "diffusion_steps"),
    (dict(diffusion_strategy="random"), "diffusion_strategy"),
])
def test_what_initialize_refuses(params, over, why):
    engine = _engine(params, **over)
    with pytest.raises(NotImplementedError, match=why):
        engine.initialize()
    engine.destroy()


def test_a_mask_token_outside_the_vocabulary_is_refused(params):
    engine = _engine(params, cfg=tiny(mask_token_id=96))
    with pytest.raises(NotImplementedError, match="embedding row"):
        engine.initialize()
    engine.destroy()


def test_requests_and_calls_that_are_refused(params):
    engine = _engine(params).initialize()
    try:
        with pytest.raises(NotImplementedError, match="frequency penalty"):
            engine.generate(_request([1, 2, 3], 4, frequency_penalty=0.5), timeout=60)
        for call in (lambda: engine.export_session("x"),
                     lambda: engine.import_session({}, None, None),
                     lambda: engine.export_fabric_blocks([])):
            with pytest.raises(NotImplementedError, match="block boundaries"):
                call()
        assert engine._fabric_on is False
    finally:
        engine.destroy()


# -- what a device trace will call the new work -------------------------------------------

def test_the_chunk_program_holds_the_new_scopes_and_its_name(params):
    """`jit_chunk_diffusion` (so `^jit_chunk` reads it), and the scopes of a
    forward: the model's forward and the reveal under `decode_step/denoise`,
    the block's read under `layer/attn/attention_block`, `unmask` beside
    `sample`, the emission and advance under `decode_step/commit`."""
    import jax
    import jax.numpy as jnp

    from test_trace_names import _has_scope

    engine = _engine(params, paged_attn_impl="pallas").initialize()
    try:
        kq, vq = engine._kv_operands()
        R, B = 4, 4
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
        f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
        bools = lambda *s: jax.ShapeDtypeStruct(s, bool)  # noqa: E731
        block = (i32(R, B), bools(R, B), f32(R, B), i32(R, B), i32(R))
        lowered = engine._get_diffusion_chunk_fn(False, 2).lower(
            engine.params, kq, vq, i32(R, 2), block, i32(R), bools(R),
            jax.ShapeDtypeStruct((R, 2), jnp.uint32), f32(R), f32(R), bools(R), i32(R))
        text = lowered.as_text(debug_info=True)
        assert engine._diffusion_forwards() == 10
    finally:
        engine.destroy()
    assert "jit_chunk_diffusion" in text and "paged_attention_block" in text
    # (the layers are a scan inside the forward: their scopes follow its body's)
    scopes = ["layer/attn/attention_block", "decode_step/denoise/sample",
              "decode_step/denoise/unmask", "decode_step/commit", "decode_step/denoise/embed",
              "layer/attn/kv_write/pool_write", "decode_step/denoise/lm_head"]
    missing = [s for s in scopes if not _has_scope(text, s)]
    assert not missing, missing
