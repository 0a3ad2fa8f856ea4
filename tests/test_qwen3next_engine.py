"""Qwen3-Next through `JaxDecodeEngine`'s normal path at a tiny width on the
CPU: prefill then decode through the pools (the paged pool of the gated
full-attention layers, the state rows of the Gated DeltaNet layers) against
the float32 reference's full forward, logits compared, across a bucket's
padding, chunk boundaries, a fork and a reused slot; `StateSlots.holds` at
every rung that reuses KV (a late group member, a parked slot); what
`initialize()` and the migration calls refuse. The model, its weights and the
helpers are tests/test_qwen3next.py's."""

import jax.numpy as jnp
import numpy as np
import pytest

from test_qwen3next import (  # noqa: F401 — `params` is a fixture
    FULL,
    LOGP_TOL,
    _ids,
    held_slice,
    kind_rollout,
    params,
    qwen2,
    qwen3next_ref,
    tiny,
)


def _engine(cfg, params, **over):
    from areal_tpu.api.cli_args import JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    kw = dict(context_length=256, max_running_requests=4, new_tokens_per_chunk=16, page_size=4,
              dtype="float32", kv_cache_dtype="float32")
    kw.update(over)
    engine = JaxDecodeEngine(JaxDecodeConfig(**kw))
    engine.set_model(params, cfg)
    return engine


def _group(engine, prompt, lens):
    import asyncio

    async def go():
        engine.pause_generation()
        tasks = [asyncio.ensure_future(engine.agenerate(kind_rollout._request(prompt, n, 1.0)))
                 for n in lens]
        await asyncio.sleep(0)
        engine.continue_generation()
        return await asyncio.gather(*tasks)

    return asyncio.run(go())


def _agrees(resp, params, cfg):
    seq = list(resp.input_tokens) + list(resp.output_tokens)
    ref = qwen3next_ref.token_logprobs(params, cfg, seq)
    np.testing.assert_allclose(np.asarray(resp.output_logprobs), ref[resp.input_len - 1:],
                               atol=LOGP_TOL)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8)])
def test_prefill_then_decode_through_the_pools(params, held, first):
    """A group of three through `JaxDecodeEngine`: one prefill of 69 tokens
    in a bucket of 128 (padding), two forks (the full layers' blocks aliased,
    the state rows copied) before anything decodes, then 40 / 25 / 33 new
    tokens over chunks of 16 (chunk boundaries), logits against the
    reference's full forward."""
    cfg = tiny(held, first)
    p = held_slice(params, first, held)
    engine = _engine(cfg, p).initialize()
    try:
        pools = engine._kv_operands()[0]
        assert pools["full"].shape[:2] == (2, 4 * 64 + 1)
        assert pools["state"]["S"].shape == (6, 1 + 4, 8, 16, 16)
        assert pools["state"]["S"].dtype == jnp.float32
        assert pools["state"]["conv"].shape == (6, 1 + 4, 3, 256)
        assert "state" not in engine._kv_operands()[1]
        resps = _group(engine, _ids(9, 70).tolist(), (40, 25, 33))
        m = engine.get_metrics()
        null = [np.asarray(a[:, 0]) for a in engine._kv_operands()[0]["state"].values()]
    finally:
        engine.destroy()
    assert (m["prefills_total"], m["prefix_forks_total"]) == (1, 2)
    for r in resps:
        _agrees(r, p, cfg)
    assert all((a == 0).all() for a in null)  # the null slot's rows stay zero
    # live slots x 6 linear layers x token steps (whole chunks of 16)
    steps = 48 + 32 + 48
    assert m["gdn_state_updates_total"] == 6 * steps
    per_update = 2 * (8 * 16 * 16 * 4 + 3 * 256 * 4)
    assert m["gdn_state_bytes_total"] == 6 * steps * per_update
    assert m["kv_full_bytes_read_total"] == m["kv_full_rows_read_total"] * 2 * 2 * 16 * 4
    assert m["kv_full_rows_read_total"] > 0 and m["kv_window_rows_read_total"] == 0
    assert (m["moe_absent_pairs_total"] == 0) == (held == 16)


def test_a_late_group_member_prefills_again(params):
    """The donor has decoded: its state holds more than the prompt, so a
    second request with the same prompt is prefilled again, into the slot the
    first one freed, and both agree with the reference (a reused slot starts
    from the prefill's state, not from what the slot held)."""
    engine = _engine(FULL, params, max_running_requests=1).initialize()
    try:
        prompt = _ids(12, 50).tolist()
        first = engine.generate(kind_rollout._request(prompt, 20, 1.0), 300.0)
        again = engine.generate(kind_rollout._request(prompt, 10, 1.0), 300.0)
        other = engine.generate(kind_rollout._request(_ids(13, 30).tolist(), 10, 1.0), 300.0)
        m = engine.get_metrics()
    finally:
        engine.destroy()
    assert m["prefills_total"] == 3 and m["prefix_forks_total"] + m["prefix_inplace_total"] == 0
    for r in (first, again, other):
        _agrees(r, params, FULL)


def test_a_prompt_of_one_token_decodes_from_an_empty_state(params):
    """No prefill at all: the slot a longer request just left is zeroed
    before the chunk folds the one prompt token into it."""
    engine = _engine(FULL, params, max_running_requests=1).initialize()
    try:
        engine.generate(kind_rollout._request(_ids(17, 40).tolist(), 20, 1.0), 300.0)
        r = engine.generate(kind_rollout._request([7], 24, 1.0), 300.0)
        assert engine.get_metrics()["prefills_total"] == 1
    finally:
        engine.destroy()
    _agrees(r, params, FULL)


def test_a_donor_that_has_not_decoded_is_forked_and_one_token_later_is_not(params):
    """Driven by hand: after the prefill a second member forks; once a chunk
    has been dispatched for the donor a third member cannot."""
    from areal_tpu.api.cli_args import GenerationHyperparameters
    from areal_tpu.engine.jax_decode import _Slot

    engine = _engine(FULL, params, new_tokens_per_chunk=1).initialize()
    try:
        engine.pause_generation()
        prompt = _ids(14, 40).tolist()
        g = GenerationHyperparameters(max_new_tokens=8, temperature=1.0)

        def admit(rid):
            item = _Slot(rid=rid, prompt=prompt, gconfig=g, future=None, loop=None)
            engine._request_q.put(item)
            with engine._sched_lock:
                engine._admit()
            return item

        admit("a")
        assert engine._slot_cache.state.holds(0, 39)
        admit("b")
        assert engine._n_prefills == 1 and engine._n_prefix_forks == 1
        with engine._sched_lock:
            engine._run_chunk(engine._active_mask())  # one token for both
        assert not engine._slot_cache.state.holds(0, 39) and engine._slot_cache.state.holds(0, 40)
        admit("c")
        assert engine._n_prefills == 2 and engine._n_prefix_forks == 1
    finally:
        engine.destroy()


def test_a_parked_slot_is_resumed_only_at_its_exact_length(params):
    """An interrupted request's state stays parked in its slot: a resume at
    exactly the parked length prefills nothing; had a run-ahead chunk folded
    more tokens into the state, it is prefilled again."""
    from areal_tpu.api.cli_args import GenerationHyperparameters
    from areal_tpu.engine.jax_decode import _Slot

    engine = _engine(FULL, params, new_tokens_per_chunk=4).initialize()
    try:
        engine.pause_generation()
        prompt = _ids(15, 30).tolist()
        g = GenerationHyperparameters(max_new_tokens=12, temperature=1.0)

        def run(rid, prompt, chunks):
            item = _Slot(rid=rid, prompt=prompt, gconfig=g, future=None, loop=None)
            engine._request_q.put(item)
            with engine._sched_lock:
                engine._admit()
                for _ in range(chunks):
                    engine._run_chunk(engine._active_mask())
            return item

        item = run("r1", prompt, 1)
        assert engine.abort_all() == 1 and item.stop_reason == "interrupt"
        slot, covered, _ = engine._parked["r1"]
        assert covered == 29 + 4 and engine._slot_cache.state.holds(slot, covered)
        before = engine._n_prefills
        run("r1", prompt + item.tokens, 1)
        assert engine._n_prefills == before  # resumed in place
        item2 = run("r2", _ids(16, 30).tolist(), 1)
        engine.abort_all()
        slot, covered, _ = engine._parked["r2"]
        engine._slot_cache.state.note_written(np.arange(4) == slot, np.array([covered + 4]))  # ran ahead
        before = engine._n_prefills
        run("r2", list(item2.prompt) + item2.tokens, 1)
        assert engine._n_prefills == before + 1
    finally:
        engine.destroy()


@pytest.mark.parametrize("over,why", [
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(kv_host_pool_mb=16.0), "host tier"),
    (dict(role="prefill"), "migration"),
    (dict(spec_decode="ngram", spec_k=2), "roll each slot's recurrent state back"),
])
def test_what_initialize_refuses(params, over, why):
    engine = _engine(FULL, params, **over)
    with pytest.raises(NotImplementedError, match=why):
        engine.initialize()
    engine.destroy()


def test_migration_calls_and_the_verify_step_refuse(params):
    engine = _engine(FULL, params).initialize()
    try:
        assert not engine._fabric_on
        for call in (lambda: engine.export_session("x"), lambda: engine.import_session({}, None, None),
                     lambda: engine.export_fabric_blocks([])):
            with pytest.raises(NotImplementedError, match="recurrent state"):
                call()
        with pytest.raises(NotImplementedError, match="roll each slot's state back"):
            qwen2.verify_step_paged(params, jnp.zeros((4, 2), jnp.int32), jnp.zeros(4, jnp.int32),
                                    *engine._kv_operands(), jnp.zeros((4, 1), jnp.int32), FULL)
        with pytest.raises(NotImplementedError, match="suffix prefill"):
            qwen2.prefill_with_prefix(params, jnp.zeros(8, jnp.int32), jnp.zeros((2, 8, 2, 16)),
                                      jnp.zeros((2, 8, 2, 16)), jnp.int32(4), FULL)
    finally:
        engine.destroy()


# -- what a device trace will call the new work -----------------------------------


@pytest.mark.parametrize("program,scopes", [
    ("decode", ["decode_step/layer/attn/gdn_step", "decode_step/layer/attn/conv_state",
                "decode_step/layer/attn/qkvz", "decode_step/layer/attn/out_proj",
                "decode_step/layer/attn/attention_full"]),
    ("prefill", ["layer/attn/gdn_chunk_scan", "layer/attn/conv", "layer/attn/conv_state",
                 "layer/attn/attention_full"]),
])
def test_programs_hold_the_new_scopes(params, program, scopes):
    """The names `tools/trace_report.py`'s scope table shows for a linear
    layer's work, in the lowered decode step and prefill, and the kernel's
    own name; `scope_of` keeps them as written."""
    import os
    import sys

    import jax

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                                    "tools"))
    from test_trace_names import _has_scope
    from trace_report import scope_of

    if program == "decode":
        engine = _engine(FULL, params).initialize()
        try:
            kq, vq = engine._kv_operands()
            R = 4

            def step(p, t, pos, k, v, bt, act):
                with jax.named_scope("decode_step"):
                    return qwen2.decode_step_paged(p, t, pos, k, v, bt, FULL, active=act,
                                                   attn_impl="pallas", moe_load=True)

            text = jax.jit(step).lower(
                params, jnp.zeros(R, jnp.int32), jnp.zeros(R, jnp.int32), kq, vq,
                jnp.zeros((R, 8), jnp.int32), jnp.ones(R, bool)).as_text(debug_info=True)
        finally:
            engine.destroy()
        assert "gdn_step" in text and "paged_attention" in text
    else:
        text = jax.jit(lambda p, i: qwen2.prefill(
            p, i, jnp.arange(128), FULL, valid=jnp.arange(128) < 100, with_logits=False)).lower(
            params, jnp.zeros(128, jnp.int32)).as_text(debug_info=True)
    missing = [s for s in scopes if not _has_scope(text, s)]
    assert not missing, missing
    assert scope_of("jit(chunk)/while/body/closed_call/decode_step/layer/attn/gdn_step/mul") == (
        "chunk/decode_step/layer/attn/gdn_step/mul")
    assert scope_of("jit(prefill_batched)/vmap(layer)/attn/gdn_chunk_scan/while/body/dot_general") \
        == "prefill_batched/layer/attn/gdn_chunk_scan/dot_general"
