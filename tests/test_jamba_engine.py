"""Jamba through `JaxDecodeEngine`'s normal path at a tiny width on the CPU:
the batched wave of distinct prompts (each prompt's state and convolution rows
in its own slot's row, padding past its length leaving the state untouched),
prefill then decode through BOTH caches of a slot (the float32 state rows of
the state-space layers, the one-kv-head rows of the attention layers in the
paged pool) by chunks against the float32 reference's full forward, groups of
four over eight slots, the fork (a sibling's state a copy, its attention
blocks aliased) and `holds`, a reused slot, what `initialize()` refuses from
the `state` row of `kv_pool.py`'s table, the counters, and the new scopes. The
model, its weights and the helpers are tests/test_jamba.py's."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_jamba import (  # noqa: F401 — `params` is a fixture
    CFG,
    LOGP_TOL,
    REPO,
    _ids,
    jamba_ref,
    params,
    qwen2,
)

from benchmark.lib import kind_rollout  # noqa: E402


def _engine(cfg, params, **over):
    from areal_tpu.api.cli_args import JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    kw = dict(context_length=256, max_running_requests=8, new_tokens_per_chunk=16, page_size=4,
              dtype="float32", kv_cache_dtype="float32")
    kw.update(over)
    engine = JaxDecodeEngine(JaxDecodeConfig(**kw))
    engine.set_model(params, cfg)
    return engine


def _groups(engine, groups):
    """`groups`: [(prompt, output lengths)], all queued before anything runs."""
    import asyncio

    async def go():
        engine.pause_generation()
        tasks = [asyncio.ensure_future(engine.agenerate(kind_rollout._request(prompt, n, 1.0)))
                 for prompt, lens in groups for n in lens]
        await asyncio.sleep(0)
        engine.continue_generation()
        return await asyncio.gather(*tasks)

    return asyncio.run(go())


def _agrees(resp, params, cfg):
    seq = list(resp.input_tokens) + list(resp.output_tokens)
    ref = jamba_ref.token_logprobs(params, cfg, seq)
    np.testing.assert_allclose(np.asarray(resp.output_logprobs), ref[resp.input_len - 1:],
                               atol=LOGP_TOL)


def test_two_groups_of_four_over_eight_slots(params):
    """Two DISTINCT prompts of 41 and 58 tokens in one bucket of 64: one
    batched wave of two (padding past each true length), three forks a group
    (the attention layers' blocks aliased, the state rows copied) before
    anything decodes, then 20-40 new tokens over chunks of 16:
    log-probabilities against the reference's full forward."""
    engine = _engine(CFG, params).initialize()
    try:
        kq, vq = engine._kv_operands()
        n_state = len(CFG.cache_layers["state"])
        assert set(kq) == {"full", "state"} and set(vq) == {"full"}
        assert kq["full"].shape == (2, 8 * 64 + 1, 4, 12)  # ONE kv head of 12
        assert kq["state"]["S"].shape == (n_state, 1 + 8, 16, 96)
        assert kq["state"]["S"].dtype == jnp.float32
        assert kq["state"]["conv"].shape == (n_state, 1 + 8, 3, 96)
        assert engine._slot_cache.kinds == ("pools", "state")
        resps = _groups(engine, [(_ids(9, 42).tolist(), (40, 25, 33, 20)),
                                 (_ids(10, 59).tolist(), (22, 36, 28, 31))])
        m = engine.get_metrics()
        waves = sorted(engine._batched_prefill_fns)
        update_nbytes = engine._slot_cache.state_update_nbytes
        null = [np.asarray(a[:, 0]) for a in engine._kv_operands()[0]["state"].values()]
    finally:
        engine.destroy()
    assert (m["prefills_total"], m["prefix_forks_total"]) == (2, 6)
    assert (64, 2) in waves
    for r in resps:
        _agrees(r, params, CFG)
    assert all((a == 0).all() for a in null)  # the null slot's rows stay zero
    # live slots x state-space layers x token steps, at the cache's own bytes
    # an update: state and convolution rows, in and out
    steps = sum(-(-n // 16) * 16 for n in (40, 25, 33, 20, 22, 36, 28, 31))
    assert m["gdn_state_updates_total"] == n_state * steps
    per_update = 2 * (16 * 96 * 4 + 3 * 96 * 4)
    assert m["gdn_state_bytes_total"] == n_state * steps * per_update
    assert update_nbytes == per_update
    # the two attention layers' rows: K and V of one head of 12 in float32
    assert m["kv_full_rows_read_total"] > 2 * 41 * steps / 2
    assert m["kv_full_bytes_read_total"] == m["kv_full_rows_read_total"] * 2 * 12 * 4
    assert m["kv_window_rows_read_total"] == 0 and m["moe_pairs_total"] == 0


def test_a_wave_of_distinct_prompts_lands_each_state_in_its_own_row(params):
    """The batched prefill alone: three prompts of 20, 45 and 64 tokens in a
    bucket of 64 into slots 5, 1 and 3; each slot's state and last three
    pre-convolution rows are that prompt's own whole-length prefill's, every
    other slot's rows and the null row stay zero."""
    engine = _engine(CFG, params).initialize()
    try:
        lens = (20, 45, 64)
        prompts = [np.r_[_ids(40 + i, n), np.zeros(64 - n, np.int32)] for i, n in enumerate(lens)]
        slots = (5, 1, 3)
        for s in slots:
            assert engine._alloc.ensure(s, 64)
        fn = engine._get_batched_prefill_fn(64, 3)
        tables = jax.tree.map(lambda *rows: jnp.asarray(np.stack(rows)),
                              *[engine._slot_cache.tables(s, 16) for s in slots])
        kq, vq = fn(engine.params, *engine._kv_operands(), jnp.asarray(np.stack(prompts)),
                    jnp.arange(64, dtype=jnp.int32), tables, jnp.asarray(lens, jnp.int32))
        state = jax.tree.map(np.asarray, kq["state"])
    finally:
        engine.destroy()
    for s, p, n in zip(slots, prompts, lens):
        want = qwen2.prefill(params, jnp.asarray(p[:n]), jnp.arange(n), CFG)[3]
        for k in ("S", "conv"):
            np.testing.assert_allclose(state[k][:, 1 + s], np.asarray(want[k]), atol=2e-5)
    for k in ("S", "conv"):
        others = [r for r in range(9) if r - 1 not in slots]
        assert (state[k][:, others] == 0).all()


def test_a_fork_copies_the_state_aliases_the_blocks_and_holds_one_length(params):
    engine = _engine(CFG, params).initialize()
    try:
        a, b = _groups(engine, [(_ids(21, 54).tolist(), (30, 30))])
        m = engine.get_metrics()
        S = np.asarray(engine._kv_operands()[0]["state"]["S"])
        cache = engine._slot_cache
        # a state is good for exactly one length: what the two slots hold now
        # (53 prompt rows and 32 decoded) seeds nothing else
        assert cache.holds(0, int(cache.state.count[0]))
        assert not cache.holds(0, 53) and not cache.holds(0, int(cache.state.count[0]) + 1)
        # the fork itself, on the account: full blocks aliased, state row copied
        copies = cache.fork(0, 5, 52)
        assert [c[0].__wrapped__.__name__ for c in copies][-1] == "fork_state"
        assert list(cache.alloc.tables[5, :13]) == list(cache.alloc.tables[0, :13])
        assert int(cache.state.count[5]) == int(cache.state.count[0])
    finally:
        engine.destroy()
    assert (m["prefills_total"], m["prefix_forks_total"]) == (1, 1)
    assert list(a.output_tokens) != list(b.output_tokens)  # sampled apart
    _agrees(a, params, CFG)
    _agrees(b, params, CFG)
    assert np.abs(S[:, 1] - S[:, 2]).max() > 1e-3


def test_a_late_member_prefills_again_and_a_freed_slot_starts_from_zero(params):
    """One slot: the donor has decoded, so the same prompt is prefilled again
    into the slot it freed; a prompt of one token starts from a zeroed state."""
    engine = _engine(CFG, params, max_running_requests=1).initialize()
    try:
        prompt = _ids(12, 50).tolist()
        first = engine.generate(kind_rollout._request(prompt, 20, 1.0), 300.0)
        again = engine.generate(kind_rollout._request(prompt, 10, 1.0), 300.0)
        one = engine.generate(kind_rollout._request([7], 24, 1.0), 300.0)
        m = engine.get_metrics()
    finally:
        engine.destroy()
    assert m["prefills_total"] == 2 and m["prefix_forks_total"] + m["prefix_inplace_total"] == 0
    for r in (first, again, one):
        _agrees(r, params, CFG)


# -- what this kind cannot serve: the `state` row of the one table ------------------


@pytest.mark.parametrize("over,why", [
    (dict(kv_dtype="int8"), "kv_dtype='int8' needs .*: a pool of a dict has no scale pool"),
    (dict(kv_host_pool_mb=1.0), "kv_host_pool_mb > 0 .* needs all a slot has cached in one"),
    (dict(role="prefill"), "role='prefill' .*migration"),
    (dict(role="decode"), "role='decode' .*migration"),
    (dict(spec_decode="ngram", spec_k=2), "roll each slot's recurrent state back"),
])
def test_what_initialize_refuses(params, over, why):
    engine = _engine(CFG, params, **over)
    with pytest.raises(NotImplementedError, match=why) as e:
        engine.initialize()
    assert "jamba" in str(e.value)
    assert "a recurrent state a slot for the linear layers" in str(e.value)
    engine.destroy()


def test_migration_calls_the_verify_step_and_the_suffix_prefill_refuse(params):
    engine = _engine(CFG, params).initialize()
    try:
        assert not engine._fabric_on
        for call in (lambda: engine.export_session("x"),
                     lambda: engine.import_session({}, None, None)):
            with pytest.raises(NotImplementedError, match="recurrent state"):
                call()
        with pytest.raises(NotImplementedError, match="suffix prefill"):
            qwen2.prefill_with_prefix(params, jnp.zeros(8, jnp.int32), jnp.zeros((2, 8, 1, 12)),
                                      jnp.zeros((2, 8, 1, 12)), jnp.int32(4), CFG)
    finally:
        engine.destroy()


def test_no_models_name_is_tested_in_the_engine():
    for name in ("jax_decode.py", "kv_pool.py"):
        with open(os.path.join(REPO, "areal_tpu/engine", name)) as f:
            text = f.read().lower()
        assert "jamba" not in text and "mamba" not in text and "ssm_" not in text, name


# -- what a device trace will call the new work -----------------------------------


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("program,scopes", [
    ("decode", ["decode_step/layer/attn/in_proj", "decode_step/layer/attn/conv_state",
                "decode_step/layer/attn/ssm_params", "decode_step/layer/attn/ssm_step",
                "decode_step/layer/attn/out_gate", "decode_step/layer/attn/out_proj",
                "decode_step/layer/attn/qkv", "decode_step/layer/attn/attention_full"]),
    ("prefill", ["layer/attn/in_proj", "layer/attn/conv", "layer/attn/conv_state",
                 "layer/attn/ssm_params", "layer/attn/ssm_scan", "layer/attn/out_gate",
                 "layer/attn/out_proj", "layer/attn/qkv"]),
])
def test_programs_hold_the_new_scopes_and_no_rotary_table(params, program, scopes):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    from test_trace_names import _has_scope
    from trace_report import scope_of

    if program == "decode":
        engine = _engine(CFG, params).initialize()
        try:
            kq, vq = engine._kv_operands()
            R = 8

            def step(p, t, pos, k, v, bt, act):
                with jax.named_scope("decode_step"):
                    return qwen2.decode_step_paged(p, t, pos, k, v, bt, CFG, active=act,
                                                   attn_impl="pallas", moe_load=True)

            text = _lowered(step, params, jnp.zeros(R, jnp.int32), jnp.zeros(R, jnp.int32), kq, vq,
                            jnp.zeros((R, 8), jnp.int32), jnp.ones(R, bool))
        finally:
            engine.destroy()
        assert "ssm_step" in text and "paged_attention" in text
    else:
        text = _lowered(lambda p, i: qwen2.prefill(
            p, i, jnp.arange(128), CFG, valid=jnp.arange(128) < 100),
            params, jnp.zeros(128, jnp.int32))
    missing = [s for s in scopes if not _has_scope(text, s)]
    assert not missing, missing
    there = [s for s in ("rope", "gdn_step", "kda_step", "gdn_chunk_scan") if f"/{s}/" in text
             or f"/{s}\"" in text]
    assert not there, there
    assert scope_of("jit(chunk)/while/body/closed_call/decode_step/while/body/layer/attn/"
                    "ssm_step/mul") == "chunk/decode_step/layer/attn/ssm_step/mul"
    assert scope_of("jit(prefill_batched)/vmap(layer)/attn/ssm_scan/while/body/mul") \
        == "prefill_batched/layer/attn/ssm_scan/mul"
