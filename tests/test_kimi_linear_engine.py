"""Kimi-Linear through `JaxDecodeEngine`'s normal path at a tiny width on the
CPU: prefill then decode through BOTH caches of a slot (the state rows of the
Kimi Delta Attention layers, the latent rows of the attention layers in the
paged pool) against the float32 reference's full forward, log-probabilities
compared, across a bucket's padding, chunk boundaries, a fork and a reused
slot, for a share of the experts too; one fork aliasing the blocks and copying
the state, then divergent decode; what `initialize()` and the migration calls
refuse for the pair, by message; the new scopes in the lowered programs and in
`tools/trace_report.py`; the other hybrids' programs left as the parent's. The
model, its weights and the helpers are tests/test_kimi_linear.py's."""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_kimi_linear import (  # noqa: F401 — `params` is a fixture
    FULL,
    LOGP_TOL,
    REPO,
    _ids,
    held_slice,
    kimi_linear_ref,
    params,
    qwen2,
    tiny,
)

from benchmark.lib import kind_rollout  # noqa: E402


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
    ref = kimi_linear_ref.token_logprobs(params, cfg, seq)
    np.testing.assert_allclose(np.asarray(resp.output_logprobs), ref[resp.input_len - 1:],
                               atol=LOGP_TOL)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8)])
def test_prefill_then_decode_through_both_caches(params, held, first):
    """A group of three through `JaxDecodeEngine`: one prefill of 69 tokens
    in a bucket of 128 (padding), two forks (the latent layer's blocks
    aliased, the state rows copied, in one fork) before anything decodes,
    then 40 / 25 / 33 new tokens over chunks of 16, log-probabilities against
    the reference's full forward (the recurrence, the expanded attention)."""
    cfg = tiny(held, first)
    p = held_slice(params, first, held)
    engine = _engine(cfg, p).initialize()
    try:
        kq, vq = engine._kv_operands()
        assert set(kq) == {"latent", "state"} and vq == {}
        assert kq["latent"].shape == (1, 4 * 64 + 1, 4, cfg.latent_row_lanes)
        assert kq["state"]["S"].shape == (3, 1 + 4, 4, 16, 16)
        assert kq["state"]["S"].dtype == jnp.float32
        assert kq["state"]["conv"].shape == (3, 1 + 4, 3, 3 * 64)
        assert engine._slot_cache.kinds == ("pools", "state", "latent")
        resps = _group(engine, _ids(9, 70).tolist(), (40, 25, 33))
        m = engine.get_metrics()
        null = [np.asarray(a[:, 0]) for a in engine._kv_operands()[0]["state"].values()]
    finally:
        engine.destroy()
    assert (m["prefills_total"], m["prefix_forks_total"]) == (1, 2)
    for r in resps:
        _agrees(r, p, cfg)
    assert all((a == 0).all() for a in null)  # the null slot's rows stay zero
    # live slots x 3 KDA layers x token steps (whole chunks of 16), at the
    # cache's own bytes an update: state and convolution rows, in and out
    steps = 48 + 32 + 48
    assert m["gdn_state_updates_total"] == 3 * steps
    per_update = 2 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    assert m["gdn_state_bytes_total"] == 3 * steps * per_update
    # the one latent layer's rows, at the lanes the pool stores
    assert m["kv_latent_rows_read_total"] > 69 * steps
    assert m["kv_latent_bytes_read_total"] == m["kv_latent_rows_read_total"] * 128 * 4
    assert m["kv_full_rows_read_total"] == 0 and m["kv_window_rows_read_total"] == 0
    assert (m["moe_absent_pairs_total"] == 0) == (held == 16)
    # held experts with a pair, a sparse layer and token step: at most all of
    # them in three sparse layers at each of the 48 token steps the three
    # slots' chunks share; one group: every live token's group lands here
    assert 0 < m["moe_group_experts_touched_total"] <= held * 3 * 48
    assert m["chunks_consumed_token_steps_total"] == 48  # what every sum above is over
    assert m["moe_group_tokens_here_total"] == 3 * steps


def test_a_prefill_the_compiler_refuses_runs_a_bucket_wider(params, monkeypatch):
    """XLA:TPU refused ONE bucket of this model's prefill at compile time (PR
    45). Here the compiler is made to refuse the bucket of 128 positions (a
    custom call it has no target for): the engine runs the same prompt as a
    pass over 192, its rows cut back to the bucket, and the prefill, the two
    forks and the decode agree with the reference as they do unrefused."""
    from areal_tpu.engine import jax_decode

    real, asked = jax_decode.prefill, []

    def prefill(p, ids, *args, **kw):
        asked.append(ids.shape[0])
        if ids.shape[0] == 128:
            jax.ffi.ffi_call("refused_by_the_compiler", jax.ShapeDtypeStruct((), jnp.float32),
                             has_side_effect=True)()
        return real(p, ids, *args, **kw)

    monkeypatch.setattr(jax_decode, "prefill", prefill)
    engine = _engine(FULL, params).initialize()
    try:
        resps = _group(engine, _ids(9, 70).tolist(), (40, 25, 33))
        m = engine.get_metrics()
        ran = {key: fn.tokens for key, fn in engine._batched_prefill_fns.items()}
    finally:
        engine.destroy()
    assert asked == [128, 192] and ran == {(128, 1): 192}
    assert (m["prefills_total"], m["prefix_forks_total"]) == (1, 2)
    for r in resps:
        _agrees(r, params, FULL)


def test_a_fault_that_is_not_the_shapes_is_raised_as_it_came():
    """Every width fails alike: the bucket's own error comes back, after the
    two wider passes were tried; a program that has run is not wrapped again."""
    from areal_tpu.engine.jax_decode import _PrefillOrWider

    asked = []

    def program(tokens, fails=True):
        def fn(x):
            asked.append(tokens)
            if fails:
                raise jax.errors.JaxRuntimeError(f"fault at {tokens}")
            return x

        return fn

    with pytest.raises(jax.errors.JaxRuntimeError, match="fault at 64"):
        _PrefillOrWider(program, 64)(1)
    assert asked == [64, 128, 192]
    sound = _PrefillOrWider(lambda tokens: program(tokens, fails=False), 64)
    assert (sound(1), sound(2), sound.tokens) == (1, 2, 64) and asked[3:] == [64, 64]


def test_a_fork_then_divergent_decode(params):
    """The donor's prompt is prefilled once, a second member
    forks it (blocks aliased in the table, the state's rows copied), and the
    two then decode different tokens: each agrees with the reference, and
    the donor's state is not the fork's."""
    engine = _engine(FULL, params).initialize()
    try:
        a, b = _group(engine, _ids(21, 53).tolist(), (30, 30))
        m = engine.get_metrics()
        S = np.asarray(engine._kv_operands()[0]["state"]["S"])
    finally:
        engine.destroy()
    assert (m["prefills_total"], m["prefix_forks_total"]) == (1, 1)
    assert list(a.output_tokens) != list(b.output_tokens)  # sampled apart
    _agrees(a, params, FULL)
    _agrees(b, params, FULL)
    assert np.abs(S[:, 1] - S[:, 2]).max() > 1e-3


def test_a_late_group_member_prefills_again_and_one_token_decodes_from_zero(params):
    """The donor has decoded: its state holds more than the prompt, so a
    second request with the same prompt is prefilled again into the slot the
    first one freed; a prompt of one token starts from a zeroed state."""
    engine = _engine(FULL, params, max_running_requests=1).initialize()
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
        _agrees(r, params, FULL)


def test_a_prompt_longer_than_the_dense_prefill(params, monkeypatch):
    """Above `PREFILL_DENSE_MAX` the latent layer's prefill goes a block of
    keys at a time; the KDA layers' chunk scan is the same either way."""
    monkeypatch.setattr(qwen2, "PREFILL_DENSE_MAX", 32)
    engine = _engine(FULL, params).initialize()
    try:
        r = engine.generate(kind_rollout._request(_ids(31, 100).tolist(), 20, 1.0), 300.0)
    finally:
        engine.destroy()
    _agrees(r, params, FULL)


# -- what the pair of caches cannot serve ------------------------------------------


@pytest.mark.parametrize("over,why", [
    (dict(kv_dtype="int8"), "kv_dtype='int8' needs .*: a pool of a dict has no scale pool"),
    (dict(kv_host_pool_mb=1.0), "kv_host_pool_mb > 0 .* needs all a slot has cached in one"),
    (dict(role="prefill"), "role='prefill' .*migration"),
    (dict(spec_decode="ngram", spec_k=2),
     "roll each slot's recurrent state back.*the absorbed attention scores one query"),
    (dict(weight_dtype="int8"), "weight_dtype='int8' needs .*: the low-rank projections"),
])
def test_what_initialize_refuses_for_the_pair(params, over, why):
    """Each mechanism's refusal names BOTH kinds where both lack what it
    needs: the union of the table's `state` and `latent` rows."""
    engine = _engine(FULL, params, **over)
    with pytest.raises(NotImplementedError, match=why) as e:
        engine.initialize()
    assert "a recurrent state a slot for the linear layers" in str(e.value)
    assert "latent attention: one cached row a token" in str(e.value)
    engine.destroy()


def test_migration_calls_the_verify_step_and_the_suffix_prefill_refuse(params):
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
            qwen2.prefill_with_prefix(params, jnp.zeros(8, jnp.int32), jnp.zeros((1, 8, 1, 128)),
                                      jnp.zeros((1, 8, 1, 0)), jnp.int32(4), FULL)
    finally:
        engine.destroy()


# -- what a device trace will call the new work -----------------------------------


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("program,scopes,absent", [
    ("decode", ["decode_step/layer/attn/kda_step", "decode_step/layer/attn/conv_state",
                "decode_step/layer/attn/qkv", "decode_step/layer/attn/kda_gate",
                "decode_step/layer/attn/out_gate", "decode_step/layer/attn/out_proj",
                "decode_step/layer/attn/q_proj", "decode_step/layer/attn/kv_latent",
                "decode_step/layer/attn/absorb_q", "decode_step/layer/attn/latent_attention",
                "decode_step/layer/attn/absorb_out", "decode_step/dense_layer/layer/attn/kda_step"],
     ["rope", "q_lora", "gdn_step", "group_route"]),
    ("prefill", ["layer/attn/kda_chunk_scan", "layer/attn/conv", "layer/attn/conv_state",
                 "layer/attn/qkv", "layer/attn/kda_gate", "layer/attn/out_gate",
                 "layer/attn/latent_attention", "layer/attn/kv_latent"],
     ["rope", "q_lora", "gdn_chunk_scan"]),
    ("forward", ["layer/attn/kda_chunk_scan", "layer/attn/conv", "layer/attn/qkv",
                 "layer/attn/kda_gate", "layer/attn/out_gate", "layer/attn/latent_attention"],
     ["rope", "q_lora", "gdn_chunk_scan"]),
])
def test_programs_hold_the_new_scopes_and_no_rotary_table(params, program, scopes, absent):
    """The names `tools/trace_report.py`'s scope table shows for a KDA
    layer's and a no-rotation latent layer's work, and the kernel's own name;
    no `rope` scope (no table, no rotation) and no low-rank query anywhere."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
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

            text = _lowered(step, params, jnp.zeros(R, jnp.int32), jnp.zeros(R, jnp.int32), kq, vq,
                            jnp.zeros((R, 8), jnp.int32), jnp.ones(R, bool))
        finally:
            engine.destroy()
        assert "kda_step" in text and "paged_attention_latent" in text
    elif program == "prefill":
        # (with the logits: without them the last layer, a latent one, hands
        # over its rows and its attention is dead code)
        text = _lowered(lambda p, i: qwen2.prefill(
            p, i, jnp.arange(128), FULL, valid=jnp.arange(128) < 100),
            params, jnp.zeros(128, jnp.int32))
    else:
        text = _lowered(lambda p, i: qwen2.forward(
            p, i, jnp.arange(128), jnp.zeros(128, jnp.int32), FULL), params,
            jnp.zeros(128, jnp.int32))
    missing = [s for s in scopes if not _has_scope(text, s)]
    assert not missing, missing
    there = [s for s in absent if f"/{s}/" in text or f"/{s}\"" in text]
    assert not there, there
    assert scope_of("jit(chunk)/while/body/closed_call/decode_step/layer/attn/kda_step/mul") == (
        "chunk/decode_step/layer/attn/kda_step/mul")
    assert scope_of("jit(prefill_batched)/vmap(layer)/attn/kda_chunk_scan/while/body/dot_general") \
        == "prefill_batched/layer/attn/kda_chunk_scan/dot_general"


# -- the other hybrids' programs are the parent's --------------------------------
# recorded at 404ab1f (PR 44) by this file's `lowered_programs` run there
# (`python tests/test_kimi_linear_engine.py`): Qwen3-Next shares `%gdn_step`'s
# frame, `_gdn_conv` and the convolution's step with the KDA mixer, DeepSeek-V2
# the latent projection, the pool's write and the grouped load vector

PARENT_SHA256 = {
    "dsv2.decode_step": "d71b903d88e6d0d1",
    "dsv2.forward": "9eb22aa9c5970b70",
    "dsv2.prefill": "b2174b5a997c9de9",
    "dsv2_part.decode_step": "c97151f51053422b",
    "dsv2_part.forward": "03f887a69245986f",
    "dsv2_part.prefill": "78867ff578fc2ad0",
    "qwen3next.decode_step": "68b16d4e5d4c39d9",
    "qwen3next.forward": "bca0b7382ffeaf2b",
    "qwen3next.prefill": "8b50c9ab2209080e",
    "qwen3next_part.decode_step": "92763e16b84cbd6c",
    "qwen3next_part.forward": "0bb07068e80406e5",
    "qwen3next_part.prefill": "527df21116cb6681",
}


def _others():
    from test_deepseek_v2 import FULL as dsv2
    from test_deepseek_v2 import PART as dsv2_part
    from test_qwen3next import FULL as qwen3next
    from test_qwen3next import PART as qwen3next_part

    return {"qwen3next": qwen3next, "qwen3next_part": qwen3next_part, "dsv2": dsv2,
            "dsv2_part": dsv2_part}


def lowered_programs() -> dict:
    """{name: lowered text} of the two accepted hybrids' programs: `forward`,
    `prefill` and the decode step over their dicts of pools, the step kernel
    through Pallas so that `%gdn_step`'s call is in the text (without
    locations: a Mosaic kernel's module carries its source lines)."""
    from test_trace_names import _location_free

    out = {}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    for name, cfg in _others().items():
        p = jax.eval_shape(lambda c=cfg: qwen2.init_params(c, jax.random.PRNGKey(0)))
        T, R, nb, bsz = 24, 2, 4, 4
        layers = cfg.cache_layers
        if cfg.latent:
            kp, vp = {"latent": f32(len(layers["latent"]), 9, bsz, cfg.latent_row_lanes)}, {}
        else:
            D = cfg.num_key_value_heads * cfg.head_dim_
            kp = {"full": f32(len(layers["full"]), 9, bsz, D)}
            vp = dict(kp)
        if layers["state"]:
            n = len(layers["state"])
            kp["state"] = {
                "S": f32(n, 1 + R, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                         cfg.linear_value_head_dim),
                "conv": f32(n, 1 + R, cfg.linear_conv_kernel_dim - 1, cfg.linear_conv_channels)}
        out[f"{name}.forward"] = jax.jit(
            lambda p, i, q, s, c=cfg: qwen2.forward(p, i, q, s, c)).lower(
            p, i32(T), i32(T), i32(T)).as_text()
        out[f"{name}.prefill"] = jax.jit(
            lambda p, i, q, c=cfg: qwen2.prefill(p, i, q, c, with_logits=False)).lower(
            p, i32(T), i32(T)).as_text()
        out[f"{name}.decode_step"] = jax.jit(
            lambda p, t, n, kp, vp, bt, c=cfg: qwen2.decode_step_paged(
                p, t, n, kp, vp, bt, c, active=jnp.ones(R, bool),
                attn_impl="pallas" if c.cache_layers["state"] else "xla",
                moe_load=True)).lower(p, i32(R), i32(R), kp, vp, i32(R, nb)).as_text()
    return out


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def lowered():
    return lowered_programs()


@pytest.mark.parametrize("name", sorted(f"{m}.{p}" for m in ("qwen3next", "qwen3next_part",
                                                             "dsv2", "dsv2_part")
                                        for p in ("forward", "prefill", "decode_step")))
def test_the_accepted_hybrids_lowered_programs_are_the_parents(lowered, name):
    assert _sha(lowered[name]) == PARENT_SHA256[name], (
        f"{name}: the lowered program of a model that is not kimi_linear changed; if the "
        "change is meant, record `python tests/test_kimi_linear_engine.py` anew")


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for k, v in sorted(lowered_programs().items()):
        print(f'    "{k}": "{_sha(v)}",')
