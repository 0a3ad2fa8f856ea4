"""The float32 reference against the program at a tiny width on the CPU, and
how far the comparison moves when a piece of the mathematics is wrong."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths  # noqa: F401
from benchmark.lib import harness, weights
from benchmark.reference import qwen2_ref
from benchmark.reference.qwen2_ref import MAX_ABS_TOL, MEAN_ABS_TOL

from areal_tpu.models.qwen2 import ModelConfig, forward

TINY = ModelConfig(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=3,
                   num_attention_heads=4, num_key_value_heads=2, rope_theta=1e6,
                   tie_word_embeddings=True, dtype="float32", param_dtype="float32",
                   attn_impl="dense")
SEED = 2**31 + 99


@pytest.fixture(scope="module")
def params():
    return weights.seeded_params(TINY, SEED)


def _program_logprobs(params, cfg, ids):
    T = len(ids)
    logits = forward(params, jnp.asarray(ids), jnp.arange(T), jnp.zeros(T, jnp.int32), cfg)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return np.asarray(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])


def test_seeded_weights_are_a_function_of_the_seed_and_not_flat(params):
    again = weights.seeded_params(TINY, SEED)
    other = weights.seeded_params(TINY, SEED + 1)
    same = jax.tree.map(lambda a, b: bool((a == b).all()), params, again)
    assert all(jax.tree.leaves(same))
    assert not bool((params["embed"]["embedding"] == other["embed"]["embedding"]).all())
    assert float(jnp.abs(params["layers"]["attn"]["q_bias"]).mean()) > 0.1  # biases matter
    ids = np.random.default_rng(0).integers(1, 256, 48)
    lp = qwen2_ref.token_logprobs(params, TINY, ids)
    assert lp.std() > 0.5  # log-probabilities differ from token to token


@pytest.mark.parametrize("n,pad_to", [(17, 0), (48, 0), (48, 128)])
def test_reference_agrees_with_the_program(params, n, pad_to):
    ids = np.random.default_rng(n).integers(1, 256, n).astype(np.int32)
    got = _program_logprobs(params, TINY, ids)
    ref = qwen2_ref.token_logprobs(params, TINY, ids, pad_to=pad_to)
    assert ref.shape == (n - 1,)
    c = harness.compare_with_reference("tiny", got, ref)
    assert c["ok"] and c["max_abs"] < 1e-4, c  # float32 against float32


def _drop_bias(p):
    p = jax.tree.map(lambda x: x, p)
    for k in ("q_bias", "k_bias", "v_bias"):
        p["layers"]["attn"][k] = jnp.zeros_like(p["layers"]["attn"][k])
    return p


@pytest.mark.parametrize("what", ["no_qkv_bias", "wrong_rope_base", "sees_the_future", "bf16_compute"])
def test_what_the_tolerance_catches_and_what_it_lets_pass(params, what):
    ids = np.random.default_rng(5).integers(1, 256, 64).astype(np.int32)
    ref = qwen2_ref.token_logprobs(params, TINY, ids)
    if what == "no_qkv_bias":
        got = _program_logprobs(_drop_bias(params), TINY, ids)
    elif what == "wrong_rope_base":
        got = _program_logprobs(params, dataclasses.replace(TINY, rope_theta=1e4), ids)
    elif what == "sees_the_future":
        # a mask that lets a query see one token ahead: score sequence
        # shifted so that position t holds token t + 1's context
        got = _program_logprobs(params, TINY, np.r_[ids[1:], ids[:1]])
    else:
        # as on the chip: bf16 weights and bf16 compute; the reference reads
        # the same bf16 weights and computes in float32
        bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        cfg = dataclasses.replace(TINY, dtype="bfloat16", param_dtype="bfloat16")
        got = _program_logprobs(bf16, cfg, ids)
        ref = qwen2_ref.token_logprobs(bf16, cfg, ids)
    c = harness.compare_with_reference(what, got, ref)
    if what == "bf16_compute":
        assert c["ok"], c  # float32 against bf16 compute passes
    else:
        assert not c["ok"] and c["mean_abs"] > 5 * MEAN_ABS_TOL, c


@pytest.mark.parametrize("kv,passes", [("float32", True), ("int8", False)])
def test_the_decode_engine_meets_the_tolerance_unless_its_pool_is_int8(params, kv, passes):
    """Through `JaxDecodeEngine` itself, as the rollout cell checks it: a
    request deep enough to span pages and chunks."""
    from areal_tpu.api.cli_args import JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine
    from benchmark.lib import kind_rollout

    engine = JaxDecodeEngine(JaxDecodeConfig(
        context_length=512, max_running_requests=4, new_tokens_per_chunk=128, page_size=128,
        dtype="float32", kv_cache_dtype=kv))
    engine.set_model(params, TINY)
    engine.initialize()
    try:
        prompt = np.random.default_rng(9).integers(1, 256, 100).tolist()
        resp = engine.generate(kind_rollout._request(prompt, 300, 1.0), 300.0)
        done = [{"resp": resp, "want": 300, "group": 0}]
        (c,) = kind_rollout.check_decode(None, engine, done, 1, pad_to=512)
    finally:
        engine.destroy()
    assert c["tokens"] == 300 and c["ok"] is passes, c
    if not passes:
        assert c["max_abs"] > 2 * MAX_ABS_TOL, c


def test_check_sample_takes_the_longest_and_spreads_the_rest():
    from types import SimpleNamespace

    from benchmark.lib.kind_rollout import check_sample

    def req(want, got=None, group=0):
        return {"want": want, "group": group,
                "resp": SimpleNamespace(output_len=want if got is None else got, input_len=100)}

    done = [req(n) for n in range(10, 110, 10)] + [req(500, got=320)]  # one flushed short
    assert [r["want"] for r in check_sample(done, 4)] == [30, 70, 90, 100]
    assert [r["want"] for r in check_sample(done[:3], 4)] == [10, 20, 30]
    assert check_sample([req(500, got=3)], 4) == []


def test_prefill_waves_fit_the_engines_budget_and_cover_every_batch_size():
    from benchmark.lib.kind_rollout import prefill_waves

    # 15 prompts a bucket (8 + 4 + 2 + 1): 15 * (256 + 192) = 6,720 fit a pass of 8,192, 128 more do not
    assert prefill_waves({256: 253, 192: 193, 128: 127}, 8192, 128) == [[253] * 15 + [193] * 15, [127] * 15]
    assert prefill_waves({256: 256}, 8192, 128) == [[256] * 15]
    # 8 x 1,280 is over the budget: batches of 4, 2 and 1 only; a bucket a wave where slots are few
    assert prefill_waves({1280: 1000}, 8192, 128) == [[1000] * 7]
    assert prefill_waves({64: 64, 128: 100}, 8192, 4) == [[100] * 7, [64] * 7]


def test_tolerances_are_stated():
    assert 0 < MEAN_ABS_TOL < MAX_ABS_TOL < 1.0
