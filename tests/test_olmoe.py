"""OLMoE through the normal path, at a tiny width on the CPU, against the
float32 reference (`benchmark/reference/olmoe_ref.py`): forward, loss and every
leaf's gradient, prefill then paged decode through `JaxDecodeEngine`; what the
comparison catches when a piece of the mathematics is wrong; that routing is
exact under any skew and a token's result does not depend on its batch-mates;
the registry and the HF tensor names."""

import dataclasses
import json
import os
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import kind_rollout, kind_rollout_moe, weights  # noqa: E402
from benchmark.reference import olmoe_ref  # noqa: E402
from benchmark.reference.olmoe_ref import MAX_ABS_TOL, MEAN_ABS_TOL  # noqa: E402

from areal_tpu.models import qwen2  # noqa: E402
from areal_tpu.models.qwen2 import (  # noqa: E402
    PADDING_SEGMENT,
    ModelConfig,
    forward,
    moe_mlp,
    param_shapes,
    prefill,
)

# the guide's catalog entry for OLMoE-1B-7B-0125-Instruct, every key
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "tie_word_embeddings": False, "vocab_size": 50304,
}
# the same family at a tiny width: 64 experts and 8 a token as published, so
# that near-ties at the eighth expert are as frequent as in the real router
TINY_HF = dict(CATALOG, vocab_size=256, hidden_size=64, intermediate_size=32,
               num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4)
TINY = ModelConfig.from_hf_config(TINY_HF, dtype="float32", param_dtype="float32",
                                  attn_impl="dense")
E, K = TINY.num_experts, TINY.num_experts_per_tok
SEED = 2**31 + 99
F32_TOL = 1e-4  # float32 program against float32 reference


@pytest.fixture(scope="module")
def params():
    return weights.seeded_params(TINY, SEED)


def _program_logprobs(params, cfg, ids, segments=None):
    T = len(ids)
    seg = jnp.zeros(T, jnp.int32) if segments is None else jnp.asarray(segments)
    pos = np.concatenate([np.arange(n) for n in np.bincount(np.asarray(seg))])
    logits = forward(params, jnp.asarray(ids), jnp.asarray(pos), seg, cfg)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return np.asarray(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, 256, n).astype(np.int32)


# -- registry ---------------------------------------------------------------


def test_from_hf_config_on_the_catalogs_keys_gives_the_published_shapes():
    cfg = ModelConfig.from_hf_config(CATALOG)
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size_) == (64, 8, 1024)
    assert not cfg.norm_topk_prob and cfg.shared_expert_intermediate_size == 0
    assert cfg.qk_norm and cfg.qk_norm_full and not cfg.qkv_bias and not cfg.attn_out_bias
    assert not cfg.tie_word_embeddings and cfg.rms_norm_eps == 1e-5 and cfg.rope_theta == 10000
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_) == (16, 16, 128)
    shapes = param_shapes(cfg)
    attn, mlp = shapes["layers"]["attn"], shapes["layers"]["mlp"]
    assert attn["q_norm"] == (16, 2048) and attn["k_norm"] == (16, 2048)  # [L, nH*hd]
    assert attn["k_kernel"] == (16, 2048, 16, 128) and "q_bias" not in attn
    assert mlp["router_kernel"] == (16, 2048, 64)
    assert mlp["gate_kernel"] == mlp["up_kernel"] == (16, 64, 2048, 1024)
    assert mlp["down_kernel"] == (16, 64, 1024, 2048)
    assert shapes["lm_head"]["kernel"] == (2048, 50304)
    n = sum(int(np.prod(s)) for s in jax.tree.leaves(shapes, is_leaf=lambda x: isinstance(x, tuple)))
    # layers, embedding + head, the final norm: 6.92B
    assert n == 16 * 419_569_664 + 206_045_184 + 2048 == 6_919_161_856


@pytest.mark.parametrize("hf", [
    dict(CATALOG, model_type="olmo2"),
    dict(CATALOG, model_type="deepseek_v3"),
    dict(CATALOG, clip_qkv=8.0),
    dict(CATALOG, attention_bias=True),
], ids=["olmo2", "deepseek_v3", "clip_qkv", "attention_bias"])
def test_what_the_registry_does_not_name_raises(hf):
    with pytest.raises(NotImplementedError):
        ModelConfig.from_hf_config(hf)


def test_options_of_the_dropping_dispatch_are_gone():
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    assert not fields & {"capacity_factor", "moe_group_size"}
    assert not hasattr(qwen2, "_moe_group_size")


# -- the model against the reference ------------------------------------------


@pytest.mark.parametrize("n,pad_to", [(17, 0), (48, 0), (48, 128)])
def test_forward_agrees_with_the_reference(params, n, pad_to):
    ids = _ids(n, n)
    ref = olmoe_ref.token_logprobs(params, TINY, ids, pad_to=pad_to)
    assert ref.shape == (n - 1,) and ref.std() > 0.5
    c = kind_rollout_moe.compare_with_reference("tiny", _program_logprobs(params, TINY, ids), ref)
    assert c["ok"] and c["max_abs"] < F32_TOL, c


@pytest.fixture(scope="module")
def both_grads(params):
    ids = _ids(3, 40)

    def loss(p):
        T = len(ids)
        logits = forward(p, jnp.asarray(ids), jnp.arange(T), jnp.zeros(T, jnp.int32), TINY)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])

    got = jax.value_and_grad(loss)(params)
    return got, olmoe_ref.loss_and_grads(params, TINY, ids)


LEAVES = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
    param_shapes(TINY), is_leaf=lambda x: isinstance(x, tuple))[0]]


def test_loss_agrees_with_the_reference(both_grads):
    (loss, _), (ref_loss, _) = both_grads
    assert abs(float(loss) - float(ref_loss)) < 1e-5 and float(ref_loss) > 1.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_agrees_with_the_reference(both_grads, leaf):
    (_, grads), (_, ref_grads) = both_grads
    got = dict((jax.tree_util.keystr(p), g) for p, g in
               jax.tree_util.tree_flatten_with_path(grads)[0])[leaf]
    ref = dict((jax.tree_util.keystr(p), g) for p, g in
               jax.tree_util.tree_flatten_with_path(ref_grads)[0])[leaf]
    scale = float(jnp.abs(ref).max())
    assert scale > 0, f"{leaf}: the reference's gradient is zero (the router's included)"
    assert float(jnp.abs(got - ref).max()) <= 1e-4 * scale + 1e-7, leaf


def test_the_reference_follows_transformers_modeling_olmoe(params, tmp_path):
    torch = pytest.importorskip("torch")
    from transformers import OlmoeConfig, OlmoeForCausalLM

    from areal_tpu.models.hf_io import save_hf_params

    out = save_hf_params(params, TINY, str(tmp_path / "ckpt"))
    hf_cfg = OlmoeConfig(**{k: v for k, v in TINY_HF.items() if k != "model_type"},
                         attn_implementation="eager")
    model = OlmoeForCausalLM(hf_cfg).eval().float()
    from safetensors.torch import load_file

    state = {}
    for f in os.listdir(out):
        if f.endswith(".safetensors"):
            state.update(load_file(os.path.join(out, f)))
    missing, unexpected = model.load_state_dict(state, strict=False)
    assert not unexpected and all("rotary" in m or "inv_freq" in m for m in missing), (
        missing, unexpected)
    ids = _ids(11, 33)
    with torch.no_grad():
        logits = model(torch.tensor(ids.astype(np.int64))[None]).logits[0].float()
        hf = torch.log_softmax(logits, -1)[torch.arange(32), torch.tensor(ids[1:].astype(np.int64))]
    ref = olmoe_ref.token_logprobs(params, TINY, ids)
    assert np.abs(ref - hf.numpy()).max() < 5e-4


# -- what the comparison catches ---------------------------------------------


def _per_head_norm(params):
    p = jax.tree.map(lambda x: x, params)
    hd = TINY.head_dim_
    for k in ("q_norm", "k_norm"):
        p["layers"]["attn"][k] = params["layers"]["attn"][k][:, :hd]
    return p, dataclasses.replace(TINY, qk_norm_full=False)


def _bf16_router_softmax():
    real = jax.nn.softmax

    def softmax(x, axis=-1, **kw):
        if x.ndim == 2 and x.shape[-1] == E:  # the router's [T, E] alone
            return real(x.astype(jnp.bfloat16), axis=axis).astype(jnp.float32)
        return real(x, axis=axis, **kw)

    return mock.patch.object(jax.nn, "softmax", softmax)


VARIANTS = ["dropped_pair", "renormalised_top_k", "per_head_qk_norm", "bf16_router_softmax"]


@pytest.mark.parametrize("what", VARIANTS)
def test_what_the_comparison_catches(params, what):
    """Each wrong variant of the mathematics against the published one. Four
    move the comparison far past the tolerances the chip run is held to. The
    fifth, a router softmax in bf16, does not (it flips near-ties at the
    eighth expert and rounds the gate weights to 8 bits: a quarter of what
    bf16 compute everywhere else moves, measured below), so float32 router
    arithmetic is held here, where the program and the reference agree to
    1e-5 and a bf16 softmax stands out by three orders of magnitude."""
    ids = _ids(5, 64)
    ref = olmoe_ref.token_logprobs(params, TINY, ids)
    if what == "dropped_pair":  # every token loses its eighth expert
        got = _program_logprobs(params, dataclasses.replace(TINY, num_experts_per_tok=K - 1), ids)
    elif what == "renormalised_top_k":
        got = _program_logprobs(params, dataclasses.replace(TINY, norm_topk_prob=True), ids)
    elif what == "per_head_qk_norm":
        got = _program_logprobs(*_per_head_norm(params), ids)
    else:
        with _bf16_router_softmax():
            got = _program_logprobs(params, TINY, ids)
    c = kind_rollout_moe.compare_with_reference(what, got, ref)
    assert c["max_abs"] > 100 * F32_TOL, c  # the float32 comparison catches all
    if what == "bf16_router_softmax":
        assert c["ok"] and c["mean_abs"] < MEAN_ABS_TOL / 4, c
    elif what == "dropped_pair":  # the least of each token's eight weights
        assert not c["ok"] and c["mean_abs"] > MEAN_ABS_TOL, c
    else:
        assert not c["ok"] and c["mean_abs"] > 3 * MEAN_ABS_TOL, c


def test_bf16_compute_passes(params):
    """As on the chip: bf16 weights and compute against the float32 reference
    reading the same bf16 weights."""
    ids = _ids(5, 64)
    bf16 = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    cfg = dataclasses.replace(TINY, dtype="bfloat16", param_dtype="bfloat16")
    ref = olmoe_ref.token_logprobs(bf16, cfg, ids)
    c = kind_rollout_moe.compare_with_reference("bf16", _program_logprobs(bf16, cfg, ids), ref)
    assert c["ok"], c


@pytest.mark.parametrize("kv,passes", [("float32", True), ("int8", False)])
def test_prefill_then_paged_decode_through_the_engine(params, kv, passes):
    """Through `JaxDecodeEngine` itself, as the cell checks it: a request deep
    enough to span pages and chunks, log-probabilities against the
    reference's full forward. The fifth wrong variant, an int8 pool, fails."""
    from areal_tpu.api.cli_args import JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    engine = JaxDecodeEngine(JaxDecodeConfig(
        context_length=512, max_running_requests=4, new_tokens_per_chunk=128, page_size=128,
        dtype="float32", kv_cache_dtype=kv))
    engine.set_model(params, TINY)
    engine.initialize()
    try:
        prompt = _ids(9, 100).tolist()
        resp = engine.generate(kind_rollout._request(prompt, 300, 1.0), 300.0)
        done = [{"resp": resp, "want": 300, "group": 0}]
        (c,) = kind_rollout_moe.check_decode(None, engine, done, 1, pad_to=512)
        m = engine.get_metrics()
    finally:
        engine.destroy()
    assert c["tokens"] == 300 and c["ok"] is passes, c
    if passes:
        assert c["max_abs"] < 10 * F32_TOL, c
        # one live slot: K pairs a layer a token step, no expert twice
        per_step = K * TINY.num_hidden_layers
        assert m["moe_pairs_total"] >= 300 * per_step and m["moe_pairs_total"] % per_step == 0
        assert m["moe_hot_expert_pairs_total"] * K == m["moe_pairs_total"]
    else:
        assert c["max_abs"] > 2 * MAX_ABS_TOL, c


# -- exact routing ------------------------------------------------------------


def _skewed(params):
    """Expert 3's router column scaled up, so that it takes a large share of
    the tokens, and made to follow hidden dimension 0 (see the test)."""
    p = jax.tree.map(lambda x: x, params)
    r = np.asarray(params["layers"]["mlp"]["router_kernel"]).copy()  # [L, H, E]
    r[:, :, 3] *= 6.0
    r[:, 0, 3] = 4.0
    p["layers"]["mlp"]["router_kernel"] = jnp.asarray(r)
    return p


def test_a_skewed_router_loses_nothing(params):
    p = _skewed(params)
    lp = jax.tree.map(lambda a: a[0], p["layers"]["mlp"])
    x = np.random.default_rng(0).normal(size=(96, TINY.hidden_size))
    x[:, 0] = 3.0  # every token leans towards expert 3
    _, _, load = moe_mlp(lp, jnp.asarray(x, jnp.float32), TINY, with_load=True)
    pairs, hot = load.tolist()
    assert pairs == 96 * K and hot >= 90  # the skew is real: 7.5 x the mean load
    ids = _ids(21, 64)
    c = kind_rollout_moe.compare_with_reference(
        "skewed", _program_logprobs(p, TINY, ids), olmoe_ref.token_logprobs(p, TINY, ids))
    assert c["ok"] and c["max_abs"] < F32_TOL, c


def test_a_tokens_result_does_not_depend_on_its_batch_mates(params):
    """What decode-against-trainer log-probabilities rest on: the same rows
    alone, among other rows, and in another order give the same outputs."""
    lp = jax.tree.map(lambda a: a[1], _skewed(params)["layers"]["mlp"])
    rng = np.random.default_rng(1)
    mine = jnp.asarray(rng.normal(size=(8, TINY.hidden_size)), jnp.float32)
    others = jnp.asarray(rng.normal(size=(120, TINY.hidden_size)), jnp.float32)
    alone, _ = moe_mlp(lp, mine, TINY)
    crowd, _ = moe_mlp(lp, jnp.concatenate([others[:60], mine, others[60:]]), TINY)
    np.testing.assert_allclose(np.asarray(crowd[60:68]), np.asarray(alone), rtol=0, atol=1e-6)
    # and through the whole model: a sequence packed with others against alone
    a, b = _ids(31, 24), _ids(32, 40)
    packed = _program_logprobs(params, TINY, np.r_[b, a], np.r_[np.zeros(40), np.ones(24)].astype(np.int32))
    np.testing.assert_allclose(packed[40:], _program_logprobs(params, TINY, a), atol=1e-5)


def test_pad_rows_and_dead_slots_route_nowhere_and_add_nothing(params):
    lp = jax.tree.map(lambda a: a[0], params["layers"]["mlp"])
    x = jnp.asarray(np.random.default_rng(2).normal(size=(32, TINY.hidden_size)), jnp.float32)
    valid = jnp.asarray(np.arange(32) % 3 != 0)
    y, _, load = moe_mlp(lp, x, TINY, valid=valid, with_load=True)
    assert load[0] == int(valid.sum()) * K  # dead rows are not counted
    assert float(jnp.abs(y[~valid]).max()) == 0.0
    alone, _ = moe_mlp(lp, x[valid], TINY)
    np.testing.assert_allclose(np.asarray(y[valid]), np.asarray(alone), rtol=0, atol=1e-6)
    # a garbage pad row cannot reach a live one, in the value or the gradient
    poisoned = x.at[0].set(jnp.nan)
    y2, _ = moe_mlp(lp, poisoned, TINY, valid=valid)
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y))
    g = jax.grad(lambda w: moe_mlp(dict(lp, gate_kernel=w), poisoned, TINY, valid=valid)[0].sum())(
        lp["gate_kernel"])
    assert bool(jnp.isfinite(g).all())
    # prefill buckets: the pad tail of a bucket changes nothing before it
    ids = _ids(41, 48)
    bucket = np.r_[ids, np.zeros(16, np.int32)]
    logits, _, _ = prefill(params, jnp.asarray(bucket), jnp.arange(64), TINY,
                           valid=jnp.arange(64) < 48)
    whole = forward(params, jnp.asarray(ids), jnp.arange(48), jnp.zeros(48, jnp.int32), TINY)
    np.testing.assert_allclose(np.asarray(logits[:48]), np.asarray(whole), atol=2e-4)
    seg = np.r_[np.zeros(48), np.full(16, PADDING_SEGMENT)].astype(np.int32)
    packed = forward(params, jnp.asarray(bucket), jnp.r_[jnp.arange(48), jnp.zeros(16, jnp.int32)],
                     jnp.asarray(seg), TINY)
    np.testing.assert_allclose(np.asarray(packed[:48]), np.asarray(whole), atol=2e-4)


# -- HF tensor names -----------------------------------------------------------


def test_hf_names_round_trip(params, tmp_path):
    from areal_tpu.models.hf_io import (
        flatten_params,
        hf_name_to_ours,
        load_hf_params,
        ours_name_to_hf,
        save_hf_params,
    )

    names = {ours_name_to_hf(path, "olmoe"): w.shape for path, w in flatten_params(params, TINY).items()}
    H, M = TINY.hidden_size, TINY.moe_intermediate_size_
    assert names["model.layers.0.self_attn.q_norm.weight"] == (H,)
    assert names["model.layers.3.self_attn.k_norm.weight"] == (H,)
    assert "model.layers.1.mlp.gate.weight" in names and "lm_head.weight" in names
    assert f"model.layers.2.mlp.experts.{E - 1}.down_proj.weight" in names
    assert all(hf_name_to_ours(n) is not None for n in names)
    assert not [n for n in names if "bias" in n or "shared_expert" in n]

    out = save_hf_params(params, TINY, str(tmp_path / "ckpt"))
    with open(os.path.join(out, "config.json"), "w") as f:
        json.dump(TINY_HF, f)
    cfg = ModelConfig.from_hf_config(out, dtype="float32", param_dtype="float32")
    loaded = load_hf_params(out, cfg, dtype="float32")
    assert loaded["layers"]["mlp"]["gate_kernel"].shape == (4, E, H, M)
    assert loaded["layers"]["mlp"]["router_kernel"].shape == (4, H, E)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 params, loaded)
