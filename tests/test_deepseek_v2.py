"""DeepSeek-V2 (`deepseek_v2`) through the normal path, at a tiny width on
the CPU, against the float32 reference
(`benchmark/reference/deepseek_v2_ref.py`, expanded form only): the registry
and each refusal by its message; `forward` at three lengths and on packed
segments; loss and every leaf's gradient; the absorbed decode form equal to
the expanded one; the YaRN table against the closed form, and a table without
`mscale` or without the rotary part failing; the router with forced near-ties
at the group and the expert boundary; the share test of the model-configs
guide's section 4; the HF names and the rotary lanes' permutation there and
back. The engine's side is `tests/test_deepseek_v2_engine.py`."""

import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import weights  # noqa: E402
from benchmark.reference import deepseek_v2_ref  # noqa: E402

from areal_tpu.models import qwen2  # noqa: E402
from areal_tpu.models.qwen2 import ModelConfig, forward, moe_mlp, rope_table  # noqa: E402

with open(os.path.join(REPO, "benchmark/configs/deepseek-v2.json")) as _f:
    CONFIG_FILE = json.load(_f)

YARN = dict(type="yarn", factor=40, original_max_position_embeddings=64, beta_fast=32,
            beta_slow=1, mscale=0.707, mscale_all_dim=0.707)
# the same family at a tiny width: a leading dense layer and three sparse
# ones, 16 experts in 4 groups of which 2 are kept, one group or all held
TINY_HF = dict(
    model_type="deepseek_v2", vocab_size=96, hidden_size=48, intermediate_size=80,
    moe_intermediate_size=24, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=12,
    n_routed_experts=16, n_shared_experts=2, num_experts_per_tok=3, n_group=4, topk_group=2,
    topk_method="group_limited_greedy", routed_scaling_factor=4.0, norm_topk_prob=False,
    scoring_func="softmax", first_k_dense_replace=1, moe_layer_freq=1, rms_norm_eps=1e-6,
    rope_theta=10000, max_position_embeddings=4096, tie_word_embeddings=False,
    hidden_act="silu", attention_bias=False, rope_scaling=YARN)
SEED = 2**31 + 38
F32_TOL = 1e-4  # float32 program against float32 reference


def tiny(held=16, first=0, hf=None, **over):
    hf = dict(hf or TINY_HF, n_routed_experts=held, num_experts_published=16, expert_first=first)
    return ModelConfig.from_hf_config(hf, dtype="float32", param_dtype="float32", **over)


def seeded(cfg):
    return weights.seeded_params(cfg, SEED)


FULL = tiny()
PART = tiny(held=4, first=8)


@pytest.fixture(scope="module")
def params():
    return seeded(FULL)


def held_slice(params, first, count):
    """The tree of a chip that holds experts [first, first + count)."""
    def cut(path, x):
        name = str(path[-1].key)
        if name in ("gate_kernel", "up_kernel", "down_kernel") and x.ndim == 3:
            return x[first:first + count]
        return x

    return jax.tree_util.tree_map_with_path(cut, params)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, 96, n).astype(np.int32)


def _program_logprobs(params, cfg, ids, segments=None, positions=None):
    T = len(ids)
    seg = jnp.zeros(T, jnp.int32) if segments is None else jnp.asarray(segments)
    pos = jnp.arange(T) if positions is None else jnp.asarray(positions)
    logits = forward(params, jnp.asarray(ids), pos, seg, cfg)
    return np.asarray(jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1))


# -- registry -------------------------------------------------------------------


def test_from_hf_config_on_the_configurations_file():
    mc = ModelConfig.from_hf_config(CONFIG_FILE)
    assert (mc.model_type, mc.kv_lora_rank, mc.q_lora_rank) == ("deepseek_v2", 512, 1536)
    assert (mc.qk_nope_head_dim, mc.qk_rope_head_dim, mc.v_head_dim, mc.head_dim_) == (
        128, 64, 128, 192)
    assert (mc.num_experts, mc.num_experts_published, mc.expert_first) == (20, 160, 0)
    assert (mc.moe_n_group, mc.moe_topk_group, mc.num_experts_per_tok) == (8, 3, 6)
    assert (mc.first_k_dense, mc.shared_expert_intermediate_size, mc.shared_expert_gated) == (
        1, 3072, False)
    assert (mc.norm_topk_prob, mc.routed_scaling_factor, mc.moe_scoring) == (False, 16.0, "softmax")
    assert mc.rope_scaling_ == ("yarn", 40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert mc.mixed and mc.latent and not mc.scan_layers and not mc.qkv_bias
    assert mc.cache_layers == {"full": (), "window": (), "state": (), "latent": (0, 1, 2, 3, 4)}
    assert (mc.latent_row, mc.latent_row_lanes, mc.rotary_dim) == (576, 640, 64)
    assert abs(mc.latent_softmax_scale - 0.11472) < 5e-6


@pytest.mark.parametrize("over,err", [
    (dict(moe_layer_freq=2), "moe_layer_freq=2"),
    (dict(scoring_func="sigmoid"), "scoring_func 'sigmoid'"),
    (dict(topk_method="noaux_tc"), "topk_method 'noaux_tc'"),
    (dict(n_group=3), "does not divide into n_group=3"),
    (dict(n_routed_experts=6, num_experts_published=16), "not whole routing groups of 4"),
    (dict(n_routed_experts=4, num_experts_published=16, expert_first=2), "not whole routing"),
    (dict(q_lora_rank=None), "without q_lora_rank"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(num_experts_per_tok=9), "exceeds the 8 experts of the kept groups"),
    (dict(rope_scaling=dict(type="dynamic", factor=2)), "rope_scaling type 'dynamic'"),
])
def test_what_from_hf_config_does_not_serve_raises(over, err):
    with pytest.raises(NotImplementedError, match=err):
        ModelConfig.from_hf_config(dict(TINY_HF, **over))


def test_greedy_routing_is_one_group():
    mc = ModelConfig.from_hf_config(dict(TINY_HF, topk_method="greedy"))
    assert (mc.moe_n_group, mc.moe_topk_group) == (1, 1)


@pytest.mark.parametrize("impl", ["flash", "ring"])
def test_flash_and_ring_refuse_by_message(impl):
    cfg = dataclasses.replace(FULL, attn_impl=impl)
    with pytest.raises(NotImplementedError, match="q/k 24 wide, v 12"):
        qwen2.resolve_attn_impl(cfg)


def test_a_latent_stack_does_not_stack():
    with pytest.raises(ValueError, match="no uniform per-layer pytree"):
        qwen2.param_shapes(dataclasses.replace(FULL, scan_layers=True))
    shapes = qwen2.param_shapes(FULL)
    assert set(shapes["layers_1"]["attn"]) == {
        "q_a_kernel", "q_a_norm", "q_b_kernel", "kv_a_kernel", "kv_a_norm", "kv_b_kernel",
        "o_kernel"}
    assert shapes["layers_0"]["mlp"]["gate_kernel"] == (48, 80)  # the dense layer
    assert shapes["layers_1"]["mlp"]["router_kernel"] == (48, 16)
    assert shapes["layers_1"]["mlp"]["shared_gate_kernel"] == (48, 48)
    assert "shared_router_kernel" not in shapes["layers_1"]["mlp"]
    axes = qwen2.param_logical_axes(FULL)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) == jax.tree.structure(
        shapes, is_leaf=lambda x: isinstance(x, tuple))


# -- forward, loss, gradients ----------------------------------------------------


@pytest.mark.parametrize("impl", ["dense", "chunked"])
@pytest.mark.parametrize("n", [40, 23, 7])
def test_forward_agrees_with_the_reference(params, impl, n):
    cfg = dataclasses.replace(FULL, attn_impl=impl)
    ids = _ids(n, n)
    got = _program_logprobs(params, cfg, ids)[np.arange(n - 1), ids[1:]]
    np.testing.assert_allclose(got, deepseek_v2_ref.token_logprobs(params, FULL, ids),
                               atol=F32_TOL)


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8), (8, 4)])
def test_forward_of_a_share_agrees_with_the_reference(params, held, first):
    cfg, p = tiny(held, first), held_slice(params, first, held)
    ids = _ids(5, 33)
    got = _program_logprobs(p, cfg, ids)[np.arange(32), ids[1:]]
    np.testing.assert_allclose(got, deepseek_v2_ref.token_logprobs(p, cfg, ids), atol=F32_TOL)


@pytest.mark.parametrize("impl", ["dense", "chunked"])
def test_packed_segments_agree_with_the_reference(params, impl):
    """Three sequences and a padding tail in one stream: each is its own
    causal forward at its own positions."""
    cfg = dataclasses.replace(FULL, attn_impl=impl)
    lens = (17, 9, 12)
    seqs = [_ids(50 + i, n) for i, n in enumerate(lens)]
    ids = np.concatenate(seqs + [np.zeros(6, np.int32)])
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lens)] + [np.full(6, -1)])
    pos = np.concatenate([np.arange(n) for n in lens] + [np.zeros(6, np.int64)])
    lp = _program_logprobs(params, cfg, ids, seg, pos)
    at = 0
    for s in seqs:
        n = len(s)
        got = lp[at:at + n][np.arange(n - 1), s[1:]]
        np.testing.assert_allclose(got, deepseek_v2_ref.token_logprobs(params, FULL, s),
                                   atol=F32_TOL)
        at += n


@pytest.fixture(scope="module")
def both_grads(params):
    ids = _ids(3, 30)

    def nll(p):
        logits = forward(p, jnp.asarray(ids), jnp.arange(30), jnp.zeros(30, jnp.int32), FULL)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(lp[jnp.arange(29), jnp.asarray(ids[1:])])

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(nll)(params)
    return got, deepseek_v2_ref.loss_and_grads(params, FULL, ids)


def test_loss_agrees_with_the_reference(both_grads):
    (loss, _), (ref_loss, _) = both_grads
    assert abs(float(loss) - float(ref_loss)) < F32_TOL


LEAVES = sorted(jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
    qwen2.param_shapes(FULL), is_leaf=lambda x: isinstance(x, tuple))[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_agrees_with_the_reference(both_grads, leaf):
    (_, grads), (_, ref_grads) = both_grads
    got = dict((jax.tree_util.keystr(p), g) for p, g in
               jax.tree_util.tree_flatten_with_path(grads)[0])[leaf]
    ref = dict((jax.tree_util.keystr(p), g) for p, g in
               jax.tree_util.tree_flatten_with_path(ref_grads)[0])[leaf]
    scale = max(float(jnp.abs(ref).max()), 1e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=F32_TOL * scale)
    assert float(jnp.abs(ref).max()) > 0 or "expert" in leaf or "kernel" in leaf


# -- the two forms of the attention ----------------------------------------------


def test_the_absorbed_form_equals_the_expanded_one(params):
    """One layer's attention on the same rows: expanded to heads over the
    stream, and a token at a time in the absorbed form over the cached rows."""
    cfg, a = FULL, params["layers_1"]["attn"]
    T, bsz, nb = 21, 4, 6
    x = jnp.asarray(np.random.default_rng(0).standard_normal((T, cfg.hidden_size)), jnp.float32)
    cos, sin = rope_table(jnp.arange(T), cfg.rotary_dim, cfg.rope_theta, cfg.rope_scaling_)
    want = qwen2.latent_attention(a, x, cos, sin, jnp.zeros(T, jnp.int32), None, cfg)
    pool = jnp.zeros((2, 1 + nb, bsz, cfg.latent_row_lanes))
    table = jnp.arange(1, 1 + nb, dtype=jnp.int32)[None]
    for t in range(T):
        valid = (jnp.arange(nb * bsz) <= t)[None]
        place = (table, table[:, t // bsz], jnp.array([t % bsz]), valid, None)
        out, pool = qwen2._latent_decode_attention(
            a, x[t:t + 1], cos[t:t + 1], sin[t:t + 1], pool, 1, place, cfg, "xla")
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want[t]), atol=F32_TOL)
    # what was cached is the normed latent and the turned rotary head, padded
    _, _, row = qwen2._latent_project(a, x, cos, sin, cfg)
    rows = np.asarray(pool)[1, 1:].reshape(nb * bsz, -1)[:T]
    np.testing.assert_allclose(rows[:, :cfg.latent_row], np.asarray(row), atol=1e-6)
    assert not rows[:, cfg.latent_row:].any() and not np.asarray(pool)[0].any()


# -- YaRN -------------------------------------------------------------------------


def test_the_yarn_table_against_the_closed_form():
    """At the published numbers: the ramp runs over frequencies 10-23 of 32,
    cos and sin carry 1, the softmax scale 0.11472."""
    mc = ModelConfig.from_hf_config(CONFIG_FILE)
    inv, (low, high) = deepseek_v2_ref.yarn_inv_freq(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    assert (low, high) == (10, 23)
    assert low == math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    assert high == math.ceil(64 * math.log(4096 / (2 * math.pi)) / (2 * math.log(10000)))
    theta = 10000.0 ** (-np.arange(32) / 32)
    r = np.clip((np.arange(32) - 10) / 13, 0, 1)
    np.testing.assert_allclose(np.asarray(inv), theta * (1 - r) + theta / 40 * r, rtol=1e-6)
    pos = jnp.asarray([0, 1, 517, 16383])
    cos, sin = rope_table(pos, mc.rotary_dim, mc.rope_theta, mc.rope_scaling_)
    ang = np.asarray(pos, np.float64)[:, None] * (theta * (1 - r) + theta / 40 * r)[None]
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-3)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4 and abs(192 ** -0.5 * m * m - mc.latent_softmax_scale) < 1e-9
    assert abs(qwen2.yarn_mscale(40, 0.707) / qwen2.yarn_mscale(40, 0.707) - 1) == 0


def test_yarn_serves_any_model_that_declares_it():
    hf = dict(model_type="qwen2", vocab_size=64, hidden_size=32, intermediate_size=48,
              num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
              rope_scaling=dict(type="yarn", factor=4.0, original_max_position_embeddings=32))
    mc = ModelConfig.from_hf_config(hf)
    assert mc.rope_scaling_ == ("yarn", 4.0, 32, 32.0, 1.0, 1.0, 0.0)
    cos, _ = rope_table(jnp.asarray([0, 3]), 16, mc.rope_theta, mc.rope_scaling_)
    # HF's attention factor without mscale keys: 0.1 ln(factor) + 1 on cos and sin
    assert abs(float(cos[0, 0]) - (0.1 * math.log(4.0) + 1)) < 1e-6


@pytest.mark.parametrize("what", ["no_mscale", "no_rotary", "no_yarn"])
def test_a_table_without_mscale_or_without_the_rotary_part_fails(params, what):
    hf = dict(TINY_HF)
    if what == "no_mscale":
        hf["rope_scaling"] = dict(YARN, mscale_all_dim=0.0, mscale=0.0)
    elif what == "no_yarn":
        hf["rope_scaling"] = None
    wrong = tiny(hf=hf)
    ids = _ids(8, 40)
    if what == "no_rotary":
        table = qwen2.rope_table
        try:
            qwen2.rope_table = lambda pos, *a, **k: tuple(
                t * 0 + (i == 0) for i, t in enumerate(table(pos, *a, **k)))
            got = _program_logprobs(params, wrong, ids)
        finally:
            qwen2.rope_table = table
    else:
        got = _program_logprobs(params, wrong, ids)
    ref = deepseek_v2_ref.token_logprobs(params, FULL, ids)
    assert np.abs(got[np.arange(39), ids[1:]] - ref).max() > 50 * F32_TOL


# -- the router --------------------------------------------------------------------


def _routed(layer_p, h, cfg):
    """The program's expert ids and weights a token, from `moe_mlp` with
    indicator experts: expert e returns e's one-hot, so y reads the weights."""
    E, H = cfg.num_experts_published_, h.shape[-1]
    assert E <= H


def test_the_router_against_the_reference_with_forced_near_ties(params):
    """Scores built so that the second and third group, and the third and
    fourth expert, lie a few float32 ulps apart: the program's choice is the
    reference's, ties to the lower index in both."""
    cfg = FULL
    rng = np.random.default_rng(5)
    T, H, E = 64, cfg.hidden_size, 16
    m = dict(params["layers_1"]["mlp"])
    h = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    logits = rng.standard_normal((T, E)).astype(np.float32)
    # group boundary: the best of group 2 equals (or all but) the best of group 1
    for t in range(T):
        g = np.argsort(-logits[t].reshape(4, 4).max(-1))
        a, b = g[1] * 4 + logits[t, g[1] * 4:g[1] * 4 + 4].argmax(), \
            g[2] * 4 + logits[t, g[2] * 4:g[2] * 4 + 4].argmax()
        logits[t, b] = np.nextafter(logits[t, a], -np.inf, dtype=np.float32) if t % 2 else logits[t, a]
    # a router that returns these logits for these rows: least squares is exact (T > H? no:
    # use an identity embedding of the rows instead)
    h = jnp.eye(T, H, dtype=jnp.float32) * 3.0
    router = np.zeros((H, E), np.float32)
    router[:min(T, H)] = logits[:min(T, H)] / 3.0
    m["router_kernel"] = jnp.asarray(router)
    n = min(T, H)
    s = jax.nn.softmax(h[:n] @ m["router_kernel"], axis=-1)
    idx, ranked = deepseek_v2_ref.route(s, 3, 4, 2)
    # the program, through moe_mlp: experts that return a constant mark their weight
    y_ref, _ = deepseek_v2_ref._moe(
        m, h[:n], dict(deepseek_v2_ref.layer_statics(cfg, 1)))
    y, _ = moe_mlp(m, h[:n], cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=F32_TOL)
    # the kept groups hold every chosen expert, three of them, weights s * 4
    groups = np.asarray(idx[:, :3]) // 4
    assert all(len(set(g)) <= 2 for g in groups)
    # expert boundary: force the third and fourth score of the kept groups together
    s2 = np.asarray(s).copy()
    for t in range(n):
        s2[t, idx[t, 3]] = s2[t, idx[t, 2]]
    idx2, _ = deepseek_v2_ref.route(jnp.asarray(s2), 3, 4, 2)
    assert (np.asarray(idx2[:, 2]) == np.minimum(np.asarray(idx[:, 2]), np.asarray(idx[:, 3]))).all()


def test_group_limited_choice_differs_from_a_plain_top_k(params):
    cfg = FULL
    plain = dataclasses.replace(cfg, moe_n_group=1, moe_topk_group=1)
    h = jnp.asarray(np.random.default_rng(2).standard_normal((200, cfg.hidden_size)), jnp.float32)
    m = params["layers_2"]["mlp"]
    y, _, load = moe_mlp(m, h, cfg, with_load=True)
    y_plain, _ = moe_mlp(m, h, plain)
    assert float(jnp.abs(y - y_plain).max()) > 1e-3
    # pairs, hottest, tokens whose groups are here (all are), held experts touched (all 16)
    assert load.shape == (4,) and int(load[0]) == 600 and int(load[2]) == 200
    assert int(load[3]) == 16


def test_the_load_vector_counts_tokens_whose_groups_are_here(params):
    p = held_slice(params, 8, 4)
    h = jnp.asarray(np.random.default_rng(2).standard_normal((200, 48)), jnp.float32)
    valid = jnp.arange(200) < 150
    _, _, load = moe_mlp(p["layers_2"]["mlp"], h, PART, valid=valid, with_load=True)
    here, hot, absent, tokens, touched = (int(x) for x in load)
    assert here + absent == 150 * 3 and 0 < here < absent and hot <= here
    s = jax.nn.softmax(h[:150] @ p["layers_2"]["mlp"]["router_kernel"], axis=-1)
    kept = np.argsort(-np.asarray(s).reshape(150, 4, 4).max(-1), axis=1)[:, :2]
    assert tokens == int((kept == 2).any(axis=1).sum())  # experts 8-11 are group 2
    # the held experts with a pair: all four over 150 tokens, none with no valid token
    assert touched == 4
    _, _, none = moe_mlp(p["layers_2"]["mlp"], h, PART, valid=jnp.zeros(200, bool), with_load=True)
    assert [int(x) for x in none] == [0, 0, 0, 0, 0]
    _, _, one = moe_mlp(p["layers_2"]["mlp"], h, PART, valid=jnp.arange(200) == 0, with_load=True)
    assert int(one[4]) == int(one[0]) <= 3  # one token's pairs land on distinct experts


# -- the share test ------------------------------------------------------------------


def test_the_parts_all_groups_give_add_up_to_the_uncut_layer(params):
    """Section 4 of the model-configs guide: the 4 groups' partial results,
    with the shared experts counted once, add up to what the uncut layer gives."""
    m = params["layers_2"]["mlp"]
    h = jnp.asarray(np.random.default_rng(7).standard_normal((50, 48)), jnp.float32)
    whole, _ = moe_mlp(m, h, FULL)
    ref_whole, _ = deepseek_v2_ref._moe(m, h, dict(deepseek_v2_ref.layer_statics(FULL, 2)))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref_whole), atol=F32_TOL)
    act = jax.nn.silu(h @ m["shared_gate_kernel"]) * (h @ m["shared_up_kernel"])
    shared = act @ m["shared_down_kernel"]
    total = shared
    for g in range(4):
        cfg = tiny(held=4, first=4 * g)
        part, _ = moe_mlp(held_slice({"m": m}, 4 * g, 4)["m"], h, cfg)
        ref_part, _ = deepseek_v2_ref._moe(
            held_slice({"m": m}, 4 * g, 4)["m"], h,
            dict(deepseek_v2_ref.layer_statics(cfg, 2)))
        np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part), atol=F32_TOL)
        total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=F32_TOL)


# -- HF names ---------------------------------------------------------------------------


def test_hf_names_and_the_rotary_lanes_round_trip(params, tmp_path):
    pytest.importorskip("safetensors")
    from areal_tpu.models.hf_io import (
        _convert_tensor,
        _unconvert_tensor,
        flatten_params,
        hf_name_to_ours,
        load_hf_params,
        ours_name_to_hf,
        save_hf_params,
    )

    p = held_slice(params, 8, 4)
    names = {ours_name_to_hf(path, "deepseek_v2"): w.shape
             for path, w in flatten_params(p, PART).items()}
    assert names["model.layers.0.mlp.gate_proj.weight"] == (48, 80)  # the dense layer, 2-D
    assert names["model.layers.1.self_attn.q_a_proj.weight"] == (48, 24)
    assert names["model.layers.1.self_attn.q_a_layernorm.weight"] == (24,)
    assert names["model.layers.1.self_attn.q_b_proj.weight"] == (24, 96)
    assert names["model.layers.1.self_attn.kv_a_proj_with_mqa.weight"] == (48, 40)
    assert names["model.layers.1.self_attn.kv_a_layernorm.weight"] == (32,)
    assert names["model.layers.1.self_attn.kv_b_proj.weight"] == (32, 112)
    assert names["model.layers.1.self_attn.o_proj.weight"] == (4, 12, 48)
    assert names["model.layers.1.mlp.gate.weight"] == (48, 16)
    assert names["model.layers.1.mlp.shared_experts.down_proj.weight"] == (48, 48)
    assert "model.layers.2.mlp.experts.8.up_proj.weight" in names
    assert "model.layers.2.mlp.experts.0.up_proj.weight" not in names
    assert all(hf_name_to_ours(n) is not None for n in names)

    # a checkpoint's rotary lanes are interleaved pairs: lane 2i and 2i + 1 of
    # the checkpoint are lane i and i + rope/2 of the tree
    path = ("layers_1", "attn", "kv_a_kernel")
    ckpt = np.arange(40 * 48, dtype=np.float32).reshape(40, 48)  # torch [out, in]
    ours = _convert_tensor(path, ckpt, PART)
    assert ours.shape == (48, 40)
    np.testing.assert_array_equal(ours[:, :32], ckpt.T[:, :32])
    np.testing.assert_array_equal(ours[:, 32:36], ckpt.T[:, 32:40:2])
    np.testing.assert_array_equal(ours[:, 36:], ckpt.T[:, 33:40:2])
    np.testing.assert_array_equal(_unconvert_tensor(path, ours, PART), ckpt)
    path = ("layers_1", "attn", "q_b_kernel")
    ckpt = np.arange(96 * 24, dtype=np.float32).reshape(96, 24)
    ours = _convert_tensor(path, ckpt, PART).reshape(24, 4, 24)
    np.testing.assert_array_equal(ours[:, 1, :16], ckpt.T[:, 24:40])
    np.testing.assert_array_equal(ours[:, 1, 16:20], ckpt.T[:, 40:48:2])
    np.testing.assert_array_equal(_unconvert_tensor(path, ours.reshape(24, 96), PART), ckpt)

    out = save_hf_params(p, PART, str(tmp_path / "ckpt"))
    with open(os.path.join(out, "config.json"), "w") as f:
        json.dump(dict(TINY_HF, n_routed_experts=4, num_experts_published=16, expert_first=8), f)
    cfg = ModelConfig.from_hf_config(out, dtype="float32", param_dtype="float32")
    loaded = load_hf_params(out, cfg, dtype="float32")
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 p, loaded)


def test_interleaved_rotation_equals_rotate_half_after_the_permutation():
    """The checkpoint's convention (rotate lanes 2i, 2i + 1 together) on
    interleaved lanes gives the same scores as `rotate_half` on permuted ones."""
    rng = np.random.default_rng(0)
    rope, T = 8, 5
    q, k = rng.standard_normal((T, rope)), rng.standard_normal((T, rope))
    ang = np.arange(T)[:, None] * (10000.0 ** (-np.arange(rope // 2) / (rope // 2)))[None]

    def interleaved(x):
        out = np.empty_like(x)
        out[:, 0::2] = x[:, 0::2] * np.cos(ang) - x[:, 1::2] * np.sin(ang)
        out[:, 1::2] = x[:, 1::2] * np.cos(ang) + x[:, 0::2] * np.sin(ang)
        return out

    perm = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])

    def half(x):
        x1, x2 = x[:, :rope // 2], x[:, rope // 2:]
        return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                               x2 * np.cos(ang) + x1 * np.sin(ang)], axis=1)

    np.testing.assert_allclose(interleaved(q) @ interleaved(k).T,
                               half(q[:, perm]) @ half(k[:, perm]).T, atol=1e-12)
