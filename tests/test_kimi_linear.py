"""Kimi-Linear (`kimi_linear`) through the normal path, at a tiny width on the
CPU, against the float32 reference (`benchmark/reference/kimi_linear_ref.py`:
the delta rule as its token recurrence, the latent attention expanded): the
registry and each refusal by its message; `forward` at three lengths, for a
share of the experts and on packed segments; loss and every leaf's gradient;
the chunk scan under a vector decay against the recurrence, at decays a plain
`exp(-G)` split cannot hold; the step op in both decay layouts, interpreted
kernel against `jax.numpy`; the absorbed decode form equal to the expanded one
with no rotation; the router with forced near-ties; the share test of the
model-configs guide's section 4; the HF names there and back; the
configuration's bytes at the published widths. The engine's side is
`tests/test_kimi_linear_engine.py`."""

import json
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
from benchmark.reference import kimi_linear_ref  # noqa: E402

from areal_tpu.models import qwen2  # noqa: E402
from areal_tpu.models.qwen2 import (  # noqa: E402
    ModelConfig,
    _kda_chunk_scan,
    forward,
    moe_mlp,
    prefill,
)
from areal_tpu.ops.gdn_step import gdn_step, gdn_step_reference  # noqa: E402

with open(os.path.join(REPO, "benchmark/configs/kimi-linear-48b-a3b.json")) as _f:
    CONFIG_FILE = json.load(_f)

# the same family at a tiny width: the published period (KDA, KDA, KDA, MLA),
# the first layer dense, 16 sigmoid-routed experts of which all or 4 are held
TINY_HF = dict(
    model_type="kimi_linear", vocab_size=96, hidden_size=48, intermediate_size=80,
    moe_intermediate_size=24, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
    head_dim=12, kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, mla_use_nope=True,
    linear_attn_config=dict(full_attn_layers=[4, 8], kda_layers=[1, 2, 3, 5, 6, 7], head_dim=16,
                            num_heads=4, short_conv_kernel_size=4),
    num_experts=16, num_experts_per_token=3, num_shared_experts=1, moe_renormalize=True,
    moe_router_activation_func="sigmoid", routed_scaling_factor=2.446, num_expert_group=1,
    topk_group=1, use_grouped_topk=True, first_k_dense_replace=1, moe_layer_freq=1,
    num_nextn_predict_layers=0, rms_norm_eps=1e-5, rope_theta=10000, rope_scaling=None,
    tie_word_embeddings=False, hidden_act="silu")
SEED = 2**31 + 45
F32_TOL = 1e-4  # float32 program against float32 reference
LOGP_TOL = 5e-4  # the same on log-probabilities of sampled tokens


def tiny(held=16, first=0, hf=None, **over):
    hf = dict(hf or TINY_HF, num_experts=held, num_experts_published=16, expert_first=first)
    return ModelConfig.from_hf_config(hf, dtype="float32", param_dtype="float32", **over)


def seeded(cfg):
    """`weights.py`'s draw with a router bias that matters: it starts at zero
    and would choose nothing."""
    p = weights.seeded_params(cfg, SEED)
    for i in range(cfg.num_hidden_layers):
        m = p[f"layers_{i}"]["mlp"]
        if "router_bias" in m:
            m["router_bias"] = 0.1 * jax.random.normal(
                jax.random.PRNGKey(i), m["router_bias"].shape, jnp.float32)
    return p


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


def _forward_logits(params, cfg, ids, segments=None, positions=None):
    T = len(ids)
    seg = jnp.zeros(T, jnp.int32) if segments is None else jnp.asarray(segments)
    pos = jnp.arange(T) if positions is None else jnp.asarray(positions)
    with jax.default_matmul_precision("highest"):
        return np.asarray(forward(params, jnp.asarray(ids), pos, seg, cfg))


# -- registry -------------------------------------------------------------------


def test_from_hf_config_on_the_configurations_file():
    mc = ModelConfig.from_hf_config(CONFIG_FILE)
    assert (mc.model_type, mc.kv_lora_rank, mc.q_lora_rank) == ("kimi_linear", 512, 0)
    assert (mc.qk_nope_head_dim, mc.qk_rope_head_dim, mc.v_head_dim, mc.head_dim_) == (
        128, 64, 128, 192)
    assert mc.layer_types == ("linear_attention",) * 3 + ("full_attention",) + (
        "linear_attention",) * 3 + ("full_attention",)
    assert mc.cache_layers == {"full": (), "window": (), "state": (0, 1, 2, 4, 5, 6),
                               "latent": (3, 7)}
    assert (mc.linear_num_key_heads, mc.linear_num_value_heads, mc.linear_key_head_dim,
            mc.linear_value_head_dim, mc.linear_conv_kernel_dim, mc.linear_conv_channels) == (
        32, 32, 128, 128, 4, 12288)
    assert mc.linear_decay_lanes and mc.pos_embed == "none"
    assert (mc.num_experts, mc.num_experts_published, mc.expert_first) == (32, 256, 0)
    assert (mc.num_experts_per_tok, mc.moe_n_group, mc.moe_grouped) == (8, 1, True)
    assert (mc.first_k_dense, mc.shared_expert_intermediate_size, mc.shared_expert_gated) == (
        1, 1024, False)
    assert (mc.norm_topk_prob, mc.routed_scaling_factor, mc.moe_scoring, mc.moe_router_bias) == (
        True, 2.446, "sigmoid", True)
    assert mc.mixed and mc.latent and not mc.scan_layers and not mc.qkv_bias
    assert (mc.latent_row, mc.latent_row_lanes) == (576, 640)
    assert abs(mc.latent_softmax_scale - 192 ** -0.5) < 1e-9 and mc.rms_norm_eps == 1e-5


@pytest.mark.parametrize("over,err", [
    (dict(mla_use_nope=False), "mla_use_nope false"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers > 0"),
    (dict(rope_scaling=dict(type="yarn", factor=4)), "takes no positional encoding to scale"),
    (dict(q_lora_rank=24), "q_lora_rank=24"),
    (dict(moe_layer_freq=2), "moe_layer_freq=2"),
    (dict(num_expert_group=4, topk_group=2), "num_expert_group / topk_group != 1"),
    (dict(moe_router_activation_func="tanh"), "moe_router_activation_func 'tanh'"),
    (dict(linear_attn_config=dict(TINY_HF["linear_attn_config"], kda_layers=[1, 2])),
     "need each of 1..4 in exactly one"),
    (dict(linear_attn_config=dict(TINY_HF["linear_attn_config"], num_heads=2)),
     "num_heads=2"),
])
def test_what_from_hf_config_does_not_serve_raises(over, err):
    with pytest.raises(NotImplementedError, match=err):
        ModelConfig.from_hf_config(dict(TINY_HF, **over))


def test_experts_held_outside_the_published_range_raise():
    with pytest.raises(ValueError, match=r"holds experts \[14, 18\) of 16"):
        tiny(held=4, first=14)


def test_a_stack_of_two_mixers_does_not_stack():
    with pytest.raises(ValueError, match="no uniform"):
        qwen2.param_shapes(tiny(scan_layers=True))
    shapes = qwen2.param_shapes(FULL)
    assert set(shapes["layers_0"]["attn"]) == {
        "q_kernel", "k_kernel", "v_kernel", "q_conv_kernel", "k_conv_kernel", "v_conv_kernel",
        "f_a_kernel", "f_b_kernel", "A_log", "dt_bias", "b_kernel", "g_a_kernel", "g_b_kernel",
        "o_norm", "o_kernel"}
    # a full-rank query: no bottleneck, no query norm
    assert set(shapes["layers_3"]["attn"]) == {
        "q_kernel", "kv_a_kernel", "kv_a_norm", "kv_b_kernel", "o_kernel"}
    assert shapes["layers_3"]["attn"]["q_kernel"] == (48, 4, 24)
    assert "router_kernel" not in shapes["layers_0"]["mlp"]  # the leading dense layer
    assert shapes["layers_1"]["mlp"]["router_bias"] == (16,)


# -- forward --------------------------------------------------------------------


@pytest.mark.parametrize("n", [150, 64, 37])  # chunks of 64: two and a part, one, a part
def test_forward_agrees_with_the_reference(params, n):
    ids = _ids(n, n)
    np.testing.assert_allclose(_forward_logits(params, FULL, ids),
                               np.asarray(kimi_linear_ref.logits(params, FULL, ids)),
                               atol=F32_TOL)


def test_forward_of_a_share_agrees_with_the_reference(params):
    p, ids = held_slice(params, 8, 4), _ids(1, 90)
    got = _forward_logits(p, PART, ids)
    np.testing.assert_allclose(got, np.asarray(kimi_linear_ref.logits(p, PART, ids)),
                               atol=F32_TOL)
    # and it is another function than the whole model's
    assert np.abs(got - _forward_logits(params, FULL, ids)).max() > 1e-2


def test_packed_segments_agree_with_the_reference(params):
    """Three sequences and a padding tail in one stream: the state, the
    convolutions and the latent attention all start anew at a boundary."""
    lens = [70, 33, 64]
    ids = [_ids(20 + i, n) for i, n in enumerate(lens)]
    pad = 25
    stream = np.concatenate(ids + [np.zeros(pad, np.int32)])
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lens)] + [np.full(pad, -1)])
    pos = np.concatenate([np.arange(n) for n in lens] + [np.zeros(pad, int)])
    got = _forward_logits(params, FULL, stream, seg.astype(np.int32), pos)
    at = 0
    for one in ids:
        n = len(one)
        np.testing.assert_allclose(got[at:at + n],
                                   np.asarray(kimi_linear_ref.logits(params, FULL, one)),
                                   atol=F32_TOL)
        at += n


@pytest.fixture(scope="module")
def both_grads(params):
    ids = _ids(3, 80)

    def nll(p):
        logits = forward(p, jnp.asarray(ids), jnp.arange(80), jnp.zeros(80, jnp.int32), FULL)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(lp[jnp.arange(79), jnp.asarray(ids[1:])])

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(nll)(params)
    return got, kimi_linear_ref.loss_and_grads(params, FULL, ids)


def test_loss_agrees_with_the_reference(both_grads):
    (loss, _), (ref_loss, _) = both_grads
    assert abs(float(loss) - float(ref_loss)) < F32_TOL


LEAVES = sorted(jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
    qwen2.param_shapes(FULL), is_leaf=lambda x: isinstance(x, tuple))[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_agrees_with_the_reference(both_grads, leaf):
    """`jax.grad` through the chunk scan (its factored decays, its triangular
    solve) and the expanded attention against `jax.grad` through the token
    recurrence, relative to the leaf's largest gradient."""
    (_, grads), (_, ref_grads) = both_grads
    got = dict((jax.tree_util.keystr(p), g) for p, g in
               jax.tree_util.tree_flatten_with_path(grads)[0])[leaf]
    ref = dict((jax.tree_util.keystr(p), g) for p, g in
               jax.tree_util.tree_flatten_with_path(ref_grads)[0])[leaf]
    assert np.isfinite(np.asarray(got)).all()
    scale = max(float(jnp.abs(ref).max()), 1e-3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=F32_TOL * scale)


# -- the two forms of the delta rule under a vector decay --------------------------


def _rule_inputs(T, H=3, dk=8, dv=8, seed=1, a_max=0.5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (T, H, dk))
    k = jax.random.normal(ks[1], (T, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, H, dv))
    g = -a_max * jax.random.uniform(ks[3], (T, H, dk))
    beta = jax.random.uniform(ks[4], (T, H))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta, seg):
    def one(S, x):
        q_t, k_t, v_t, g_t, b_t, new, real = x
        S = jnp.where(new, 0.0, S)
        o, S2 = gdn_step_reference(S, q_t, k_t, v_t, g_t, b_t)
        return jnp.where(real, S2, S), o

    seg = np.asarray(seg)
    real = seg != -1
    last = np.maximum.accumulate(np.where(real, np.arange(len(seg)), -1))
    owner = np.where(last >= 0, seg[np.maximum(last, 0)], seg)
    new = np.r_[False, owner[1:] != owner[:-1]] & real
    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]))
    S, o = jax.lax.scan(one, S0, (q, k, v, g, beta, jnp.asarray(new), jnp.asarray(real)))
    return o, S


@pytest.mark.parametrize("name,seg", [
    ("one sequence, 150 = 2 x 64 + 22", np.zeros(150, int)),
    ("one sequence shorter than a sub-block", np.zeros(11, int)),
    ("exactly a chunk", np.zeros(64, int)),
    ("two sequences, a boundary inside a sub-block", np.r_[np.zeros(50, int), np.ones(100, int)]),
    ("a boundary on a chunk's edge, then padding",
     np.r_[np.zeros(64, int), np.ones(30, int), 2 * np.ones(40, int), -np.ones(16, int)]),
    ("a bucket's padding over whole chunks", np.r_[np.zeros(20, int), -np.ones(130, int)]),
])
def test_the_chunk_scan_is_the_recurrence(name, seg):
    q, k, v, g, beta = _rule_inputs(len(seg))
    real = jnp.asarray(seg != -1)
    g, beta = jnp.where(real[:, None, None], g, 0), jnp.where(real[:, None], beta, 0)
    with jax.default_matmul_precision("highest"):
        o, S = _kda_chunk_scan(q, k, v, g, beta, jnp.asarray(seg, jnp.int32))
        want_o, want_S = _recurrence(q, k, v, g, beta, seg)
    np.testing.assert_allclose(np.asarray(o)[seg != -1], np.asarray(want_o)[seg != -1], atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S), atol=1e-5)


def test_the_chunk_scan_holds_at_the_fastest_published_decay():
    """`A_log` = log 16 and a softplus of up to 1.3: a step's log decay
    reaches -20, so `exp(-G)` passes float32's largest number inside one
    chunk of 64. Over 4,096 tokens the scan stays finite and within 1e-4 of
    the recurrence; a plain `q exp(G)`, `k exp(-G)` split does not."""
    T = 4096
    q, k, v, g, beta = _rule_inputs(T, H=2, dk=16, dv=16, seed=3, a_max=1.0)
    g = g * 20.8  # -16 * softplus(x) for x up to 0.8
    assert float(g.min()) < -20
    seg = jnp.zeros(T, jnp.int32)
    with jax.default_matmul_precision("highest"):
        o, S = jax.jit(_kda_chunk_scan)(q, k, v, g, beta, seg)
        want_o, want_S = _recurrence(q, k, v, g, beta, np.zeros(T, int))
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=1e-4)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S), atol=1e-4)
    # the plain split, one chunk: exp(-G) overflows and the pairs are not numbers
    G = jnp.cumsum(g[:64], axis=0)
    plain = jnp.einsum("ihk,jhk->hij", q[:64] * jnp.exp(G), k[:64] * jnp.exp(-G))
    assert not np.isfinite(np.asarray(plain)).all()


@pytest.mark.parametrize("lanes", [True, False])
@pytest.mark.parametrize("active", [None, [True, False, True, True, False]])
def test_the_step_kernel_is_its_arithmetic_in_both_decay_layouts(active, lanes):
    """`gdn_step` through Pallas (interpreted here) against `jax.numpy`, the
    decay a vector over the key lanes (a column block) or one number a head
    (a row): the live slots' rows of the one layer move, the others and the
    null row stay."""
    n, R, Hv, dk, dv = 2, 5, 8, 16, 16
    S = jax.random.normal(jax.random.PRNGKey(0), (n, 1 + R, Hv, dk, dv)).at[:, 0].set(0)
    q, k, v, g, beta = _rule_inputs(R, Hv, dk, dv, seed=2)
    if not lanes:
        g = g[..., 0]
    act = None if active is None else jnp.asarray(active)
    o_x, S_x = gdn_step(S, q, k, v, g, beta, 1, act, impl="xla")
    o_p, S_p = gdn_step(S, q, k, v, g, beta, 1, act, impl="pallas", interpret=True)
    live = np.ones(R, bool) if active is None else np.asarray(active)
    np.testing.assert_allclose(np.asarray(S_p), np.asarray(S_x), atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_p)[live], np.asarray(o_x)[live], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(S_p[0]), np.asarray(S[0]))  # the other layer
    np.testing.assert_array_equal(np.asarray(S_p[1, 0]), 0)  # the null row
    np.testing.assert_array_equal(np.asarray(S_p[1, 1:][~live]), np.asarray(S[1, 1:][~live]))
    # against the reference's own step, a slot at a time
    for r in np.flatnonzero(live):
        want_S, want_o = kimi_linear_ref.delta_rule_step(
            S[1, 1 + r], (q[r], k[r], v[r], g[r], beta[r]))
        np.testing.assert_allclose(np.asarray(S_x[1, 1 + r]), np.asarray(want_S), atol=1e-5)
        np.testing.assert_allclose(np.asarray(o_x[r]), np.asarray(want_o), atol=1e-5)
    if lanes:  # a vector decay is no scalar decay: the lanes differ
        _, S_s = gdn_step(S, q, k, v, g[..., 0], beta, 1, act, impl="xla")
        assert np.abs(np.asarray(S_s) - np.asarray(S_x)).max() > 1e-2


@pytest.mark.parametrize("real,bucket", [(100, 128), (100, 256), (64, 128), (3, 64), (1, 64)])
def test_padding_does_not_enter_the_state(params, real, bucket):
    """A prefill hands over the state at the prompt's last REAL token, the
    last three real pre-convolution rows and the latent layers' rows alone,
    whatever its bucket."""
    ids = _ids(6, real)
    _, want_rows, _, want = jax.jit(lambda p, i: prefill(p, i, jnp.arange(real), FULL))(
        params, jnp.asarray(ids))
    padded = np.zeros(bucket, np.int32)
    padded[:real] = ids
    _, rows, vs, got = jax.jit(lambda p, i: prefill(
        p, i, jnp.arange(bucket), FULL, valid=jnp.arange(bucket) < real))(
        params, jnp.asarray(padded))
    assert rows.shape == (1, bucket, 1, FULL.latent_row_lanes) and vs.shape[-1] == 0
    assert got["S"].shape == (3, 4, 16, 16) and got["conv"].shape == (3, 3, 3 * 64)
    np.testing.assert_allclose(np.asarray(rows[:, :real]), np.asarray(want_rows), atol=1e-5)
    for name in ("S", "conv"):
        np.testing.assert_allclose(np.asarray(got[name]), np.asarray(want[name]), atol=1e-5)


# -- the two forms of the attention ----------------------------------------------


def test_the_absorbed_form_equals_the_expanded_one_with_no_rotation(params):
    """The latent layer's attention on the same rows: expanded to heads over
    the stream, and a token at a time in the absorbed form over the cached
    rows, neither with a table: q_pe and k_pe go as projected."""
    cfg, a = FULL, params["layers_3"]["attn"]
    T, bsz, nb = 21, 4, 6
    x = jnp.asarray(np.random.default_rng(0).standard_normal((T, cfg.hidden_size)), jnp.float32)
    want = qwen2.latent_attention(a, x, None, None, jnp.zeros(T, jnp.int32), None, cfg)
    pool = jnp.zeros((2, 1 + nb, bsz, cfg.latent_row_lanes))
    table = jnp.arange(1, 1 + nb, dtype=jnp.int32)[None]
    for t in range(T):
        valid = (jnp.arange(nb * bsz) <= t)[None]
        place = (table, table[:, t // bsz], jnp.array([t % bsz]), valid, None)
        out, pool = qwen2._latent_decode_attention(
            a, x[t:t + 1], None, None, pool, 1, place, cfg, "xla")
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want[t]), atol=F32_TOL)
    # what was cached is the normed latent and k_pe as projected, padded
    kv = x @ a["kv_a_kernel"]
    rows = np.asarray(pool)[1, 1:].reshape(nb * bsz, -1)[:T]
    np.testing.assert_allclose(rows[:, 32:40], np.asarray(kv[:, 32:]), atol=1e-6)
    assert not rows[:, cfg.latent_row:].any() and not np.asarray(pool)[0].any()
    # and the reference's expanded attention agrees with both
    st = dict(kimi_linear_ref.layer_statics(cfg, 3))
    with jax.default_matmul_precision("highest"):
        ref = kimi_linear_ref._attention(a, x, st)
    np.testing.assert_allclose(np.asarray(want), np.asarray(ref), atol=F32_TOL)


# -- the router ---------------------------------------------------------------------


def test_the_router_against_the_reference_with_forced_near_ties(params):
    """Scores built so that the third and fourth `s + b` lie a float32 ulp
    apart or are equal: the program's choice is the reference's (ties to the
    lower index in both), the bias enters the choice and never the weight,
    and the weights are renormalised over the chosen, times 2.446."""
    cfg = FULL
    rng = np.random.default_rng(5)
    H, E = cfg.hidden_size, 16
    n = H
    m = dict(params["layers_1"]["mlp"])
    m["router_bias"] = jnp.zeros(E, jnp.float32)
    logits = rng.standard_normal((n, E)).astype(np.float32)
    for t in range(n):
        order = np.argsort(-logits[t])
        a, b = order[2], order[3]
        logits[t, b] = (np.nextafter(logits[t, a], -np.inf, dtype=np.float32)
                        if t % 2 else logits[t, a])
    h = jnp.eye(n, H, dtype=jnp.float32) * 3.0
    m["router_kernel"] = jnp.asarray(logits / 3.0)
    st = dict(kimi_linear_ref.layer_statics(cfg, 1))
    with jax.default_matmul_precision("highest"):
        y_ref, margin = kimi_linear_ref._moe(m, h, st)
        y, _ = moe_mlp(m, h, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=F32_TOL)
    assert float(margin.max()) < 1e-5  # every token's routing is a near-tie
    # a bias that lifts the least likely expert into every token's choice
    # changes who is chosen, and the chosen one weighs by its own small score
    s = jax.nn.sigmoid(h @ m["router_kernel"])
    low = int(jnp.argmin(s.sum(axis=0)))
    m["router_bias"] = jnp.zeros(E, jnp.float32).at[low].set(5.0)
    with jax.default_matmul_precision("highest"):
        y_b, _ = moe_mlp(m, h, cfg)
        y_b_ref, _ = kimi_linear_ref._moe(m, h, st)
    np.testing.assert_allclose(np.asarray(y_b), np.asarray(y_b_ref), atol=F32_TOL)
    idx, _ = kimi_linear_ref.route(s, m["router_bias"], 3)
    assert (np.asarray(idx[:, 0]) == low).all()
    assert np.abs(np.asarray(y_b) - np.asarray(y)).max() > 1e-3


def test_the_load_vector_counts_the_held_experts_touched(params):
    """One routing group: every valid token's group lands here, and the held
    experts with at least one pair are counted, in the same int32 vector."""
    p = held_slice(params, 8, 4)
    m = p["layers_2"]["mlp"]
    h = jnp.asarray(np.random.default_rng(2).standard_normal((200, 48)), jnp.float32)
    valid = jnp.arange(200) < 150
    _, _, load = moe_mlp(m, h, PART, valid=valid, with_load=True)
    here, hot, absent, tokens, touched = (int(x) for x in load)
    assert here + absent == 150 * 3 and 0 < here < absent and hot <= here
    assert (tokens, touched) == (150, 4)
    idx, _ = kimi_linear_ref.route(
        jax.nn.sigmoid(h[:150] @ m["router_kernel"]), m["router_bias"], 3)
    chosen = np.asarray(idx[:, :3])
    assert here == int(((chosen >= 8) & (chosen < 12)).sum())
    _, _, none = moe_mlp(m, h, PART, valid=jnp.zeros(200, bool), with_load=True)
    assert [int(x) for x in none] == [0, 0, 0, 0, 0]
    _, _, one = moe_mlp(m, h, PART, valid=jnp.arange(200) == 0, with_load=True)
    assert int(one[4]) == int(one[0]) <= 3 and int(one[3]) == 1
    assert qwen2.decode_load_len(PART) == 5 + 2 + 1 + 1
    assert qwen2.decode_load_len(FULL) == 4 + 2 + 1 + 1


# -- the share test ------------------------------------------------------------------


def test_the_parts_all_shares_give_add_up_to_the_uncut_layer(params):
    """Section 4 of the model-configs guide: the partial results of the 4
    chips that hold 4 experts each, with the shared expert counted once, add
    up to what the uncut reference gives for the whole layer."""
    m = params["layers_2"]["mlp"]
    h = jnp.asarray(np.random.default_rng(7).standard_normal((50, 48)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, _ = moe_mlp(m, h, FULL)
        ref_whole = kimi_linear_ref.moe_layer(m, h, FULL)
        np.testing.assert_allclose(np.asarray(whole), np.asarray(ref_whole), atol=F32_TOL)
        act = jax.nn.silu(h @ m["shared_gate_kernel"]) * (h @ m["shared_up_kernel"])
        shared = act @ m["shared_down_kernel"]
        total = shared
        for first in range(0, 16, 4):
            cfg = tiny(held=4, first=first)
            held = held_slice({"m": m}, first, 4)["m"]
            part, _ = moe_mlp(held, h, cfg)
            ref_part = kimi_linear_ref.moe_layer(held, h, cfg)
            np.testing.assert_allclose(np.asarray(part), np.asarray(ref_part), atol=F32_TOL)
            total = total + (part - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref_whole), atol=F32_TOL)


# -- HF names ---------------------------------------------------------------------------


def test_hf_names_round_trip(params, tmp_path):
    pytest.importorskip("safetensors")
    from areal_tpu.models.hf_io import (
        flatten_params,
        hf_name_to_ours,
        load_hf_params,
        ours_name_to_hf,
        save_hf_params,
    )

    p = held_slice(params, 8, 4)
    names = {ours_name_to_hf(path, "kimi_linear"): w.shape
             for path, w in flatten_params(p, PART).items()}
    kda = "model.layers.0.self_attn."
    assert {n[len(kda):] for n in names if n.startswith(kda)} == {
        "q_proj.weight", "k_proj.weight", "v_proj.weight", "q_conv1d.weight", "k_conv1d.weight",
        "v_conv1d.weight", "A_log", "dt_bias", "f_a_proj.weight", "f_b_proj.weight",
        "b_proj.weight", "g_a_proj.weight", "g_b_proj.weight", "o_norm.weight", "o_proj.weight"}
    mla = "model.layers.3.self_attn."
    assert {n[len(mla):] for n in names if n.startswith(mla)} == {
        "q_proj.weight", "kv_a_proj_with_mqa.weight", "kv_a_layernorm.weight",
        "kv_b_proj.weight", "o_proj.weight"}
    assert names["model.layers.0.mlp.gate_proj.weight"] == (48, 80)  # the dense layer, 2-D
    assert names["model.layers.1.block_sparse_moe.gate.weight"] == (48, 16)
    assert names["model.layers.1.block_sparse_moe.gate.e_score_correction_bias"] == (16,)
    assert names["model.layers.1.block_sparse_moe.shared_experts.down_proj.weight"] == (24, 48)
    assert "model.layers.2.block_sparse_moe.experts.8.w3.weight" in names
    assert "model.layers.2.block_sparse_moe.experts.0.w3.weight" not in names
    assert all(hf_name_to_ours(n) is not None for n in names)

    out = save_hf_params(p, PART, str(tmp_path / "ckpt"))
    from safetensors import safe_open

    with safe_open(os.path.join(out, "model.safetensors"), framework="numpy") as f:
        # torch layouts: Linear [out, in], Conv1d [channels, 1, width]
        assert f.get_tensor(kda + "q_proj.weight").shape == (64, 48)
        assert f.get_tensor(kda + "q_conv1d.weight").shape == (64, 1, 4)
        assert f.get_tensor(kda + "f_b_proj.weight").shape == (64, 16)
        assert f.get_tensor(mla + "q_proj.weight").shape == (96, 48)
        np.testing.assert_array_equal(
            f.get_tensor(mla + "kv_a_proj_with_mqa.weight"),
            np.asarray(p["layers_3"]["attn"]["kv_a_kernel"]).T)  # no lanes permuted
    with open(os.path.join(out, "config.json"), "w") as f:
        json.dump(dict(TINY_HF, num_experts=4, num_experts_published=16, expert_first=8), f)
    cfg = ModelConfig.from_hf_config(out, dtype="float32", param_dtype="float32")
    loaded = load_hf_params(out, cfg, dtype="float32")
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 p, loaded)


# -- the configuration's bytes -------------------------------------------------------


def test_the_configurations_bytes_at_the_published_widths():
    """Parameters as ISSUE 45 counts them from the row's keys, the latent
    row's bytes, a slot's state and what one update of it moves."""
    from areal_tpu.engine.kv_pool import SlotCache

    mc = ModelConfig.from_hf_config(CONFIG_FILE)
    shapes = qwen2.param_shapes(mc)

    def count(tree):
        return sum(int(np.prod(s)) for s in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, tuple)))

    assert count(shapes["layers_0"]["attn"]) == 39_514_272
    assert count(shapes["layers_3"]["attn"]) == 29_114_880
    assert count(shapes["layers_0"]["mlp"]) == 63_700_992
    assert count({k: v for k, v in shapes["layers_1"]["mlp"].items()
                  if k.startswith("shared")}) == 7_077_888
    assert count(shapes) == CONFIG_FILE["parameters"]
    cache = SlotCache(mc, slots=128, block_size=128, n_blocks=8193, max_blocks_per_slot=64,
                      kv_dtype=jnp.bfloat16)
    assert cache.kinds == ("pools", "state", "latent")
    assert cache.row_nbytes == 640 * 2 and cache.block_nbytes == 2 * 128 * 1280
    # 2 MiB of float32 state and 72 KiB of convolution rows, in and out
    assert cache.state_update_nbytes == 2 * (32 * 128 * 128 * 4 + 3 * 12288 * 2) == 4_341_760
    shapes = {k: (s, str(d)) for k, (s, d) in cache._state_shapes.items()}
    assert shapes == {"S": ((6, 129, 32, 128, 128), "float32"),
                      "conv": ((6, 129, 3, 12288), "bfloat16")}
