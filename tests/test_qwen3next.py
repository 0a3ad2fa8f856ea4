"""Qwen3-Next (`qwen3_next`) through the normal path, at a tiny width on the
CPU, against the float32 reference (`benchmark/reference/qwen3next_ref.py`,
whose delta rule is the token-by-token recurrence): forward, loss and every
leaf's gradient; the chunked scan against the recurrence; the decode kernel
against its arithmetic; what a slot's state is good for (`StateSlots`; through `JaxDecodeEngine`'s
pools in tests/test_qwen3next_engine.py); the share test of the
model-configs guide's section 4; the registry and what it refuses; what the
rotary embedding on a prefix of the lanes;
the HF names and layouts."""

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

from benchmark.lib import kind_rollout, kind_rollout_linear, weights  # noqa: E402
from benchmark.reference import qwen3next_ref  # noqa: E402

from areal_tpu.engine.kv_pool import StateSlots  # noqa: E402
from areal_tpu.models import qwen2  # noqa: E402
from areal_tpu.models.qwen2 import (  # noqa: E402
    ModelConfig,
    _gdn_chunk_scan,
    apply_rope,
    forward,
    moe_mlp,
    prefill,
    rope_table,
)
from areal_tpu.ops.gdn_step import gdn_step, gdn_step_reference  # noqa: E402

# the model-configs guide's catalog entry for Qwen3-Next-80B-A3B-Instruct, `config`, every key
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
    "linear_key_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144, "mlp_only_layers": [],
    "model_type": "qwen3_next", "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}

# the same family at a tiny width: two periods of (linear, linear, linear,
# full), 4 key / 8 value heads of 16, 16 experts of which 4 or all are held
TINY_HF = dict(
    model_type="qwen3_next", vocab_size=96, hidden_size=64, intermediate_size=80,
    num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    rope_theta=10000.0, rms_norm_eps=1e-6, full_attention_interval=4,
    partial_rotary_factor=0.25, linear_conv_kernel_dim=4, linear_key_head_dim=16,
    linear_num_key_heads=4, linear_num_value_heads=8, linear_value_head_dim=16,
    num_experts=16, num_experts_per_tok=3, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, norm_topk_prob=True, decoder_sparse_step=1,
    mlp_only_layers=[], tie_word_embeddings=False, max_position_embeddings=4096,
    rope_scaling=None)
SEED = 2**31 + 32
# float32 program against float32 reference: rounding alone, through 8 layers
# whose recurrence the two sides order differently (chunks and a triangular
# solve against a token at a time); logits of magnitude ~10
F32_TOL = 2e-3
LOGP_TOL = 5e-4  # the same on log-probabilities of sampled tokens


def tiny(held=16, first=0, **over):
    hf = dict(TINY_HF, num_experts=held, num_experts_published=16, expert_first=first)
    return ModelConfig.from_hf_config(hf, dtype="float32", param_dtype="float32", **over)


FULL = tiny()
PART = tiny(held=4, first=8)


@pytest.fixture(scope="module")
def params():
    """`weights.py`'s tree with the mixer's own leaves redrawn as the kind does."""
    return kind_rollout_linear.redraw_mixer_leaves(weights.seeded_params(FULL, SEED), SEED)


def held_slice(params, first, count):
    """The tree of a chip that holds experts [first, first + count)."""
    def cut(path, x):
        if str(path[-1].key) in ("gate_kernel", "up_kernel", "down_kernel") and x.ndim == 3:
            return x[first:first + count]
        return x

    return jax.tree_util.tree_map_with_path(cut, params)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, 96, n).astype(np.int32)


def _forward_logits(params, cfg, ids):
    T = len(ids)
    run = jax.jit(lambda p, i: forward(p, i, jnp.arange(T), jnp.zeros(T, jnp.int32), cfg))
    return run(params, jnp.asarray(ids))


# -- registry -----------------------------------------------------------------


def test_from_hf_config_on_the_catalogs_config():
    cfg = ModelConfig.from_hf_config(CATALOG)
    assert cfg.num_hidden_layers == 48 and len(cfg.layer_types) == 48
    assert cfg.layer_types[:5] == ("linear_attention",) * 3 + ("full_attention", "linear_attention")
    assert (cfg.num_experts, cfg.num_experts_published_, cfg.num_experts_per_tok) == (512, 512, 10)
    assert (cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size) == (512, 512)
    assert cfg.shared_expert_gated and cfg.moe_scoring == "softmax" and cfg.norm_topk_prob
    assert (cfg.head_dim_, cfg.rotary_dim, cfg.rope_theta) == (256, 64, 10000000)
    assert cfg.qk_norm and cfg.attn_output_gate and not cfg.qkv_bias and not cfg.norm_zero_centered
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads) == (16, 32)
    assert (cfg.linear_key_head_dim, cfg.linear_value_head_dim, cfg.linear_conv_channels) == (
        128, 128, 8192)
    assert not cfg.scan_layers and cfg.mixed
    assert [cfg.layer_linear(i) for i in range(4)] == [True, True, True, False]
    L = cfg.cache_layers
    assert (len(L["full"]), len(L["window"]), len(L["state"])) == (12, 0, 36)
    assert L["full"][:2] == (3, 7)
    hash(cfg)  # a jit static


@pytest.mark.parametrize("over,err", [
    (dict(mlp_only_layers=[0]), "mlp_only_layers"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}), "rope_scaling"),
    (dict(layer_types=["sliding_attention"] * 48), "layer_types"),
    (dict(num_experts=64, num_experts_published=512, expert_first=480), "holds experts"),
])
def test_what_from_hf_config_does_not_serve_raises(over, err):
    with pytest.raises((NotImplementedError, ValueError), match=err):
        ModelConfig.from_hf_config(dict(CATALOG, **over))


def test_layer_types_may_be_given_or_follow_from_the_interval():
    given = ModelConfig.from_hf_config(dict(
        TINY_HF, layer_types=(["linear_attention"] * 3 + ["full_attention"]) * 12))
    assert given.layer_types == FULL.layer_types  # the first 8 of a published list count


# -- rotary embedding on a prefix of the lanes ---------------------------------


@pytest.mark.parametrize("factor", [1.0, 0.5, 0.25])
def test_rope_turns_only_the_lanes_the_table_covers(factor):
    cfg = tiny(partial_rotary_factor=factor)
    rot = cfg.rotary_dim
    assert rot == int(16 * factor)
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 16))
    cos, sin = rope_table(jnp.arange(5) + 3, rot, 10000.0)
    assert cos.shape == (5, rot // 2)
    y = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(np.asarray(y[..., rot:]), np.asarray(x[..., rot:]))
    want = qwen3next_ref._rope(x, jnp.arange(5) + 3, 10000.0, rot)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-6)
    assert not np.allclose(np.asarray(y[1:, :, :rot]), np.asarray(x[1:, :, :rot]))


def test_every_path_takes_the_rotary_width_from_the_config():
    """`forward`, the pipelined stage, `prefill`, `decode_step_paged` and
    `verify_step_paged` all build their table from `cfg.rotary_dim`."""
    import inspect

    src = inspect.getsource(qwen2)
    # (one place builds it, `_rope_tables`, which a model with no positional
    # encoding answers with no table; the five paths call that)
    assert src.count("cfg.rotary_dim, cfg.rope_theta, cfg.rope_scaling_)") == 1
    assert src.count("_rope_tables(") == 1 + 5
    assert "cfg.head_dim_, cfg.rope_theta" not in src


# -- the trainer's forward -----------------------------------------------------


@pytest.mark.parametrize("n", [150, 64, 37])  # chunks of 64: two and a part, one, a part
def test_forward_agrees_with_the_reference(params, n):
    ids = _ids(1, n)
    got = _forward_logits(params, FULL, ids)
    want = qwen3next_ref.logits(params, FULL, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=F32_TOL)


def test_forward_with_a_share_of_the_experts_agrees_with_the_reference(params):
    p = held_slice(params, 8, 4)
    ids = _ids(2, 70)
    np.testing.assert_allclose(np.asarray(_forward_logits(p, PART, ids)),
                               np.asarray(qwen3next_ref.logits(p, PART, ids)), atol=F32_TOL)


def test_packed_sequences_reset_the_state_and_the_convolution(params):
    a, b = _ids(3, 50), _ids(4, 70)
    ids = np.concatenate([a, b, np.zeros(8, np.int32)])
    seg = np.concatenate([np.zeros(50), np.ones(70), -np.ones(8)]).astype(np.int32)
    pos = np.concatenate([np.arange(50), np.arange(70), np.zeros(8)]).astype(np.int32)
    got = jax.jit(lambda p, i, q, g: forward(p, i, q, g, FULL))(
        params, jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(seg))
    assert bool(jnp.isfinite(got).all())
    for lo, hi, one in ((0, 50, a), (50, 120, b)):
        np.testing.assert_allclose(np.asarray(got[lo:hi]),
                                   np.asarray(qwen3next_ref.logits(params, FULL, one)),
                                   atol=2 * F32_TOL)


@pytest.fixture(scope="module")
def both_grads(params):
    ids = _ids(5, 90)

    def nll(p):
        lp = jax.nn.log_softmax(_forward_logits(p, FULL, ids), axis=-1)
        return -jnp.mean(lp[jnp.arange(89), jnp.asarray(ids[1:])])

    loss, grads = jax.value_and_grad(nll)(params)
    ref_loss, ref_grads = qwen3next_ref.loss_and_grads(params, FULL, ids)
    return loss, grads, ref_loss, ref_grads


def test_loss_agrees_with_the_reference(both_grads):
    loss, _, ref_loss, _ = both_grads
    assert abs(float(loss) - float(ref_loss)) < 1e-4


LEAVES = sorted(
    "/".join(str(k.key) for k in path)
    for path, _ in jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: qwen2.init_params(FULL, jax.random.PRNGKey(0))))[0]
    if str(path[0].key) in ("embed", "lm_head", "final_norm", "layers_0", "layers_3"))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_agrees_with_the_reference(both_grads, leaf):
    """`jax.grad` through the chunked scan and its triangular solve against
    `jax.grad` through the recurrence: a linear layer's and a full layer's
    leaves, the embedding and the head. Relative to the leaf's largest
    gradient: float32 rounding through 8 layers."""
    _, grads, _, ref_grads = both_grads
    got, want = grads, ref_grads
    for k in leaf.split("/"):
        got, want = got[k], want[k]
    scale = float(jnp.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-3 * scale + 1e-7)


# -- the two forms of the delta rule --------------------------------------------


def _rule_inputs(T, H=3, dk=8, dv=8, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (T, H, dk))
    k = jax.random.normal(ks[1], (T, H, dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (T, H, dv))
    g = -0.5 * jax.random.uniform(ks[3], (T, H))
    beta = jax.random.uniform(ks[4], (T, H))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta, seg):
    S = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]))
    out, prev = [], None
    for t in range(q.shape[0]):
        if seg[t] == -1:
            out.append(jnp.zeros(v.shape[1:]))
            continue
        if prev is not None and seg[t] != prev:
            S = jnp.zeros_like(S)
        prev = seg[t]
        o, S = gdn_step_reference(S, q[t], k[t], v[t], g[t], beta[t])
        out.append(o)
    return jnp.stack(out), S


@pytest.mark.parametrize("name,seg", [
    ("one sequence, 150 = 2 x 64 + 22", np.zeros(150, int)),
    ("one sequence shorter than a chunk", np.zeros(37, int)),
    ("exactly a chunk", np.zeros(64, int)),
    ("two sequences, a boundary inside a chunk", np.r_[np.zeros(50, int), np.ones(100, int)]),
    ("a boundary on a chunk's edge, then padding",
     np.r_[np.zeros(64, int), np.ones(30, int), 2 * np.ones(40, int), -np.ones(16, int)]),
    ("a bucket's padding over whole chunks", np.r_[np.zeros(20, int), -np.ones(130, int)]),
])
def test_the_chunked_scan_is_the_recurrence(name, seg):
    q, k, v, g, beta = _rule_inputs(len(seg))
    real = jnp.asarray(seg != -1)[:, None]
    g, beta = jnp.where(real, g, 0), jnp.where(real, beta, 0)
    o, S = _gdn_chunk_scan(q, k, v, g, beta, jnp.asarray(seg, jnp.int32))
    want_o, want_S = _recurrence(q, k, v, g, beta, seg)
    np.testing.assert_allclose(np.asarray(o)[seg != -1], np.asarray(want_o)[seg != -1], atol=1e-5)
    np.testing.assert_allclose(np.asarray(S), np.asarray(want_S), atol=1e-5)


@pytest.mark.parametrize("active", [None, [True, False, True, True, False]])
def test_the_decode_kernel_is_its_arithmetic(active):
    """`gdn_step` through Pallas (interpreted here) against `jax.numpy`: the
    live slots' rows of the one layer move, the others and the null row stay."""
    n, R, Hv, dk, dv = 2, 5, 8, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    S = jax.random.normal(ks[0], (n, 1 + R, Hv, dk, dv)).at[:, 0].set(0)
    q, k, v, g, beta = _rule_inputs(R, Hv, dk, dv, seed=2)
    act = None if active is None else jnp.asarray(active)
    o_x, S_x = gdn_step(S, q, k, v, g, beta, 1, act, impl="xla")
    o_p, S_p = gdn_step(S, q, k, v, g, beta, 1, act, impl="pallas", interpret=True)
    live = np.ones(R, bool) if active is None else np.asarray(active)
    np.testing.assert_allclose(np.asarray(S_p), np.asarray(S_x), atol=1e-5)
    np.testing.assert_allclose(np.asarray(o_p)[live], np.asarray(o_x)[live], atol=1e-5)
    np.testing.assert_array_equal(np.asarray(S_p[0]), np.asarray(S[0]))  # the other layer
    np.testing.assert_array_equal(np.asarray(S_p[1, 0]), 0)  # the null row
    np.testing.assert_array_equal(np.asarray(S_p[1, 1:][~live]), np.asarray(S[1, 1:][~live]))
    want_o, want_S = gdn_step_reference(S[1, 1:], q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(S_x[1, 1:])[live], np.asarray(want_S)[live], atol=1e-6)


@pytest.mark.parametrize("real,bucket", [(100, 128), (100, 256), (64, 128), (3, 64), (1, 64)])
def test_padding_does_not_enter_the_state(params, real, bucket):
    """A prefill hands over the state at the prompt's last REAL token and the
    last three real pre-convolution rows, whatever its bucket."""
    ids = _ids(6, real)
    _, _, _, want = jax.jit(lambda p, i: prefill(p, i, jnp.arange(real), FULL))(
        params, jnp.asarray(ids))
    padded = np.zeros(bucket, np.int32)
    padded[:real] = ids
    lg, ks, _, got = jax.jit(lambda p, i: prefill(
        p, i, jnp.arange(bucket), FULL, valid=jnp.arange(bucket) < real))(
        params, jnp.asarray(padded))
    assert ks.shape[0] == 2 and got["S"].shape == (6, 8, 16, 16) and got["conv"].shape == (6, 3, 256)
    # (a projection over another number of rows rounds differently in float32)
    np.testing.assert_allclose(np.asarray(got["S"]), np.asarray(want["S"]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(got["conv"]), np.asarray(want["conv"]), atol=1e-4)
    np.testing.assert_allclose(np.asarray(lg[:real]),
                               np.asarray(qwen3next_ref.logits(params, FULL, ids)), atol=F32_TOL)


# -- the share test (model-configs guide, section 4) ----------------------------


def test_the_parts_all_shares_give_add_up_to_the_uncut_layer(params):
    """16 experts published in 4 shares of 4: the four shares' routed parts
    plus the gated shared expert counted once are the uncut layer's MoE
    output, by the program and by the reference."""
    mlp = params["layers_1"]["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(7), (40, 64))
    whole, _ = moe_mlp(mlp, h, FULL)
    ref_whole = qwen3next_ref.moe_layer(mlp, h, FULL)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref_whole), atol=1e-5)
    no_shared = {k: v for k, v in mlp.items() if not k.startswith("shared_")}
    import dataclasses

    routed_cfg = dataclasses.replace(FULL, shared_expert_intermediate_size=0)
    shared_only = whole - moe_mlp(no_shared, h, routed_cfg)[0]
    total = shared_only
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(routed_cfg, num_experts=4, num_experts_published=16,
                                  expert_first=first)
        part, _ = moe_mlp(held_slice(no_shared, first, 4), h, cfg)
        total = total + part
        # and the reference's share, shared expert included, is the program's
        share_cfg = tiny(4, first)
        np.testing.assert_allclose(
            np.asarray(moe_mlp(held_slice(mlp, first, 4), h, share_cfg)[0]),
            np.asarray(qwen3next_ref.moe_layer(held_slice(mlp, first, 4), h, share_cfg)),
            atol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=1e-5)


# -- what a slot's state is good for -------------------------------------------


def test_what_a_state_holds():
    s = StateSlots(3)
    assert s.holds(0, 0) and not s.holds(0, 5)
    s.reset(1, 40)  # a prefill of 40 tokens
    assert s.holds(1, 40) and not s.holds(1, 39) and not s.holds(1, 41)
    s.note_written(np.array([False, True, False]), np.array([56]))  # a chunk of 16 dispatched
    assert s.holds(1, 56) and not s.holds(1, 40)
    s.note_written(np.array([False, True, False]), np.array([50]))  # a rewound length: no way back
    assert s.holds(1, 56) and not s.holds(1, 50)
    s.reset(2, int(s.count[1]))  # a fork copies what the donor holds
    assert s.holds(2, 56)
    assert StateSlots.row(0) == 1  # row 0 is the null slot


# -- HF tensor names and layouts -------------------------------------------------


def test_hf_names_round_trip(params, tmp_path):
    pytest.importorskip("safetensors")
    from areal_tpu.models.hf_io import (
        _convert_tensor,
        flatten_params,
        hf_name_to_ours,
        load_hf_params,
        ours_name_to_hf,
        save_hf_params,
    )

    p = held_slice(params, 8, 4)
    names = {ours_name_to_hf(path, "qwen3_next"): w.shape
             for path, w in flatten_params(p, PART).items()}
    assert names["model.layers.0.linear_attn.in_proj_qkvz.weight"] == (64, 2 * 64 + 2 * 128)
    assert names["model.layers.0.linear_attn.conv1d.weight"] == (256, 4)
    assert names["model.layers.0.linear_attn.A_log"] == (8,)
    assert names["model.layers.3.self_attn.q_proj.weight"] == (64, 4, 32)  # q and its gate
    assert names["model.layers.3.mlp.shared_expert_gate.weight"] == (64, 1)
    assert "model.layers.2.mlp.experts.8.up_proj.weight" in names
    assert "model.layers.2.mlp.experts.0.up_proj.weight" not in names
    assert "model.layers.3.linear_attn.A_log" not in names
    assert all(hf_name_to_ours(n) is not None for n in names)

    # a checkpoint groups the fused projection by key head: [q | k | v v | z z] a head
    H, nk, dk, dv, r = 64, 4, 16, 16, 2
    per = 2 * dk + 2 * r * dv
    hf = np.arange(nk * per * H, dtype=np.float32).reshape(nk * per, H)  # torch [out, in]
    ours = _convert_tensor(("layers_0", "attn", "qkvz_kernel"), hf, PART)
    assert ours.shape == (H, nk * per)
    head1 = hf.reshape(nk, per, H)[1]
    np.testing.assert_array_equal(ours[:, dk:2 * dk], head1[:dk].T)  # q of key head 1
    np.testing.assert_array_equal(ours[:, nk * dk + dk: nk * dk + 2 * dk], head1[dk:2 * dk].T)
    v0 = 2 * nk * dk
    np.testing.assert_array_equal(ours[:, v0 + r * dv: v0 + 2 * r * dv],
                                  head1[2 * dk: 2 * dk + r * dv].T)  # value heads 2 and 3

    out = save_hf_params(p, PART, str(tmp_path / "ckpt"))
    from safetensors import safe_open

    with safe_open(os.path.join(out, "model.safetensors"), framework="numpy") as f:
        stored = f.get_tensor("model.layers.0.input_layernorm.weight")
        gated = f.get_tensor("model.layers.0.linear_attn.norm.weight")
    # norms are stored zero-centred, the gated norm's scale as it is
    np.testing.assert_allclose(stored + 1, np.asarray(p["layers_0"]["input_norm"]), atol=1e-6)
    np.testing.assert_array_equal(gated, np.asarray(p["layers_0"]["attn"]["norm"]))
    with open(os.path.join(out, "config.json"), "w") as f:
        json.dump(dict(TINY_HF, num_experts=4, num_experts_published=16, expert_first=8), f)
    cfg = ModelConfig.from_hf_config(out, dtype="float32", param_dtype="float32")
    loaded = load_hf_params(out, cfg, dtype="float32")
    jax.tree.map(lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6),
                 p, loaded)
