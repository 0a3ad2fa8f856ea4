"""Jamba (`jamba`: AI21-Jamba2-3B) through the normal path, at a tiny width on
the CPU, against the float32 reference (`benchmark/reference/jamba_ref.py`:
the recurrence token by token, attention a dense masked softmax): the
registry on the catalog's keys and each refusal by its key; `forward` on a
packed stream of three segments; the gradient of the label log-probabilities;
`prefill` then decoding through the cache; the chunked scan against the
token-by-token one; the scanned runs against the same model held unstacked;
the HF names there and back; a state or a recurrence in bf16 fails the
tolerance. The engine's side is `tests/test_jamba_engine.py`."""

import dataclasses
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

from benchmark.lib import flops_ssm, kind_rollout_ssm, weights  # noqa: E402
from benchmark.reference import jamba_ref  # noqa: E402

from areal_tpu.models import hf_io, qwen2  # noqa: E402
from areal_tpu.models.qwen2 import (  # noqa: E402
    PADDING_SEGMENT,
    ModelConfig,
    _ssm_chunk_scan,
    decode_step_paged,
    forward,
    prefill,
)

with open(os.path.join(REPO, "benchmark/configs/ai21-jamba2-3b.json")) as _f:
    CONFIG_FILE = json.load(_f)
PUBLISHED = {k: v for k, v in CONFIG_FILE.items()
             if k not in ("source", "reduced", "assumed", "deployment", "parameters")}

# the same family at a tiny width: a period of 6 with the attention layer at 2,
# so that 11 layers hold a run of 2 (unstacked), one of 5 and one of 4 (scanned)
TINY_HF = dict(
    model_type="jamba", vocab_size=96, hidden_size=48, intermediate_size=80,
    num_hidden_layers=12, num_attention_heads=4, num_key_value_heads=1,
    attn_layer_period=6, attn_layer_offset=2, expert_layer_period=2, expert_layer_offset=1,
    num_experts=1, num_experts_per_tok=1, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
    mamba_dt_rank=6, mamba_conv_bias=True, mamba_proj_bias=False, rms_norm_eps=1e-6,
    sliding_window=None, tie_word_embeddings=True, hidden_act="silu",
    max_position_embeddings=262144)
SEED = 2**31 + 50
F32_TOL = 2e-4  # float32 program against float32 reference, logits
LOGP_TOL = 5e-4  # the same on log-probabilities


def tiny(**over):
    return ModelConfig.from_hf_config(TINY_HF, dtype="float32", param_dtype="float32",
                                      attn_impl="dense", **over)


CFG = tiny()


def seeded(cfg):
    return kind_rollout_ssm.redraw_mixer_leaves(weights.seeded_params(cfg, SEED), SEED)


@pytest.fixture(scope="module")
def params():
    return seeded(CFG)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, 96, n).astype(np.int32)


def _forward_logits(params, cfg, ids, segments=None, positions=None):
    T = len(ids)
    seg = jnp.zeros(T, jnp.int32) if segments is None else jnp.asarray(segments)
    pos = jnp.arange(T) if positions is None else jnp.asarray(positions)
    with jax.default_matmul_precision("highest"):
        return np.asarray(forward(params, jnp.asarray(ids), pos, seg, cfg))


def unstacked(params, cfg):
    """The same model with every layer held as `layers_{i}`: (tree, config)."""
    flat = hf_io.flatten_params(params, cfg)
    tree: dict = {}
    for path, w in flat.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = jnp.asarray(w)
    return tree, _NoRuns(**dataclasses.asdict(cfg))


@dataclasses.dataclass(frozen=True)
class _NoRuns(ModelConfig):
    @property
    def layer_runs(self):
        return ()


# -- registry ---------------------------------------------------------------------


def test_from_hf_config_on_the_catalogs_keys():
    mc = ModelConfig.from_hf_config(PUBLISHED)
    assert (mc.model_type, mc.num_hidden_layers, mc.hidden_size, mc.vocab_size) == (
        "jamba", 28, 2560, 65536)
    attention = [i for i, t in enumerate(mc.layer_types) if t == "full_attention"]
    assert attention == [7, 21] and mc.layer_types.count("mamba") == 26
    assert mc.cache_layers["full"] == (7, 21) and len(mc.cache_layers["state"]) == 26
    assert mc.cache_layers["window"] == () and "latent" not in mc.cache_layers
    assert (mc.ssm_state_size, mc.ssm_expand, mc.ssm_inner, mc.ssm_dt_rank,
            mc.linear_conv_kernel_dim, mc.ssm_conv_bias) == (16, 2, 5120, 160, 4, True)
    assert (mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim_) == (20, 1, 128)
    assert mc.pos_embed == "none" and not any(mc.layer_rope(i) for i in range(28))
    assert mc.num_experts == 0 and not any(mc.layer_sparse(i) for i in range(28))
    assert mc.tie_word_embeddings and not mc.qkv_bias and mc.rms_norm_eps == 1e-6
    assert mc.mixed and not mc.scan_layers and not mc.latent
    # the stack scans by runs: 7, 13 and 6 state-space layers, the two
    # attention layers in line
    assert mc.layer_runs == ((0, 7), (8, 21), (22, 28))
    assert [k for k, _, _ in mc.stack_plan] == [
        "run_0_7", "layers_7", "run_8_21", "layers_21", "run_22_28"]
    assert mc.slot_state_shapes == {"S": (16, 5120), "conv": (3, 5120)}
    # 3.03B parameters: the embedding once (tied), 26 mixers of 41.2M, 2
    # attention layers of 13.8M, 28 MLPs of 62.9M, norms
    shapes = jax.tree.leaves(qwen2.param_shapes(mc), is_leaf=lambda s: isinstance(s, tuple))
    total = sum(int(np.prod(s)) for s in shapes)
    assert total == 3_029_337_472 == flops_ssm.param_count(mc)
    assert flops_ssm.ssm_mixer_params(mc) == 41_241_792
    assert flops_ssm.attention_params(mc) == 13_762_560 and flops_ssm.mlp_params(mc) == 62_914_560


@pytest.mark.parametrize("over,key", [
    (dict(num_experts=16), "num_experts"),
    (dict(sliding_window=4096), "sliding_window"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias"),
])
def test_what_is_not_served_raises_by_its_key(over, key):
    with pytest.raises(NotImplementedError, match=f"jamba with {key}"):
        ModelConfig.from_hf_config({**TINY_HF, **over})


def test_dt_rank_auto_is_a_sixteenth_of_the_width():
    assert ModelConfig.from_hf_config({**PUBLISHED, "mamba_dt_rank": "auto"}).ssm_dt_rank == 160


def test_a_property_of_the_stack_decides_what_scans():
    """Runs of at least `SCAN_RUN_MIN` recurrent layers under a dense MLP are
    stacked; a run of two is not; the three 3 : 1 hybrids and every other
    mixed stack keep `layers_{i}` alone."""
    assert CFG.layer_runs == ((3, 8), (9, 12)) or CFG.layer_runs == ((3, 8),)
    assert [k for k, _, _ in CFG.stack_plan][:4] == ["layers_0", "layers_1", "layers_2", "run_3_8"]
    for name in ("qwen3-next-80b-a3b", "kimi-linear-48b-a3b", "k-exaone-236b-a23b", "deepseek-v2"):
        with open(os.path.join(REPO, "benchmark/configs", name + ".json")) as f:
            mc = ModelConfig.from_hf_config(json.load(f))
        assert mc.layer_runs == ()
        assert [k for k, _, _ in mc.stack_plan] == [
            f"layers_{i}" for i in range(mc.num_hidden_layers)]


# -- forward, its gradient ---------------------------------------------------------


def test_forward_on_a_packed_stream_of_three_segments(params):
    """Three sequences in one stream, then padding: the scan and the
    convolution restart at each boundary, attention stays inside a segment."""
    lens = (37, 70, 21)
    seqs = [_ids(i, n) for i, n in enumerate(lens)]
    ids = np.concatenate(seqs + [np.zeros(9, np.int32)])
    seg = np.concatenate([np.full(n, i) for i, n in enumerate(lens)] + [np.full(9, PADDING_SEGMENT)])
    pos = np.concatenate([np.arange(n) for n in lens] + [np.zeros(9, np.int64)])
    got = _forward_logits(params, CFG, ids, seg.astype(np.int32), pos.astype(np.int32))
    at = 0
    for s in seqs:
        ref = np.asarray(jamba_ref.logits(params, CFG, s))
        np.testing.assert_allclose(got[at:at + len(s)], ref, atol=F32_TOL, rtol=F32_TOL)
        at += len(s)


def test_no_rope_scope_in_the_models_programs(params):
    ids = jnp.asarray(_ids(1, 24))
    text = jax.jit(lambda p: forward(p, ids, jnp.arange(24), jnp.zeros(24, jnp.int32), CFG)
                   ).lower(params).as_text(debug_info=True)
    assert "/rope" not in text and "rope/" not in text
    for scope in ("in_proj", "conv", "ssm_params", "ssm_scan", "out_gate", "out_proj"):
        assert f"attn/{scope}" in text, scope


def test_gradient_of_the_label_logprobs(params):
    ids = _ids(4, 45)
    loss_ref, g_ref = jamba_ref.loss_and_grads(params, CFG, ids)

    def nll(p):
        logits = forward(p, jnp.asarray(ids), jnp.arange(45), jnp.zeros(45, jnp.int32), CFG)
        lp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.mean(jnp.take_along_axis(lp, jnp.asarray(ids[1:])[:, None], axis=-1))

    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(nll)(params)
    assert abs(float(loss) - float(loss_ref)) < 1e-4
    flat, flat_ref = (jax.tree_util.tree_leaves_with_path(t) for t in (g, g_ref))
    assert len(flat) == len(flat_ref) > 20
    for (path, a), (_, b) in zip(flat, flat_ref):
        scale = float(jnp.max(jnp.abs(b))) + 1e-8
        assert float(jnp.max(jnp.abs(a - b))) / scale < 2e-3, jax.tree_util.keystr(path)


# -- the chunked scan ----------------------------------------------------------------


@pytest.mark.parametrize("T,chunk", [(64, 16), (50, 16), (37, 64), (33, 8)])
def test_chunk_scan_against_the_token_by_token_recurrence(T, chunk):
    """Chunk sizes that do and do not divide the length, two segments and a
    padded tail: outputs and the final state against `lax.scan` over tokens."""
    N, Di = 16, 40
    ks = jax.random.split(jax.random.PRNGKey(T), 5)
    u = jax.random.normal(ks[0], (T, Di))
    dt = jax.random.uniform(ks[1], (T, Di), jnp.float32, 1e-3, 0.3)
    B, C = jax.random.normal(ks[2], (T, N)), jax.random.normal(ks[3], (T, N))
    A = -jnp.exp(jax.random.normal(ks[4], (N, Di)))
    cut, pad = T // 3, 4
    seg = np.r_[np.zeros(cut, np.int32), np.ones(T - cut - pad, np.int32),
                np.full(pad, PADDING_SEGMENT, np.int32)]
    dt = jnp.where(jnp.asarray(seg != PADDING_SEGMENT)[:, None], dt, 0.0)
    y, h = _ssm_chunk_scan(u, dt, B, C, A, jnp.asarray(seg), chunk=chunk)
    D0 = jnp.zeros((Di,))

    def run(lo, hi):
        def one(h, xs):
            return jamba_ref.ssm_step(h, (*xs, A, D0))

        return jax.lax.scan(one, jnp.zeros((N, Di)), (dt[lo:hi], u[lo:hi], B[lo:hi], C[lo:hi]))

    (h0, y0), (h1, y1) = run(0, cut), run(cut, T - pad)
    np.testing.assert_allclose(np.asarray(y[:cut]), np.asarray(y0), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(y[cut:T - pad]), np.asarray(y1), atol=2e-5, rtol=2e-5)
    # padding leaves the state as the last real token left it
    np.testing.assert_allclose(np.asarray(h), np.asarray(h1), atol=2e-5, rtol=2e-5)


# -- prefill, then decoding through the cache ------------------------------------------


def _pools(cfg, R, nb, bsz, st=None):
    n_state = len(cfg.cache_layers["state"])
    n_full = len(cfg.cache_layers["full"])
    lanes = cfg.num_key_value_heads * cfg.head_dim_
    shapes = cfg.slot_state_shapes
    kq = {"full": jnp.zeros((n_full, 1 + R * nb, bsz, lanes)),
          "state": {k: jnp.zeros((n_state, 1 + R, *s)) for k, s in shapes.items()}}
    return kq, {"full": jnp.zeros((n_full, 1 + R * nb, bsz, lanes))}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_then_decode_token_by_token_through_the_cache(params, impl):
    """A prompt of 41 in a bucket of 64 (padding past its true length leaves
    the state untouched), its rows and state into slot 1 of 3, then 30 tokens
    decoded one at a time with slot 0 dead and slot 2 on another sequence:
    logits against the reference's full forward."""
    R, nb, bsz, n0, n1 = 3, 12, 8, 41, 71
    a, b = _ids(11, n1), _ids(12, n1)
    ref_a = np.asarray(jamba_ref.logits(params, CFG, a))
    ref_b = np.asarray(jamba_ref.logits(params, CFG, b))
    kq, vq = _pools(CFG, R, nb, bsz)
    bt = 1 + jnp.arange(R * nb, dtype=jnp.int32).reshape(R, nb)
    with jax.default_matmul_precision("highest"):
        for slot, seq in ((1, a), (2, b)):
            padded = jnp.asarray(np.r_[seq[:n0], np.zeros(64 - n0, np.int32)])
            lg, ks, vs, st = prefill(params, padded, jnp.arange(64), CFG,
                                     valid=jnp.arange(64) < n0)
            np.testing.assert_allclose(np.asarray(lg[:n0]), (ref_a if slot == 1 else ref_b)[:n0],
                                       atol=F32_TOL, rtol=F32_TOL)
            # the whole-length prefill's state: the padded one's, to rounding
            st0 = prefill(params, jnp.asarray(seq[:n0]), jnp.arange(n0), CFG)[3]
            for k in st:
                np.testing.assert_allclose(np.asarray(st[k]), np.asarray(st0[k]), atol=2e-5)
            pad = nb * bsz - 64
            for pool, rows in ((kq, ks), (vq, vs)):
                r = jnp.pad(rows.reshape(rows.shape[0], 64, -1), ((0, 0), (0, pad), (0, 0)))
                pool["full"] = pool["full"].at[:, bt[slot]].set(r.reshape(-1, nb, bsz, r.shape[-1]))
            kq["state"] = {k: kq["state"][k].at[:, 1 + slot].set(st[k]) for k in st}
        active = jnp.asarray([False, True, True])
        step = jax.jit(lambda t, p, kq, vq: decode_step_paged(
            params, t, p, kq, vq, bt, CFG, active=active, attn_impl=impl))
        for t in range(n0, n1):
            logits, kq, vq = step(jnp.asarray([0, a[t], b[t]]), jnp.asarray([0, t, t]), kq, vq)
            np.testing.assert_allclose(np.asarray(logits[1]), ref_a[t], atol=F32_TOL, rtol=F32_TOL)
            np.testing.assert_allclose(np.asarray(logits[2]), ref_b[t], atol=F32_TOL, rtol=F32_TOL)
    S = np.asarray(kq["state"]["S"])
    assert (S[:, 0] == 0).all() and (S[:, 1] == 0).all()  # the null row, the dead slot
    assert np.abs(S[:, 2:]).max() > 0


def test_scanned_runs_against_the_same_model_held_unstacked(params):
    """`forward`, `prefill` and a decode step of the tree with its runs
    stacked against the same leaves held `layers_{i}`: the same logits and
    caches to float32 rounding."""
    flat_params, flat_cfg = unstacked(params, CFG)
    assert flat_cfg.layer_runs == () and "run_3_8" in params and "layers_4" in flat_params
    ids = _ids(21, 40)
    np.testing.assert_allclose(_forward_logits(params, CFG, ids),
                               _forward_logits(flat_params, flat_cfg, ids), atol=2e-5, rtol=2e-5)
    with jax.default_matmul_precision("highest"):
        got = prefill(params, jnp.asarray(ids), jnp.arange(40), CFG)
        want = prefill(flat_params, jnp.asarray(ids), jnp.arange(40), flat_cfg)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)
        R, nb, bsz = 2, 8, 8
        kq, vq = _pools(CFG, R, nb, bsz)
        kq["state"] = {k: kq["state"][k].at[:, 1:].set(
            jnp.broadcast_to(got[3][k][:, None], (got[3][k].shape[0], R, *got[3][k].shape[1:])))
            for k in got[3]}
        bt = 1 + jnp.arange(R * nb, dtype=jnp.int32).reshape(R, nb)
        args = (jnp.asarray([5, 9]), jnp.asarray([0, 0]), kq, vq, bt)
        a = decode_step_paged(params, *args, CFG, active=jnp.asarray([True, True]))
        b = decode_step_paged(flat_params, *args, flat_cfg, active=jnp.asarray([True, True]))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=2e-5, rtol=2e-5)


# -- one precision lower fails ------------------------------------------------------------


def test_a_bf16_state_fails_the_comparison(params):
    """The reference with its state rounded to bf16's 7 mantissa bits after
    every token against itself: beyond `LOGP_TOL` (5e-4: four times what the
    float32 program reads against the float32 reference at this width, whose
    differences are summation order), which the float32 program is inside."""
    seq = _ids(31, 120)
    ref = jamba_ref.token_logprobs(params, CFG, seq)
    rounded = jamba_ref.token_logprobs(params, CFG, seq, state_bits=7)
    lp = jax.nn.log_softmax(_forward_logits(params, CFG, seq)[:-1], axis=-1)
    got = np.take_along_axis(np.asarray(lp), seq[1:, None], axis=-1)[:, 0]
    assert np.abs(got - ref).max() < LOGP_TOL
    assert np.abs(rounded - ref).max() > 4 * LOGP_TOL


def test_a_bf16_recurrence_fails_the_comparison(params):
    """The program's own scan fed bf16 operands (what a mixer in the compute
    dtype would do): the final state moves by parts in a hundred."""
    T, N, Di = 96, 16, 40
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    u = jax.random.normal(ks[0], (T, Di))
    dt = jax.random.uniform(ks[1], (T, Di), jnp.float32, 1e-3, 0.05)
    B, C = jax.random.normal(ks[2], (T, N)), jax.random.normal(ks[3], (T, N))
    A = -jnp.exp(jax.random.normal(ks[4], (N, Di)))
    seg = jnp.zeros(T, jnp.int32)
    _, h = _ssm_chunk_scan(u, dt, B, C, A, seg)
    low = [t.astype(jnp.bfloat16).astype(jnp.float32) for t in (u, dt, B, C, A)]
    _, h16 = _ssm_chunk_scan(*low, seg)
    assert float(jnp.max(jnp.abs(h16 - h)) / jnp.max(jnp.abs(h))) > 1e-3


# -- HF names -----------------------------------------------------------------------------


def test_hf_names_round_trip_on_a_seeded_tree(params, tmp_path):
    from safetensors.numpy import load_file

    hf_io.save_hf_params(params, CFG, str(tmp_path))
    tensors = load_file(os.path.join(tmp_path, "model.safetensors"))
    Di, N, H, Rk = CFG.ssm_inner, 16, 48, 6
    want = {
        "model.embed_tokens.weight": (96, H), "model.final_layernorm.weight": (H,),
        "model.layers.4.mamba.in_proj.weight": (2 * Di, H),
        "model.layers.4.mamba.conv1d.weight": (Di, 1, 4), "model.layers.4.mamba.conv1d.bias": (Di,),
        "model.layers.4.mamba.x_proj.weight": (Rk + 2 * N, Di),
        "model.layers.4.mamba.dt_proj.weight": (Di, Rk), "model.layers.4.mamba.dt_proj.bias": (Di,),
        "model.layers.4.mamba.A_log": (Di, N), "model.layers.4.mamba.D": (Di,),
        "model.layers.4.mamba.dt_layernorm.weight": (Rk,),
        "model.layers.4.mamba.b_layernorm.weight": (N,),
        "model.layers.4.mamba.c_layernorm.weight": (N,),
        "model.layers.4.mamba.out_proj.weight": (H, Di),
        "model.layers.4.feed_forward.gate_proj.weight": (80, H),
        "model.layers.4.feed_forward.down_proj.weight": (H, 80),
        "model.layers.4.input_layernorm.weight": (H,),
        "model.layers.4.pre_ff_layernorm.weight": (H,),
        "model.layers.2.self_attn.q_proj.weight": (H, H),
        "model.layers.2.self_attn.k_proj.weight": (12, H),
        "model.layers.2.self_attn.o_proj.weight": (H, H),
    }
    for name, shape in want.items():
        assert tensors[name].shape == shape, name
    assert not any("lm_head" in k or "rotary" in k for k in tensors)
    # a layer of a stacked run and an unstacked one, each 19 or 9 tensors
    assert sum(k.startswith("model.layers.4.") for k in tensors) == 17
    assert sum(k.startswith("model.layers.2.") for k in tensors) == 9
    back = hf_io.load_hf_params(str(tmp_path), CFG)
    assert jax.tree.structure(back) == jax.tree.structure(params)
    for (path, a), (_, b) in zip(jax.tree_util.tree_leaves_with_path(params),
                                 jax.tree_util.tree_leaves_with_path(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), jax.tree_util.keystr(path))
    for name in want:
        assert hf_io.hf_name_to_ours(name) is not None, name
