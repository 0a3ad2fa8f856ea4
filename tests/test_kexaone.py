"""K-EXAONE (`exaone_moe`) through the normal path, at a tiny width on the
CPU, against the float32 reference (`benchmark/reference/kexaone_ref.py`):
forward, loss and every leaf's gradient; prefill then decode through the ring
and the paged cache of `JaxDecodeEngine`, across a page wrap of the ring, a
fork of a group and a prompt longer than the chunked bucket; the router's
rule; the share test of the model-configs guide's section 4; the registry and
what it refuses; what the engine refuses at `initialize()`; the HF names."""

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

from benchmark.lib import kind_rollout, kind_rollout_hybrid, weights  # noqa: E402
from benchmark.reference import kexaone_ref  # noqa: E402

from areal_tpu.engine.kv_pool import WindowRing  # noqa: E402
from areal_tpu.models import qwen2  # noqa: E402
from areal_tpu.models.qwen2 import (  # noqa: E402
    ModelConfig,
    forward,
    moe_mlp,
    param_shapes,
    prefill,
    prefill_with_prefix,
    ring_pages,
)

with open(os.path.join(REPO, "benchmark/configs/k-exaone-236b-a23b.json")) as _f:
    CONFIG_FILE = json.load(_f)
# the model-configs guide's catalog entry for K-EXAONE-236B-A23B, `config`, every key
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 6144,
    "intermediate_size": 18432,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 12,
    "max_position_embeddings": 262144, "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0], "n_group": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_pattern": "LLLG", "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600,
}

# the same family at a tiny width: two periods after the leading dense layer,
# 16 experts of which 4 or all 16 are held, window 8 (page 4 in the engine)
TINY_HF = dict(
    model_type="exaone_moe", vocab_size=96, hidden_size=32, intermediate_size=48,
    num_hidden_layers=9, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rope_parameters={"rope_theta": 10000.0, "rope_type": "default"}, rms_norm_eps=1e-5,
    sliding_window=8,
    layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 3,  # first 9 count
    first_k_dense_replace=1, mlp_layer_types=["dense"] + ["sparse"] * 11,
    num_experts=16, num_experts_per_tok=4, moe_intermediate_size=16, num_shared_experts=1,
    scoring_func="sigmoid", norm_topk_prob=True, routed_scaling_factor=2.5, n_group=1,
    topk_group=1, num_nextn_predict_layers=0, tie_word_embeddings=False,
    max_position_embeddings=4096)
SEED = 2**31 + 30
F32_TOL = 1e-4  # float32 program against float32 reference


def tiny(held=16, first=0, **over):
    hf = dict(TINY_HF, num_experts=held, num_experts_published=16, expert_first=first)
    return ModelConfig.from_hf_config(hf, dtype="float32", param_dtype="float32", **over)


def seeded(cfg):
    """`weights.py`'s tree with the router's bias redrawn as the kind does,
    at a scale that moves the choice of many tokens."""
    p = kind_rollout_hybrid.redraw_router_bias(weights.seeded_params(cfg, SEED), SEED)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x * 5.0 if str(path[-1].key) == "router_bias" else x, p)


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


def _program_logprobs(params, cfg, ids):
    T = len(ids)
    logits = forward(params, jnp.asarray(ids), jnp.arange(T), jnp.zeros(T, jnp.int32), cfg)
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return np.asarray(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])


# -- registry -----------------------------------------------------------------


def test_from_hf_config_on_the_catalogs_config():
    hf = dict(CATALOG, num_nextn_predict_layers=0)
    cfg = ModelConfig.from_hf_config(hf)
    assert cfg.num_hidden_layers == 48 and len(cfg.layer_types) == 48
    assert cfg.layer_types[:5] == ("sliding_attention",) * 3 + ("full_attention", "sliding_attention")
    assert (cfg.num_experts, cfg.num_experts_published_, cfg.num_experts_per_tok) == (128, 128, 8)
    assert (cfg.moe_intermediate_size, cfg.shared_expert_intermediate_size) == (2048, 2048)
    assert (cfg.first_k_dense, cfg.sliding_window, cfg.rope_theta) == (1, 128, 1000000)
    assert cfg.moe_scoring == "sigmoid" and cfg.routed_scaling_factor == 2.5
    assert cfg.qk_norm and not cfg.qk_norm_full and not cfg.qkv_bias and cfg.nope_full_layers
    assert not cfg.scan_layers and cfg.mixed and not cfg.shared_expert_gated
    assert [cfg.layer_rope(i) for i in range(4)] == [True, True, True, False]
    assert [cfg.layer_sparse(i) for i in range(3)] == [False, True, True]
    assert len(cfg.cache_layers["full"]) == 12 and len(cfg.cache_layers["window"]) == 36
    hash(cfg)  # a jit static


def test_the_configuration_file_holds_the_catalogs_keys_and_cuts_four():
    differs = sorted(k for k, v in CATALOG.items() if CONFIG_FILE.get(k) != v)
    assert differs == sorted(CONFIG_FILE["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size", "num_nextn_predict_layers"])


@pytest.mark.parametrize("over,err", [
    (dict(n_group=8), "n_group"),
    (dict(topk_group=4), "n_group"),
    (dict(num_nextn_predict_layers=1), "multi-token prediction"),
    (dict(layer_types=["sliding_attention"] * 4), "layer_types"),
    (dict(layer_types=["sliding_attention"] * 8 + ["chunked_attention"]), "layer_types"),
    (dict(layer_types=None), "layer_types"),
    (dict(mlp_layer_types=["sparse"] * 9), "mlp_layer_types"),
    (dict(scoring_func="tanh"), "scoring_func"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}), "rope_type"),
    (dict(sliding_window=None), "sliding_window"),
    (dict(num_experts=8, num_experts_published=16, expert_first=12), "holds experts"),
])
def test_what_from_hf_config_does_not_serve_raises(over, err):
    with pytest.raises((NotImplementedError, ValueError), match=err):
        ModelConfig.from_hf_config(dict(TINY_HF, **over))


def test_a_mixed_stack_does_not_stack():
    with pytest.raises(ValueError, match="scan_layers=False"):
        param_shapes(dataclasses.replace(FULL, scan_layers=True))
    shapes = param_shapes(PART)
    assert "gate_kernel" in shapes["layers_0"]["mlp"] and "router_kernel" not in shapes["layers_0"]["mlp"]
    assert shapes["layers_0"]["mlp"]["gate_kernel"] == (32, 48)
    assert shapes["layers_1"]["mlp"]["gate_kernel"] == (4, 32, 16)  # the held experts
    assert shapes["layers_1"]["mlp"]["router_kernel"] == (32, 16)  # the published width
    assert shapes["layers_1"]["mlp"]["router_bias"] == (16,)
    assert "shared_router_kernel" not in shapes["layers_1"]["mlp"]
    axes = qwen2.param_logical_axes(PART)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) == jax.tree.structure(
        shapes, is_leaf=lambda x: isinstance(x, tuple))


# -- the trainer's forward against the reference -------------------------------


@pytest.mark.parametrize("impl", ["auto", "dense"])  # window layers chunked, or dense
@pytest.mark.parametrize("n", [40, 23])
def test_forward_agrees_with_the_reference(params, impl, n):
    ids = _ids(n, n)
    cfg = dataclasses.replace(FULL, attn_impl=impl)
    got = _program_logprobs(params, cfg, ids)
    ref = kexaone_ref.token_logprobs(params, FULL, ids, pad_to=48)
    np.testing.assert_allclose(got, ref, atol=F32_TOL)


@pytest.fixture(scope="module")
def both_grads(params):
    ids = _ids(3, 36)

    def nll(p):
        T = len(ids)
        logits = forward(p, jnp.asarray(ids), jnp.arange(T), jnp.zeros(T, jnp.int32), FULL)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(lp[jnp.arange(T - 1), jnp.asarray(ids[1:])])

    return jax.value_and_grad(nll)(params), kexaone_ref.loss_and_grads(params, FULL, ids)


def test_loss_agrees_with_the_reference(both_grads):
    (loss, _), (ref_loss, _) = both_grads
    assert abs(float(loss) - float(ref_loss)) < F32_TOL


LEAVES = sorted(jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(
    param_shapes(FULL), is_leaf=lambda x: isinstance(x, tuple))[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_of_every_leaf_agrees_with_the_reference(both_grads, leaf):
    (_, grads), (_, ref_grads) = both_grads
    got = {jax.tree_util.keystr(p): g for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    ref = {jax.tree_util.keystr(p): g for p, g in jax.tree_util.tree_flatten_with_path(ref_grads)[0]}
    scale = max(float(jnp.abs(ref[leaf]).max()), 1e-3)
    np.testing.assert_allclose(np.asarray(got[leaf]), np.asarray(ref[leaf]), atol=2e-3 * scale)
    if "router_bias" in leaf:
        # the bias enters the choice, not the weight: no gradient reaches it
        assert float(jnp.abs(got[leaf]).max()) == 0.0
    elif "mlp" in leaf or "attn" in leaf:
        assert float(jnp.abs(ref[leaf]).max()) > 0.0


# -- the router ----------------------------------------------------------------


def test_the_routers_rule(params):
    """Sigmoid scores over the published width, the choice on score + bias,
    the weights the chosen scores over their sum, times 2.5; the shared
    expert added once, ungated."""
    mlp_p = params["layers_2"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, 32), jnp.float32)
    y, _ = moe_mlp(mlp_p, x, FULL)
    s = np.asarray(jax.nn.sigmoid(x @ mlp_p["router_kernel"]), np.float64)
    b = np.asarray(mlp_p["router_bias"], np.float64)
    order = np.argsort(-(s + b), axis=-1)[:, :4]
    assert (order != np.argsort(-s, axis=-1)[:, :4]).any(), "the bias moves no choice: a weak test"
    want = np.zeros((24, 32))
    xs = np.asarray(x, np.float64)

    def swiglu(h, g, u, d):
        a = h @ np.asarray(g, np.float64)
        return ((a / (1 + np.exp(-a))) * (h @ np.asarray(u, np.float64))) @ np.asarray(d, np.float64)

    for t in range(24):
        w = s[t, order[t]]
        w = 2.5 * w / (w.sum() + 1e-20)
        for e, we in zip(order[t], w):
            want[t] += we * swiglu(xs[t], mlp_p["gate_kernel"][e], mlp_p["up_kernel"][e],
                                   mlp_p["down_kernel"][e])
    want += swiglu(xs, mlp_p["shared_gate_kernel"], mlp_p["shared_up_kernel"],
                   mlp_p["shared_down_kernel"])
    np.testing.assert_allclose(np.asarray(y), want, atol=F32_TOL)


def test_the_parts_all_shares_give_add_up_to_the_uncut_layer(params):
    """The guide's share test: four chips of four experts each, the shared
    expert counted once, against the uncut reference's layer; and the load
    counters of a share."""
    mlp_p = params["layers_5"]["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(8), (40, 32), jnp.float32)
    h = x  # the reference's _moe takes the normed rows
    with jax.default_matmul_precision("highest"):
        whole, _ = kexaone_ref._moe(mlp_p, h, 4, True, 2.5, 0)
        shared = kexaone_ref._swiglu(h, mlp_p["shared_gate_kernel"], mlp_p["shared_up_kernel"],
                                     mlp_p["shared_down_kernel"])
    total, pairs = np.zeros((40, 32)), 0
    for first in (0, 4, 8, 12):
        cfg = tiny(held=4, first=first)
        part = held_slice({"layers_5": {"mlp": mlp_p}}, first, 4)["layers_5"]["mlp"]
        y, _, load = moe_mlp(part, x, cfg, with_load=True)
        with jax.default_matmul_precision("highest"):
            ref, _ = kexaone_ref._moe(part, h, 4, True, 2.5, first)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=F32_TOL)
        total += np.asarray(y, np.float64) - np.asarray(shared, np.float64)
        n, hot, absent = (int(v) for v in load)
        assert n + absent == 40 * 4 and 0 < hot <= n
        pairs += n
    assert pairs == 40 * 4  # every pair is some chip's
    np.testing.assert_allclose(total + np.asarray(shared), np.asarray(whole), atol=4 * F32_TOL)
    full_y, _, full_load = moe_mlp(mlp_p, x, FULL, with_load=True)
    assert full_load.shape == (2,) and int(full_load[0]) == 160
    np.testing.assert_allclose(np.asarray(full_y), np.asarray(whole), atol=F32_TOL)


# -- the ring ------------------------------------------------------------------


@pytest.mark.parametrize("window,bsz", [(8, 4), (128, 128), (5, 4), (9, 4), (100, 16)])
def test_the_ring_reads_exactly_the_window(window, bsz):
    """Write positions 0..n-1 into a ring in order; at every step the cells
    `_ring_valid` admits hold exactly the window's positions."""
    pages = ring_pages(window, bsz)
    cells = np.full(pages * bsz, -1)
    for p in range(3 * pages * bsz + 5):
        blk, off = qwen2._ring_coords(jnp.asarray([p]), jnp.asarray([0]), bsz, pages)
        cells[(int(blk[0]) - 1) * bsz + int(off[0])] = p
        seen = np.asarray(qwen2._ring_valid(jnp.asarray([p]), window, bsz, pages))[0]
        assert sorted(cells[seen]) == list(range(max(p - window + 1, 0), p + 1)), p


def test_what_a_ring_holds():
    ring = WindowRing(n_slots=3, window=128, block_size=128)
    assert ring.pages == 2 and ring.n_blocks == 7 and list(ring.blocks(2)) == [5, 6]
    ring.reset(1, 1000)  # a prompt of 1,000 rows
    assert ring.holds(1, 1000) and not ring.holds(1, 1001)
    ring.note_written(np.array([1]), np.array([1128]))  # one chunk later
    assert ring.holds(1, 1000)
    ring.note_written(np.array([1]), np.array([1130]))  # row 873's cell is overwritten
    assert not ring.holds(1, 1000) and ring.holds(1, 1100)
    ring.note_written(np.array([1]), np.array([900]))  # a rewind does not bring rows back
    assert ring.hi[1] == 1130


# -- prefill, then decode through the ring and the paged cache -----------------


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


@pytest.mark.parametrize("held,first", [(16, 0), (4, 8)])
def test_prefill_then_decode_through_ring_and_pages(params, held, first):
    """A group of three through `JaxDecodeEngine`: one prefill and two forks
    (full layers' blocks aliased, ring pages copied), 40 new tokens over a
    ring of three pages of 4 (many wraps) and chunks of 16, against the
    reference's full forward."""
    cfg = tiny(held, first)
    p = held_slice(params, first, held)
    engine = _engine(cfg, p).initialize()
    try:
        pools = engine._kv_operands()[0]
        L = cfg.cache_layers
        assert pools["full"].shape[:2] == (len(L["full"]), 4 * 64 + 1)
        assert pools["window"].shape[:2] == (len(L["window"]), 1 + 4 * 3)  # slots x ring pages
        resps = _group(engine, _ids(9, 70).tolist(), (40, 25, 33))
        m = engine.get_metrics()
    finally:
        engine.destroy()
    assert (m["prefills_total"], m["prefix_forks_total"]) == (1, 2)
    for r in resps:
        seq = list(r.input_tokens) + list(r.output_tokens)
        ref = kexaone_ref.token_logprobs(p, cfg, seq)
        np.testing.assert_allclose(np.asarray(r.output_logprobs), ref[r.input_len - 1:],
                                   atol=10 * F32_TOL)
    steps = m["moe_pairs_total"] + m["moe_absent_pairs_total"]
    assert steps % (4 * 8) == 0 and steps >= (40 + 25 + 33) * 4 * 8  # k pairs x 8 sparse layers
    assert (m["moe_absent_pairs_total"] == 0) == (held == 16)
    assert m["kv_window_rows_read_total"] > 0
    assert m["kv_full_rows_read_total"] > m["kv_window_rows_read_total"]


def test_a_prompt_longer_than_the_chunked_bucket(params):
    """Above `PREFILL_DENSE_MAX` the prefill's attention goes a block of keys
    at a time, window and full layers alike."""
    prompt = _ids(11, qwen2.PREFILL_DENSE_MAX + 70).tolist()
    engine = _engine(FULL, params, context_length=1280, page_size=16).initialize()
    try:
        (r,) = _group(engine, prompt, (20,))
    finally:
        engine.destroy()
    ref = kexaone_ref.token_logprobs(params, FULL, list(r.input_tokens) + list(r.output_tokens))
    np.testing.assert_allclose(np.asarray(r.output_logprobs), ref[r.input_len - 1:],
                               atol=10 * F32_TOL)


def test_long_prompts_of_one_wave_are_prefilled_one_a_program(params):
    """Distinct prompts of one bucket share a batched prefill up to
    `PREFILL_DENSE_MAX` tokens, as for every model; above it (the chunked
    prefill) each goes through a program of its own."""
    import asyncio

    async def wave(engine, prompts):
        engine.pause_generation()
        tasks = [asyncio.ensure_future(engine.agenerate(kind_rollout._request(p, 2, 1.0)))
                 for p in prompts]
        await asyncio.sleep(0)
        engine.continue_generation()
        return await asyncio.gather(*tasks)

    long = qwen2.PREFILL_DENSE_MAX + 70
    engine = _engine(FULL, params, context_length=1280, page_size=16).initialize()
    try:
        asyncio.run(wave(engine, [_ids(21, 40).tolist(), _ids(22, 40).tolist()]))
        assert set(engine._batched_prefill_fns) == {(64, 2)}
        asyncio.run(wave(engine, [_ids(23, long).tolist(), _ids(24, long).tolist()]))
        m, batched = engine.get_metrics(), set(engine._batched_prefill_fns)
    finally:
        engine.destroy()
    assert m["prefills_total"] == 4 and batched == {(64, 2), (1152, 1)}


def test_a_donor_whose_ring_has_moved_on_is_not_forked(params):
    """A second request with the first one's prompt, after the first has
    decoded far past the window: its ring no longer holds the prompt's tail,
    so the engine prefills again (and agrees with the reference)."""
    engine = _engine(FULL, params).initialize()
    try:
        prompt = _ids(12, 50).tolist()
        first = engine.generate(kind_rollout._request(prompt, 60, 1.0), 300.0)
        again = engine.generate(kind_rollout._request(prompt, 10, 1.0), 300.0)
        m = engine.get_metrics()
    finally:
        engine.destroy()
    assert m["prefills_total"] == 2 and m["prefix_forks_total"] + m["prefix_inplace_total"] == 0
    for r in (first, again):
        ref = kexaone_ref.token_logprobs(params, FULL, list(r.input_tokens) + list(r.output_tokens))
        np.testing.assert_allclose(np.asarray(r.output_logprobs), ref[r.input_len - 1:],
                                   atol=10 * F32_TOL)


def test_prefill_with_a_prefix_reads_each_layers_kind(params):
    """`prefill_with_prefix` over a cached prefix equals the tail of a whole
    prefill, window and full layers alike."""
    ids = jnp.asarray(_ids(13, 48))
    _, ks, vs = prefill(params, ids, jnp.arange(48), FULL, with_logits=False)
    n = 29
    sk, sv = prefill_with_prefix(params, ids[n:], ks[:, :32], vs[:, :32], jnp.int32(n), FULL)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(ks[:, n:]), atol=F32_TOL)
    np.testing.assert_allclose(np.asarray(sv), np.asarray(vs[:, n:]), atol=F32_TOL)


def test_the_verify_step_agrees_with_the_decode_step(params):
    """Three positions a slot in one forward against three decode steps, over
    ring and pages (the ring's slack holds the width)."""
    cfg, bsz, R = FULL, 4, 2
    pages = ring_pages(cfg.sliding_window, bsz)
    L = cfg.cache_layers

    def pools():
        z = lambda n, blocks: jnp.zeros((n, blocks, bsz, 2 * 8), jnp.float32)  # noqa: E731
        return {"full": z(len(L["full"]), 1 + R * 8), "window": z(len(L["window"]), 1 + R * pages)}

    bt = jnp.asarray(1 + np.arange(R * 8).reshape(R, 8), jnp.int32)
    toks = jnp.asarray(np.random.default_rng(4).integers(1, 96, (R, 14)), jnp.int32)
    kp, vp = pools(), pools()
    for t in range(11):
        _, kp, vp = qwen2.decode_step_paged(params, toks[:, t], jnp.full(R, t, jnp.int32), kp, vp,
                                            bt, cfg, attn_impl="xla")
    want, k1, v1 = [], kp, vp
    for j in range(3):
        lg, k1, v1 = qwen2.decode_step_paged(params, toks[:, 11 + j], jnp.full(R, 11 + j, jnp.int32),
                                             k1, v1, bt, cfg, attn_impl="xla")
        want.append(lg)
    got, _, _ = qwen2.verify_step_paged(params, toks[:, 11:14], jnp.full(R, 11, jnp.int32), kp, vp,
                                        bt, cfg, attn_impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.stack([np.asarray(w) for w in want], 1),
                               atol=F32_TOL)


# -- what the engine does not serve for a mixed stack --------------------------


@pytest.mark.parametrize("over,why", [
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(kv_host_pool_mb=16.0), "host tier"),
    (dict(role="prefill"), "migration"),
    (dict(spec_decode="ngram", spec_k=8), "verify"),
])
def test_what_initialize_refuses(params, over, why):
    engine = _engine(FULL, params, **over)
    with pytest.raises(NotImplementedError, match=why):
        engine.initialize()
    engine.destroy()


def test_migration_calls_refuse(params):
    engine = _engine(FULL, params).initialize()
    try:
        for call in (lambda: engine.export_session("x"), lambda: engine.import_session({}, None, None),
                     lambda: engine.export_fabric_blocks([])):
            with pytest.raises(NotImplementedError, match="mixed stack"):
                call()
    finally:
        engine.destroy()


# -- HF tensor names -----------------------------------------------------------


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
    names = {ours_name_to_hf(path, "exaone_moe"): w.shape
             for path, w in flatten_params(p, PART).items()}
    assert names["model.layers.0.mlp.gate_proj.weight"] == (32, 48)  # the dense layer, 2-D
    assert names["model.layers.1.mlp.gate.e_score_correction_bias"] == (16,)
    assert names["model.layers.1.mlp.shared_experts.down_proj.weight"] == (16, 32)
    assert names["model.layers.3.self_attn.q_norm.weight"] == (8,)
    # a chip's experts keep their published numbers
    assert "model.layers.2.mlp.experts.8.up_proj.weight" in names
    assert "model.layers.2.mlp.experts.11.up_proj.weight" in names
    assert "model.layers.2.mlp.experts.0.up_proj.weight" not in names
    assert all(hf_name_to_ours(n) is not None for n in names)

    out = save_hf_params(p, PART, str(tmp_path / "ckpt"))
    with open(os.path.join(out, "config.json"), "w") as f:
        json.dump(dict(TINY_HF, num_experts=4, num_experts_published=16, expert_first=8), f)
    cfg = ModelConfig.from_hf_config(out, dtype="float32", param_dtype="float32")
    loaded = load_hf_params(out, cfg, dtype="float32")
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
                 p, loaded)
