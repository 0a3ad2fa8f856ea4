"""SDAR-MoE (`model_type: sdar_moe`: the Qwen3-MoE decoder layer under a
block-causal mask) at a tiny width on the CPU, seeded random weights: the
config reader, `forward` and its gradient, `prefill` (dense and chunked) and
the block step over a paged pool against the plain float32 reference
(`benchmark/reference/sdar_ref.py`); what fails (a causal mask, one precision
lower); what the flash and ring kernels refuse; the tensor names; and every
OTHER model's lowered programs, text-equal to the commit before the block
mask came (their SHA-256, recorded there: `python tests/test_sdar.py` prints
them)."""

import dataclasses
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from areal_tpu.models import qwen2  # noqa: E402
from areal_tpu.models.qwen2 import ModelConfig, init_params  # noqa: E402

TOL = 2e-5  # float32 program against float32 reference, log-probabilities
MASK = 95

HF = dict(
    model_type="sdar_moe", vocab_size=96, hidden_size=32, intermediate_size=64,
    moe_intermediate_size=16, num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, num_experts=8, num_experts_per_tok=2,
    norm_topk_prob=True, rope_theta=1e6, rms_norm_eps=1e-6, tie_word_embeddings=False,
    attention_bias=False, decoder_sparse_step=1, mlp_only_layers=[],
    use_sliding_window=False, sliding_window=None, max_window_layers=2,
    block_length=4, mask_token_id=MASK,
)


def tiny(**over):
    return ModelConfig.from_hf_config({**HF, **over}, dtype="float32", param_dtype="float32")


CFG = tiny()


@pytest.fixture(scope="module")
def params():
    from benchmark.lib.weights import seeded_params

    return seeded_params(CFG, 7)


def _ids(seed, n):
    return np.random.default_rng(seed).integers(1, 90, n).astype(np.int32)


def _forward_logprobs(params, cfg, ids):
    T = len(ids)
    logits = qwen2.forward(params, jnp.asarray(ids), jnp.arange(T, dtype=jnp.int32),
                           jnp.zeros(T, jnp.int32), cfg)
    return np.asarray(jax.nn.log_softmax(logits, axis=-1))


# -- the config -----------------------------------------------------------------

def test_sdar_moe_is_read_as_qwen3_moe_under_a_block_mask():
    assert "sdar_moe" in qwen2.MODEL_TYPES
    cfg = ModelConfig.from_hf_config({k: v for k, v in HF.items()
                                      if k not in ("block_length", "mask_token_id")})
    # the family's published defaults where config.json is silent
    assert (cfg.block_length, cfg.mask_token_id) == (4, 151669)
    assert cfg.qk_norm and not cfg.qk_norm_full and not cfg.qkv_bias
    assert (cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_intermediate_size) == (8, 2, 16)
    assert cfg.norm_topk_prob and cfg.sliding_window is None and not cfg.mixed
    assert (CFG.block_length, CFG.mask_token_id, CFG.block_length_) == (4, MASK, 4)
    hash(CFG)  # a jit static
    moe = ModelConfig.from_hf_config({**HF, "model_type": "qwen3_moe"})
    assert moe.block_length is None and moe.block_length_ == 1
    with pytest.raises(NotImplementedError, match="heterogeneous"):
        ModelConfig.from_hf_config({**HF, "mlp_only_layers": [0]})


def test_tensor_names_are_qwen3_moes():
    from areal_tpu.models.hf_io import ours_name_to_hf

    names = {
        ("layers_3", "attn", "q_kernel"): "model.layers.3.self_attn.q_proj.weight",
        ("layers_3", "attn", "o_kernel"): "model.layers.3.self_attn.o_proj.weight",
        ("layers_3", "attn", "q_norm"): "model.layers.3.self_attn.q_norm.weight",
        ("layers_3", "attn", "k_norm"): "model.layers.3.self_attn.k_norm.weight",
        ("layers_3", "mlp", "router_kernel"): "model.layers.3.mlp.gate.weight",
        ("layers_3", "mlp", "expert_5", "gate_kernel"):
            "model.layers.3.mlp.experts.5.gate_proj.weight",
        ("layers_3", "mlp", "expert_5", "down_kernel"):
            "model.layers.3.mlp.experts.5.down_proj.weight",
        ("lm_head", "kernel"): "lm_head.weight",
    }
    for path, want in names.items():
        assert ours_name_to_hf(path, "sdar_moe") == want
        assert ours_name_to_hf(path, "qwen3_moe") == want


# -- forward, its gradient, prefill ------------------------------------------------

@pytest.mark.parametrize("impl", ["dense", "chunked"])
@pytest.mark.parametrize("T", [14, 16])
def test_forward_meets_the_reference_under_the_block_mask(params, impl, T):
    from benchmark.reference import sdar_ref

    ids = _ids(T, T)
    ref = sdar_ref.forward_logits(params, CFG, ids)
    got = _forward_logprobs(params, dataclasses.replace(CFG, attn_impl=impl), ids)
    assert np.abs(got - ref).max() < TOL


def test_a_packed_stream_keeps_each_sequences_own_blocks(params):
    """Two sequences in one stream, the second starting off a block boundary
    of the stream: blocks follow each sequence's positions."""
    from benchmark.reference import sdar_ref

    a, b = _ids(1, 6), _ids(2, 9)
    ids = np.concatenate([a, b, np.zeros(1, np.int32)])
    pos = np.concatenate([np.arange(6), np.arange(9), np.zeros(1)]).astype(np.int32)
    seg = np.concatenate([np.zeros(6), np.ones(9), [qwen2.PADDING_SEGMENT]]).astype(np.int32)
    for impl in ("dense", "chunked"):
        cfg = dataclasses.replace(CFG, attn_impl=impl)
        got = np.asarray(jax.nn.log_softmax(qwen2.forward(
            params, jnp.asarray(ids), jnp.asarray(pos), jnp.asarray(seg), cfg), axis=-1))
        assert np.abs(got[:6] - sdar_ref.forward_logits(params, CFG, a)).max() < TOL
        assert np.abs(got[6:15] - sdar_ref.forward_logits(params, CFG, b)).max() < TOL


def test_the_gradient_meets_the_references(params):
    from benchmark.reference import sdar_ref

    ids = _ids(5, 12)
    loss_ref, g_ref = sdar_ref.loss_and_grads(params, CFG, ids)

    def nll(p):
        T = len(ids)
        logits = qwen2.forward(p, jnp.asarray(ids), jnp.arange(T, dtype=jnp.int32),
                               jnp.zeros(T, jnp.int32), CFG)
        lp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(lp[jnp.arange(T), jnp.asarray(ids)])

    loss, g = jax.value_and_grad(nll)(params)
    assert abs(float(loss) - float(loss_ref)) < TOL
    flat, flat_ref = jax.tree.leaves(g), jax.tree.leaves(g_ref)
    assert len(flat) == len(flat_ref) >= 10
    scale = max(float(jnp.abs(r).max()) for r in flat_ref)
    assert scale > 1e-3
    for a, r in zip(flat, flat_ref):
        assert float(jnp.abs(a - r).max()) < 1e-4 * scale


def test_a_causal_mask_fails(params):
    from benchmark.reference import sdar_ref
    from benchmark.reference.sdar_ref import MEAN_ABS_TOL

    ids = _ids(3, 16)
    ref = sdar_ref.forward_logits(params, CFG, ids)
    causal = _forward_logprobs(params, dataclasses.replace(CFG, block_length=None), ids)
    own = np.abs(causal - ref)[np.arange(16), ids]
    assert own.mean() > 2 * MEAN_ABS_TOL


@pytest.mark.parametrize("impl", ["flash", "ring"])
def test_the_flash_and_ring_kernels_refuse_a_block_mask(impl):
    with pytest.raises(NotImplementedError, match="block-causal"):
        qwen2.resolve_attn_impl(dataclasses.replace(CFG, attn_impl=impl))
    assert qwen2.resolve_attn_impl(dataclasses.replace(CFG, attn_impl="chunked")) == "chunked"
    assert qwen2.resolve_attn_impl(CFG) == "dense"  # auto, off the chip


@pytest.mark.parametrize("bucket,covered", [(16, 12), (64, 40)])
def test_prefill_honours_the_block_mask(params, bucket, covered, monkeypatch):
    """Dense, and (the threshold lowered) the chunked attention of a long
    bucket: logits and rows of the whole blocks, padding after them."""
    from benchmark.reference import sdar_ref

    ids = _ids(bucket, covered)
    padded = np.zeros(bucket, np.int32)
    padded[:covered] = ids
    ref = sdar_ref.forward_logits(params, CFG, ids)
    outs = {}
    for name, dense_max in (("dense", 1024), ("chunked", 8)):
        monkeypatch.setattr(qwen2, "PREFILL_DENSE_MAX", dense_max)
        logits, ks, vs = qwen2.prefill(
            params, jnp.asarray(padded), jnp.arange(bucket, dtype=jnp.int32), CFG,
            valid=jnp.arange(bucket) < covered)
        got = np.asarray(jax.nn.log_softmax(logits, axis=-1))[:covered]
        assert np.abs(got - ref).max() < TOL, name
        outs[name] = (np.asarray(ks)[:, :covered], np.asarray(vs)[:, :covered])
    assert np.abs(outs["dense"][0] - outs["chunked"][0]).max() < TOL
    with pytest.raises(NotImplementedError, match="block boundary"):
        qwen2.prefill_with_prefix(params, jnp.asarray(padded), outs["dense"][0],
                                  outs["dense"][1], jnp.int32(4), CFG)


# -- the block step over a paged pool ------------------------------------------------

def _pool_after_prefill(params, ids, covered, page=4, pages=8):
    """A pool of one slot whose pages 1.. hold the rows of `ids[:covered]`."""
    L, nkv, hd = CFG.num_hidden_layers, CFG.num_key_value_heads, CFG.head_dim_
    _, ks, vs = qwen2.prefill(params, jnp.asarray(ids[:covered]),
                              jnp.arange(covered, dtype=jnp.int32), CFG, with_logits=False)
    pools = []
    for rows in (ks, vs):
        pool = jnp.zeros((L, pages + 1, page, nkv * hd), jnp.float32)
        rows = jnp.pad(rows.reshape(L, covered, -1), ((0, 0), (0, pages * page - covered), (0, 0)))
        pools.append(pool.at[:, 1:].set(rows.reshape(L, pages, page, -1)))
    return pools[0], pools[1], jnp.arange(1, pages + 1, dtype=jnp.int32)[None]


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_block_step_meets_the_reference_at_every_state(params, impl):
    """Prefill of two blocks, then a third block through its states: all
    masks, two revealed, clean (the commit pass, whose rows the next block
    reads), then the fourth block's first state over the committed rows."""
    from benchmark.reference import sdar_ref

    ids = _ids(11, 16)
    kp, vp, bt = _pool_after_prefill(params, ids, 8)
    step = jax.jit(lambda kp, vp, toks, base: qwen2.diffusion_step_paged(
        params, toks, base, kp, vp, bt, CFG, active=jnp.ones(1, bool), attn_impl=impl,
        moe_load=True))
    blk = ids[8:12]
    for state in ([MASK] * 4, [blk[0], MASK, MASK, blk[3]], list(blk)):
        logits, kp, vp, load = step(kp, vp, jnp.asarray([state], jnp.int32),
                                    jnp.asarray([8], jnp.int32))
        got = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))
        ref = sdar_ref.state_logprobs(params, CFG, ids[:8], state)
        assert np.abs(got - ref).max() < TOL
        # [pairs, busiest expert's pairs, cached rows read]: 4 rows x top-2 x
        # 2 layers; the block's horizon is 12 rows a layer
        assert load.tolist()[0] == 16 and load.tolist()[2] == 24
    logits, kp, vp, _ = step(kp, vp, jnp.full((1, 4), MASK, jnp.int32),
                             jnp.asarray([12], jnp.int32))
    got = np.asarray(jax.nn.log_softmax(logits[0], axis=-1))
    assert np.abs(got - sdar_ref.state_logprobs(params, CFG, ids[:12], [MASK] * 4)).max() < TOL
    none, *_ = qwen2.diffusion_step_paged(
        params, jnp.asarray([list(blk)], jnp.int32), jnp.asarray([8], jnp.int32), kp, vp, bt,
        CFG, with_logits=False)
    assert none is None
    with pytest.raises(ValueError, match="block_length"):
        qwen2.diffusion_step_paged(
            params, jnp.asarray([list(blk)], jnp.int32), jnp.asarray([8], jnp.int32), kp, vp,
            bt, dataclasses.replace(CFG, block_length=None))


def test_one_precision_lower_fails(params):
    """The reference with its weights at float8's three mantissa bits against
    the float32 program: outside the tolerances the cell is held to."""
    from benchmark.reference import sdar_ref
    from benchmark.reference.sdar_ref import MEAN_ABS_TOL

    ids = _ids(13, 16)
    got = _forward_logprobs(params, CFG, ids)[np.arange(16), ids]
    low = sdar_ref.forward_logits(sdar_ref.round_mantissa(params, 3), CFG, ids)[np.arange(16), ids]
    d = np.abs(got - low)
    assert d.mean() > MEAN_ABS_TOL
    same = sdar_ref.forward_logits(sdar_ref.round_mantissa(params, 23), CFG, ids)
    assert np.abs(same[np.arange(16), ids] - got).max() < TOL


# -- every other model's programs are the parent's -----------------------------------

DENSE = dict(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, dtype="float32",
             param_dtype="float32")
OTHERS = {
    "qwen2": ModelConfig(**DENSE),
    "olmoe": ModelConfig(**DENSE, model_type="olmoe", qkv_bias=False, qk_norm=True,
                         qk_norm_full=True, num_experts=8, num_experts_per_tok=2,
                         moe_intermediate_size=16, norm_topk_prob=False),
    "mistral_window": ModelConfig(**DENSE, model_type="mistral", qkv_bias=False,
                                  sliding_window=8),
}


def lowered_programs() -> dict:
    """name -> the StableHLO text of a program of a model that is not a
    block-diffusion model: the trainer's forward (dense and chunked), the
    prefill, the decode step, the verify step, and the engine's chunk."""
    from areal_tpu.api.cli_args import InferenceEngineConfig, JaxDecodeConfig
    from areal_tpu.engine.jax_decode import JaxDecodeEngine

    out = {}
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    for name, cfg in OTHERS.items():
        p = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        for impl in ("dense", "chunked"):
            c = dataclasses.replace(cfg, attn_impl=impl)
            out[f"{name}.forward.{impl}"] = jax.jit(
                lambda p, i, q, s, c=c: qwen2.forward(p, i, q, s, c)
            ).lower(p, i32(24), i32(24), i32(24)).as_text()
        out[f"{name}.prefill"] = jax.jit(
            lambda p, i, q, c=cfg: qwen2.prefill(p, i, q, c)
        ).lower(p, i32(16), i32(16)).as_text()
        L, D = cfg.num_hidden_layers, cfg.num_key_value_heads * cfg.head_dim_
        pool = jax.ShapeDtypeStruct((L, 9, 4, D), jnp.float32)
        out[f"{name}.decode_step"] = jax.jit(
            lambda p, t, q, k, v, b, c=cfg: qwen2.decode_step_paged(p, t, q, k, v, b, c,
                                                                    attn_impl="xla")
        ).lower(p, i32(2), i32(2), pool, pool, i32(2, 4)).as_text()
        out[f"{name}.verify_step"] = jax.jit(
            lambda p, t, q, k, v, b, c=cfg: qwen2.verify_step_paged(p, t, q, k, v, b, c,
                                                                    attn_impl="xla")
        ).lower(p, i32(2, 3), i32(2), pool, pool, i32(2, 4)).as_text()
    for name in ("qwen2", "olmoe"):
        cfg = OTHERS[name]
        eng = JaxDecodeEngine(
            JaxDecodeConfig(context_length=64, max_running_requests=2, new_tokens_per_chunk=4,
                            page_size=16, dtype="float32", kv_cache_dtype="float32"),
            InferenceEngineConfig())
        eng.set_model(init_params(cfg, jax.random.PRNGKey(0)), cfg)
        eng.initialize()
        try:
            kq, vq = eng._kv_operands()
            R = 2
            f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
            args = (eng.params, kq, vq, i32(R, 2), i32(R), i32(R),
                    jax.ShapeDtypeStruct((R,), bool), jax.ShapeDtypeStruct((R, 2), jnp.uint32),
                    f32(R), f32(R), jax.ShapeDtypeStruct((R,), bool), i32(R))
            out[f"{name}.engine_chunk"] = eng._get_chunk_fn(False, False, 2).lower(*args).as_text()
            out[f"{name}.engine_verify"] = eng._get_verify_fn(False, 2, 3).lower(
                *args, i32(R, 2), i32(R)).as_text()
        finally:
            eng.destroy()
    return out


# recorded at 8efe7dd (PR 35), this file's `lowered_programs` run there
PARENT_SHA256 = {
    "mistral_window.decode_step": "7d8a11e59634ce78",
    "mistral_window.forward.chunked": "7d9dc2d9a579aec0",
    "mistral_window.forward.dense": "8c912392e76e3191",
    "mistral_window.prefill": "92a3321f5eee62e7",
    "mistral_window.verify_step": "dd9051542bb4130a",
    "olmoe.decode_step": "e239787e978a9522",
    "olmoe.engine_chunk": "b86d835ab6e1ae77",
    "olmoe.engine_verify": "31f5f2a50e736ec7",
    "olmoe.forward.chunked": "187ab6cfe1ce79fc",
    "olmoe.forward.dense": "068535e4b1ddb9f9",
    "olmoe.prefill": "ebb37ac801b94dda",
    "olmoe.verify_step": "84657f50e3cf8bed",
    "qwen2.decode_step": "2d21c9e9178f07a9",
    "qwen2.engine_chunk": "6c424689f0cab802",
    "qwen2.engine_verify": "c799ea5c9806a984",
    "qwen2.forward.chunked": "782ca2633b4af6ee",
    "qwen2.forward.dense": "465c709095d11c1c",
    "qwen2.prefill": "805a58589968d955",
    "qwen2.verify_step": "322b048cad84d623",
}


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.fixture(scope="module")
def lowered():
    return lowered_programs()


@pytest.mark.parametrize("name", sorted(PARENT_SHA256))
def test_other_models_lowered_programs_are_the_parents(lowered, name):
    assert _sha(lowered[name]) == PARENT_SHA256[name], (
        f"{name}: the lowered program of a model with no block mask changed; if the change "
        "is meant, record `python tests/test_sdar.py` anew")


def test_a_block_length_of_one_is_the_causal_model():
    assert len(PARENT_SHA256) >= 19
    cfg = OTHERS["qwen2"]
    one = dataclasses.replace(cfg, block_length=1, mask_token_id=3)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    p = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    texts = [jax.jit(lambda p, i, q, s, c=c: qwen2.forward(p, i, q, s, c)
                     ).lower(p, i32(24), i32(24), i32(24)).as_text() for c in (cfg, one)]
    assert texts[0] == texts[1]


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for k, v in sorted(lowered_programs().items()):
        print(f'    "{k}": "{_sha(v)}",')


def test_weights_rounded_as_they_are_used_are_the_rounded_tree(params):
    """`state_logprobs(weight_bits=3)` (no second tree: what the chip has
    room for) reads what `round_mantissa`'s tree reads."""
    from benchmark.reference import sdar_ref

    ids = _ids(17, 12)
    a = sdar_ref.state_logprobs(params, CFG, ids[:8], ids[8:], weight_bits=3)
    b = sdar_ref.state_logprobs(sdar_ref.round_mantissa(params, 3), CFG, ids[:8], ids[8:])
    assert np.abs(a - b).max() < TOL
    assert np.abs(a - sdar_ref.state_logprobs(params, CFG, ids[:8], ids[8:])).max() > 0.05
