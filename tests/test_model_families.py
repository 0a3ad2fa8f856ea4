"""HF numerical parity for the widened model-family registry.

The reference supports gemma / mixtral / qwen2_moe through its per-family
from_hf converters (realhf/api/from_hf/{gemma,mixtral,qwen2.py + registry});
here one flag-parameterized decoder covers them, so each family gets a
golden test against the transformers implementation on a tiny random
checkpoint, exercising config parsing, weight mapping, and forward math.
"""

import json

import numpy as np
import pytest

pytestmark = pytest.mark.slow

import jax

from areal_tpu.models.hf_io import load_hf_params, save_hf_params
from areal_tpu.models.qwen2 import ModelConfig, decode_step_paged, forward, prefill

torch = pytest.importorskip("torch")


def _decode_consistency(cfg, params, T=10, atol=2e-3):
    """prefill + decode_step_paged must agree with the packed training
    forward — the decode engine serves THESE functions, and family-specific
    terms (o_bias, wpe, shared expert) are easy to drop from one path only."""
    rng = np.random.RandomState(7)
    ids = rng.randint(0, cfg.vocab_size, (T,))
    ref = np.asarray(
        forward(params, ids, np.arange(T), np.zeros(T, dtype=np.int32), cfg)
    )
    logits, ks, vs = prefill(params, ids[:-1], np.arange(T - 1), cfg)
    np.testing.assert_allclose(np.asarray(logits), ref[:-1], atol=atol, rtol=1e-3)

    # slot 0 holds the prompt in blocks 1 and 2 of a paged pool; slot 1 is
    # dead and writes to null block 0
    L = cfg.num_hidden_layers
    D = cfg.num_key_value_heads * cfg.head_dim_
    bsz, R = 8, 2
    nb = -(-(T + 1) // bsz)
    k_pool = np.zeros((L, 1 + R * nb, bsz, D), np.float32)
    v_pool = np.zeros((L, 1 + R * nb, bsz, D), np.float32)
    bt = np.arange(1, 1 + R * nb, dtype=np.int32).reshape(R, nb)
    for pool, rows in ((k_pool, ks), (v_pool, vs)):
        rows = np.asarray(rows).reshape(L, T - 1, D)
        for p in range(T - 1):
            pool[:, bt[0, p // bsz], p % bsz] = rows[:, p]
    lg, _, _ = decode_step_paged(
        params,
        np.array([ids[-1], 0], np.int32),
        np.array([T - 1, 0], np.int32),
        k_pool,
        v_pool,
        bt,
        cfg,
        active=np.array([True, False]),
        attn_impl="xla",
    )
    np.testing.assert_allclose(np.asarray(lg)[0], ref[-1], atol=atol, rtol=1e-3)


def _randomize_biases(model):
    """HF inits GPT-2 biases to zero; perturb them so bias-dropping bugs
    can't hide behind zeros."""
    with torch.no_grad():
        for n, p in model.named_parameters():
            if n.endswith(".bias"):
                p.add_(torch.randn_like(p) * 0.05)


def _save_tiny(model, tmp_path, expect_type):
    model_dir = tmp_path / "hf"
    model.save_pretrained(model_dir, safe_serialization=True)
    with open(model_dir / "config.json") as f:
        assert json.load(f)["model_type"] == expect_type
    return str(model_dir)


def _parity(model, model_dir, vocab, T=12, atol=2e-3, **overrides):
    cfg = ModelConfig.from_hf_config(
        model_dir, dtype="float32", param_dtype="float32", **overrides
    )
    params = load_hf_params(model_dir, cfg)
    rng = np.random.RandomState(1)
    ids = rng.randint(0, vocab, (T,))
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids)[None]).logits[0].numpy()
    ours = np.asarray(
        forward(params, ids, np.arange(T), np.zeros(T, dtype=np.int32), cfg)
    )
    np.testing.assert_allclose(ours, hf_logits, atol=atol, rtol=1e-3)
    return cfg, params


def test_gemma_numerical_parity(tmp_path):
    """Gemma-1: GeGLU MLP, zero-centered RMSNorm, sqrt(H)-scaled embeddings,
    tied lm_head, explicit head_dim != H/nH."""
    from transformers import GemmaConfig, GemmaForCausalLM

    hf_cfg = GemmaConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,  # nH*hd = 64 != H=32: the real gemma geometry quirk
        max_position_embeddings=128,
        rms_norm_eps=1e-6,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = GemmaForCausalLM(hf_cfg).eval().float()
    model_dir = _save_tiny(model, tmp_path, "gemma")
    cfg, params = _parity(model, model_dir, 96)
    _decode_consistency(cfg, params)
    assert cfg.norm_zero_centered and cfg.normalize_embed
    assert cfg.tie_word_embeddings and not cfg.qkv_bias
    assert cfg.hidden_act == "gelu_pytorch_tanh"


def test_mixtral_numerical_parity(tmp_path):
    """Mixtral: block_sparse_moe.* weight names, w1/w3/w2 expert layout,
    renormalized top-k routing."""
    from transformers import MixtralConfig, MixtralForCausalLM

    hf_cfg = MixtralConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=48,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_local_experts=4,
        num_experts_per_tok=2,
        max_position_embeddings=128,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = MixtralForCausalLM(hf_cfg).eval().float()
    model_dir = _save_tiny(model, tmp_path, "mixtral")
    cfg, params = _parity(model, model_dir, 96)
    assert cfg.num_experts == 4 and cfg.norm_topk_prob
    assert cfg.moe_intermediate_size_ == 48

    # roundtrip preserves mixtral naming
    out = save_hf_params(params, cfg, str(tmp_path / "ckpt"))
    reloaded = load_hf_params(out, cfg, dtype="float32")
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        ),
        params,
        reloaded,
    )


def test_qwen2_moe_numerical_parity(tmp_path):
    """Qwen2-MoE: routed experts + sigmoid-gated shared expert, qkv bias,
    unnormalized top-k gates (norm_topk_prob=False)."""
    from transformers import Qwen2MoeConfig, Qwen2MoeForCausalLM

    hf_cfg = Qwen2MoeConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        num_experts=4,
        num_experts_per_tok=2,
        moe_intermediate_size=16,
        shared_expert_intermediate_size=48,
        norm_topk_prob=False,
        decoder_sparse_step=1,
        mlp_only_layers=[],
        max_position_embeddings=128,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = Qwen2MoeForCausalLM(hf_cfg).eval().float()
    model_dir = _save_tiny(model, tmp_path, "qwen2_moe")
    cfg, params = _parity(model, model_dir, 96)
    _decode_consistency(cfg, params)
    assert cfg.shared_expert_intermediate_size == 48
    assert cfg.qkv_bias and not cfg.norm_topk_prob


def test_olmoe_numerical_parity(tmp_path):
    """OLMoE: RMSNorm over the WHOLE q and k projections, float32 softmax
    over all experts then an unnormalised top-k, experts as wide as
    `intermediate_size`, no biases, untied head."""
    from transformers import OlmoeConfig, OlmoeForCausalLM

    hf_cfg = OlmoeConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=16,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=4,
        num_experts=8,
        num_experts_per_tok=2,
        norm_topk_prob=False,
        max_position_embeddings=128,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = OlmoeForCausalLM(hf_cfg).eval().float()
    with torch.no_grad():  # HF inits norm weights to 1: a dropped norm hides
        for n, p in model.named_parameters():
            if "norm" in n:
                p.add_(torch.randn_like(p) * 0.1)
    model_dir = _save_tiny(model, tmp_path, "olmoe")
    cfg, params = _parity(model, model_dir, 96)
    _decode_consistency(cfg, params)
    assert cfg.num_experts == 8 and cfg.moe_intermediate_size_ == 16
    assert cfg.qk_norm and cfg.qk_norm_full and not cfg.norm_topk_prob
    assert not cfg.qkv_bias and not cfg.tie_word_embeddings
    assert params["layers"]["attn"]["q_norm"].shape == (2, 32)

    out = save_hf_params(params, cfg, str(tmp_path / "ckpt"))
    reloaded = load_hf_params(out, cfg, dtype="float32")
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        ),
        params,
        reloaded,
    )


def test_gpt2_numerical_parity(tmp_path):
    """GPT-2: LayerNorm+bias, learned wpe positions, fused Conv1D c_attn
    split at load, fc MLP with gelu_new, tied head."""
    from transformers import GPT2Config, GPT2LMHeadModel

    hf_cfg = GPT2Config(
        vocab_size=96,
        n_positions=64,
        n_embd=32,
        n_layer=2,
        n_head=4,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = GPT2LMHeadModel(hf_cfg).eval().float()
    _randomize_biases(model)
    model_dir = _save_tiny(model, tmp_path, "gpt2")
    cfg, params = _parity(model, model_dir, 96)
    _decode_consistency(cfg, params)
    assert cfg.norm_type == "layernorm" and cfg.pos_embed == "learned"
    assert cfg.mlp_style == "fc" and cfg.attn_out_bias
    assert cfg.hidden_act == "gelu_new" and cfg.tie_word_embeddings
    assert cfg.intermediate_size == 128  # 4 * n_embd default

    # roundtrip re-fuses c_attn and keeps transformer.* Conv1D layout
    out = save_hf_params(params, cfg, str(tmp_path / "ckpt"))
    reloaded = load_hf_params(out, cfg, dtype="float32")
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6
        ),
        params,
        reloaded,
    )


def test_qwen2_moe_heterogeneous_rejected(tmp_path):
    with pytest.raises(NotImplementedError):
        ModelConfig.from_hf_config(
            {
                "model_type": "qwen2_moe",
                "vocab_size": 96,
                "hidden_size": 32,
                "intermediate_size": 64,
                "num_hidden_layers": 4,
                "num_attention_heads": 4,
                "mlp_only_layers": [0, 1],
            }
        )


def test_gemma2_rejected():
    with pytest.raises(NotImplementedError):
        ModelConfig.from_hf_config(
            {
                "model_type": "gemma2",
                "vocab_size": 96,
                "hidden_size": 32,
                "intermediate_size": 64,
                "num_hidden_layers": 2,
                "num_attention_heads": 4,
            }
        )


def test_mistral_sliding_window_parity(tmp_path):
    """Mistral v0.1-class sliding window: parity vs HF at T > window, the
    regime where ignoring the window is silently wrong; decode/prefill
    must agree with forward; flash impl must reject loudly."""
    from transformers import MistralConfig, MistralForCausalLM

    hf_cfg = MistralConfig(
        vocab_size=96,
        hidden_size=32,
        intermediate_size=64,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        sliding_window=8,
        max_position_embeddings=128,
        attn_implementation="eager",
    )
    torch.manual_seed(0)
    model = MistralForCausalLM(hf_cfg).eval().float()
    model_dir = _save_tiny(model, tmp_path, "mistral")
    cfg, params = _parity(model, model_dir, 96, T=24)
    assert cfg.sliding_window == 8
    _decode_consistency(cfg, params, T=24)

    from areal_tpu.models.qwen2 import resolve_attn_impl

    with pytest.raises(NotImplementedError):
        resolve_attn_impl(
            ModelConfig(sliding_window=8, attn_impl="flash")
        )
    # auto resolves to the O(T)-memory chunked online-softmax path
    assert resolve_attn_impl(
        ModelConfig(sliding_window=8, attn_impl="auto")
    ) == "chunked"


def test_qwen2_max_window_layers_semantics():
    """HF windows layers with layer_idx >= max_window_layers: the stock
    Qwen2.5 shape (mwl == L) must mean NO window (review regression)."""
    base = dict(
        model_type="qwen2", vocab_size=96, hidden_size=32,
        intermediate_size=64, num_hidden_layers=4, num_attention_heads=4,
        use_sliding_window=True, sliding_window=8,
    )
    # mwl == L (stock shape): no layer windowed
    cfg = ModelConfig.from_hf_config({**base, "max_window_layers": 4})
    assert cfg.sliding_window is None
    # key absent: conservative no-window
    cfg = ModelConfig.from_hf_config(dict(base))
    assert cfg.sliding_window is None
    # mwl == 0: every layer windowed
    cfg = ModelConfig.from_hf_config({**base, "max_window_layers": 0})
    assert cfg.sliding_window == 8
    # mixed stack: loud rejection
    with pytest.raises(NotImplementedError):
        ModelConfig.from_hf_config({**base, "max_window_layers": 2})
