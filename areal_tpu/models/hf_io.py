"""HF checkpoint ⇄ areal_tpu param tree conversion.

Parity target: the reference loads HF models directly via transformers
(areal/engine/base_hf_engine.py:180-187) and converts between formats in
realhf/api/from_hf/*. Here conversion is a declarative name/layout table:
HF stores linear weights as [out, in] (torch convention); our kernels are
[in, out]-shaped einsum operands with heads split out, so loading is a
transpose + reshape per tensor.

Supports Qwen2/2.5 (qkv bias), Qwen3 (qk norm), Llama/Mistral, Gemma,
Qwen3-MoE / Qwen2-MoE (shared expert), Mixtral (block_sparse_moe.*), OLMoE
K-EXAONE (a leading dense layer, router bias, shared_experts; a chip's
share of the experts by their published numbers) and Qwen3-Next (Gated
DeltaNet layers under `linear_attn.*`, their fused projections grouped by
key head; a doubled `q_proj`; zero-centred norm weights) layouts. Files: model.safetensors or sharded model-*-of-*.safetensors with
index.
"""

from __future__ import annotations

import json
import os

import jax.numpy as jnp
import numpy as np

from areal_tpu.models.qwen2 import ModelConfig, param_shapes

try:  # safetensors is baked in
    from safetensors import safe_open
    from safetensors.numpy import save_file
except ImportError:  # pragma: no cover
    safe_open = None
    save_file = None


def _iter_hf_tensors(model_dir: str):
    """Yield (name, np.ndarray) from single or sharded safetensors files."""
    index_path = os.path.join(model_dir, "model.safetensors.index.json")
    if os.path.exists(index_path):
        with open(index_path) as f:
            index = json.load(f)
        shards = sorted(set(index["weight_map"].values()))
    else:
        shards = ["model.safetensors"]
    for shard in shards:
        path = os.path.join(model_dir, shard)
        with safe_open(path, framework="numpy") as f:
            for name in f.keys():
                yield name, f.get_tensor(name)


# Jamba's state-space mixer: our leaf -> its name under `mamba.`
_JAMBA_MIXER = {
    "in_kernel": "in_proj.weight",
    "conv_kernel": "conv1d.weight",
    "conv_bias": "conv1d.bias",
    "x_kernel": "x_proj.weight",
    "dt_norm": "dt_layernorm.weight",
    "b_norm": "b_layernorm.weight",
    "c_norm": "c_layernorm.weight",
    "dt_kernel": "dt_proj.weight",
    "dt_bias": "dt_proj.bias",
    "ssm_A_log": "A_log",
    "D": "D",
    "out_kernel": "out_proj.weight",
}
# those of them a checkpoint holds transposed ([out, in]; `A_log` [channels,
# state lanes]) and its Conv1d kernel [channels, 1, width]
_JAMBA_TRANSPOSED = ("in_kernel", "x_kernel", "dt_kernel", "ssm_A_log", "out_kernel")


def hf_name_to_ours(name: str) -> tuple[str, ...] | None:
    """Map one HF tensor name to a path in our (unstacked) param tree.

    Returns None for tensors we ignore (e.g. rotary inv_freq buffers).
    """
    name = name.removeprefix("model.")
    if name == "embed_tokens.weight":
        return ("embed", "embedding")
    if name in ("norm.weight", "final_layernorm.weight"):  # (the second: Jamba's)
        return ("final_norm",)
    if name == "lm_head.weight":
        return ("lm_head", "kernel")
    if name == "score.weight":  # HF TokenClassification value head
        return ("value_head", "kernel")
    if name == "score.bias":
        return ("value_head", "bias")
    if name.startswith("layers."):
        parts = name.split(".")
        i = int(parts[1])
        rest = ".".join(parts[2:])
        table = {
            "self_attn.q_proj.weight": ("attn", "q_kernel"),
            "self_attn.k_proj.weight": ("attn", "k_kernel"),
            "self_attn.v_proj.weight": ("attn", "v_kernel"),
            "self_attn.o_proj.weight": ("attn", "o_kernel"),
            "self_attn.q_proj.bias": ("attn", "q_bias"),
            "self_attn.k_proj.bias": ("attn", "k_bias"),
            "self_attn.v_proj.bias": ("attn", "v_bias"),
            "self_attn.q_norm.weight": ("attn", "q_norm"),
            "self_attn.k_norm.weight": ("attn", "k_norm"),
            "mlp.gate_proj.weight": ("mlp", "gate_kernel"),
            "mlp.up_proj.weight": ("mlp", "up_kernel"),
            "mlp.down_proj.weight": ("mlp", "down_kernel"),
            "mlp.gate.weight": ("mlp", "router_kernel"),  # MoE router
            # Qwen2-MoE shared expert (sigmoid-gated dense MLP)
            "mlp.shared_expert.gate_proj.weight": ("mlp", "shared_gate_kernel"),
            "mlp.shared_expert.up_proj.weight": ("mlp", "shared_up_kernel"),
            "mlp.shared_expert.down_proj.weight": ("mlp", "shared_down_kernel"),
            "mlp.shared_expert_gate.weight": ("mlp", "shared_router_kernel"),
            # K-EXAONE (DeepSeek-V3's names: no checkpoint here to read them
            # from, benchmark/configs/k-exaone-236b-a23b.json `assumed`): the
            # router's selection bias and the ungated shared expert
            "mlp.gate.e_score_correction_bias": ("mlp", "router_bias"),
            "mlp.shared_experts.gate_proj.weight": ("mlp", "shared_gate_kernel"),
            "mlp.shared_experts.up_proj.weight": ("mlp", "shared_up_kernel"),
            "mlp.shared_experts.down_proj.weight": ("mlp", "shared_down_kernel"),
            # Qwen3-Next's Gated DeltaNet mixer (transformers' Qwen3NextGatedDeltaNet
            # names: no checkpoint here to read them from,
            # benchmark/configs/qwen3-next-80b-a3b.json `assumed`)
            "linear_attn.in_proj_qkvz.weight": ("attn", "qkvz_kernel"),
            "linear_attn.in_proj_ba.weight": ("attn", "ba_kernel"),
            "linear_attn.conv1d.weight": ("attn", "conv_kernel"),
            "linear_attn.dt_bias": ("attn", "dt_bias"),
            "linear_attn.A_log": ("attn", "A_log"),
            "linear_attn.norm.weight": ("attn", "norm"),
            "linear_attn.out_proj.weight": ("attn", "out_kernel"),
            # DeepSeek-V2's latent attention (its modelling code's names as
            # ISSUE 38's author knew them: no checkpoint here to read them
            # from, benchmark/configs/deepseek-v2.json `assumed`)
            "self_attn.q_a_proj.weight": ("attn", "q_a_kernel"),
            "self_attn.q_a_layernorm.weight": ("attn", "q_a_norm"),
            "self_attn.q_b_proj.weight": ("attn", "q_b_kernel"),
            "self_attn.kv_a_proj_with_mqa.weight": ("attn", "kv_a_kernel"),
            "self_attn.kv_a_layernorm.weight": ("attn", "kv_a_norm"),
            "self_attn.kv_b_proj.weight": ("attn", "kv_b_kernel"),
            # Kimi-Linear's Kimi Delta Attention mixer, whose q, k, v and o
            # projections carry the attention's names, and its experts under
            # Mixtral's (its modelling code's names as ISSUE 45's author knew
            # them: no checkpoint here to read them from,
            # benchmark/configs/kimi-linear-48b-a3b.json `assumed`)
            "self_attn.q_conv1d.weight": ("attn", "q_conv_kernel"),
            "self_attn.k_conv1d.weight": ("attn", "k_conv_kernel"),
            "self_attn.v_conv1d.weight": ("attn", "v_conv_kernel"),
            "self_attn.A_log": ("attn", "A_log"),
            "self_attn.dt_bias": ("attn", "dt_bias"),
            "self_attn.f_a_proj.weight": ("attn", "f_a_kernel"),
            "self_attn.f_b_proj.weight": ("attn", "f_b_kernel"),
            "self_attn.b_proj.weight": ("attn", "b_kernel"),
            "self_attn.g_a_proj.weight": ("attn", "g_a_kernel"),
            "self_attn.g_b_proj.weight": ("attn", "g_b_kernel"),
            "self_attn.o_norm.weight": ("attn", "o_norm"),
            "block_sparse_moe.gate.e_score_correction_bias": ("mlp", "router_bias"),
            "block_sparse_moe.shared_experts.gate_proj.weight": ("mlp", "shared_gate_kernel"),
            "block_sparse_moe.shared_experts.up_proj.weight": ("mlp", "shared_up_kernel"),
            "block_sparse_moe.shared_experts.down_proj.weight": ("mlp", "shared_down_kernel"),
            # Jamba's state-space mixer, its dense MLP and its second norm
            # (its modelling code's names as ISSUE 50's author knew them: no
            # checkpoint here to read them from,
            # benchmark/configs/ai21-jamba2-3b.json `assumed`)
            **{f"mamba.{hf}": ("attn", ours) for ours, hf in _JAMBA_MIXER.items()},
            "feed_forward.gate_proj.weight": ("mlp", "gate_kernel"),
            "feed_forward.up_proj.weight": ("mlp", "up_kernel"),
            "feed_forward.down_proj.weight": ("mlp", "down_kernel"),
            "pre_ff_layernorm.weight": ("post_attn_norm",),
            # Mixtral router
            "block_sparse_moe.gate.weight": ("mlp", "router_kernel"),
            "input_layernorm.weight": ("input_norm",),
            "post_attention_layernorm.weight": ("post_attn_norm",),
        }
        if rest in table:
            return (f"layers_{i}",) + table[rest]
        # MoE experts: mlp.experts.{m}.{gate,up,down}_proj.weight → a
        # per-expert path that assemble_params stacks along axis 0.
        if rest.startswith("mlp.experts."):
            sub = rest.split(".")
            m = int(sub[2])
            proj = sub[3]  # gate_proj | up_proj | down_proj
            leaf = {"gate_proj": "gate_kernel", "up_proj": "up_kernel",
                    "down_proj": "down_kernel"}.get(proj)
            if leaf and sub[4] == "weight":
                return (f"layers_{i}", "mlp", f"expert_{m}", leaf)
        # Mixtral experts: block_sparse_moe.experts.{m}.w{1,2,3}.weight
        # (w1 = gate, w3 = up, w2 = down — HF MixtralBlockSparseTop2MLP)
        if rest.startswith("block_sparse_moe.experts."):
            sub = rest.split(".")
            m = int(sub[2])
            leaf = {"w1": "gate_kernel", "w3": "up_kernel",
                    "w2": "down_kernel"}.get(sub[3])
            if leaf and sub[4] == "weight":
                return (f"layers_{i}", "mlp", f"expert_{m}", leaf)
    return None


# Qwen3-Next's norms store a zero-centred weight w and scale by 1 + w; the
# tree holds the effective scale (the Gated DeltaNet's own gated norm,
# ("attn", "norm"), is a plain scale and is left as it is)
_ZERO_CENTRED_NORMS = ("input_norm", "post_attn_norm", "final_norm", "q_norm", "k_norm")


def _gdn_groups(cfg: ModelConfig, leaf: str) -> list[int]:
    """Columns a key head owns of each part of a fused Gated DeltaNet
    projection, in the checkpoint's order: [q, k, v, z] or [b, a]."""
    r = cfg.linear_num_value_heads // cfg.linear_num_key_heads
    if leaf == "ba_kernel":
        return [r, r]
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    return [dk, dk, r * dv, r * dv]


def _gdn_ungroup(w: np.ndarray, cfg: ModelConfig, leaf: str) -> np.ndarray:
    """[H, key heads x (parts of one key head)] (a checkpoint's grouping) →
    [H, part by part, heads in order] (the tree's)."""
    H, nk = w.shape[0], cfg.linear_num_key_heads
    parts = np.split(w.reshape(H, nk, -1), np.cumsum(_gdn_groups(cfg, leaf))[:-1], axis=-1)
    return np.concatenate([part.reshape(H, -1) for part in parts], axis=-1)


def _gdn_group(w: np.ndarray, cfg: ModelConfig, leaf: str) -> np.ndarray:
    """Inverse of `_gdn_ungroup`."""
    H, nk = w.shape[0], cfg.linear_num_key_heads
    sizes = [nk * g for g in _gdn_groups(cfg, leaf)]
    parts = np.split(w, np.cumsum(sizes)[:-1], axis=-1)
    return np.concatenate([part.reshape(H, nk, -1) for part in parts], axis=-1).reshape(H, -1)


def _latent_rotary_lanes(w: np.ndarray, cfg: ModelConfig, leaf: str, load: bool) -> np.ndarray:
    """A latent model's `q_b_kernel` / `kv_a_kernel` [in, out] with the
    rotary lanes reordered: a DeepSeek-V2 checkpoint holds them as
    interleaved pairs (lanes 2i and 2i + 1 turn together; its modelling code
    de-interleaves q_pe and k_pe at every call), the tree as `rotate_half`
    pairs them (lane i with lane i + rope/2). `load`: checkpoint -> tree,
    once; else the way back."""
    rope = cfg.qk_rope_head_dim
    perm = np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])
    if not load:
        perm = np.argsort(perm)
    if leaf == "kv_a_kernel":
        C = cfg.kv_lora_rank
        return np.concatenate([w[:, :C], w[:, C:][:, perm]], axis=1)
    nH, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    heads = w.reshape(w.shape[0], nH, nope + rope)
    return np.concatenate(
        [heads[..., :nope], heads[..., nope:][..., perm]], axis=-1
    ).reshape(w.shape)


_LATENT_KERNELS = ("q_a_kernel", "q_b_kernel", "kv_a_kernel", "kv_b_kernel")
# Kimi Delta Attention's leaves that are plain [out, in] matrices, and its
# depthwise convolutions (torch Conv1d [channels, 1, width])
_KDA_MATRICES = ("f_a_kernel", "f_b_kernel", "b_kernel", "g_a_kernel", "g_b_kernel")
_KDA_CONVS = ("q_conv_kernel", "k_conv_kernel", "v_conv_kernel")


def _convert_tensor(path: tuple[str, ...], w: np.ndarray, cfg: ModelConfig) -> np.ndarray:
    """Torch [out, in] → our einsum layout."""
    nH, nKV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
    H = cfg.hidden_size
    leaf = path[-1]
    if cfg.model_type == "qwen3_next":
        if leaf in _ZERO_CENTRED_NORMS:
            return w + 1
        if leaf in ("qkvz_kernel", "ba_kernel"):
            return _gdn_ungroup(np.ascontiguousarray(w.T), cfg, leaf)
        if leaf == "conv_kernel":  # torch Conv1d [channels, 1, width]
            return w.reshape(w.shape[0], w.shape[-1])
        if leaf == "out_kernel":
            return np.ascontiguousarray(w.T)
    if cfg.model_type == "jamba" and path[-2:-1] == ("attn",):
        if leaf in _JAMBA_TRANSPOSED:
            return np.ascontiguousarray(w.T)
        if leaf == "conv_kernel":
            return w.reshape(w.shape[0], w.shape[-1])
    if leaf in _KDA_MATRICES:
        return np.ascontiguousarray(w.T)
    if leaf in _KDA_CONVS:
        return w.reshape(w.shape[0], w.shape[-1])
    if leaf in _LATENT_KERNELS:
        w = np.ascontiguousarray(w.T)
        # (a model with no positional encoding turns no lanes: as they are)
        if leaf in ("q_b_kernel", "kv_a_kernel") and cfg.pos_embed == "rope":
            w = _latent_rotary_lanes(w, cfg, leaf, load=True)
        return w
    if leaf in ("q_kernel", "k_kernel", "v_kernel"):
        n = nH if leaf == "q_kernel" else nKV
        # (under `attn_output_gate` a query head is 2 hd wide: q, then its gate)
        return np.ascontiguousarray(w.T).reshape(H, n, -1)
    if leaf == "o_kernel":
        # (a latent model's heads come out `v_head_dim` wide, not `head_dim`)
        return np.ascontiguousarray(w.T).reshape(nH, -1, H)
    if leaf in ("q_bias",):
        return w.reshape(nH, hd)
    if leaf in ("k_bias", "v_bias"):
        return w.reshape(nKV, hd)
    if leaf in ("gate_kernel", "up_kernel", "down_kernel", "kernel",
                "router_kernel", "shared_gate_kernel", "shared_up_kernel",
                "shared_down_kernel", "shared_router_kernel"):
        return np.ascontiguousarray(w.T)
    return w  # norms, embedding


def _unconvert_tensor(path: tuple[str, ...], w: np.ndarray, cfg: ModelConfig) -> np.ndarray:
    """Our layout → torch [out, in]."""
    H = cfg.hidden_size
    leaf = path[-1]
    if cfg.model_type == "qwen3_next":
        if leaf in _ZERO_CENTRED_NORMS:
            return w - 1
        if leaf in ("qkvz_kernel", "ba_kernel"):
            return np.ascontiguousarray(_gdn_group(w, cfg, leaf).T)
        if leaf == "conv_kernel":
            return w.reshape(w.shape[0], 1, w.shape[1])
        if leaf == "out_kernel":
            return np.ascontiguousarray(w.T)
    if cfg.model_type == "jamba" and path[-2:-1] == ("attn",):
        if leaf in _JAMBA_TRANSPOSED:
            return np.ascontiguousarray(w.T)
        if leaf == "conv_kernel":
            return w.reshape(w.shape[0], 1, w.shape[1])
    if leaf in _KDA_MATRICES:
        return np.ascontiguousarray(w.T)
    if leaf in _KDA_CONVS:
        return w.reshape(w.shape[0], 1, w.shape[1])
    if leaf in _LATENT_KERNELS:
        if leaf in ("q_b_kernel", "kv_a_kernel") and cfg.pos_embed == "rope":
            w = _latent_rotary_lanes(w, cfg, leaf, load=False)
        return np.ascontiguousarray(w.T)
    if leaf in ("q_kernel", "k_kernel", "v_kernel"):
        return np.ascontiguousarray(w.reshape(H, -1).T)
    if leaf == "o_kernel":
        return np.ascontiguousarray(w.reshape(-1, H).T)
    if leaf in ("q_bias", "k_bias", "v_bias"):
        return w.reshape(-1)
    if leaf in ("gate_kernel", "up_kernel", "down_kernel", "kernel",
                "router_kernel", "shared_gate_kernel", "shared_up_kernel",
                "shared_down_kernel", "shared_router_kernel"):
        return np.ascontiguousarray(w.T)
    return w


def _gpt2_flat(model_dir: str, cfg: ModelConfig) -> dict:
    """GPT-2 checkpoint → flat path dict.

    GPT-2 needs its own mapping pass: weights are Conv1D ([in, out] — no
    transpose, unlike Linear), the QKV projection is one fused `c_attn`
    tensor split three ways, and layernorms carry biases (reference
    counterpart: realhf/api/from_hf/gpt2.py sd_from_gpt2)."""
    H = cfg.hidden_size
    nH, hd = cfg.num_attention_heads, cfg.head_dim_
    flat: dict[tuple[str, ...], np.ndarray] = {}
    for name, w in _iter_hf_tensors(model_dir):
        name = name.removeprefix("transformer.")
        if name == "wte.weight":
            flat[("embed", "embedding")] = w
        elif name == "wpe.weight":
            flat[("pos_embed", "embedding")] = w
        elif name == "ln_f.weight":
            flat[("final_norm",)] = w
        elif name == "ln_f.bias":
            flat[("final_norm_bias",)] = w
        elif name == "lm_head.weight":  # untied head (torch Linear [V, H])
            flat[("lm_head", "kernel")] = np.ascontiguousarray(w.T)
        elif name == "score.weight":  # critic value head
            flat[("value_head", "kernel")] = np.ascontiguousarray(w.T)
        elif name == "score.bias":
            flat[("value_head", "bias")] = w
        elif name.startswith("h."):
            parts = name.split(".")
            li = f"layers_{int(parts[1])}"
            rest = ".".join(parts[2:])
            if rest == "ln_1.weight":
                flat[(li, "input_norm")] = w
            elif rest == "ln_1.bias":
                flat[(li, "input_norm_bias")] = w
            elif rest == "ln_2.weight":
                flat[(li, "post_attn_norm")] = w
            elif rest == "ln_2.bias":
                flat[(li, "post_attn_norm_bias")] = w
            elif rest == "attn.c_attn.weight":  # [H, 3H] fused qkv
                q, k, v = np.split(w, 3, axis=1)
                flat[(li, "attn", "q_kernel")] = q.reshape(H, nH, hd)
                flat[(li, "attn", "k_kernel")] = k.reshape(H, nH, hd)
                flat[(li, "attn", "v_kernel")] = v.reshape(H, nH, hd)
            elif rest == "attn.c_attn.bias":  # [3H]
                q, k, v = np.split(w, 3)
                flat[(li, "attn", "q_bias")] = q.reshape(nH, hd)
                flat[(li, "attn", "k_bias")] = k.reshape(nH, hd)
                flat[(li, "attn", "v_bias")] = v.reshape(nH, hd)
            elif rest == "attn.c_proj.weight":  # [H, H], already [in, out]
                flat[(li, "attn", "o_kernel")] = w.reshape(nH, hd, H)
            elif rest == "attn.c_proj.bias":
                flat[(li, "attn", "o_bias")] = w
            elif rest == "mlp.c_fc.weight":  # [H, I]
                flat[(li, "mlp", "fc1_kernel")] = w
            elif rest == "mlp.c_fc.bias":
                flat[(li, "mlp", "fc1_bias")] = w
            elif rest == "mlp.c_proj.weight":  # [I, H]
                flat[(li, "mlp", "fc2_kernel")] = w
            elif rest == "mlp.c_proj.bias":
                flat[(li, "mlp", "fc2_bias")] = w
            # attn.bias / attn.masked_bias causal-mask buffers: ignored
    return flat


def load_hf_params(
    model_dir: str, cfg: ModelConfig, dtype: str | None = None
) -> dict:
    """Load an HF checkpoint dir into our param tree (numpy leaves).

    With cfg.scan_layers, per-layer tensors are stacked along axis 0.
    """
    dtype = dtype or cfg.param_dtype
    if cfg.model_type == "gpt2":
        return assemble_params(_gpt2_flat(model_dir, cfg), cfg, dtype)
    flat: dict[tuple[str, ...], np.ndarray] = {}
    for name, w in _iter_hf_tensors(model_dir):
        path = hf_name_to_ours(name)
        if path is None:
            continue
        flat[path] = _convert_tensor(path, w, cfg)

    return assemble_params(flat, cfg, dtype)


def assemble_params(
    flat: dict[tuple[str, ...], np.ndarray], cfg: ModelConfig, dtype: str
) -> dict:
    """Build the (possibly layer-stacked) tree from flat unstacked entries."""
    out: dict = {}

    def put(tree, path, value):
        for k in path[:-1]:
            tree = tree.setdefault(k, {})
        tree[path[-1]] = value

    cast = lambda x: jnp.asarray(x, dtype=jnp.dtype(dtype))  # noqa: E731
    if cfg.num_experts:
        # Stack per-expert entries (…, "expert_{m}", leaf) → (…, leaf) [E, ...]
        expert_keys = [
            p for p in flat if any(s.startswith("expert_") for s in p)
        ]
        grouped: dict[tuple, dict[int, np.ndarray]] = {}
        for p in expert_keys:
            k = next(i for i, s in enumerate(p) if s.startswith("expert_"))
            m = int(p[k].split("_")[1])
            tgt = p[:k] + p[k + 1 :]
            grouped.setdefault(tgt, {})[m] = flat.pop(p)
        # (a chip that holds a share of the experts takes its own, by
        # their published numbers)
        held = range(cfg.expert_first, cfg.expert_first + cfg.num_experts)
        for tgt, by_idx in grouped.items():
            flat[tgt] = np.stack([by_idx[m] for m in held], axis=0)
    if cfg.tie_word_embeddings or cfg.is_critic:
        flat = {p: w for p, w in flat.items() if p[0] != "lm_head"}
    if cfg.is_critic and ("value_head", "kernel") not in flat:
        # initializing a critic from a causal-LM checkpoint: fresh value head
        flat[("value_head", "kernel")] = np.zeros(
            (cfg.hidden_size, 1), dtype=np.float32
        )
    if cfg.is_critic and ("value_head", "bias") not in flat:
        flat[("value_head", "bias")] = np.zeros((1,), dtype=np.float32)
    if not cfg.is_critic:
        flat = {p: w for p, w in flat.items() if p[0] != "value_head"}
    if cfg.scan_layers:
        L = cfg.num_hidden_layers
        layer_paths = sorted(
            {p[1:] for p in flat if p[0].startswith("layers_")}
        )
        for sub in layer_paths:
            stacked = np.stack(
                [flat[(f"layers_{i}",) + sub] for i in range(L)], axis=0
            )
            put(out, ("layers",) + sub, cast(stacked))
        for p, w in flat.items():
            if not p[0].startswith("layers_"):
                put(out, p, cast(w))
    else:
        # (a run of like layers is held stacked under its own key)
        run_of = {f"layers_{i}": (key, a) for key, a, b in cfg.stack_plan
                  if b - a > 1 for i in range(a, b)}
        runs: dict[tuple, dict[int, np.ndarray]] = {}
        for p, w in flat.items():
            if p[0] in run_of:
                key, a = run_of[p[0]]
                runs.setdefault((key,) + p[1:], {})[int(p[0][7:]) - a] = w
            else:
                put(out, p, cast(w))
        for p, by_idx in runs.items():
            put(out, p, cast(np.stack([by_idx[j] for j in range(len(by_idx))])))

    _validate_against_shapes(out, cfg)
    return out


def _validate_against_shapes(params: dict, cfg: ModelConfig) -> None:
    expected = param_shapes(cfg)

    def walk(exp, got, path):
        if isinstance(exp, dict):
            missing = set(exp) - set(got)
            extra = set(got) - set(exp)
            if missing or extra:
                raise ValueError(
                    f"param tree mismatch at {'/'.join(path)}: "
                    f"missing={sorted(missing)} extra={sorted(extra)}"
                )
            for k in exp:
                walk(exp[k], got[k], path + (k,))
        else:
            if tuple(got.shape) != tuple(exp):
                raise ValueError(
                    f"shape mismatch at {'/'.join(path)}: "
                    f"expected {exp}, got {tuple(got.shape)}"
                )

    walk(expected, params, ())


def flatten_params(params: dict, cfg: ModelConfig) -> dict[tuple[str, ...], np.ndarray]:
    """Inverse of assemble_params: unstack scan layers into layers_{i}."""
    flat: dict[tuple[str, ...], np.ndarray] = {}

    def walk(tree, path):
        if isinstance(tree, dict):
            for k, v in tree.items():
                walk(v, path + (k,))
        else:
            flat[path] = np.asarray(tree)

    walk(params, ())
    if cfg.scan_layers:
        out: dict[tuple[str, ...], np.ndarray] = {}
        for p, w in flat.items():
            if p[0] == "layers":
                for i in range(cfg.num_hidden_layers):
                    out[(f"layers_{i}",) + p[1:]] = w[i]
            else:
                out[p] = w
        flat = out
    elif cfg.layer_runs:
        starts = {key: a for key, a, b in cfg.stack_plan if b - a > 1}
        out = {}
        for p, w in flat.items():
            if p[0] in starts:
                for j in range(w.shape[0]):
                    out[(f"layers_{starts[p[0]] + j}",) + p[1:]] = w[j]
            else:
                out[p] = w
        flat = out
    if cfg.num_experts:
        # Unstack [E, ...] expert tensors into per-expert paths.
        out2: dict[tuple[str, ...], np.ndarray] = {}
        for p, w in flat.items():
            if (
                len(p) >= 2
                and p[-2] == "mlp"
                and p[-1] in ("gate_kernel", "up_kernel", "down_kernel")
                and w.ndim == 3  # not a leading dense layer's 2-D kernels
            ):
                for m in range(cfg.num_experts):
                    out2[
                        p[:-1] + (f"expert_{cfg.expert_first + m}", p[-1])
                    ] = w[m]
            else:
                out2[p] = w
        flat = out2
    return flat


def ours_name_to_hf(path: tuple[str, ...], model_type: str = "qwen2") -> str:
    """Our param path → the HF tensor name for `model_type`'s layout.
    Only MoE naming differs by family (mixtral's block_sparse_moe.* vs the
    qwen mlp.* names); everything else is the shared llama-style schema."""
    leaf_table = {
        ("attn", "q_kernel"): "self_attn.q_proj.weight",
        ("attn", "k_kernel"): "self_attn.k_proj.weight",
        ("attn", "v_kernel"): "self_attn.v_proj.weight",
        ("attn", "o_kernel"): "self_attn.o_proj.weight",
        ("attn", "q_bias"): "self_attn.q_proj.bias",
        ("attn", "k_bias"): "self_attn.k_proj.bias",
        ("attn", "v_bias"): "self_attn.v_proj.bias",
        ("attn", "q_norm"): "self_attn.q_norm.weight",
        ("attn", "k_norm"): "self_attn.k_norm.weight",
        ("mlp", "gate_kernel"): "mlp.gate_proj.weight",
        ("mlp", "up_kernel"): "mlp.up_proj.weight",
        ("mlp", "down_kernel"): "mlp.down_proj.weight",
        ("mlp", "router_kernel"): "mlp.gate.weight",
        ("mlp", "shared_gate_kernel"): "mlp.shared_expert.gate_proj.weight",
        ("mlp", "shared_up_kernel"): "mlp.shared_expert.up_proj.weight",
        ("mlp", "shared_down_kernel"): "mlp.shared_expert.down_proj.weight",
        ("mlp", "shared_router_kernel"): "mlp.shared_expert_gate.weight",
        ("input_norm",): "input_layernorm.weight",
        ("post_attn_norm",): "post_attention_layernorm.weight",
        ("attn", "qkvz_kernel"): "linear_attn.in_proj_qkvz.weight",
        ("attn", "ba_kernel"): "linear_attn.in_proj_ba.weight",
        ("attn", "conv_kernel"): "linear_attn.conv1d.weight",
        ("attn", "dt_bias"): "linear_attn.dt_bias",
        ("attn", "A_log"): "linear_attn.A_log",
        ("attn", "norm"): "linear_attn.norm.weight",
        ("attn", "out_kernel"): "linear_attn.out_proj.weight",
        ("attn", "q_a_kernel"): "self_attn.q_a_proj.weight",
        ("attn", "q_a_norm"): "self_attn.q_a_layernorm.weight",
        ("attn", "q_b_kernel"): "self_attn.q_b_proj.weight",
        ("attn", "kv_a_kernel"): "self_attn.kv_a_proj_with_mqa.weight",
        ("attn", "kv_a_norm"): "self_attn.kv_a_layernorm.weight",
        ("attn", "kv_b_kernel"): "self_attn.kv_b_proj.weight",
    }
    if model_type == "mixtral":
        leaf_table[("mlp", "router_kernel")] = "block_sparse_moe.gate.weight"
    if model_type == "exaone_moe":
        leaf_table[("mlp", "router_bias")] = "mlp.gate.e_score_correction_bias"
    if model_type in ("exaone_moe", "deepseek_v2"):
        for proj in ("gate", "up", "down"):
            leaf_table[("mlp", f"shared_{proj}_kernel")] = (
                f"mlp.shared_experts.{proj}_proj.weight"
            )
    if model_type == "kimi_linear":
        leaf_table.update({
            ("attn", "q_conv_kernel"): "self_attn.q_conv1d.weight",
            ("attn", "k_conv_kernel"): "self_attn.k_conv1d.weight",
            ("attn", "v_conv_kernel"): "self_attn.v_conv1d.weight",
            ("attn", "A_log"): "self_attn.A_log",
            ("attn", "dt_bias"): "self_attn.dt_bias",
            ("attn", "f_a_kernel"): "self_attn.f_a_proj.weight",
            ("attn", "f_b_kernel"): "self_attn.f_b_proj.weight",
            ("attn", "b_kernel"): "self_attn.b_proj.weight",
            ("attn", "g_a_kernel"): "self_attn.g_a_proj.weight",
            ("attn", "g_b_kernel"): "self_attn.g_b_proj.weight",
            ("attn", "o_norm"): "self_attn.o_norm.weight",
            ("mlp", "router_kernel"): "block_sparse_moe.gate.weight",
            ("mlp", "router_bias"): "block_sparse_moe.gate.e_score_correction_bias",
            **{("mlp", f"shared_{proj}_kernel"):
               f"block_sparse_moe.shared_experts.{proj}_proj.weight"
               for proj in ("gate", "up", "down")},
        })
    if model_type == "jamba":
        leaf_table.update({
            **{("attn", ours): f"mamba.{hf}" for ours, hf in _JAMBA_MIXER.items()},
            **{("mlp", f"{proj}_kernel"): f"feed_forward.{proj}_proj.weight"
               for proj in ("gate", "up", "down")},
            ("post_attn_norm",): "pre_ff_layernorm.weight",
        })
    if path == ("embed", "embedding"):
        return "model.embed_tokens.weight"
    if path == ("final_norm",):
        return ("model.final_layernorm.weight" if model_type == "jamba"
                else "model.norm.weight")
    if path == ("lm_head", "kernel"):
        return "lm_head.weight"
    if path == ("value_head", "kernel"):
        return "score.weight"
    if path == ("value_head", "bias"):
        return "score.bias"
    if path[0].startswith("layers_"):
        i = int(path[0].split("_")[1])
        if len(path) == 4 and path[2].startswith("expert_"):
            m = int(path[2].split("_")[1])
            if model_type in ("mixtral", "kimi_linear"):
                w = {
                    "gate_kernel": "w1",
                    "up_kernel": "w3",
                    "down_kernel": "w2",
                }[path[3]]
                return (
                    f"model.layers.{i}.block_sparse_moe.experts.{m}.{w}.weight"
                )
            proj = {
                "gate_kernel": "gate_proj",
                "up_kernel": "up_proj",
                "down_kernel": "down_proj",
            }[path[3]]
            return f"model.layers.{i}.mlp.experts.{m}.{proj}.weight"
        return f"model.layers.{i}." + leaf_table[path[1:]]
    raise KeyError(path)


def _gpt2_tensors(flat: dict, cfg: ModelConfig) -> dict[str, np.ndarray]:
    """Inverse of _gpt2_flat: our flat paths → transformer.* Conv1D tensors
    (qkv re-fused into c_attn)."""
    H = cfg.hidden_size
    out: dict[str, np.ndarray] = {}
    top = {
        ("embed", "embedding"): "transformer.wte.weight",
        ("pos_embed", "embedding"): "transformer.wpe.weight",
        ("final_norm",): "transformer.ln_f.weight",
        ("final_norm_bias",): "transformer.ln_f.bias",
    }
    transposed_top = {
        # torch Linear [out, in] layout, unlike the Conv1D layer weights
        ("lm_head", "kernel"): "lm_head.weight",
        ("value_head", "kernel"): "score.weight",
    }
    leaf = {
        "input_norm": "ln_1.weight",
        "input_norm_bias": "ln_1.bias",
        "post_attn_norm": "ln_2.weight",
        "post_attn_norm_bias": "ln_2.bias",
    }
    qkv_w: dict[int, dict[str, np.ndarray]] = {}
    qkv_b: dict[int, dict[str, np.ndarray]] = {}
    for path, w in flat.items():
        w = np.asarray(w)
        if path in top:
            out[top[path]] = w
        elif path in transposed_top:
            out[transposed_top[path]] = np.ascontiguousarray(w.T)
        elif path == ("value_head", "bias"):
            out["score.bias"] = w
        elif path[0].startswith("layers_"):
            i = int(path[0].split("_")[1])
            pre = f"transformer.h.{i}."
            rest = path[1:]
            if len(rest) == 1 and rest[0] in leaf:
                out[pre + leaf[rest[0]]] = w
            elif rest[0] == "attn":
                k = rest[1]
                if k in ("q_kernel", "k_kernel", "v_kernel"):
                    qkv_w.setdefault(i, {})[k[0]] = w.reshape(H, -1)
                elif k in ("q_bias", "k_bias", "v_bias"):
                    qkv_b.setdefault(i, {})[k[0]] = w.reshape(-1)
                elif k == "o_kernel":
                    out[pre + "attn.c_proj.weight"] = w.reshape(-1, H)
                elif k == "o_bias":
                    out[pre + "attn.c_proj.bias"] = w
            elif rest[0] == "mlp":
                name = {
                    "fc1_kernel": "mlp.c_fc.weight",
                    "fc1_bias": "mlp.c_fc.bias",
                    "fc2_kernel": "mlp.c_proj.weight",
                    "fc2_bias": "mlp.c_proj.bias",
                }[rest[1]]
                out[pre + name] = w
    for i, parts in qkv_w.items():
        out[f"transformer.h.{i}.attn.c_attn.weight"] = np.concatenate(
            [parts["q"], parts["k"], parts["v"]], axis=1
        )
    for i, parts in qkv_b.items():
        out[f"transformer.h.{i}.attn.c_attn.bias"] = np.concatenate(
            [parts["q"], parts["k"], parts["v"]]
        )
    return out


def save_hf_params(params: dict, cfg: ModelConfig, out_dir: str) -> str:
    """Write the param tree as a single HF-format safetensors file +
    config passthrough. Weights are saved in torch [out, in] layout so any
    HF consumer (including our decode engine reload path) can read them."""
    os.makedirs(out_dir, exist_ok=True)
    flat = flatten_params(params, cfg)
    tensors = {}
    if cfg.model_type == "gpt2":
        tensors = _gpt2_tensors(flat, cfg)
        tensors = {
            k: np.ascontiguousarray(
                v.astype(np.float32) if v.dtype == jnp.bfloat16 else v
            )
            for k, v in tensors.items()
        }
    else:
        for path, w in flat.items():
            hf_name = ours_name_to_hf(path, cfg.model_type)
            arr = _unconvert_tensor(path, np.asarray(w), cfg)
            # numpy safetensors cannot store bfloat16; upcast for the disk
            # copy
            if arr.dtype == jnp.bfloat16:
                arr = arr.astype(np.float32)
            tensors[hf_name] = np.ascontiguousarray(arr)
    save_file(tensors, os.path.join(out_dir, "model.safetensors"))
    return out_dir


# ---------------------------------------------------------------------------
# Vision tower (Qwen2-VL / Qwen2.5-VL) weight loading: HF `visual.*` names →
# areal_tpu/models/qwen2_vl.py param tree. Strict: any `visual.*` tensor the
# mapping does not recognize raises — silently dropping weights (LayerNorm
# biases, SwiGLU up_proj) would produce a wrong architecture that loads
# "successfully".
# ---------------------------------------------------------------------------


def load_hf_vision_params(model_dir: str, vcfg) -> dict:
    """Load `visual.*` tensors from an HF checkpoint dir into the vision
    param tree (see qwen2_vl.vision_param_shapes)."""
    import re

    D = vcfg.embed_dim
    nH, hd = vcfg.num_heads, vcfg.head_dim
    L = vcfg.depth
    blocks: dict = {}
    out: dict = {"patch_embed": {}, "merger": {}}
    stacks: dict[tuple[str, ...], list] = {}
    unmatched: list[str] = []

    def stash(path, i, w):
        stacks.setdefault(path, [None] * L)[i] = w

    top = {
        "visual.patch_embed.proj.weight": (
            # conv (D, C, t, p, p) -> matmul kernel [C*t*p*p, D]
            lambda w: out["patch_embed"].__setitem__("kernel", w.reshape(D, -1).T)
        ),
        "visual.merger.ln_q.weight": (
            lambda w: out["merger"].setdefault("ln_q", {}).__setitem__("scale", w)
        ),
        "visual.merger.ln_q.bias": (
            lambda w: out["merger"].setdefault("ln_q", {}).__setitem__("bias", w)
        ),
        "visual.merger.mlp.0.weight": (
            lambda w: out["merger"].__setitem__("fc1_kernel", w.T)
        ),
        "visual.merger.mlp.0.bias": (
            lambda w: out["merger"].__setitem__("fc1_bias", w)
        ),
        "visual.merger.mlp.2.weight": (
            lambda w: out["merger"].__setitem__("fc2_kernel", w.T)
        ),
        "visual.merger.mlp.2.bias": (
            lambda w: out["merger"].__setitem__("fc2_bias", w)
        ),
    }
    block_map = {
        "norm1.weight": (("norm1", "scale"), lambda w: w),
        "norm1.bias": (("norm1", "bias"), lambda w: w),
        "norm2.weight": (("norm2", "scale"), lambda w: w),
        "norm2.bias": (("norm2", "bias"), lambda w: w),
        "attn.qkv.weight": (
            ("attn", "qkv_kernel"),
            lambda w: w.reshape(3, nH, hd, D).transpose(3, 0, 1, 2),
        ),
        "attn.qkv.bias": (
            ("attn", "qkv_bias"),
            lambda w: w.reshape(3, nH, hd),
        ),
        "attn.proj.weight": (
            ("attn", "proj_kernel"),
            lambda w: w.T.reshape(nH, hd, D),
        ),
        "attn.proj.bias": (("attn", "proj_bias"), lambda w: w),
        # Qwen2-VL gelu MLP
        "mlp.fc1.weight": (("mlp", "fc1_kernel"), lambda w: w.T),
        "mlp.fc1.bias": (("mlp", "fc1_bias"), lambda w: w),
        "mlp.fc2.weight": (("mlp", "fc2_kernel"), lambda w: w.T),
        "mlp.fc2.bias": (("mlp", "fc2_bias"), lambda w: w),
        # Qwen2.5-VL SwiGLU MLP
        "mlp.gate_proj.weight": (("mlp", "gate_kernel"), lambda w: w.T),
        "mlp.gate_proj.bias": (("mlp", "gate_bias"), lambda w: w),
        "mlp.up_proj.weight": (("mlp", "up_kernel"), lambda w: w.T),
        "mlp.up_proj.bias": (("mlp", "up_bias"), lambda w: w),
        "mlp.down_proj.weight": (("mlp", "down_kernel"), lambda w: w.T),
        "mlp.down_proj.bias": (("mlp", "down_bias"), lambda w: w),
    }

    for name, w in _iter_hf_tensors(model_dir):
        if not name.startswith("visual."):
            continue
        w = np.asarray(w)
        if name in top:
            top[name](w)
            continue
        m = re.match(r"visual\.blocks\.(\d+)\.(.+)", name)
        if m and m.group(2) in block_map:
            path, conv = block_map[m.group(2)]
            stash(path, int(m.group(1)), conv(w))
            continue
        unmatched.append(name)

    if unmatched:
        raise ValueError(
            "unrecognized visual.* tensors (vision architecture not "
            f"supported by this loader): {sorted(unmatched)[:8]}..."
        )
    for path, ws in stacks.items():
        missing = [i for i, x in enumerate(ws) if x is None]
        if missing:
            raise ValueError(
                f"vision blocks missing layer(s) {missing} for {path}"
            )
        node = blocks
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = np.stack(ws)
    out["blocks"] = blocks
    return out
