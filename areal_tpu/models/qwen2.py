"""Qwen2/2.5/3-, Llama/Mistral-, Gemma- and MoE-class decoder, TPU-first.

Replaces the reference's HF-model-plus-patches approach (areal/engine/
base_hf_engine.py loads transformers models; realhf/impl/model/nn/
real_llm_api.py is a custom torch transformer with explicit TP/PP modules).
Here the model is a set of *pure functions* over an explicit parameter
pytree:

- no framework modules: params are a nested dict mirroring HF names, so
  weight conversion is a transpose table, and sharding is a parallel tree of
  logical axis tuples consumed by areal_tpu.parallel.mesh.
- parallelism is *not* in the model: a single GSPMD sharding annotation per
  param subsumes Column/RowParallelLinear, Ulysses all-to-all, and FSDP
  gather/scatter. XLA inserts the collectives.
- the hot path is three big einsums per layer (QKV, scores·V, MLP) — all
  MXU-shaped, bf16, with f32 softmax/norms.
- sequences arrive *packed*: 1-D token stream + segment_ids; attention is
  causal-within-segment. This is the layout the GAE kernel and FFD
  micro-batcher produce, and it keeps shapes static for XLA.
- `scan_layers` stacks per-layer params [L, ...] and runs lax.scan: O(1)
  compile time in depth, and the stacked axis is what pipeline parallelism
  shards.

Covers the reference's model families of record (realhf/api/from_hf/
registry: qwen2, qwen3, llama, mistral, gemma, mixtral, qwen2_moe/qwen3_moe)
and OLMoE — one decoder parameterized by flags rather than one module per
family: activation (`hidden_act`), Gemma's zero-centered RMSNorm + sqrt(H)
embedding scaling, Mixtral/Qwen2-MoE/OLMoE routing conventions, the
Qwen2-MoE shared expert and OLMoE's full-width q/k norm.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import json
import math
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from typing import Any

PADDING_SEGMENT = -1


def _cstr(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Activation sharding constraint by logical axes (no-op off-mesh).
    Pinning layer-boundary layouts keeps GSPMD from inventing conflicting
    layouts for scan residuals in the backward pass (full-remat reshards)."""
    from areal_tpu.parallel import mesh as mesh_lib

    return mesh_lib.constrain(x, *logical_axes)


# `model_type`s `ModelConfig.from_hf_config` knows how to read. Anything
# else raises: a config.json of an unlisted family would otherwise load as a
# dense qwen2-shaped model with whatever keys happen to match.
MODEL_TYPES = (
    "qwen2", "qwen3", "llama", "mistral", "gemma", "gemma2", "gpt2",
    "mixtral", "qwen2_moe", "qwen3_moe", "olmoe", "exaone_moe", "qwen3_next",
    "sdar_moe", "deepseek_v2", "kimi_linear", "jamba",
)

# `layer_types` entries of a mixed stack (HF's names)
_WINDOW_LAYER, _FULL_LAYER = "sliding_attention", "full_attention"
_LINEAR_LAYER = "linear_attention"
_SSM_LAYER = "mamba"
# A run of like layers at least this long is held stacked and scanned
# (`ModelConfig.layer_runs`); shorter runs stay unstacked, a Python loop.
SCAN_RUN_MIN = 4


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int | None = None
    rope_theta: float = 10000.0
    # RoPE frequency scaling (Llama-3.x "llama3" NTK-by-parts, or "linear"
    # position-interpolation). Scalar fields, not a dict, so the frozen
    # config stays hashable for jit static args.
    rope_scaling_type: str | None = None
    rope_scaling_factor: float = 1.0
    rope_low_freq_factor: float = 1.0
    rope_high_freq_factor: float = 4.0
    rope_original_max_position: int = 8192
    # "yarn" (NTK-by-parts with a ramp over the rotary frequencies): the
    # rest of its numbers, (beta_fast, beta_slow, mscale, mscale_all_dim)
    rope_yarn: tuple | None = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 32768
    # HF family tag of the source checkpoint; drives the save-side name
    # mapping (hf_io) — the forward path keys off the feature flags below.
    model_type: str = "qwen2"
    # Qwen2/2.5: bias on qkv projections; Llama: none.
    qkv_bias: bool = True
    # Qwen3: per-head RMSNorm on q and k. OLMoE norms the WHOLE q and k
    # projections (nH*hd and nKV*hd wide) before the split into heads:
    # `qk_norm_full`, set from the model type.
    qk_norm: bool = False
    qk_norm_full: bool = False
    # Sliding-window attention (Mistral v0.1-class): each token attends at
    # most `sliding_window` positions back within its segment. None = full
    # causal. Served by the dense/prefill/decode paths; the Pallas
    # flash/ring kernels reject it loudly rather than silently attending
    # globally.
    sliding_window: int | None = None
    # MLP activation: "silu" (SwiGLU families) | "gelu_pytorch_tanh" /
    # "gelu_new" / "gelu" (Gemma's GeGLU, GPT-2's fc MLP).
    hidden_act: str = "silu"
    # Gemma conventions: RMSNorm scale stored zero-centered (effective
    # scale = 1 + weight), and embeddings multiplied by sqrt(hidden_size).
    norm_zero_centered: bool = False
    normalize_embed: bool = False
    # GPT-2 conventions: mean-centering LayerNorm with bias, learned
    # absolute position embeddings (wpe), ungated fc1/act/fc2 MLP, and a
    # bias on the attention output projection.
    norm_type: str = "rmsnorm"  # "rmsnorm" | "layernorm"
    pos_embed: str = "rope"  # "rope" | "learned" | "none" (no positional encoding)
    mlp_style: str = "glu"  # "glu" (gate/up/down) | "fc" (fc1/fc2)
    attn_out_bias: bool = False
    # compute/storage dtypes
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"
    # compile-time toggles
    scan_layers: bool = True
    # each decoder layer a `jax.checkpoint` region: the backward may
    # recompute. WHAT it keeps in place of recomputing is not configured:
    # `forward(remat_kept=)` takes the names, which the trainer computes from
    # the step's shapes and the chip's room (`utils/hbm.py:choose_remat_kept`)
    remat: bool = False
    # attention implementation: "dense" materialises the [T,T] score matrix
    # (fine for short packs / CPU tests); "flash" uses the Pallas
    # online-softmax kernel (areal_tpu/ops/flash_attention.py) — O(T) memory,
    # required for long-context packs; "auto" picks flash on TPU.
    attn_impl: str = "auto"
    # Zig-zag context-parallel layout: when attention resolves to "ring"
    # and the token axis is 2n-chunk divisible, forward() permutes the
    # packed stream so every CP shard holds one early + one late chunk
    # (equal causal work) and inverts the permutation on its outputs.
    # Exact — a pure relabeling (ops/ring_attention.py zig-zag positions).
    cp_zigzag: bool = False
    # critic/reward mode: scalar value head instead of the LM head
    # (parity: the reference's AutoModelForTokenClassification path,
    # areal/engine/base_hf_engine.py:180-187)
    is_critic: bool = False
    # -- MoE (Qwen3-MoE / Mixtral / OLMoE-class; reference MoE support
    # lives in Megatron EP + realhf/impl/model/modules/moe/{router,experts}.py)
    # -- num_experts == 0 means dense MLP. Routing is exact (dropless):
    # token-expert pairs are sorted by expert and run through a grouped
    # (ragged) matmul over the stacked [E, ...] expert kernels, which are
    # sharded over the "experts" logical axis (see `moe_mlp`).
    num_experts: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int | None = None
    # Qwen2-MoE: an always-on dense expert beside the routed ones, mixed in
    # through a sigmoid gate (0 = no shared expert).
    shared_expert_intermediate_size: int = 0
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.0
    # Qwen2-MoE mixes its shared expert in through a sigmoid gate of its
    # own; K-EXAONE (DeepSeek-V3's form) adds it as it is.
    shared_expert_gated: bool = True
    # Router score: "softmax" over all experts, or "sigmoid" of each logit
    # (DeepSeek-V3, K-EXAONE). `moe_router_bias`: a per-expert buffer added
    # to the score for the CHOICE of the top k only, never to the weight.
    # `routed_scaling_factor` multiplies the (normalised) weights.
    moe_scoring: str = "softmax"
    moe_router_bias: bool = False
    routed_scaling_factor: float = 1.0
    # Experts across chips: the router is `num_experts_published` wide and
    # every token picks its k among all of them; this chip HOLDS the
    # `num_experts` experts [expert_first, expert_first + num_experts) and
    # computes their part of the layer. Pairs whose expert lives elsewhere
    # are computed by no one here (None = every expert is held).
    num_experts_published: int | None = None
    expert_first: int = 0
    # -- mixed stacks (K-EXAONE-class). `layer_types[i]` is
    # "sliding_attention" (window `sliding_window`) or "full_attention";
    # None = one kind for the whole stack. `nope_full_layers`: the full
    # layers of a mixed stack take no rotary embedding. `first_k_dense`:
    # leading layers whose MLP is dense (`intermediate_size`) before the
    # sparse ones. Hashable tuples/ints, so the frozen config stays a jit
    # static. A mixed stack has no uniform per-layer pytree: its layers
    # live unstacked (`layers_{i}`, scan_layers=False) and the layer loops
    # read each layer's kind from here, statically; its long runs of like
    # recurrent layers are held stacked and scanned (`layer_runs`).
    layer_types: tuple | None = None
    nope_full_layers: bool = False
    first_k_dense: int = 0
    # -- Qwen3-Next-class. A third layer kind, "linear_attention": a Gated
    # DeltaNet mixer (`gated_delta_net`) of `linear_num_key_heads` key and
    # `linear_num_value_heads` value heads, behind a depthwise causal
    # convolution of width `linear_conv_kernel_dim`. Its cache is no rows of
    # keys and values but a recurrent state a sequence. The attention layers
    # of such a model gate their output by a sigmoid of a second half of
    # `q_proj` (`attn_output_gate`) and rotate only the first
    # `partial_rotary_factor` of each head's lanes.
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    # Kimi-Linear-class: the linear layers are Kimi Delta Attention, the same
    # delta rule with the decay a VECTOR over a head's key lanes (`S <-
    # Diag(exp(g)) S`), q, k and v from projections and convolutions of
    # their own, two low-rank gates and a sigmoid on the output.
    linear_decay_lanes: bool = False
    # -- Jamba-class. A fourth layer kind, "mamba": Mamba-1's selective
    # state-space mixer (`mamba_mixer`), `ssm_inner` = `ssm_expand` x hidden
    # channels behind a depthwise causal convolution of width
    # `linear_conv_kernel_dim` (with a bias under `ssm_conv_bias`), each
    # channel a diagonal recurrence over `ssm_state_size` lanes whose step
    # `dt`, input `B` and output `C` are functions of the token (through a
    # `ssm_dt_rank` bottleneck; Jamba norms the three). Its cache is a
    # float32 state `[ssm_state_size, ssm_inner]` a slot and the
    # convolution's last rows, as a linear layer's (`slot_state_shapes`).
    # 0 lanes: no such layers.
    ssm_state_size: int = 0
    ssm_expand: int = 2
    ssm_dt_rank: int = 0
    ssm_conv_bias: bool = True
    attn_output_gate: bool = False
    partial_rotary_factor: float = 1.0
    # -- SDAR-class (generation by diffusion over blocks). `block_length` B > 1:
    # attention is block-causal, position j visible to position i iff
    # j // B <= i // B (causal across blocks, both directions inside one;
    # blocks are aligned to absolute position 0), and the decode engine
    # generates a block of B positions at a time by denoising
    # `mask_token_id` placeholders (engine/jax_decode.py). None or 1: causal,
    # every other model.
    block_length: int | None = None
    mask_token_id: int | None = None
    # -- DeepSeek-V2-class latent attention (MLA). `kv_lora_rank` C > 0: a
    # token's cache is ONE row a layer, `[c_kv (C) | k_pe (qk_rope_head_dim)]`,
    # the normed latent and one rotary head shared by every query head; keys
    # and values are `kv_b_kernel`'s expansion of c_kv (`qk_nope_head_dim`
    # and `v_head_dim` lanes a head), the query comes through a low-rank
    # `q_lora_rank` bottleneck with a norm of its own. `forward` and `prefill`
    # expand to heads; the decode step absorbs `kv_b_kernel` into the query
    # and the output and attends over the cached rows themselves
    # (`_latent_decode_attention`). `moe_n_group` > 1: group-limited routing,
    # a token keeps the `moe_topk_group` best of `moe_n_group` groups of
    # consecutive experts (a group's score its best expert's) and picks its
    # k among those groups' experts. `moe_grouped`: the router is declared
    # group-limited at however many groups (at one the grouping is vacuous),
    # and `moe_mlp`'s load vector also counts the tokens whose kept groups
    # land here and the held experts with a pair. `q_lora_rank` 0: a
    # full-rank query (`q_kernel`). Beside linear layers (Kimi-Linear) the
    # attention layers alone are latent: a slot's cache is a state AND rows.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    moe_n_group: int = 1
    moe_topk_group: int = 1
    moe_grouped: bool = False
    # vocab chunk for the fused LM-head loss (ops/fused_xent.py): peak
    # logits transient is [tokens, loss_vocab_chunk]
    loss_vocab_chunk: int = 16384
    # -- LoRA (parity: the reference's peft path, areal/engine/
    # fsdp_engine.py:270 + TrainEngineConfig.use_lora/lora_rank/...).
    # rank 0 = disabled. Adapters live in a SEPARATE top-level "lora"
    # subtree (params["lora"]), so the engine can differentiate/optimize
    # that subtree alone while the frozen base rides under stop_gradient —
    # XLA then dead-code-eliminates the base weight-gradient matmuls.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # HF-style target module names; mapped onto kernel leaves below.
    lora_targets: tuple = ("q_proj", "v_proj")

    @property
    def head_dim_(self) -> int:
        return self.head_dim or self.hidden_size // self.num_attention_heads

    @property
    def num_experts_published_(self) -> int:
        return self.num_experts_published or self.num_experts

    @property
    def block_length_(self) -> int:
        """Positions of one attention block: 1 is the causal mask."""
        return int(self.block_length or 1)

    @property
    def mixed(self) -> bool:
        """Layers of more than one kind: no stacked scan, two caches."""
        return (self.layer_types is not None or self.first_k_dense > 0
                or self.latent)

    @property
    def latent(self) -> bool:
        """Latent attention: one cached row a token and layer, no V side."""
        return self.kv_lora_rank > 0

    @property
    def latent_row(self) -> int:
        """Lanes of a cached latent row that carry something: `[c_kv | k_pe]`."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_lanes(self) -> int:
        """Lanes a latent pool's row HAS: `latent_row` up to whole vregs of
        128. A TPU stores an array's minor dimension in tiles of 128 lanes
        whatever its logical size, and Mosaic copies whole tiles, so the pad
        is written out (zeros) and every byte count sees it."""
        return -(-self.latent_row // 128) * 128

    @property
    def rotary_dim(self) -> int:
        """Lanes of a head that the rotary embedding turns (the first ones;
        a latent model's rotary part is a head of its own, `qk_rope_head_dim`)."""
        if self.latent:
            return self.qk_rope_head_dim
        return int(self.head_dim_ * self.partial_rotary_factor)

    def layer_window(self, i) -> int | None:
        """Layer i's attention window (None = full causal). `i` is a
        python int in a mixed stack; a uniform stack ignores it (it may be
        a scan's traced index)."""
        if self.layer_types is None:
            return self.sliding_window
        return self.sliding_window if self.layer_types[i] == _WINDOW_LAYER else None

    def layer_linear(self, i) -> bool:
        """Whether layer i's mixer is a recurrence (the Gated DeltaNet, Kimi
        Delta Attention, a state-space mixer) and not attention: its cache is
        a state a slot."""
        return self.layer_types is not None and self.layer_types[i] in (
            _LINEAR_LAYER, _SSM_LAYER)

    @property
    def ssm_inner(self) -> int:
        """Channels of a state-space mixer."""
        return self.ssm_expand * self.hidden_size

    @property
    def slot_state_shapes(self) -> dict:
        """What ONE slot keeps for ONE recurrent layer, whichever mixer the
        model's recurrent layers are: {"S": the float32 state's shape,
        "conv": the convolution's cached rows' (pre-convolution channels, in
        the cache's dtype)}. The one place that knows; the slot cache, the
        step kernels' callers and the byte counts ask here."""
        K = self.linear_conv_kernel_dim
        if self.ssm_state_size:
            # the state's lanes are the channels, its sublanes the state's
            return {"S": (self.ssm_state_size, self.ssm_inner),
                    "conv": (K - 1, self.ssm_inner)}
        return {"S": (self.linear_num_value_heads, self.linear_key_head_dim,
                      self.linear_value_head_dim),
                "conv": (K - 1, self.linear_conv_channels)}

    @property
    def layer_runs(self) -> tuple:
        """The runs `(first, past_last)` of an unstacked tree that are held
        STACKED and scanned: maximal runs, at least `SCAN_RUN_MIN` long, of
        recurrent layers under a dense MLP (their body takes the layer's
        place in the state pool traced). A property of the stack: every
        other layer stays `layers_{i}`, one body a layer in the program."""
        if self.scan_layers or self.layer_types is None:
            return ()
        like = [self.layer_linear(i) and not self.layer_sparse(i)
                for i in range(self.num_hidden_layers)]
        runs, a = [], None
        for i, ok in enumerate(like + [False]):
            if ok and a is None:
                a = i
            elif not ok and a is not None:
                if i - a >= SCAN_RUN_MIN:
                    runs.append((a, i))
                a = None
        return tuple(runs)

    @property
    def stack_plan(self) -> tuple:
        """An unstacked tree's top-level layer entries in layer order, as
        `(key, first, past_last)`: `layers_{i}` for one layer, `run_{a}_{b}`
        for a stacked run (`layer_runs`)."""
        plan, i = [], 0
        starts = dict(self.layer_runs)
        while i < self.num_hidden_layers:
            b = starts.get(i, i + 1)
            plan.append((f"run_{i}_{b}" if i in starts else f"layers_{i}", i, b))
            i = b
        return tuple(plan)

    @property
    def linear_conv_channels(self) -> int:
        """Channels the convolution runs over: q, k and v side by side."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    def layer_rope(self, i) -> bool:
        """Whether layer i rotates q and k."""
        if self.pos_embed != "rope":
            return False
        return not (self.nope_full_layers and self.layer_window(i) is None)

    def layer_sparse(self, i) -> bool:
        """Whether layer i's MLP is the routed one."""
        return bool(self.num_experts) and (
            self.first_k_dense == 0 or i >= self.first_k_dense
        )

    @property
    def cache_layers(self) -> dict:
        """{"full": layer indices with a paged cache, "window": those with
        a ring, "state": those with a recurrent state a slot, "latent": those
        with one latent row a token} of a mixed stack, in layer order."""
        L = range(self.num_hidden_layers)
        state = tuple(i for i in L if self.layer_linear(i))
        if self.latent:
            return {"full": (), "window": (), "state": state,
                    "latent": tuple(i for i in L if i not in state)}
        return {
            "full": tuple(
                i for i in L if self.layer_window(i) is None and i not in state
            ),
            "window": tuple(i for i in L if self.layer_window(i) is not None),
            "state": state,
        }

    @classmethod
    def from_hf_config(cls, path_or_dict, **overrides) -> "ModelConfig":
        """Build from an HF config.json (dict or model dir path)."""
        if isinstance(path_or_dict, str):
            with open(os.path.join(path_or_dict, "config.json")) as f:
                hf = json.load(f)
        else:
            hf = dict(path_or_dict)
        model_type = hf.get("model_type", "qwen2")
        if model_type not in MODEL_TYPES:
            raise NotImplementedError(
                f"model_type {model_type!r} is not in the registry "
                f"{MODEL_TYPES}: loading it as a dense qwen2-shaped model "
                "would silently drop what makes it that family"
            )
        if model_type == "gpt2":
            # GPT2Config uses its own key names; normalize them up front so
            # the shared kw block below reads one schema.
            hf = dict(hf)
            hf.setdefault("hidden_size", hf["n_embd"])
            hf.setdefault(
                "intermediate_size", hf.get("n_inner") or 4 * hf["n_embd"]
            )
            hf.setdefault("num_hidden_layers", hf["n_layer"])
            hf.setdefault("num_attention_heads", hf["n_head"])
            hf.setdefault("max_position_embeddings", hf["n_positions"])
            hf.setdefault("rms_norm_eps", hf.get("layer_norm_epsilon", 1e-5))
            hf.setdefault(
                "hidden_act", hf.get("activation_function", "gelu_new")
            )
            hf.setdefault("tie_word_embeddings", True)
        sw_kw: dict = {}
        if model_type in ("mistral", "mixtral") and hf.get("sliding_window"):
            sw_kw = dict(sliding_window=int(hf["sliding_window"]))
        elif model_type in (
            "qwen2", "qwen2_moe", "qwen3", "qwen3_moe", "sdar_moe"
        ) and hf.get("use_sliding_window"):
            # HF windows only layers with layer_idx >= max_window_layers:
            # mwl >= L means NO layer is windowed (the shape Qwen2.5 ships,
            # e.g. 28/28); mwl == 0 windows every layer; anything between
            # is a mixed stack that breaks scan-over-layers uniformity.
            # A missing key defaults to "no window" — conservative-correct
            # for stock configs.
            L = hf["num_hidden_layers"]
            mwl = hf.get("max_window_layers", L)
            if mwl is None or mwl >= L:
                pass  # no layer windowed
            elif mwl == 0:
                sw_kw = dict(sliding_window=int(hf["sliding_window"]))
            else:
                raise NotImplementedError(
                    "use_sliding_window with 0 < max_window_layers < "
                    "num_hidden_layers (mixed full/window layers) is not "
                    "supported"
                )
        # Llama/Mistral-family checkpoints share the qwen2 decoder layout
        # and tensor names exactly (RMSNorm + SwiGLU + RoPE GQA, biasless
        # qkv); what distinguishes Llama-3.x is its RoPE frequency scaling,
        # parsed below. Parity: the reference's per-family from_hf registry
        # (realhf/api/from_hf/{llama,qwen2}.py) collapses to one config here.
        rope_kw: dict = {}
        rs = hf.get("rope_scaling") or {}
        rs_type = rs.get("rope_type", rs.get("type"))
        if rs_type in ("llama3",):
            rope_kw = dict(
                rope_scaling_type="llama3",
                rope_scaling_factor=rs.get("factor", 8.0),
                rope_low_freq_factor=rs.get("low_freq_factor", 1.0),
                rope_high_freq_factor=rs.get("high_freq_factor", 4.0),
                rope_original_max_position=rs.get(
                    "original_max_position_embeddings", 8192
                ),
            )
        elif rs_type == "linear":
            rope_kw = dict(
                rope_scaling_type="linear",
                rope_scaling_factor=rs.get("factor", 1.0),
            )
        elif rs_type == "yarn":
            rope_kw = dict(
                rope_scaling_type="yarn",
                rope_scaling_factor=float(rs.get("factor", 1.0)),
                rope_original_max_position=int(rs.get(
                    "original_max_position_embeddings",
                    hf.get("max_position_embeddings", 8192),
                )),
                rope_yarn=(
                    float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
                    float(rs.get("mscale", 1.0)), float(rs.get("mscale_all_dim", 0.0)),
                ),
            )
        elif rs_type not in (None, "default", "mrope"):
            # dynamic etc.: loading would silently misplace positions
            raise NotImplementedError(
                f"rope_scaling type {rs_type!r} not implemented "
                "(supported: llama3, linear, yarn)"
            )
        if model_type == "exaone_moe":
            # `rope_parameters` is this family's nesting of theta and type
            rp = hf.get("rope_parameters") or {}
            hf = {**hf, "rope_theta": rp.get("rope_theta", hf.get("rope_theta", 1e6))}
            if rp.get("rope_type", "default") != "default":
                raise NotImplementedError(
                    f"exaone_moe rope_type {rp.get('rope_type')!r} is not "
                    "implemented (served: default)"
                )
        kw = dict(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_hidden_layers=hf["num_hidden_layers"],
            num_attention_heads=hf["num_attention_heads"],
            num_key_value_heads=hf.get(
                "num_key_value_heads", hf["num_attention_heads"]
            ),
            head_dim=hf.get("head_dim"),
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            max_position_embeddings=hf.get("max_position_embeddings", 32768),
            model_type=model_type,
            qkv_bias=model_type in ("qwen2", "qwen2_moe"),
            qk_norm=model_type in ("qwen3", "qwen3_moe", "olmoe", "sdar_moe"),
            qk_norm_full=model_type == "olmoe",
            # act_fn raises on anything unsupported, so an exotic
            # hidden_act fails loudly at trace time instead of silently
            # running silu.
            hidden_act=hf.get("hidden_act", "silu"),
            **rope_kw,
            **sw_kw,
        )
        if model_type in ("qwen3_moe", "sdar_moe"):
            if model_type == "sdar_moe" and (
                hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1
            ):
                raise NotImplementedError(
                    "sdar_moe with mlp_only_layers/decoder_sparse_step != 1 "
                    "(heterogeneous dense/sparse layers) is not supported"
                )
            kw.update(
                num_experts=hf.get("num_experts", 0),
                num_experts_per_tok=hf.get("num_experts_per_tok", 2),
                moe_intermediate_size=hf.get("moe_intermediate_size"),
                norm_topk_prob=hf.get("norm_topk_prob", True),
                router_aux_loss_coef=hf.get("router_aux_loss_coef", 0.0),
            )
            if model_type == "sdar_moe":
                # SDAR: the Qwen3-MoE decoder layer under a block-causal
                # mask. config.json gives neither key (the block length is
                # an argument of the family's sampler, the mask token an
                # added token of its tokenizer): the family's published
                # defaults, which a caller's config may override.
                kw.update(
                    block_length=int(hf.get("block_length", 4)),
                    mask_token_id=int(hf.get("mask_token_id", 151669)),
                )
        elif model_type == "qwen2_moe":
            # Qwen1.5/2-MoE: routed experts + a sigmoid-gated shared expert.
            # Only the homogeneous all-sparse stack is supported — a
            # dense/sparse layer mix (mlp_only_layers / decoder_sparse_step)
            # would break scan-over-layers' uniform per-layer pytree.
            if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
                raise NotImplementedError(
                    "qwen2_moe with mlp_only_layers/decoder_sparse_step != 1 "
                    "(heterogeneous dense/sparse layers) is not supported"
                )
            kw.update(
                num_experts=hf.get("num_experts", 60),
                num_experts_per_tok=hf.get("num_experts_per_tok", 4),
                moe_intermediate_size=hf.get("moe_intermediate_size"),
                shared_expert_intermediate_size=hf.get(
                    "shared_expert_intermediate_size", 0
                ),
                norm_topk_prob=hf.get("norm_topk_prob", False),
                router_aux_loss_coef=hf.get("router_aux_loss_coef", 0.0),
            )
        elif model_type == "mixtral":
            # Mixtral: top-k over full-softmax probs, renormalized — the
            # norm_topk_prob=True convention; experts reuse
            # intermediate_size; weights live under block_sparse_moe.*.
            kw.update(
                num_experts=hf.get("num_local_experts", 8),
                num_experts_per_tok=hf.get("num_experts_per_tok", 2),
                moe_intermediate_size=hf["intermediate_size"],
                norm_topk_prob=True,
                router_aux_loss_coef=hf.get("router_aux_loss_coef", 0.0),
            )
        elif model_type == "olmoe":
            # OLMoE (transformers modeling_olmoe.py): softmax over all
            # experts in float32, top-k, weights NOT renormalised; experts
            # are `intermediate_size` wide (config.json has no expert-width
            # key of its own); no shared expert, no biases. `clip_qkv`
            # (a clamp on q/k/v) is null in the published configs.
            if hf.get("clip_qkv") is not None or hf.get("attention_bias"):
                raise NotImplementedError(
                    "olmoe with clip_qkv (a q/k/v clamp) or attention_bias "
                    "set is not implemented"
                )
            kw.update(
                num_experts=hf.get("num_experts", 64),
                num_experts_per_tok=hf.get("num_experts_per_tok", 8),
                moe_intermediate_size=hf["intermediate_size"],
                norm_topk_prob=hf.get("norm_topk_prob", False),
                router_aux_loss_coef=hf.get("router_aux_loss_coef", 0.0),
                rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            )
        elif model_type == "exaone_moe":
            kw.update(_exaone_moe_kw(hf))
        elif model_type == "qwen3_next":
            kw.update(_qwen3_next_kw(hf))
        elif model_type == "deepseek_v2":
            kw.update(_deepseek_v2_kw(hf))
        elif model_type == "kimi_linear":
            kw.update(_kimi_linear_kw(hf))
        elif model_type == "jamba":
            kw.update(_jamba_kw(hf))
        elif model_type == "gemma":
            # Gemma-1 (reference: realhf/api/from_hf/gemma.py — GeGLU MLP,
            # zero-centered RMSNorm, sqrt(H)-scaled embeddings, tied head).
            kw.update(
                # HF Gemma ignores legacy `hidden_act` and defaults the
                # newer `hidden_activation` field to gelu_pytorch_tanh.
                hidden_act=hf.get("hidden_activation") or "gelu_pytorch_tanh",
                norm_zero_centered=True,
                normalize_embed=True,
                tie_word_embeddings=hf.get("tie_word_embeddings", True),
            )
        elif model_type == "gemma2":
            raise NotImplementedError(
                "gemma2 (attention softcapping, pre+post norms, sliding "
                "window) is not implemented; supported gemma family: gemma"
            )
        elif model_type == "gpt2":
            # GPT-2 (reference: realhf/api/from_hf/gpt2.py — its CPU-test
            # workhorse): LayerNorm+bias, wpe positions, fc MLP, MHA with
            # fused c_attn (split at load, hf_io._gpt2_flat).
            if hf.get("scale_attn_by_inverse_layer_idx") or hf.get(
                "reorder_and_upcast_attn"
            ):
                raise NotImplementedError(
                    "gpt2 variants with scale_attn_by_inverse_layer_idx / "
                    "reorder_and_upcast_attn would silently mis-scale "
                    "attention; not implemented"
                )
            if hf.get("add_cross_attention"):
                raise NotImplementedError(
                    "gpt2 with add_cross_attention: the crossattention.* "
                    "tensors have no slot in the causal-LM tree and would "
                    "be silently dropped"
                )
            kw.update(
                norm_type="layernorm",
                pos_embed="learned",
                mlp_style="fc",
                qkv_bias=True,
                attn_out_bias=True,
            )
        kw.update(overrides)
        return cls(**kw)

    @property
    def moe_intermediate_size_(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def rope_scaling_(self) -> tuple | None:
        """Hashable scaling spec for `rope_table`, or None when unscaled."""
        if self.rope_scaling_type == "llama3":
            return (
                "llama3",
                self.rope_scaling_factor,
                self.rope_low_freq_factor,
                self.rope_high_freq_factor,
                self.rope_original_max_position,
            )
        if self.rope_scaling_type == "linear":
            return ("linear", self.rope_scaling_factor)
        if self.rope_scaling_type == "yarn":
            return (
                "yarn",
                self.rope_scaling_factor,
                self.rope_original_max_position,
                *(self.rope_yarn or (32.0, 1.0, 1.0, 0.0)),
            )
        return None

    @property
    def latent_softmax_scale(self) -> float:
        """The latent attention's softmax scale: a head's q/k width to the
        -1/2, times YaRN's `mscale(factor, mscale_all_dim)` squared where the
        model declares that (DeepSeek-V2 folds the long-context temperature
        into the scale, its tables' own factor being 1)."""
        scale = float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_scaling_type == "yarn" and self.rope_yarn and self.rope_yarn[3]:
            m = yarn_mscale(self.rope_scaling_factor, self.rope_yarn[3])
            scale *= m * m
        return scale


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def _held_experts_kw(hf: dict) -> dict:
    """The experts this chip holds of the published ones: `num_experts` of
    `num_experts_published` (all of them when not given) from `expert_first`."""
    held = int(hf["num_experts"])
    published = int(hf.get("num_experts_published", held))
    first = int(hf.get("expert_first", 0))
    if not 0 <= first <= published - held:
        raise ValueError(
            f"{hf.get('model_type')} holds experts [{first}, {first + held}) "
            f"of {published}"
        )
    return dict(num_experts=held, num_experts_published=published, expert_first=first)


def _exaone_moe_kw(hf: dict) -> dict:
    """K-EXAONE (`exaone_moe`): window and full layers in one stack with
    rotary embedding on the window layers only, per-head q/k norm, leading
    dense layers, then sigmoid-scored experts with a selection bias,
    normalised top-k times `routed_scaling_factor`, and an ungated shared
    expert. Raises on what is not served."""
    L = hf["num_hidden_layers"]
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise NotImplementedError(
            "exaone_moe with n_group/topk_group != 1 (group-limited "
            "routing) is not implemented"
        )
    if hf.get("num_nextn_predict_layers", 0):
        raise NotImplementedError(
            "exaone_moe with num_nextn_predict_layers > 0: the multi-token "
            "prediction layer is not implemented (it takes no part in the "
            "main model's logits; set it to 0 to serve the model without it)"
        )
    if hf.get("scoring_func", "sigmoid") not in ("sigmoid", "softmax"):
        raise NotImplementedError(
            f"exaone_moe scoring_func {hf['scoring_func']!r} is not implemented"
        )
    types = hf.get("layer_types")
    if types is None:
        raise NotImplementedError("exaone_moe without layer_types")
    # a config cut in depth keeps the published list whole: the first L count
    types = tuple(types[:L])
    if len(types) != L or any(t not in (_WINDOW_LAYER, _FULL_LAYER) for t in types):
        raise NotImplementedError(
            f"exaone_moe layer_types {types!r}: need {L} entries of "
            f"{_WINDOW_LAYER!r} / {_FULL_LAYER!r}"
        )
    window = hf.get("sliding_window")
    if _WINDOW_LAYER in types and not window:
        raise NotImplementedError("exaone_moe window layers without sliding_window")
    k_dense = int(hf.get("first_k_dense_replace", 0))
    mlp_types = hf.get("mlp_layer_types")
    if mlp_types is not None:
        mlp_types = tuple(mlp_types[:L])
        want = ("dense",) * min(k_dense, L) + ("sparse",) * max(L - k_dense, 0)
        if mlp_types != want:
            raise NotImplementedError(
                f"exaone_moe mlp_layer_types {mlp_types!r} is not "
                f"first_k_dense_replace={k_dense} dense layers then sparse ones"
            )
    n_shared = int(hf.get("num_shared_experts", 0))
    return dict(
        qk_norm=True,
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        sliding_window=int(window) if window else None,
        layer_types=types,
        nope_full_layers=True,
        first_k_dense=k_dense,
        **_held_experts_kw(hf),
        num_experts_per_tok=hf.get("num_experts_per_tok", 8),
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_intermediate_size=n_shared * hf["moe_intermediate_size"],
        shared_expert_gated=False,
        norm_topk_prob=hf.get("norm_topk_prob", True),
        moe_scoring=hf.get("scoring_func", "sigmoid"),
        moe_router_bias=True,
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        router_aux_loss_coef=hf.get("router_aux_loss_coef", 0.0),
        # layers of two kinds do not stack
        scan_layers=False,
    )


def _qwen3_next_kw(hf: dict) -> dict:
    """Qwen3-Next (`qwen3_next`): Gated DeltaNet layers with one gated
    full-attention layer every `full_attention_interval`, per-head q/k norm,
    rotary embedding on a prefix of the head's lanes, every layer sparse
    (softmax routing normalised over the chosen, a sigmoid-gated shared
    expert). Norm weights are held as effective scales (`hf_io` adds the 1 a
    checkpoint leaves out). Raises on what is not served."""
    L = hf["num_hidden_layers"]
    if hf.get("mlp_only_layers") or hf.get("decoder_sparse_step", 1) != 1:
        raise NotImplementedError(
            "qwen3_next with mlp_only_layers / decoder_sparse_step != 1 "
            "(dense layers among the sparse ones) is not implemented"
        )
    if hf.get("rope_scaling"):
        raise NotImplementedError(
            f"qwen3_next with rope_scaling {hf['rope_scaling']!r} is not "
            "implemented (served: none)"
        )
    types = hf.get("layer_types")
    if types is None:
        interval = int(hf.get("full_attention_interval", 4))
        types = [
            _FULL_LAYER if (i + 1) % interval == 0 else _LINEAR_LAYER
            for i in range(L)
        ]
    # a config cut in depth keeps the published list whole: the first L count
    types = tuple(types[:L])
    if len(types) != L or any(t not in (_LINEAR_LAYER, _FULL_LAYER) for t in types):
        raise NotImplementedError(
            f"qwen3_next layer_types {types!r}: need {L} entries of "
            f"{_LINEAR_LAYER!r} / {_FULL_LAYER!r}"
        )
    return dict(
        qk_norm=True,
        layer_types=types,
        partial_rotary_factor=float(hf.get("partial_rotary_factor", 0.25)),
        attn_output_gate=True,
        linear_num_key_heads=int(hf["linear_num_key_heads"]),
        linear_num_value_heads=int(hf["linear_num_value_heads"]),
        linear_key_head_dim=int(hf["linear_key_head_dim"]),
        linear_value_head_dim=int(hf["linear_value_head_dim"]),
        linear_conv_kernel_dim=int(hf.get("linear_conv_kernel_dim", 4)),
        **_held_experts_kw(hf),
        num_experts_per_tok=hf.get("num_experts_per_tok", 10),
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_intermediate_size=hf.get("shared_expert_intermediate_size", 0),
        norm_topk_prob=hf.get("norm_topk_prob", True),
        router_aux_loss_coef=hf.get("router_aux_loss_coef", 0.0),
        # layers of two kinds do not stack
        scan_layers=False,
    )


def _deepseek_v2_kw(hf: dict) -> dict:
    """DeepSeek-V2 (`deepseek_v2`): latent attention (a low-rank query, one
    cached row `[c_kv | k_pe]` a token, `kv_b_proj` expanding it to heads),
    leading dense layers, then softmax-scored experts chosen inside the
    `topk_group` best of `n_group` expert groups, unnormalised weights times
    `routed_scaling_factor`, and ungated shared experts. Raises on what is
    not served."""
    if hf.get("moe_layer_freq", 1) != 1:
        raise NotImplementedError(
            f"deepseek_v2 with moe_layer_freq={hf['moe_layer_freq']} (dense "
            "layers among the sparse ones) is not implemented"
        )
    if hf.get("scoring_func", "softmax") != "softmax":
        raise NotImplementedError(
            f"deepseek_v2 scoring_func {hf['scoring_func']!r} is not "
            "implemented (served: softmax)"
        )
    method = hf.get("topk_method", "greedy")
    if method not in ("group_limited_greedy", "greedy"):
        raise NotImplementedError(
            f"deepseek_v2 topk_method {method!r} is not implemented (served: "
            "group_limited_greedy, greedy)"
        )
    if not hf.get("q_lora_rank") or not hf.get("kv_lora_rank"):
        raise NotImplementedError(
            "deepseek_v2 without q_lora_rank / kv_lora_rank (a full-rank "
            "query projection) is not implemented"
        )
    if hf.get("attention_bias"):
        raise NotImplementedError("deepseek_v2 with attention_bias is not implemented")
    held = _held_experts_kw({**hf, "num_experts": hf["n_routed_experts"]})
    n_group, topk_group = 1, 1
    if method == "group_limited_greedy":
        n_group, topk_group = int(hf.get("n_group", 1)), int(hf.get("topk_group", 1))
    published = held["num_experts_published"]
    if n_group < 1 or published % n_group or not 1 <= topk_group <= n_group:
        raise NotImplementedError(
            f"deepseek_v2 n_routed_experts={published} does not divide into "
            f"n_group={n_group} groups (topk_group={topk_group})"
        )
    size = published // n_group
    if n_group > 1 and (held["num_experts"] % size or held["expert_first"] % size):
        raise NotImplementedError(
            f"deepseek_v2 holds experts [{held['expert_first']}, "
            f"{held['expert_first'] + held['num_experts']}): not whole routing "
            f"groups of {size} (a group is what a chip of the deployment holds)"
        )
    if hf["num_experts_per_tok"] > topk_group * size:
        raise NotImplementedError(
            f"deepseek_v2 num_experts_per_tok={hf['num_experts_per_tok']} "
            f"exceeds the {topk_group * size} experts of the kept groups"
        )
    nope, rope = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    return dict(
        kv_lora_rank=int(hf["kv_lora_rank"]),
        q_lora_rank=int(hf["q_lora_rank"]),
        qk_nope_head_dim=nope,
        qk_rope_head_dim=rope,
        v_head_dim=int(hf["v_head_dim"]),
        # a head's q/k width; keys and values are not cached by head
        head_dim=nope + rope,
        first_k_dense=int(hf.get("first_k_dense_replace", 0)),
        **held,
        num_experts_per_tok=int(hf["num_experts_per_tok"]),
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_intermediate_size=(
            int(hf.get("n_shared_experts") or 0) * hf["moe_intermediate_size"]
        ),
        shared_expert_gated=False,
        norm_topk_prob=bool(hf.get("norm_topk_prob", False)),
        moe_scoring="softmax",
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        router_aux_loss_coef=float(hf.get("aux_loss_alpha", 0.0)),
        moe_n_group=n_group,
        moe_topk_group=topk_group,
        moe_grouped=n_group > 1,
        # no uniform stack: a leading dense layer, and one pool of rows
        scan_layers=False,
    )


def _kimi_linear_kw(hf: dict) -> dict:
    """Kimi-Linear (`kimi_linear`): Kimi Delta Attention layers (a delta rule
    whose decay is a vector over a head's key lanes) with a latent-attention
    layer among every few that takes NO positional encoding (`mla_use_nope`)
    and a full-rank query, leading dense layers, then sigmoid-scored experts
    with a selection bias, weights renormalised over the chosen times
    `routed_scaling_factor`, and ungated shared experts. `linear_attn_config`
    numbers its layers from 1. Raises on what is not served."""
    L = hf["num_hidden_layers"]
    if not hf.get("mla_use_nope", False):
        raise NotImplementedError(
            "kimi_linear with mla_use_nope false (rotary latent attention "
            "beside the linear layers) is not implemented"
        )
    if hf.get("rope_scaling"):
        raise NotImplementedError(
            f"kimi_linear with rope_scaling {hf['rope_scaling']!r}: its latent "
            "attention takes no positional encoding to scale"
        )
    if hf.get("num_nextn_predict_layers", 0):
        raise NotImplementedError(
            "kimi_linear with num_nextn_predict_layers > 0: the multi-token "
            "prediction layer is not implemented (it takes no part in the "
            "main model's logits; set it to 0 to serve the model without it)"
        )
    if hf.get("q_lora_rank"):
        raise NotImplementedError(
            f"kimi_linear with q_lora_rank={hf['q_lora_rank']} (a low-rank "
            "query) is not implemented (served: null, a full-rank query)"
        )
    if hf.get("moe_layer_freq", 1) != 1:
        raise NotImplementedError(
            f"kimi_linear with moe_layer_freq={hf['moe_layer_freq']} (dense "
            "layers among the sparse ones) is not implemented"
        )
    if hf.get("num_expert_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise NotImplementedError(
            "kimi_linear with num_expert_group / topk_group != 1 (sigmoid "
            "scores under group-limited routing) is not implemented"
        )
    scoring = hf.get("moe_router_activation_func", "sigmoid")
    if scoring not in ("sigmoid", "softmax"):
        raise NotImplementedError(
            f"kimi_linear moe_router_activation_func {scoring!r} is not implemented"
        )
    lin = hf.get("linear_attn_config") or {}
    kda = {int(i) for i in lin.get("kda_layers", ())}
    full = {int(i) for i in lin.get("full_attn_layers", ())}
    # a config cut in depth keeps the published lists whole: the first L count
    types = tuple(
        _LINEAR_LAYER if i in kda else _FULL_LAYER if i in full else None
        for i in range(1, L + 1)
    )
    if None in types or kda & full:
        raise NotImplementedError(
            f"kimi_linear linear_attn_config names layers {sorted(kda)} (kda) "
            f"and {sorted(full)} (full): need each of 1..{L} in exactly one"
        )
    heads, dk = int(lin["num_heads"]), int(lin["head_dim"])
    if heads != hf["num_attention_heads"]:
        raise NotImplementedError(
            f"kimi_linear linear_attn_config num_heads={heads} differs from "
            f"num_attention_heads={hf['num_attention_heads']}: the two mixers' "
            "projections carry one set of names by head"
        )
    nope, rope = int(hf["qk_nope_head_dim"]), int(hf["qk_rope_head_dim"])
    n_shared = int(hf.get("num_shared_experts") or 0)
    return dict(
        rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
        pos_embed="none",
        layer_types=types,
        linear_decay_lanes=True,
        linear_num_key_heads=heads,
        linear_num_value_heads=heads,
        linear_key_head_dim=dk,
        linear_value_head_dim=dk,
        linear_conv_kernel_dim=int(lin.get("short_conv_kernel_size", 4)),
        kv_lora_rank=int(hf["kv_lora_rank"]),
        q_lora_rank=0,
        qk_nope_head_dim=nope,
        qk_rope_head_dim=rope,
        v_head_dim=int(hf["v_head_dim"]),
        # a latent head's q/k width (the top-level `head_dim` is used by
        # neither mixer)
        head_dim=nope + rope,
        first_k_dense=int(hf.get("first_k_dense_replace", 0)),
        **_held_experts_kw(hf),
        num_experts_per_tok=int(hf["num_experts_per_token"]),
        moe_intermediate_size=hf["moe_intermediate_size"],
        shared_expert_intermediate_size=n_shared * hf["moe_intermediate_size"],
        shared_expert_gated=False,
        norm_topk_prob=bool(hf.get("moe_renormalize", True)),
        moe_scoring=scoring,
        moe_router_bias=True,
        routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
        moe_grouped=bool(hf.get("use_grouped_topk", False)),
        # layers of two kinds do not stack
        scan_layers=False,
    )


def _jamba_kw(hf: dict) -> dict:
    """Jamba (`jamba`): Mamba-1 state-space layers with an attention layer
    every `attn_layer_period` (at `attn_layer_offset` within the period) that
    takes NO positional encoding, a dense SwiGLU MLP in every layer
    (`num_experts` 1: the "expert" layers are the same dense MLP). Raises, by
    the key's name, on what is not served."""
    if int(hf.get("num_experts", 1)) > 1:
        raise NotImplementedError(
            f"jamba with num_experts={hf['num_experts']}: the routed MLP of "
            "its expert layers is not implemented (served: num_experts 1, a "
            "dense MLP in every layer)"
        )
    if hf.get("sliding_window") is not None:
        raise NotImplementedError(
            f"jamba with sliding_window={hf['sliding_window']}: windowed "
            "attention beside state-space layers is not implemented (served: null)"
        )
    if hf.get("mamba_proj_bias", False):
        raise NotImplementedError(
            "jamba with mamba_proj_bias true: a bias on the mixer's in and out "
            "projections is not implemented (served: false)"
        )
    L, H = hf["num_hidden_layers"], hf["hidden_size"]
    period, offset = int(hf["attn_layer_period"]), int(hf["attn_layer_offset"])
    dt_rank = hf.get("mamba_dt_rank", "auto")
    return dict(
        pos_embed="none",
        layer_types=tuple(
            _FULL_LAYER if i % period == offset else _SSM_LAYER for i in range(L)
        ),
        ssm_state_size=int(hf.get("mamba_d_state", 16)),
        ssm_expand=int(hf.get("mamba_expand", 2)),
        ssm_dt_rank=-(-H // 16) if dt_rank == "auto" else int(dt_rank),
        ssm_conv_bias=bool(hf.get("mamba_conv_bias", True)),
        linear_conv_kernel_dim=int(hf.get("mamba_d_conv", 4)),
        tie_word_embeddings=hf.get("tie_word_embeddings", True),
        # attention layers among runs of state-space ones: an unstacked tree
        # whose runs are held stacked (`ModelConfig.layer_runs`)
        scan_layers=False,
    )


# ---------------------------------------------------------------------------
# Parameter tree + logical sharding axes
# ---------------------------------------------------------------------------


def _gdn_shapes(cfg: ModelConfig) -> dict:
    """The Gated DeltaNet mixer's leaves: q, k, v and the output gate z from
    one projection (columns [q | k | v | z], heads in order), the write
    strength b and the decay input a from another ([b | a])."""
    H, Hv = cfg.hidden_size, cfg.linear_num_value_heads
    value_dim = Hv * cfg.linear_value_head_dim
    return {
        "qkvz_kernel": (H, cfg.linear_conv_channels + value_dim),
        "ba_kernel": (H, 2 * Hv),
        "conv_kernel": (cfg.linear_conv_channels, cfg.linear_conv_kernel_dim),
        "dt_bias": (Hv,),
        "A_log": (Hv,),
        "norm": (cfg.linear_value_head_dim,),
        "out_kernel": (value_dim, H),
    }


def _kda_shapes(cfg: ModelConfig) -> dict:
    """The Kimi Delta Attention mixer's leaves: q, k and v each from a
    projection and a depthwise convolution of its own, the decay's low-rank
    gate `f` (to a head's key lanes: `A_log` a head, `dt_bias` a lane), the
    write strength `b` a head, the output's low-rank gate `g`, the heads'
    norm and the output projection."""
    H, n = cfg.hidden_size, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    K = cfg.linear_conv_kernel_dim
    return {
        "q_kernel": (H, n, dk),
        "k_kernel": (H, n, dk),
        "v_kernel": (H, n, dv),
        "q_conv_kernel": (n * dk, K),
        "k_conv_kernel": (n * dk, K),
        "v_conv_kernel": (n * dv, K),
        "f_a_kernel": (H, dk),
        "f_b_kernel": (dk, n * dk),
        "A_log": (n,),
        "dt_bias": (n * dk,),
        "b_kernel": (H, n),
        "g_a_kernel": (H, dv),
        "g_b_kernel": (dv, n * dv),
        "o_norm": (dv,),
        "o_kernel": (n, dv, H),
    }


def _ssm_shapes(cfg: ModelConfig) -> dict:
    """The state-space mixer's leaves: the channels u and the output gate z
    from one projection (columns [u | z]), the depthwise convolution and its
    bias, the token's step, input and output vectors from another ([dt_r | B
    | C]) with a norm each (Jamba's), the step's expansion to the channels
    with its bias, the decay `A = -exp(ssm_A_log)` held `[state lanes,
    channels]` (a checkpoint's `A_log` transposed: the channels on the TPU's
    lanes), the skip `D` and the output projection."""
    H, Di, N, Rk = cfg.hidden_size, cfg.ssm_inner, cfg.ssm_state_size, cfg.ssm_dt_rank
    return {
        "in_kernel": (H, 2 * Di),
        "conv_kernel": (Di, cfg.linear_conv_kernel_dim),
        **({"conv_bias": (Di,)} if cfg.ssm_conv_bias else {}),
        "x_kernel": (Di, Rk + 2 * N),
        "dt_norm": (Rk,),
        "b_norm": (N,),
        "c_norm": (N,),
        "dt_kernel": (Rk, Di),
        "dt_bias": (Di,),
        "ssm_A_log": (N, Di),
        "D": (Di,),
        "out_kernel": (Di, H),
    }


def _linear_shapes(cfg: ModelConfig) -> dict:
    if cfg.ssm_state_size:
        return _ssm_shapes(cfg)
    return _kda_shapes(cfg) if cfg.linear_decay_lanes else _gdn_shapes(cfg)


def _latent_shapes(cfg: ModelConfig) -> dict:
    """The latent attention's leaves. `q_b_kernel`'s columns are the heads in
    order, each `[nope | rope]` (a full-rank query, `q_lora_rank` 0: one
    `q_kernel` by head in their place); `kv_a_kernel`'s `[c_kv | k_pe]`;
    `kv_b_kernel`'s the heads in order, each `[k_nope | v]`. The rotary lanes
    are held as `rotate_half` pairs them, lane i with lane i + rope/2
    (`hf_io` permutes a checkpoint's interleaved pairs at load)."""
    H, nH = cfg.hidden_size, cfg.num_attention_heads
    C, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    query = {
        "q_a_kernel": (H, cfg.q_lora_rank),
        "q_a_norm": (cfg.q_lora_rank,),
        "q_b_kernel": (cfg.q_lora_rank, nH * (cfg.qk_nope_head_dim + rope)),
    } if cfg.q_lora_rank else {"q_kernel": (H, nH, cfg.qk_nope_head_dim + rope)}
    return {
        **query,
        "kv_a_kernel": (H, C + rope),
        "kv_a_norm": (C,),
        "kv_b_kernel": (C, nH * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o_kernel": (nH, cfg.v_head_dim, H),
    }


def _layer_shapes(cfg: ModelConfig, i: int | None = None) -> dict:
    """One layer's leaves. `i` names the layer of an unstacked tree; None is
    the stacked layer of a uniform stack."""
    H, M = cfg.hidden_size, cfg.intermediate_size
    sparse = cfg.layer_sparse(0 if i is None else i)
    S = cfg.shared_expert_intermediate_size
    nH, nKV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
    linear = i is not None and cfg.layer_linear(i)
    shapes = {
        "attn": _linear_shapes(cfg) if linear else _latent_shapes(cfg) if cfg.latent else {
            # `attn_output_gate`: each head's query lanes, then its gate's
            "q_kernel": (H, nH, 2 * hd if cfg.attn_output_gate else hd),
            "k_kernel": (H, nKV, hd),
            "v_kernel": (H, nKV, hd),
            "o_kernel": (nH, hd, H),
        },
        "mlp": (
            (
                {
                    "fc1_kernel": (H, M),
                    "fc1_bias": (M,),
                    "fc2_kernel": (M, H),
                    "fc2_bias": (H,),
                }
                if cfg.mlp_style == "fc"
                else {
                    "gate_kernel": (H, M),
                    "up_kernel": (H, M),
                    "down_kernel": (M, H),
                }
            )
            if not sparse
            else {
                "router_kernel": (H, cfg.num_experts_published_),
                **({"router_bias": (cfg.num_experts_published_,)}
                   if cfg.moe_router_bias else {}),
                "gate_kernel": (cfg.num_experts, H, cfg.moe_intermediate_size_),
                "up_kernel": (cfg.num_experts, H, cfg.moe_intermediate_size_),
                "down_kernel": (cfg.num_experts, cfg.moe_intermediate_size_, H),
                **(
                    {
                        "shared_gate_kernel": (H, S),
                        "shared_up_kernel": (H, S),
                        "shared_down_kernel": (S, H),
                        **({"shared_router_kernel": (H, 1)}
                           if cfg.shared_expert_gated else {}),
                    }
                    if S
                    else {}
                ),
            }
        ),
        "input_norm": (H,),
        "post_attn_norm": (H,),
    }
    if linear or cfg.latent:
        return shapes
    if cfg.qkv_bias:
        shapes["attn"]["q_bias"] = (nH, hd)
        shapes["attn"]["k_bias"] = (nKV, hd)
        shapes["attn"]["v_bias"] = (nKV, hd)
    if cfg.attn_out_bias:
        shapes["attn"]["o_bias"] = (H,)
    if cfg.qk_norm:
        full = cfg.qk_norm_full
        shapes["attn"]["q_norm"] = (nH * hd,) if full else (hd,)
        shapes["attn"]["k_norm"] = (nKV * hd,) if full else (hd,)
    if cfg.norm_type == "layernorm":
        shapes["input_norm_bias"] = (H,)
        shapes["post_attn_norm_bias"] = (H,)
    return shapes


_LAYER_AXES = {
    "attn": {
        "q_kernel": ("embed", "heads", "head_dim"),
        "k_kernel": ("embed", "kv_heads", "head_dim"),
        "v_kernel": ("embed", "kv_heads", "head_dim"),
        "o_kernel": ("heads", "head_dim", "embed"),
        "q_bias": ("heads", "head_dim"),
        "k_bias": ("kv_heads", "head_dim"),
        "v_bias": ("kv_heads", "head_dim"),
        "q_norm": ("norm",),
        "k_norm": ("norm",),
        "o_bias": ("norm",),
        # Gated DeltaNet (its projections mix heads of two sizes in one
        # output axis, so only the embed axis is named)
        "qkvz_kernel": ("embed", None),
        "ba_kernel": ("embed", None),
        "conv_kernel": (None, None),
        "dt_bias": ("norm",),
        "A_log": ("norm",),
        "norm": ("norm",),
        "out_kernel": (None, "embed"),
        # Kimi Delta Attention (q, k, v and o as the attention's, by head)
        "q_conv_kernel": (None, None),
        "k_conv_kernel": (None, None),
        "v_conv_kernel": (None, None),
        "f_a_kernel": ("embed", None),
        "f_b_kernel": (None, None),
        "b_kernel": ("embed", None),
        "g_a_kernel": ("embed", None),
        "g_b_kernel": (None, None),
        "o_norm": ("norm",),
        # the state-space mixer
        "in_kernel": ("embed", None),
        "conv_bias": ("norm",),
        "x_kernel": (None, None),
        "dt_norm": ("norm",),
        "b_norm": ("norm",),
        "c_norm": ("norm",),
        "dt_kernel": (None, None),
        "ssm_A_log": (None, None),
        "D": ("norm",),
        # latent attention: the low-rank axes stay whole, heads split
        "q_a_kernel": ("embed", None),
        "q_a_norm": ("norm",),
        "q_b_kernel": (None, "heads"),
        "kv_a_kernel": ("embed", None),
        "kv_a_norm": ("norm",),
        "kv_b_kernel": (None, "heads"),
    },
    "mlp": {
        "gate_kernel": ("embed", "mlp"),
        "up_kernel": ("embed", "mlp"),
        "down_kernel": ("mlp", "embed"),
        # fc style (GPT-2)
        "fc1_kernel": ("embed", "mlp"),
        "fc1_bias": ("mlp",),
        "fc2_kernel": ("mlp", "embed"),
        "fc2_bias": ("norm",),
    },
    "input_norm": ("norm",),
    "post_attn_norm": ("norm",),
    "input_norm_bias": ("norm",),
    "post_attn_norm_bias": ("norm",),
}

# HF lora target name -> (layer subtree, kernel leaf)
_LORA_TARGET_LEAVES = {
    "q_proj": ("attn", "q_kernel"),
    "k_proj": ("attn", "k_kernel"),
    "v_proj": ("attn", "v_kernel"),
    "o_proj": ("attn", "o_kernel"),
    "gate_proj": ("mlp", "gate_kernel"),
    "up_proj": ("mlp", "up_kernel"),
    "down_proj": ("mlp", "down_kernel"),
    "c_fc": ("mlp", "fc1_kernel"),
    "c_proj_mlp": ("mlp", "fc2_kernel"),
}


def _lora_leaves(cfg: ModelConfig) -> dict[tuple[str, str], tuple]:
    """{(subtree, kernel_leaf): (in_dim, out_shape...)} for enabled targets."""
    if not cfg.lora_rank:
        return {}
    shapes = _layer_shapes(cfg)
    out: dict[tuple[str, str], tuple] = {}
    for t in cfg.lora_targets:
        if t not in _LORA_TARGET_LEAVES:
            raise ValueError(
                f"lora target {t!r} not in {sorted(_LORA_TARGET_LEAVES)}"
            )
        sub, leaf = _LORA_TARGET_LEAVES[t]
        if sub == "mlp" and cfg.num_experts:
            # moe_mlp routes tokens through stacked expert kernels and
            # never reads adapter leaves — accepting the target would train
            # a dead adapter and corrupt merge_lora's 2-D einsum
            raise NotImplementedError(
                f"lora target {t!r}: adapters on MoE expert MLPs are not "
                "supported (attention targets are)"
            )
        if leaf not in shapes.get(sub, {}):
            raise ValueError(
                f"lora target {t!r} -> {sub}.{leaf} absent for this model "
                f"(mlp_style={cfg.mlp_style!r})"
            )
        out[(sub, leaf)] = shapes[sub][leaf]
    return out


def lora_param_shapes(cfg: ModelConfig) -> dict:
    """The params["lora"] subtree: per targeted kernel, a_kernel (in, r)
    and b_kernel (r, *out) — stacked [L, ...] under scan_layers like the
    base stack."""
    leaves = _lora_leaves(cfg)
    r = cfg.lora_rank
    layer: dict = {}
    for (sub, leaf), shape in leaves.items():
        # kernel layout is (in, *out) for all targets except o_kernel,
        # whose contraction is over the leading (heads, head_dim) dims
        if leaf == "o_kernel":
            a_shape = (shape[0] * shape[1], r)   # (nH*hd, r)
            b_shape = (r, shape[2])
        elif len(shape) == 3:                    # (H, n, hd) qkv
            a_shape = (shape[0], r)
            b_shape = (r, shape[1], shape[2])
        else:                                    # (in, out)
            a_shape = (shape[0], r)
            b_shape = (r, shape[1])
        layer.setdefault(sub, {})[f"{leaf}_lora_a"] = a_shape
        layer.setdefault(sub, {})[f"{leaf}_lora_b"] = b_shape
    if cfg.scan_layers:
        L = cfg.num_hidden_layers
        layer = jax.tree.map(
            lambda sh: (L, *sh), layer, is_leaf=lambda x: isinstance(x, tuple)
        )
    return layer


def lora_param_axes(cfg: ModelConfig) -> dict:
    """Logical axes for the lora subtree: A contracts the input dim
    ("embed"/"mlp"-side), B expands to the kernel's output axes; the tiny
    rank dim stays unsharded."""
    leaves = _lora_leaves(cfg)
    layer: dict = {}
    for (sub, leaf), _ in leaves.items():
        if leaf == "o_kernel":
            a_ax, b_ax = ("heads", None), (None, "embed")
        elif leaf in ("q_kernel", "k_kernel", "v_kernel"):
            kv = "kv_heads" if leaf in ("k_kernel", "v_kernel") else "heads"
            a_ax, b_ax = ("embed", None), (None, kv, "head_dim")
        elif leaf in ("down_kernel", "fc2_kernel"):
            a_ax, b_ax = ("mlp", None), (None, "embed")
        else:
            a_ax, b_ax = ("embed", None), (None, "mlp")
        layer.setdefault(sub, {})[f"{leaf}_lora_a"] = a_ax
        layer.setdefault(sub, {})[f"{leaf}_lora_b"] = b_ax
    if cfg.scan_layers:
        layer = jax.tree.map(
            lambda ax: ("layers", *ax),
            layer,
            is_leaf=lambda x: isinstance(x, tuple),
        )
    return layer


def init_lora_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """A ~ N(0, 1/r) fan-in scaled, B = 0 (delta starts at zero — the HF
    peft convention), stored in param_dtype."""
    shapes = lora_param_shapes(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    keys = jax.random.split(key, max(len(leaves), 1))

    def init_one(path_is_b, shape, k):
        if path_is_b:
            return jnp.zeros(shape, dtype=dtype)
        fan_in = shape[-2] if len(shape) >= 2 else shape[0]
        return (
            jax.random.normal(k, shape, jnp.float32) / np.sqrt(max(fan_in, 1))
        ).astype(dtype)

    flat_paths = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )[0]
    inited = [
        init_one(path[-1].key.endswith("_lora_b"), shape, k)
        for (path, shape), k in zip(flat_paths, keys)
    ]
    return jax.tree.unflatten(treedef, inited)


def merge_lora(params: dict, cfg: ModelConfig) -> dict:
    """Fold the lora deltas into the base kernels and drop the subtree —
    used for HF export and weight push (the decode engine serves plain
    kernels). W' = W + scale * A @ B with scale = alpha / r."""
    if "lora" not in params:
        return params
    assert cfg.scan_layers, "lora requires scan_layers=True"
    scale = cfg.lora_alpha / max(cfg.lora_rank, 1)
    out = {k: v for k, v in params.items() if k != "lora"}

    def merged_leaf(leaf, base, a, b):
        if leaf == "o_kernel":
            # base [L, nH, hd, H]; a [L, nH*hd, r]; b [L, r, H]
            delta = jnp.einsum("lir,lrh->lih", a, b).reshape(base.shape)
        elif leaf in ("q_kernel", "k_kernel", "v_kernel"):
            # base [L, H, n, hd]; a [L, H, r]; b [L, r, n, hd]
            delta = jnp.einsum("lhr,lrnd->lhnd", a, b)
        else:
            # base [L, i, o]; a [L, i, r]; b [L, r, o]
            delta = jnp.einsum("lir,lro->lio", a, b)
        return (
            base.astype(jnp.float32) + scale * delta.astype(jnp.float32)
        ).astype(base.dtype)

    new_layers = dict(out["layers"])
    for sub, leaves in params["lora"].items():
        new_sub = dict(new_layers[sub])
        for name in leaves:
            if not name.endswith("_lora_a"):
                continue
            leaf = name[: -len("_lora_a")]
            new_sub[leaf] = merged_leaf(
                leaf,
                new_layers[sub][leaf],
                leaves[f"{leaf}_lora_a"],
                leaves[f"{leaf}_lora_b"],
            )
        new_layers[sub] = new_sub
    out["layers"] = new_layers
    return out


def combine_layers_with_lora(params: dict, cfg: ModelConfig) -> dict:
    """The scanned layer stack with lora leaves riding alongside the base
    kernels (layer_p["attn"]["q_kernel_lora_a"], ...). attention()/mlp()
    apply the deltas to ACTIVATIONS (y += (x@A)@B·scale), never forming a
    merged weight — so the backward builds only the small dA/dB, not a
    full-size dW (the point of LoRA's memory story)."""
    if not cfg.lora_rank or "lora" not in params:
        return params["layers"]
    base = params["layers"]
    out = {k: v for k, v in base.items()}
    for sub, leaves in params["lora"].items():
        out[sub] = {**base[sub], **leaves}
    return out


def _lora_delta(layer_p: dict, leaf: str, x: jax.Array, cfg: ModelConfig):
    """scale * (x @ A) @ B for `leaf`, or None when not adapted. Output
    shape follows B's trailing dims ([..., n, hd] for qkv, [..., out]
    otherwise)."""
    a = layer_p.get(f"{leaf}_lora_a")
    if a is None:
        return None
    b = layer_p[f"{leaf}_lora_b"]
    scale = cfg.lora_alpha / max(cfg.lora_rank, 1)
    xr = jnp.einsum("...i,ir->...r", x, a)
    if b.ndim == 3:  # qkv: (r, n, hd)
        return jnp.einsum("...r,rnd->...nd", xr, b) * scale
    return jnp.einsum("...r,ro->...o", xr, b) * scale


_MOE_MLP_AXES = {
    "router_kernel": ("embed", None),
    "router_bias": (None,),
    "gate_kernel": ("experts", "embed", "mlp"),
    "up_kernel": ("experts", "embed", "mlp"),
    "down_kernel": ("experts", "mlp", "embed"),
    # qwen2_moe shared expert: a dense MLP, tp-sharded like one.
    "shared_gate_kernel": ("embed", "mlp"),
    "shared_up_kernel": ("embed", "mlp"),
    "shared_down_kernel": ("mlp", "embed"),
    "shared_router_kernel": ("embed", None),
}


def _mlp_axes(cfg: ModelConfig, i: int | None = None) -> dict:
    keys = _layer_shapes(cfg, i)["mlp"].keys()
    table = _MOE_MLP_AXES if cfg.layer_sparse(0 if i is None else i) else _LAYER_AXES["mlp"]
    return {k: table[k] for k in keys}


def param_shapes(cfg: ModelConfig) -> dict:
    layer = _layer_shapes(cfg)
    if cfg.scan_layers:
        if cfg.mixed:
            raise ValueError(
                "a mixed stack (layer_types / first_k_dense) has no uniform "
                "per-layer pytree to stack: set scan_layers=False"
            )
        L = cfg.num_hidden_layers
        layers = jax.tree.map(lambda s: (L, *s), layer, is_leaf=lambda x: isinstance(x, tuple))
        layers_tree = {"layers": layers}
    else:
        # (a run of like layers: its first layer's leaves, stacked)
        layers_tree = {
            key: _layer_shapes(cfg, a) if b - a == 1 else jax.tree.map(
                lambda s: (b - a, *s), _layer_shapes(cfg, a),
                is_leaf=lambda x: isinstance(x, tuple))
            for key, a, b in cfg.stack_plan
        }
    out = {
        "embed": {"embedding": (cfg.vocab_size, cfg.hidden_size)},
        **layers_tree,
        "final_norm": (cfg.hidden_size,),
    }
    if cfg.pos_embed == "learned":
        out["pos_embed"] = {
            "embedding": (cfg.max_position_embeddings, cfg.hidden_size)
        }
    if cfg.norm_type == "layernorm":
        out["final_norm_bias"] = (cfg.hidden_size,)
    if cfg.is_critic:
        out["value_head"] = {"kernel": (cfg.hidden_size, 1), "bias": (1,)}
    elif not cfg.tie_word_embeddings:
        out["lm_head"] = {"kernel": (cfg.hidden_size, cfg.vocab_size)}
    return out


def param_logical_axes(cfg: ModelConfig) -> dict:
    def prefix_layers(axes_tree, stacked=cfg.scan_layers):
        if stacked:
            return jax.tree.map(
                lambda a: ("layers", *a),
                axes_tree,
                is_leaf=lambda x: isinstance(x, tuple),
            )
        return axes_tree

    # only the entries present for this config (and, unstacked, this layer)
    shapes = _layer_shapes(cfg)

    def layer_axes(i=None):
        attn = shapes["attn"] if i is None else _layer_shapes(cfg, i)["attn"]
        out = {
            "attn": {k: _LAYER_AXES["attn"][k] for k in attn},
            "mlp": _mlp_axes(cfg, i),
            "input_norm": _LAYER_AXES["input_norm"],
            "post_attn_norm": _LAYER_AXES["post_attn_norm"],
        }
        if cfg.norm_type == "layernorm":
            out["input_norm_bias"] = _LAYER_AXES["input_norm_bias"]
            out["post_attn_norm_bias"] = _LAYER_AXES["post_attn_norm_bias"]
        return out

    if cfg.scan_layers:
        layers_tree = {"layers": prefix_layers(layer_axes())}
    else:
        layers_tree = {
            key: prefix_layers(layer_axes(a), b - a > 1)
            for key, a, b in cfg.stack_plan
        }
    out = {
        "embed": {"embedding": ("vocab", "embed")},
        **layers_tree,
        "final_norm": ("norm",),
    }
    if cfg.pos_embed == "learned":
        out["pos_embed"] = {"embedding": (None, "embed")}
    if cfg.norm_type == "layernorm":
        out["final_norm_bias"] = ("norm",)
    if cfg.is_critic:
        out["value_head"] = {"kernel": ("embed", "norm"), "bias": ("norm",)}
    elif not cfg.tie_word_embeddings:
        out["lm_head"] = {"kernel": ("embed", "vocab")}
    return out


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Random init (truncated-normal fan-in scaled), param_dtype storage."""
    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg.param_dtype)
    leaves, treedef = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple)
    )
    keys = jax.random.split(key, len(leaves))

    def init_one(shape, k):
        if len(shape) == 1 or (len(shape) == 2 and 0 in ()):  # norms
            return jnp.ones(shape, dtype=dtype)
        # fan-in = the contracted input dim: last-but-one for plain/stacked
        # matrices ((H,M), (L,H,M), (E,H,M) → H), first for factored attention
        # projections ((H, nH, hd) → H).
        fan_in = shape[-2] if len(shape) >= 3 and shape[-2] >= shape[0] else shape[0]
        scale = 1.0 / np.sqrt(max(fan_in, 1))
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32) * scale).astype(dtype)

    inited = [
        init_one(s, k) if len(s) > 1 else jnp.ones(s, dtype=dtype)
        for s, k in zip(leaves, keys)
    ]
    params = jax.tree.unflatten(treedef, inited)
    # biases start at zero; zero-centered norms (Gemma) too, since their
    # effective scale is 1 + weight
    def zero_special(path, x):
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if name == "dt_bias":
            if cfg.ssm_state_size:  # a step of 0.01: softplus^-1
                return jnp.full_like(x, math.log(math.expm1(0.01)))
            return x  # the Gated DeltaNet's, as its module starts it
        if name == "ssm_A_log":  # Mamba's: A[n, c] = -(n + 1)
            lanes = jnp.log(jnp.arange(1, x.shape[-2] + 1, dtype=jnp.float32))
            return jnp.broadcast_to(lanes[:, None], x.shape).astype(x.dtype)
        if name.endswith("_bias") or name == "bias":
            return jnp.zeros_like(x)
        if cfg.norm_zero_centered and name.endswith("norm"):
            return jnp.zeros_like(x)
        return x

    return jax.tree_util.tree_map_with_path(zero_special, params)


# ---------------------------------------------------------------------------
# Int8 weight serving (ISSUE 16): per-output-channel symmetric absmax.
#
# A quantized kernel leaf is a {"q": int8 (kernel's own shape),
# "scale": f32 (output dims)} dict — jax treats it as a pytree so scan,
# donation and sharding carry it untouched, and core/weight_transfer's
# flatten_named/set_named walk straight through it, which is what yields
# the `.../q` + `.../scale` wire names the DCN push ships. Only the dense
# transformer matmul kernels quantize; MoE expert/router/shared kernels,
# embed, lm_head, norms, biases and LoRA adapters stay fp.
# ---------------------------------------------------------------------------

# JaxDecodeConfig.weight_dtype values: "fp" serves the config dtype
# verbatim (the pre-quantization behavior and the numerics oracle),
# "int8" stores the dense matmul kernels in this scheme.
WEIGHT_DTYPES = ("fp", "int8")

# contraction axes per UNSTACKED kernel (the scan [L, ...] stack shifts
# every axis by one); the absmax reduces over these, leaving one scale
# per output channel so the consumer can fold it in after the matmul
_WQ_ATTN_AXES = {
    "q_kernel": (0,),
    "k_kernel": (0,),
    "v_kernel": (0,),
    "o_kernel": (0, 1),
}
_WQ_MLP_AXES = {
    "gate_kernel": (0,),
    "up_kernel": (0,),
    "down_kernel": (0,),
    "fc1_kernel": (0,),
    "fc2_kernel": (0,),
}


def _map_wq_layer(layer_tree: dict, fn, stacked: bool) -> dict:
    off = 1 if stacked else 0
    out = dict(layer_tree)
    if "attn" in layer_tree:
        sub = dict(layer_tree["attn"])
        for leaf, axes in _WQ_ATTN_AXES.items():
            if leaf in sub:
                sub[leaf] = fn(sub[leaf], tuple(a + off for a in axes))
        out["attn"] = sub
    # MoE layers (marked by their router) stay fp end to end: expert
    # kernels are ragged-routed, not dense matmuls over every token
    if "mlp" in layer_tree and "router_kernel" not in layer_tree["mlp"]:
        sub = dict(layer_tree["mlp"])
        for leaf, axes in _WQ_MLP_AXES.items():
            if leaf in sub:
                sub[leaf] = fn(sub[leaf], tuple(a + off for a in axes))
        out["mlp"] = sub
    return out


def map_quant_kernels(params: dict, fn) -> dict:
    """Rebuild the param tree with `fn(leaf, contraction_axes)` applied to
    every weight-quantizable kernel (both scan-stacked `layers` and
    per-layer `layers_{i}` forms); everything else passes through."""
    out = dict(params)
    if "layers" in params:
        out["layers"] = _map_wq_layer(params["layers"], fn, stacked=True)
    for k in params:
        if k.startswith(("layers_", "run_")):
            out[k] = _map_wq_layer(params[k], fn, stacked=k.startswith("run_"))
    return out


def quantize_weights(params: dict) -> dict:
    """fp param tree -> tree with dense matmul kernels as {"q", "scale"}.

    Idempotent on already-quantized leaves (they pass through untouched),
    so install paths can call it unconditionally."""
    from areal_tpu.ops.quant import quantize_absmax

    def one(w, axes):
        if isinstance(w, dict):  # already quantized
            return w
        q, s = quantize_absmax(w, axis=axes)
        return {"q": q, "scale": s}

    return map_quant_kernels(params, one)


def dequantize_weights(params: dict, dtype) -> dict:
    """Inverse of quantize_weights (lossy): {"q","scale"} leaves -> fp
    arrays in `dtype`. Non-quantized leaves pass through."""
    from areal_tpu.ops.quant import dequantize_absmax

    def one(w, axes):
        if not isinstance(w, dict):
            return w
        return dequantize_absmax(w["q"], w["scale"], dtype, axis=axes)

    return map_quant_kernels(params, one)


def quantize_weight_axes(axes_tree: dict) -> dict:
    """Mirror quantize_weights on a param_logical_axes tree: each
    quantizable kernel's logical-axes tuple becomes {"q": the tuple,
    "scale": the tuple minus the contraction axes} so sharding trees keep
    the same structure as the quantized params."""

    def one(ax, caxes):
        if isinstance(ax, dict):
            return ax
        return {
            "q": ax,
            "scale": tuple(a for i, a in enumerate(ax) if i not in caxes),
        }

    return map_quant_kernels(axes_tree, one)


def wq_contraction_axes(leaf: str, stacked: bool) -> tuple[int, ...] | None:
    """Contraction axes for one kernel leaf name ("q_kernel", ...), or
    None when that leaf never quantizes. `stacked` shifts for the scan
    [L, ...] layout — the form engine LoRA folds operate on."""
    ax = _WQ_ATTN_AXES.get(leaf) or _WQ_MLP_AXES.get(leaf)
    if ax is None:
        return None
    off = 1 if stacked else 0
    return tuple(a + off for a in ax)


def is_weight_quantized(params: dict) -> bool:
    """True when any dense kernel leaf is a {"q","scale"} dict."""
    found = []
    map_quant_kernels(
        params, lambda w, axes: found.append(isinstance(w, dict)) or w
    )
    return any(found)


def _w_einsum(eq: str, x: jax.Array, w, n_contract: int) -> jax.Array:
    """The matmul seam: a bare array runs the original einsum — the
    weight_dtype="fp" path stays BITWISE identical to pre-quantization
    streams — while a {"q","scale"} leaf runs the fused dequant-matmul
    (Pallas on TPU, XLA dequant-then-matmul elsewhere)."""
    if isinstance(w, dict):
        from areal_tpu.ops.quant_matmul import quant_einsum

        return quant_einsum(x, w["q"], w["scale"], n_contract)
    return jnp.einsum(eq, x, w)


# ---------------------------------------------------------------------------
# Forward computation (packed layout)
# ---------------------------------------------------------------------------


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, zero_centered: bool = False
) -> jax.Array:
    """f32 RMSNorm. `zero_centered` (Gemma): effective scale = 1 + weight."""
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = weight.astype(jnp.float32)
    if zero_centered:
        w = w + 1.0
    return (x * w).astype(dtype)


def _norm(
    x: jax.Array,
    weight: jax.Array,
    cfg: ModelConfig,
    bias: jax.Array | None = None,
) -> jax.Array:
    """Config-dispatched norm: RMSNorm (optionally zero-centered, Gemma) or
    mean-centering LayerNorm with bias (GPT-2)."""
    if cfg.norm_type == "layernorm":
        dtype = x.dtype
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + cfg.rms_norm_eps)
        y = y * weight.astype(jnp.float32)
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return y.astype(dtype)
    return rms_norm(x, weight, cfg.rms_norm_eps, cfg.norm_zero_centered)


def _activation(hidden_act: str):
    if hidden_act == "silu":
        return jax.nn.silu
    if hidden_act in ("gelu_pytorch_tanh", "gelu_new"):
        return lambda x: jax.nn.gelu(x, approximate=True)
    if hidden_act == "gelu":
        return lambda x: jax.nn.gelu(x, approximate=False)
    raise NotImplementedError(f"hidden_act={hidden_act!r}")


def act_fn(cfg: ModelConfig):
    """MLP activation from cfg.hidden_act (HF ACT2FN-compatible subset)."""
    return _activation(cfg.hidden_act)


def _scale_embed(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Gemma multiplies embedding outputs by sqrt(hidden_size)."""
    if cfg.normalize_embed:
        return x * jnp.asarray(np.sqrt(cfg.hidden_size), dtype=x.dtype)
    return x


class LMHead:
    """Lazy LM head over post-final-norm hidden states.

    Handed to `hidden_loss`-tagged loss functions instead of dense logits
    (engine/jax_engine.py loss paths): label logprobs / entropy come from
    the vocab-chunked online-logsumexp kernel (ops/fused_xent.py), so the
    f32 [T, V] logits tensor never materializes in either pass. Chunk size
    is `cfg` vocab-bounded 16k — [T, 16k] transient instead of [T, V].
    """

    def __init__(self, hidden: jax.Array, params: dict, cfg: ModelConfig):
        self.hidden = hidden
        self.params = params
        self.cfg = cfg

    def _head(self) -> tuple[jax.Array, bool]:
        if self.cfg.tie_word_embeddings:
            return self.params["embed"]["embedding"], True
        return self.params["lm_head"]["kernel"], False

    def label_logprobs(
        self, labels: jax.Array, temperature: float = 1.0
    ) -> jax.Array:
        from areal_tpu.ops.fused_xent import chunked_label_logprobs

        w, vh = self._head()
        return chunked_label_logprobs(
            self.hidden, w, labels, head_is_vh=vh, temperature=temperature,
            vocab_chunk=self.cfg.loss_vocab_chunk,
        )

    def label_logprobs_entropy(
        self, labels: jax.Array, temperature: float = 1.0
    ) -> tuple[jax.Array, jax.Array]:
        from areal_tpu.ops.fused_xent import chunked_label_logprobs

        w, vh = self._head()
        return chunked_label_logprobs(
            self.hidden,
            w,
            labels,
            head_is_vh=vh,
            temperature=temperature,
            with_entropy=True,
            vocab_chunk=self.cfg.loss_vocab_chunk,
        )

    def clamped_entropy(
        self, entropy_clamp: float, temperature: float = 1.0
    ) -> jax.Array:
        """AEnt token-space-clamped entropy (token-chunked; the clamp's
        order-statistic threshold can't ride the online vocab scan)."""
        from areal_tpu.ops.fused_xent import chunked_clamped_entropy

        w, vh = self._head()
        return chunked_clamped_entropy(
            self.hidden,
            w,
            head_is_vh=vh,
            entropy_clamp=entropy_clamp,
            temperature=temperature,
        )


def rope_table(
    positions: jax.Array,
    head_dim: int,
    theta: float,
    scaling: tuple | None = None,
) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables [T, head_dim/2], float32.

    `scaling` (from ModelConfig.rope_scaling_) applies HF-compatible RoPE
    frequency scaling: ("linear", factor) divides every frequency
    (position interpolation), ("llama3", factor, low, high, orig_max) is
    Llama-3.x NTK-by-parts — low frequencies divided by `factor`, high
    frequencies untouched, a smooth ramp between (the math of HF
    `_compute_llama3_parameters`, transformers modeling_rope_utils)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    table_scale = 1.0  # (YaRN's tables may carry a factor)
    if scaling is not None and scaling[0] == "linear":
        inv_freq = inv_freq / scaling[1]
    elif scaling is not None and scaling[0] == "llama3":
        _, factor, low_f, high_f, orig_max = scaling
        low_wl = orig_max / low_f
        high_wl = orig_max / high_f
        wavelen = 2.0 * jnp.pi / inv_freq
        # ramp: 0 at high-freq boundary (keep), 1 at low-freq boundary (scale)
        smooth = (orig_max / wavelen - low_f) / (high_f - low_f)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = jnp.where(
            wavelen > low_wl,
            inv_freq / factor,
            jnp.where(
                wavelen < high_wl,
                inv_freq,
                (1.0 - smooth) * inv_freq / factor + smooth * inv_freq,
            ),
        )
        inv_freq = scaled
    elif scaling is not None and scaling[0] == "yarn":
        # YaRN (HF `_compute_yarn_parameters`): frequencies that turn more
        # than `beta_fast` times over the original context are kept, those
        # that turn fewer than `beta_slow` times are divided by `factor`, a
        # linear ramp over the frequency index between; cos and sin carry
        # mscale(factor, mscale) / mscale(factor, mscale_all_dim)
        _, factor, orig_max, beta_fast, beta_slow, mscale, mscale_all = scaling

        def turns_at(n_rot):  # the (fractional) frequency index turning n_rot times
            return (head_dim * math.log(orig_max / (n_rot * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(turns_at(beta_fast)), 0)
        high = min(math.ceil(turns_at(beta_slow)), head_dim - 1)
        ramp = jnp.clip(
            (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
            / max(high - low, 1e-3), 0.0, 1.0,
        )
        inv_freq = inv_freq * (1.0 - ramp) + inv_freq / factor * ramp
        table_scale = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    if table_scale != 1.0:
        return jnp.cos(angles) * table_scale, jnp.sin(angles) * table_scale
    return jnp.cos(angles), jnp.sin(angles)


def _rope_tables(positions: jax.Array, cfg: "ModelConfig") -> tuple:
    """`rope_table` of the model's rotary lanes; (None, None) for a model
    that takes no positional encoding (no table in its programs)."""
    if cfg.pos_embed == "none":
        return None, None
    return rope_table(positions, cfg.rotary_dim, cfg.rope_theta, cfg.rope_scaling_)


def _split_rotary(t: jax.Array, rot: int) -> tuple:
    """(t1, t2, rest): the two halves of the first `rot` lanes of `t`'s last
    axis (HF's 'rotate_half' pairs lane i with lane i + rot/2) and, as a list
    of none or one, the lanes past them, which no rotation touches
    (`partial_rotary_factor`)."""
    d2 = rot // 2
    rest = [t[..., rot:]] if rot < t.shape[-1] else []
    return t[..., :d2], t[..., d2:rot], rest


def _rotated(t1, t2, cos, sin, rest: list) -> jax.Array:
    """The halves turned by tables that broadcast against them, the
    untouched lanes behind them."""
    return jnp.concatenate(
        [t1 * cos - t2 * sin, t2 * cos + t1 * sin, *rest], axis=-1
    )


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (HF 'rotate_half' convention) of the lanes the tables
    cover. x: [T, n, hd]; tables [T, rotary_dim/2]."""
    x1, x2, rest = _split_rotary(x, 2 * cos.shape[-1])
    cos = cos[:, None, :].astype(x1.dtype)
    sin = sin[:, None, :].astype(x1.dtype)
    return _rotated(x1, x2, cos, sin, rest)


def _window_band(T: int, sliding_window: int | None) -> jax.Array | None:
    """[T, T] bool band: q attends k iff q_idx - k_idx < window (the HF
    Mistral convention). None when unwindowed."""
    if sliding_window is None:
        return None
    idx = jnp.arange(T)
    return idx[:, None] - idx[None, :] < sliding_window


def block_horizon(position_ids: jax.Array, block_length: int) -> jax.Array | None:
    """[T] the last index of the stream each query may attend under a
    block-causal mask: the index of its block's last position (a sequence's
    tokens are contiguous, so index distance equals position distance; the
    same-segment test cuts a horizon that reaches into the next sequence).
    None for the causal mask (`block_length` 1), whose programs stay as
    they are."""
    if block_length <= 1:
        return None
    idx = jnp.arange(position_ids.shape[0], dtype=position_ids.dtype)
    return idx + (block_length - 1 - position_ids % block_length)


def segment_causal_mask(
    segment_ids: jax.Array, sliding_window: int | None = None,
    horizon: jax.Array | None = None,
) -> jax.Array:
    """[T, T] bool mask: attend iff same segment AND causal AND not padding
    (AND within `sliding_window` positions — same-segment tokens are
    contiguous in the pack, so index distance equals position distance).
    `horizon` [T] (`block_horizon`): each query's last visible index in
    place of its own."""
    T = segment_ids.shape[0]
    seg_q = segment_ids[:, None]
    seg_k = segment_ids[None, :]
    if horizon is None:
        causal = jnp.tril(jnp.ones((T, T), dtype=bool))
    else:
        causal = jnp.arange(T)[None, :] <= horizon[:, None]
    m = (seg_q == seg_k) & causal & (seg_q != PADDING_SEGMENT)
    band = _window_band(T, sliding_window)
    return m if band is None else m & band


_ATTN_IMPLS = ("auto", "flash", "dense", "ring", "chunked")


_UNIFORM = object()


def resolve_attn_impl(cfg: ModelConfig, window=_UNIFORM) -> str:
    """The trainer's attention implementation. `window` is the layer's
    window in a mixed stack, whose `attn_impl` names the implementation of
    its FULL layers: a window layer goes through "chunked" (the flash and
    ring kernels take no window yet) unless the config says "dense"."""
    if cfg.attn_impl not in _ATTN_IMPLS:
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} not in {_ATTN_IMPLS} "
            "(engine configs may also say 'pallas'/'xla' for flash/dense)"
        )
    mixed = cfg.layer_types is not None
    if window is _UNIFORM:
        window = None if mixed else cfg.sliding_window
    if cfg.latent:
        # q and k are qk_nope + qk_rope lanes a head, v is v_head_dim: the
        # Pallas flash/ring kernels take one width for all three
        if cfg.attn_impl in ("flash", "ring"):
            raise NotImplementedError(
                f"attn_impl={cfg.attn_impl!r} does not support latent "
                f"attention (q/k {cfg.qk_nope_head_dim + cfg.qk_rope_head_dim} "
                f"wide, v {cfg.v_head_dim}); use 'chunked' (O(T) memory) or "
                "'dense'"
            )
        if cfg.attn_impl != "auto":
            return cfg.attn_impl
        return "chunked" if jax.default_backend() == "tpu" else "dense"
    if cfg.block_length_ > 1:
        # a block-causal mask: like a window, the Pallas flash/ring kernels
        # have none, and attending causally would be silently wrong
        if cfg.attn_impl in ("flash", "ring"):
            raise NotImplementedError(
                f"attn_impl={cfg.attn_impl!r} does not support a block-causal "
                f"mask (block_length={cfg.block_length}); use 'chunked' "
                "(O(T) memory) or 'dense'"
            )
        if cfg.attn_impl != "auto":
            return cfg.attn_impl
        return "chunked" if jax.default_backend() == "tpu" else "dense"
    if mixed and window is not None:
        return "dense" if cfg.attn_impl == "dense" else "chunked"
    if window is not None:
        # the Pallas flash/ring kernels have no window support yet —
        # attending globally would be silently wrong. The XLA chunked
        # online-softmax path applies the window at O(T·chunk) memory
        # (dense stays available for tiny tests).
        if cfg.attn_impl in ("flash", "ring"):
            raise NotImplementedError(
                f"attn_impl={cfg.attn_impl!r} does not support "
                "sliding_window; use 'chunked' (O(T) memory) or 'dense'"
            )
        return "chunked" if cfg.attn_impl == "auto" else cfg.attn_impl
    if cfg.attn_impl != "auto":
        return cfg.attn_impl
    if jax.default_backend() != "tpu":
        return "dense"
    # Flash when tokens live on one shard; ring when the packed token axis is
    # sharded over (dp, sp) — a bare pallas_call cannot be SPMD-partitioned
    # along an axis the kernel reduces over.
    from areal_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.current_mesh()
    if mesh is not None:
        n = 1
        for a in (mesh_lib.AXIS_DP, mesh_lib.AXIS_SP):
            if a in mesh.axis_names:
                n *= mesh.shape[a]
        if n > 1:
            return "ring"
    return "flash"


def _attention_scope(cfg: ModelConfig, window) -> str:
    """Scope of the attention read itself: a mixed stack names its two
    kinds apart, so a trace tells window layers from full ones."""
    if cfg.layer_types is None:
        return "attention"
    return "attention_full" if window is None else "attention_window"


def _qk_norm(q, k, layer_p: dict, cfg: ModelConfig):
    """RMSNorm of q [..., nH, hd] and k [..., nKV, hd]: per head (Qwen3,
    weight [hd]) or over the whole projection before the split into heads
    (OLMoE, weights [nH*hd] and [nKV*hd])."""
    if not cfg.qk_norm_full:
        return _norm(q, layer_p["q_norm"], cfg), _norm(k, layer_p["k_norm"], cfg)

    def full(t, w):
        flat = t.reshape(*t.shape[:-2], t.shape[-2] * t.shape[-1])
        return _norm(flat, w, cfg).reshape(t.shape)

    return full(q, layer_p["q_norm"]), full(k, layer_p["k_norm"])


def _keep(x: jax.Array, name: str, kept: tuple[str, ...]) -> jax.Array:
    """`x`, named `name` for the backward of a checkpointed layer that keeps
    `name` (`kept`: names of `utils/hbm.py:REMAT_SETS`, handed down from
    `_maybe_remat`). A `checkpoint_name` is an equation of the traced
    program and moves the numbering of its lowered text, so a name nothing
    keeps is not emitted: the decode engine's programs, `jit_fwd_step` and a
    grad step that keeps nothing stay text for text what they were (the
    hashes of `tests/test_kimi_linear_engine.py` and its siblings). The
    flash kernels' `o` and `lse` need no such care: their names are in the
    kernels' forward RULE (`ops/flash_attention.py:_flash`), which is traced
    only under differentiation."""
    return checkpoint_name(x, name) if name in kept else x


def _keep_heads(t: jax.Array, name: str, kept: tuple[str, ...]) -> jax.Array:
    """`_keep` of `t` [T, n, hd], kept as `[T, n * hd]`: a TPU stores an
    array's two minor dimensions in (16, 128) tiles, so kept by heads 14
    heads of 64 would hold 2.3 times their bytes and 2 heads 16 times."""
    if name not in kept:
        return t
    return checkpoint_name(t.reshape(t.shape[0], -1), name).reshape(t.shape)


@jax.named_scope("attn")
def attention(
    layer_p: dict,
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    segment_ids: jax.Array,
    mask: jax.Array | None,
    cfg: ModelConfig,
    li: int | None = None,
    horizon: jax.Array | None = None,
    kept: tuple[str, ...] = (),
) -> jax.Array:
    """Packed multi-head GQA attention over one 1-D token stream [T, H].
    `li`: the layer's index in a mixed stack (its window, and whether it
    rotates q and k); `mask` is then the mask of that layer's kind.
    `horizon`: `block_horizon` of a block-causal model. `kept`: the names a
    checkpointed layer's backward keeps (`_keep`)."""
    window = cfg.layer_window(li)
    if cfg.latent:
        return _cstr(
            latent_attention(layer_p, x, cos, sin, segment_ids, mask, cfg),
            "tokens", "act_embed",
        )
    with jax.named_scope("qkv"):
        q = _w_einsum("th,hnd->tnd", x, layer_p["q_kernel"], 1)
        k = _w_einsum("th,hnd->tnd", x, layer_p["k_kernel"], 1)
        v = _w_einsum("th,hnd->tnd", x, layer_p["v_kernel"], 1)
        if cfg.lora_rank:
            q = _with_lora(layer_p, "q_kernel", q, x, cfg)
            k = _with_lora(layer_p, "k_kernel", k, x, cfg)
            v = _with_lora(layer_p, "v_kernel", v, x, cfg)
        if cfg.qkv_bias:
            q = q + layer_p["q_bias"]
            k = k + layer_p["k_bias"]
            v = v + layer_p["v_bias"]
        q, *gate = _split_output_gate(q, cfg)
        if cfg.qk_norm:
            q, k = _qk_norm(q, k, layer_p, cfg)
    if cfg.layer_rope(li):
        with jax.named_scope("rope"):
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
    # (what a checkpointed layer's backward may keep, `_maybe_remat`; the
    # flash kernels' `o` and `lse` are named in their forward rule)
    q = _keep_heads(_cstr(q, "tokens", "act_heads", None), "attn_q", kept)
    k = _keep_heads(_cstr(k, "tokens", "act_kv_heads", None), "attn_k", kept)
    v = _keep_heads(_cstr(v, "tokens", "act_kv_heads", None), "attn_v", kept)
    nH, nKV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
    T = x.shape[0]
    impl = resolve_attn_impl(cfg, window)
    with jax.named_scope(_attention_scope(cfg, window)):
        if impl == "flash":
            from areal_tpu.ops.flash_attention import flash_attention

            out = flash_attention(q, k, v, segment_ids)
        elif impl == "ring":
            from areal_tpu.ops.ring_attention import (
                ring_flash_attention,
                zigzag_eligible,
            )

            # Same predicate forward() used when (not) permuting the stream —
            # the two sites must agree or positions would be misread.
            out = ring_flash_attention(
                q, k, v, segment_ids,
                zigzag=cfg.cp_zigzag and zigzag_eligible(T),
            )
        elif impl == "chunked":
            from areal_tpu.ops.chunked_attention import chunked_attention

            out = _keep(chunked_attention(
                q, k, v, segment_ids, sliding_window=window, **(
                    {} if horizon is None else {"q_horizon": horizon}
                )
            ), "attn_out", kept)
        else:
            # GQA: broadcast kv heads to query heads via grouped einsum.
            group = nH // nKV
            if mask is None:
                mask = segment_causal_mask(segment_ids, window, horizon)
            qg = q.reshape(T, nKV, group, hd)
            scores = jnp.einsum("tkgd,skd->kgts", qg, k).astype(jnp.float32)
            scores = scores / np.sqrt(hd)
            scores = jnp.where(mask[None, None, :, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
            out = jnp.einsum("kgts,skd->tkgd", probs, v)
            out = _keep(out.reshape(T, nH, hd), "attn_out", kept)
    out = _cstr(_gate_attn_out(out, gate), "tokens", "act_heads", None)
    with jax.named_scope("o_proj"):
        proj = _w_einsum("tnd,ndh->th", out, layer_p["o_kernel"], 2)
        if cfg.lora_rank:
            d = _lora_delta(
                layer_p, "o_kernel", out.reshape(T, nH * hd), cfg
            )
            if d is not None:
                proj = proj + d
        if cfg.attn_out_bias:
            proj = proj + layer_p["o_bias"]
    return _cstr(proj, "tokens", "act_embed")


# -- latent attention (DeepSeek-V2's MLA) -------------------------------------
# With x a token's normed hidden state and h a head:
#   c_q = RMSNorm(x W_qa);  [q_nope_h | q_pe_h] = c_q W_qb;  q_pe_h = RoPE(q_pe_h)
#   [c_kv | k_pe] = x W_kva;  c_kv = RMSNorm(c_kv);  k_pe = RoPE(k_pe)   (one head)
#   the cached row is [c_kv | k_pe], after the norm and the rotation
# expanded (`forward`, `prefill`):  [k_nope_h | v_h] = c_kv W_kvb,h;
#   k_h = [k_nope_h | k_pe];  o_h = softmax_s(scale q_h . k_h(s)) v_h(s)
# absorbed (`decode_step_paged`), equal in exact arithmetic, W_uk,h and W_uv,h
# the two halves of W_kvb,h:  q~_h = q_nope_h W_uk,h^T;
#   score = scale (q~_h . c_kv(s) + q_pe_h . k_pe(s));  u_h = sum_s p c_kv(s);
#   o_h = u_h W_uv,h
# so the decode step reads the cached rows themselves and never expands them.


def _latent_project(layer_p: dict, x: jax.Array, cos, sin, cfg: ModelConfig):
    """x [..., H] -> (q_nope [..., nH, nope], q_pe [..., nH, rope] turned,
    row [..., C + rope]: the row to cache, `[c_kv | k_pe]` normed and turned).
    cos/sin: [..., rope/2], leading dims as x's; None for a model with no
    positional encoding, whose q_pe and k_pe go as they are."""
    nH, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    C = cfg.kv_lora_rank
    if cfg.q_lora_rank:
        with jax.named_scope("q_lora"):
            c_q = _norm(
                jnp.einsum("...h,hr->...r", x, layer_p["q_a_kernel"]),
                layer_p["q_a_norm"], cfg,
            )
            q = jnp.einsum("...r,rm->...m", c_q, layer_p["q_b_kernel"])
            q = q.reshape(*q.shape[:-1], nH, nope + cfg.qk_rope_head_dim)
    else:
        with jax.named_scope("q_proj"):
            q = jnp.einsum("...h,hnd->...nd", x, layer_p["q_kernel"])
    with jax.named_scope("kv_latent"):
        kv = jnp.einsum("...h,hr->...r", x, layer_p["kv_a_kernel"])
        c_kv = _norm(kv[..., :C], layer_p["kv_a_norm"], cfg)
    if cos is None:
        return q[..., :nope], q[..., nope:], jnp.concatenate(
            [c_kv, kv[..., C:]], axis=-1)
    with jax.named_scope("rope"):
        cos_b, sin_b = cos.astype(q.dtype), sin.astype(q.dtype)
        q_pe = _rotated(
            *_split_rotary(q[..., nope:], cfg.qk_rope_head_dim)[:2],
            cos_b[..., None, :], sin_b[..., None, :], [],
        )
        k_pe = _rotated(
            *_split_rotary(kv[..., C:], cfg.qk_rope_head_dim)[:2], cos_b, sin_b, []
        )
    return q[..., :nope], q_pe, jnp.concatenate([c_kv, k_pe], axis=-1)


def _latent_expand(layer_p: dict, row: jax.Array, cfg: ModelConfig):
    """Cached rows [..., C + rope] -> (k [..., nH, nope + rope], v [..., nH,
    dv]): `kv_b_kernel`'s expansion of c_kv, the one rotary head under all."""
    nH, nope, C = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    kvb = jnp.einsum("...c,cm->...m", row[..., :C], layer_p["kv_b_kernel"])
    kvb = kvb.reshape(*kvb.shape[:-1], nH, nope + cfg.v_head_dim)
    k_pe = jnp.broadcast_to(
        row[..., None, C:], (*row.shape[:-1], nH, cfg.qk_rope_head_dim)
    )
    return jnp.concatenate([kvb[..., :nope], k_pe], axis=-1), kvb[..., nope:]


def _latent_out(layer_p: dict, o: jax.Array) -> jax.Array:
    """Heads' outputs [..., nH, dv] -> [..., H]."""
    with jax.named_scope("out_proj"):
        return jnp.einsum("...nd,ndh->...h", o, layer_p["o_kernel"])


def latent_attention(layer_p: dict, x: jax.Array, cos, sin, segment_ids,
                     mask, cfg: ModelConfig) -> jax.Array:
    """The EXPANDED form over one packed stream [T, H]: 128 heads of keys
    and values built from the latent rows (`forward` and its gradient)."""
    q_nope, q_pe, row = _latent_project(layer_p, x, cos, sin, cfg)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k, v = _latent_expand(layer_p, row, cfg)
    q = _cstr(q, "tokens", "act_heads", None)
    k = _cstr(k, "tokens", "act_heads", None)
    v = _cstr(v, "tokens", "act_heads", None)
    scale = cfg.latent_softmax_scale
    with jax.named_scope("latent_attention"):
        if resolve_attn_impl(cfg) == "chunked":
            from areal_tpu.ops.chunked_attention import chunked_attention

            out = chunked_attention(q, k, v, segment_ids, sm_scale=scale)
        else:
            if mask is None:
                mask = segment_causal_mask(segment_ids)
            out = _latent_dense_attention(q, k, v, mask, scale)
    return _latent_out(layer_p, _cstr(out, "tokens", "act_heads", None))


def _latent_dense_attention(q, k, v, mask, scale: float) -> jax.Array:
    """Expanded heads under a [T, S] mask, scores and softmax in float32."""
    scores = jnp.einsum("tnd,snd->nts", q, k).astype(jnp.float32) * scale
    scores = jnp.where(mask[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("nts,snd->tnd", probs, v)


def _latent_prefill_attention(layer_p: dict, x: jax.Array, cos, sin, mask,
                              cfg: ModelConfig):
    """The expanded form over ONE sequence [T, H] (bucket padding after the
    real tokens, hidden by causality). `mask` [T, T], or None above
    `PREFILL_DENSE_MAX` tokens, where queries and keys both go a block at a
    time and a block of queries stops at its own keys. Returns (out [T, H],
    row [T, C + rope]: the rows to cache)."""
    q_nope, q_pe, row = _latent_project(layer_p, x, cos, sin, cfg)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k, v = _latent_expand(layer_p, row, cfg)
    scale = cfg.latent_softmax_scale
    with jax.named_scope("latent_attention"):
        if mask is None:
            from areal_tpu.ops.chunked_attention import causal_blocked_attention

            out = causal_blocked_attention(q, k, v, sm_scale=scale)
        else:
            out = _latent_dense_attention(q, k, v, mask, scale)
    return _latent_out(layer_p, out), row


def _latent_pool_row(row: jax.Array, lanes: int) -> jax.Array:
    """A row to cache at the width the pool stores it (`latent_row_lanes`)."""
    pad = lanes - row.shape[-1]
    if not pad:
        return row
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])


def _latent_decode_attention(layer_p: dict, x: jax.Array, cos, sin, pool,
                             ci, place, cfg: ModelConfig, attn_impl: str):
    """The ABSORBED form for R single-token queries: each slot's new row into
    the latent pool at `(ci, dest_block, dest_off)`, every head's query
    carried into the row's space, attention over the cached rows in place
    (`ops/paged_attention_latent.py`), the result carried out through the
    value half of `kv_b_kernel`. `place` = (table, dest_block, dest_off,
    valid, live). Returns (out [R, H], pool)."""
    from areal_tpu.ops.paged_attention_latent import paged_attention_latent

    table, blk, off, seen, live = place
    nH, nope, C = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_nope, q_pe, row = _latent_project(layer_p, x, cos, sin, cfg)
    D = pool.shape[-1]
    pool = _write_pool_rows(pool, _latent_pool_row(row, D), ci, blk, off)
    w_kvb = layer_p["kv_b_kernel"].reshape(C, nH, nope + cfg.v_head_dim)
    with jax.named_scope("absorb_q"):
        q_lat = jnp.einsum("rnd,cnd->rnc", q_nope, w_kvb[..., :nope])
        q_row = jnp.concatenate([q_lat, q_pe], axis=-1)
        q_row = jnp.pad(q_row, ((0, 0), (0, 0), (0, D - q_row.shape[-1])))
    with jax.named_scope("latent_attention"):
        u = paged_attention_latent(
            q_row, pool, table, seen, ci, dv=C,
            sm_scale=cfg.latent_softmax_scale, impl=attn_impl, live=live,
        )
    with jax.named_scope("absorb_out"):
        o = jnp.einsum("rnc,cnd->rnd", u, w_kvb[..., nope:])
    return _latent_out(layer_p, o), pool


def _with_lora(layer_p, leaf, y, x, cfg):
    if not cfg.lora_rank:
        return y
    d = _lora_delta(layer_p, leaf, x, cfg)
    return y if d is None else y + d


@jax.named_scope("mlp")
def mlp(
    layer_p: dict, x: jax.Array, cfg: ModelConfig, kept: tuple[str, ...] = ()
) -> jax.Array:
    act = act_fn(cfg)
    if cfg.mlp_style == "fc":
        h1 = _w_einsum("th,hm->tm", x, layer_p["fc1_kernel"], 1)
        h1 = _keep(_with_lora(layer_p, "fc1_kernel", h1, x, cfg), "mlp_up", kept)
        h = _cstr(act(h1 + layer_p["fc1_bias"]), "tokens", "act_mlp")
        out = _w_einsum("tm,mh->th", h, layer_p["fc2_kernel"], 1)
        out = _with_lora(layer_p, "fc2_kernel", out, h, cfg)
        return _cstr(out + layer_p["fc2_bias"], "tokens", "act_embed")
    gate = _w_einsum("th,hm->tm", x, layer_p["gate_kernel"], 1)
    gate = _keep(
        _with_lora(layer_p, "gate_kernel", gate, x, cfg), "mlp_gate", kept
    )
    up = _w_einsum("th,hm->tm", x, layer_p["up_kernel"], 1)
    up = _keep(_with_lora(layer_p, "up_kernel", up, x, cfg), "mlp_up", kept)
    h = _cstr(act(gate) * up, "tokens", "act_mlp")
    out = _w_einsum("tm,mh->th", h, layer_p["down_kernel"], 1)
    out = _with_lora(layer_p, "down_kernel", out, h, cfg)
    return _cstr(out, "tokens", "act_embed")


# XLA:TPU's grouped matmul (`jax.lax.ragged_dot`) takes as its row tile the
# largest power of two, at most 512, that divides the row count, and visits
# each group's rows a whole tile at a time: 64 slots x top-8 = 512 pair rows
# run a 512-row tile for the 3-8 rows a group holds. This is the tile such
# few rows are laid out for (`tests/test_trace_names.py` pins XLA's rule).
GROUPED_MATMUL_ROW_TILE = 128


def grouped_matmul_rows(rows: int, groups: int) -> int:
    """The row count `_expert_mixture_plain` lays `rows` sorted pair rows out
    at for grouped matmuls over `groups` groups: `rows` itself where a group
    holds a tile's rows or more on average (a prefill bucket, a trainer's
    batch: the 512 tile is right for them) or where XLA's tile for `rows` is
    already `GROUPED_MATMUL_ROW_TILE` or finer; else one tile of zero rows
    more, which leaves that tile the largest power of two dividing it."""
    tile = GROUPED_MATMUL_ROW_TILE
    if rows >= groups * tile or rows % (2 * tile):
        return rows
    return rows + tile


def _expert_mixture_plain(
    act, E, x, expert, gates, gate_k, up_k, down_k, first_group=None
):
    """y[t] = sum_k gates[t, k] * expert[t, k](x[t]) over stacked SwiGLU
    experts `[G, H, M]`, `[G, H, M]`, `[G, M, H]`. `expert` [T, K] int32
    in [0, E]; the id E routes a pair nowhere. The T*K pairs are sorted by
    expert and the three matmuls run as grouped (ragged) matmuls.

    G is E (one layer's kernels, `first_group` None: the trainer, and
    unstacked layers), or a multiple of it: the kernels of every layer
    `[L*E, ...]`, of which this call's E experts are the groups from
    `first_group` (int32 scalar, `li * E`) on. The other groups are empty
    and cost the grouped matmul nothing; what it buys is that a layer loop
    hands the matmul the stacked leaf itself and not a slice of it, which
    XLA:TPU copies for the custom call (`_scan_stacked`)."""
    T, K = expert.shape
    G, H = gate_k.shape[0], x.shape[-1]
    with jax.named_scope("dispatch"):
        flat = expert.reshape(T * K)
        order = jnp.argsort(flat, stable=True)  # pair rows, by expert; E last
        live = (flat < E)[order]
        group_sizes = jnp.sum(
            flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :],
            axis=0,
            dtype=jnp.int32,
        )
        if first_group is not None:
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros(G, jnp.int32), group_sizes, (first_group,)
            )
        # rows past the last group are not written by the grouped matmul:
        # zero them going in and coming out, so nothing (and no gradient)
        # of a pad row is ever read
        xs = jnp.where(live[:, None], x[order // K], 0)  # [T*K, H]
        # few rows a group: more zero rows past the last group, which set
        # the grouped matmuls' row tile (`grouped_matmul_rows`)
        pad = grouped_matmul_rows(T * K, E) - T * K
        if pad:
            xs = jnp.concatenate([xs, jnp.zeros((pad, H), xs.dtype)])
    with jax.named_scope("experts"):
        h_gate = jax.lax.ragged_dot(xs, gate_k, group_sizes)
        h_up = jax.lax.ragged_dot(xs, up_k, group_sizes)
        ys = jax.lax.ragged_dot(act(h_gate) * h_up, down_k, group_sizes)
    with jax.named_scope("combine"):
        ys = ys[: T * K]
        ys = jnp.where(live[:, None], ys, 0)
        # back to pair order [T, K, H] by the inverse permutation (a gather,
        # not a scatter-add), then the gate-weighted sum in float32
        pairs = ys[jnp.argsort(order)].reshape(T, K, H).astype(jnp.float32)
        return jnp.einsum("tkh,tk->th", pairs, gates).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _expert_mixture(hidden_act: str, num_experts: int):
    """`_expert_mixture_plain` for that activation and number of experts,
    made safe under `jax.vmap` (the decode engine's batched prefill vmaps a
    whole prefill, and `ragged_dot` has no batching rule for it): exact
    routing treats tokens independently, so a batch of sequences is folded
    into more tokens of one call; the kernels and the first group stay as
    they are. `custom_vmap` has no reverse mode, so the trainer
    differentiates the plain function through a `custom_vjp` around it."""
    from jax.custom_batching import custom_vmap

    plain = functools.partial(
        _expert_mixture_plain, _activation(hidden_act), num_experts
    )
    folded = custom_vmap(plain)

    @folded.def_vmap
    def _fold(axis_size, in_batched, x, expert, gates, *unbatched):
        if any(jax.tree.leaves(in_batched[3:])):
            raise NotImplementedError("vmap over the expert kernels")
        x, expert, gates = (
            a if b else jnp.broadcast_to(a, (axis_size, *a.shape))
            for a, b in zip((x, expert, gates), in_batched[:3])
        )
        y = folded(*(a.reshape(-1, a.shape[-1]) for a in (x, expert, gates)), *unbatched)
        return y.reshape(axis_size, -1, y.shape[-1]), True

    mixture = jax.custom_vjp(folded)
    mixture.defvjp(lambda *args: jax.vjp(plain, *args), lambda vjp, ct: vjp(ct))
    return mixture


@jax.named_scope("mlp")
def moe_mlp(
    layer_p: dict,
    x: jax.Array,
    cfg: ModelConfig,
    valid: jax.Array | None = None,
    with_load: bool = False,
) -> tuple:
    """Exact (dropless) top-k MoE: every one of a token's K experts is
    computed, whatever the routing skew, in training, prefill and decode.

    The T*K token-expert pairs are sorted by expert and the three expert
    matmuls run as grouped (ragged) matmuls over the stacked [E, ...]
    kernels (`jax.lax.ragged_dot`: a Mosaic grouped-matmul kernel on TPU,
    a masked dense product on the CPU), then each token sums its K rows
    under its gate weights. There is no capacity and no token group: a
    token's result is a function of its own row alone, never of which
    other tokens share the batch (decode log-probabilities and the
    trainer's recomputed ones rest on that). `valid` [T] bool: pad rows of
    a prefill bucket and dead decode slots are sorted past the last group,
    so they are routed to no expert, cost no expert arithmetic and add
    nothing.

    The score is a softmax over the router's width or a sigmoid of each
    logit, the k are chosen on score (+ bias), normalised over the chosen
    and scaled (`moe_scoring`, `moe_router_bias`, `norm_topk_prob`,
    `routed_scaling_factor`). Where the chip holds `num_experts` of
    `num_experts_published` experts, routing is over all of them and only
    the pairs whose expert is held reach the grouped matmul; the shared
    expert is added once. With every expert held the same code is the
    whole layer.

    Returns (y [T, H], aux_loss scalar), and with `with_load` also the
    int32 vector `[pairs computed, pairs of the busiest expert]` over the
    valid tokens (the decode engine's expert-load counters), with a third
    entry, the pairs whose expert another chip holds, where not all are held.
    """
    E, K = cfg.num_experts, cfg.num_experts_per_tok
    # the router's width; E of them, from `first` on, are held here
    E_pub, first = cfg.num_experts_published_, cfg.expert_first
    partial = E_pub != E

    with jax.named_scope("router"):
        # float32 all the way: HIGHEST keeps a TPU from rounding float32
        # operands to bf16 (bf16 operands are exact either way)
        router_logits = jnp.einsum(
            "th,he->te",
            x.astype(jnp.float32),
            layer_p["router_kernel"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if cfg.moe_scoring == "sigmoid":
            probs = jax.nn.sigmoid(router_logits)  # [T, E_pub] float32
        else:
            probs = jax.nn.softmax(router_logits, axis=-1)
        choice = probs
        if cfg.moe_n_group > 1:
            # group-limited choice: a group of consecutive experts scores as
            # its best expert does, the `moe_topk_group` best groups are
            # kept, and the k are picked among their experts alone (the
            # others' scores read 0, below any softmax score)
            with jax.named_scope("group_route"):
                G = cfg.moe_n_group
                _, kept = jax.lax.top_k(
                    probs.reshape(-1, G, E_pub // G).max(axis=-1), cfg.moe_topk_group
                )
                kept_groups = jax.nn.one_hot(kept, G, dtype=bool).any(axis=1)  # [T, G]
                choice = jnp.where(
                    jnp.repeat(kept_groups, E_pub // G, axis=1), probs, 0.0
                )
        if cfg.moe_router_bias:
            # the bias enters the choice of the k experts, not their weights
            _, topk_idx = jax.lax.top_k(
                choice + layer_p["router_bias"].astype(jnp.float32), K
            )
            gate_vals = jnp.take_along_axis(probs, topk_idx, axis=-1)
        else:
            gate_vals, topk_idx = jax.lax.top_k(choice, K)  # [T, K]
        if cfg.norm_topk_prob:
            denom = jnp.sum(gate_vals, axis=-1, keepdims=True)
            if cfg.moe_scoring == "sigmoid":
                denom = denom + 1e-20
            gate_vals = gate_vals / denom
        if cfg.routed_scaling_factor != 1.0:
            gate_vals = gate_vals * cfg.routed_scaling_factor
        if valid is not None:
            # expert id E_pub sorts last and belongs to no group
            topk_idx = jnp.where(valid[:, None], topk_idx, E_pub)
            gate_vals = jnp.where(valid[:, None], gate_vals, 0)
        if partial:
            # this chip's experts: a pair whose expert lives elsewhere goes
            # to no group here (local id E), and nothing stands in for it
            local = topk_idx - first
            held = (local >= 0) & (local < E)
            held_idx = jnp.where(held, local, E)
            gate_vals = jnp.where(held, gate_vals, 0)
        else:
            held_idx = topk_idx

    # a forward-only scan over stacked layers hands over every layer's
    # kernels and where this layer's groups start (`_scan_stacked`)
    y = _expert_mixture(cfg.hidden_act, E)(
        x, held_idx, gate_vals,
        layer_p["gate_kernel"], layer_p["up_kernel"], layer_p["down_kernel"],
        layer_p.get("first_group"),
    )

    if cfg.shared_expert_intermediate_size:
        # the shared expert: a dense SwiGLU every token runs. Qwen2-MoE
        # mixes it in via a per-token sigmoid gate (HF
        # Qwen2MoeSparseMoeBlock semantics); K-EXAONE adds it as it is.
        with jax.named_scope("shared_expert"):
            act = act_fn(cfg)
            s_gate = jnp.einsum("th,hm->tm", x, layer_p["shared_gate_kernel"])
            s_up = jnp.einsum("th,hm->tm", x, layer_p["shared_up_kernel"])
            sh = _cstr(act(s_gate) * s_up, "tokens", "act_mlp")
            ys = _cstr(
                jnp.einsum("tm,mh->th", sh, layer_p["shared_down_kernel"]),
                "tokens",
                "act_embed",
            )
            if cfg.shared_expert_gated:
                g = jax.nn.sigmoid(
                    jnp.einsum(
                        "th,hk->tk",
                        x.astype(jnp.float32),
                        layer_p["shared_router_kernel"].astype(jnp.float32),
                    )
                ).astype(x.dtype)
                ys = g * ys
            y = y + ys

    # Switch/GShard load-balancing aux over REAL tokens only:
    # E * sum_e fraction_assigned_e * mean_prob_e (over the router's width)
    assign = jax.nn.one_hot(topk_idx, E_pub, dtype=jnp.float32)  # [T, K, E]
    if valid is not None:
        w = valid.astype(jnp.float32)
        denom = jnp.maximum(w.sum(), 1.0)
        frac = (assign * w[:, None, None]).sum(axis=(0, 1)) / (denom * K)
        mean_prob = (probs * w[:, None]).sum(axis=0) / denom
    else:
        frac = assign.mean(axis=(0, 1))
        mean_prob = probs.mean(axis=0)
    aux = E_pub * jnp.sum(frac * mean_prob)
    if with_load:
        per_expert = assign.sum(axis=(0, 1)).astype(jnp.int32)  # valid pairs
        grouped = []
        if cfg.moe_grouped:
            # valid tokens whose kept groups include one held here (under one
            # group, every valid token), and the held experts with at least
            # one pair (a grouped matmul reads nothing of an empty group: the
            # weights this call had to read)
            if cfg.moe_n_group > 1:
                size = E_pub // cfg.moe_n_group
                here_tok = kept_groups[:, first // size:(first + E) // size].any(axis=1)
            else:
                here_tok = jnp.ones(x.shape[:1], bool)
            if valid is not None:
                here_tok = here_tok & valid
            held = jax.lax.dynamic_slice(per_expert, (first,), (E,))
            grouped = [here_tok.sum(dtype=jnp.int32), (held > 0).sum(dtype=jnp.int32)]
        if partial:
            here = jax.lax.dynamic_slice(per_expert, (first,), (E,))
            return y, aux, jnp.stack(
                [here.sum(), here.max(), per_expert.sum() - here.sum(), *grouped]
            )
        return y, aux, jnp.stack([per_expert.sum(), per_expert.max(), *grouped])
    return y, aux



# -- Gated DeltaNet (Qwen3-Next's linear-attention mixer) --------------------
# Per value head a state S [d_k, d_v] (float32, zero at a sequence's start):
#   S <- exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
# behind a depthwise causal convolution (width K, no bias, silu) over the
# channels [q | k | v]. A sequence's cache is S and the last K-1
# pre-convolution rows. Two forms of one recurrence: `_gdn_chunk_scan` (a
# scan over chunks of `GDN_CHUNK` tokens: forward, prefill) and one step
# (`ops/gdn_step.py`: decode).

GDN_CHUNK = 64


def _gdn_project(layer_p: dict, x: jax.Array, cfg: ModelConfig):
    """x [..., H] -> (u [..., C] pre-convolution channels [q | k | v],
    z [..., Hv, dv], beta [..., Hv] float32, g [..., Hv] float32 <= 0)."""
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    C = cfg.linear_conv_channels
    with jax.named_scope("qkvz"):
        qkvz = jnp.einsum("...h,hc->...c", x, layer_p["qkvz_kernel"])
        ba = jnp.einsum("...h,hc->...c", x, layer_p["ba_kernel"]).astype(jnp.float32)
    u, z = qkvz[..., :C], qkvz[..., C:].reshape(*x.shape[:-1], Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(layer_p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., Hv:] + layer_p["dt_bias"].astype(jnp.float32)
    )
    return u, z, beta, g


def _gdn_heads(u: jax.Array, cfg: ModelConfig):
    """Post-convolution channels [..., C] float32 -> (q, k [..., Hv, dk],
    v [..., Hv, dv]): q and k L2-normalised over their lanes, q scaled by
    dk^-0.5, both repeated to the value heads."""
    Hk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    lead = u.shape[:-1]
    q = u[..., : Hk * dk].reshape(*lead, Hk, dk)
    k = u[..., Hk * dk : 2 * Hk * dk].reshape(*lead, Hk, dk)
    v = u[..., 2 * Hk * dk :].reshape(*lead, Hv, dv)

    def l2(t):
        return t * jax.lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(q) * dk ** -0.5, Hv // Hk, axis=-2)
    k = jnp.repeat(l2(k), Hv // Hk, axis=-2)
    return q, k, v


def _gdn_output(layer_p: dict, o: jax.Array, z: jax.Array, cfg: ModelConfig):
    """o [..., Hv, dv] float32 -> [..., H]: per head `w * rmsnorm(o) *
    silu(z)` in float32, then the output projection."""
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) * layer_p["norm"].astype(jnp.float32)
    o = o * jax.nn.silu(z.astype(jnp.float32))
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("out_proj"):
        return jnp.einsum(
            "...c,ch->...h", o.reshape(*o.shape[:-2], -1).astype(dtype),
            layer_p["out_kernel"],
        )


def _gdn_conv(u: jax.Array, kernel: jax.Array, segment_ids: jax.Array,
              bias: jax.Array | None = None):
    """Depthwise causal convolution over one packed stream: u [T, C] ->
    silu(sum_j kernel[:, j] * u[t - (K-1) + j] (+ bias [C], where the model
    has one)) in float32, rows of another segment (and those before the
    stream's start) counting as zeros."""
    K = kernel.shape[1]
    T = u.shape[0]
    u32 = u.astype(jnp.float32)
    w = kernel.astype(jnp.float32)
    acc = u32 * w[:, K - 1]
    for back in range(1, K):
        shifted = jnp.pad(u32, ((back, 0), (0, 0)))[:T]
        seg = jnp.pad(segment_ids, (back, 0), constant_values=PADDING_SEGMENT)[:T]
        same = (seg == segment_ids)[:, None]
        acc = acc + jnp.where(same, shifted, 0.0) * w[:, K - 1 - back]
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    return jax.nn.silu(acc)


def _padding_joins_the_segment_before(segment_ids: jax.Array) -> jax.Array:
    """The chunk scans' segment ids: padding belongs to the segment before
    it, so that with g = 0 and beta = 0 it carries that segment's state
    across unchanged."""
    t_idx = jnp.arange(segment_ids.shape[0])
    last_real = jax.lax.cummax(
        jnp.where(segment_ids == PADDING_SEGMENT, -1, t_idx), axis=0
    )
    return jnp.where(last_real >= 0, segment_ids[jnp.maximum(last_real, 0)], segment_ids)


@jax.named_scope("gdn_chunk_scan")
def _gdn_chunk_scan(q, k, v, g, beta, segment_ids, chunk: int = GDN_CHUNK):
    """The delta rule over one packed stream, a chunk of tokens at a time.

    q, k [T, Hv, dk], v [T, Hv, dv], g, beta [T, Hv], all float32;
    `segment_ids` [T]: the state starts from zero at each segment's first
    token; a token of `PADDING_SEGMENT` must come with beta = g = 0 and then
    leaves the state as it is. Returns (o [T, Hv, dv], S [Hv, dk, dv] after
    the last token). Within a chunk the rule's triangular system
    `(I + tril(beta k k^T * decay, -1)) D = beta v - beta k decay S_prev` is
    solved in float32 for both right-hand sides; the scan over chunks carries
    S alone."""
    T, Hv, dk = q.shape
    dv = v.shape[-1]
    seg = _padding_joins_the_segment_before(segment_ids)
    pad = (-T) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (q, k, v, g, beta)
        )
        seg = jnp.pad(seg, (0, pad), mode="edge")
    n = (T + pad) // chunk
    hp = jax.lax.Precision.HIGHEST
    # [n, Hv, chunk, ...]: heads ahead of the chunk's tokens
    q, k, v = (a.reshape(n, chunk, Hv, -1).transpose(0, 2, 1, 3) for a in (q, k, v))
    g, beta = (a.reshape(n, chunk, Hv).transpose(0, 2, 1) for a in (g, beta))
    seg = seg.reshape(n, chunk)
    # tokens whose segment began before their chunk see the carried state
    seg_prev = jnp.concatenate(
        [jnp.full((1,), PADDING_SEGMENT - 1, seg.dtype), seg[:-1, -1]]
    )
    cont = (seg == seg_prev[:, None]).astype(jnp.float32)[:, None]  # [n, 1, chunk]
    G = jnp.cumsum(g, axis=-1)  # [n, Hv, chunk] log decay from the chunk's start
    same = seg[:, :, None] == seg[:, None, :]  # [n, chunk, chunk]
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    # decay from token j (after its write) to token i, 0 across a boundary
    decay = jnp.exp(jnp.where(
        (same & lower)[:, None], G[..., :, None] - G[..., None, :], -jnp.inf
    ))  # [n, Hv, chunk, chunk]
    from_prev = jnp.exp(G) * cont  # what of the carried state token i sees
    kb = k * beta[..., None]
    strict = (idx[:, None] > idx[None, :])[None, None]
    A = jnp.where(strict, jnp.einsum("nhik,nhjk->nhij", kb, k, precision=hp) * decay, 0.0)
    W = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(chunk, dtype=A.dtype),
        jnp.concatenate([v * beta[..., None], kb * from_prev[..., None]], axis=-1),
        lower=True, unit_diagonal=True,
    )
    attn = jnp.einsum("nhik,nhjk->nhij", q, k, precision=hp) * decay
    # what of the carried state, and of each token's write, is left at the
    # chunk's end
    keep = from_prev[..., -1]  # [n, Hv]
    tail = jnp.where(same[:, None, -1, :], jnp.exp(G[..., -1:] - G), 0.0)

    def step(S, xs):  # S [Hv, dk, dv]
        q_c, k_c, v_c, k_cum, attn_c, keep_c = xs
        d = v_c - jnp.einsum("hik,hkv->hiv", k_cum, S, precision=hp)
        o = jnp.einsum("hik,hkv->hiv", q_c, S, precision=hp) + jnp.einsum(
            "hij,hjv->hiv", attn_c, d, precision=hp
        )
        S = S * keep_c[:, None, None] + jnp.einsum(
            "hjk,hjv->hkv", k_c, d, precision=hp
        )
        return S, o

    S, o = jax.lax.scan(
        step, jnp.zeros((Hv, dk, dv), jnp.float32),
        (q * from_prev[..., None], k * tail[..., None], W[..., :dv], W[..., dv:],
         attn, keep),
    )
    o = o.transpose(0, 2, 1, 3).reshape(n * chunk, Hv, dv)[:T]
    return o, S


def gated_delta_net(layer_p: dict, x: jax.Array, segment_ids: jax.Array,
                    cfg: ModelConfig, true_len: jax.Array | None = None):
    """The Gated DeltaNet mixer over one packed stream x [T, H] (`forward`:
    segments reset the state and the convolution; `prefill`: one segment).
    Padding (`PADDING_SEGMENT`) does not enter the state. Returns the
    mixer's output [T, H] and, given `true_len` (a prefill: the stream is
    one sequence of that many real tokens, padding after them), also the
    cache to hand over: (S [Hv, dk, dv] float32 at the last real token, the
    last K-1 real pre-convolution rows [K-1, C])."""
    u, z, beta, g = _gdn_project(layer_p, x, cfg)
    real = (segment_ids != PADDING_SEGMENT)[:, None]
    beta = jnp.where(real, beta, 0.0)
    g = jnp.where(real, g, 0.0)
    with jax.named_scope("conv"):
        q, k, v = _gdn_heads(_gdn_conv(u, layer_p["conv_kernel"], segment_ids), cfg)
    o, S = _gdn_chunk_scan(q, k, v, g, beta, segment_ids)
    out = _gdn_output(layer_p, o, z, cfg)
    if true_len is None:
        return out
    K = cfg.linear_conv_kernel_dim
    with jax.named_scope("conv_state"):
        rows = jax.lax.dynamic_slice_in_dim(
            jnp.pad(u, ((K - 1, 0), (0, 0))), true_len, K - 1, axis=0
        )
    return out, (S, rows)


def _conv_step(conv: jax.Array, ci, u: jax.Array, kernel: jax.Array,
               active: jax.Array | None, bias: jax.Array | None = None):
    """One token of the depthwise convolution for R slots from their cached
    rows: conv [n_lin, 1 + R, K-1, C], u [R, C] this token's pre-convolution
    channels, kernel [C, K], `bias` [C] where the model has one; `ci` the
    layer's place in the pool (traced inside a scanned run). Returns (silu
    of the mixed channels [R, C] float32, conv with the slots' rows moved on;
    a slot not `active` keeps its rows)."""
    with jax.named_scope("conv_state"):
        rows = conv[ci, 1:]  # [R, K-1, C]
        window = jnp.concatenate([rows, u[:, None].astype(rows.dtype)], axis=1)
        w = kernel.astype(jnp.float32)  # [C, K]
        mixed = jnp.einsum("rkc,ck->rc", window.astype(jnp.float32), w)
        if bias is not None:
            mixed = mixed + bias.astype(jnp.float32)
        mixed = jax.nn.silu(mixed)
        new_rows = window[:, 1:]
        if active is not None:
            new_rows = jnp.where(active[:, None, None], new_rows, rows)
        conv = conv.at[ci, 1:].set(new_rows)
    return mixed, conv


def gated_delta_step(layer_p: dict, x: jax.Array, state: dict, ci: int,
                     active: jax.Array | None, cfg: ModelConfig,
                     impl: str = "auto"):
    """One decode step of linear layer number `ci` (among the linear
    layers) for R slots: x [R, H]; `state` = {"S": [n_lin, 1 + R, Hv, dk,
    dv] float32, "conv": [n_lin, 1 + R, K-1, C]}, row 0 the null slot. A
    slot that is not `active` keeps its state. Returns (out [R, H], state)."""
    from areal_tpu.ops.gdn_step import gdn_step

    u, z, beta, g = _gdn_project(layer_p, x, cfg)
    mixed, conv = _conv_step(state["conv"], ci, u, layer_p["conv_kernel"], active)
    q, k, v = _gdn_heads(mixed, cfg)
    with jax.named_scope("gdn_step"):
        o, S = gdn_step(state["S"], q, k, v, g, beta, ci, active, impl=impl)
    return _gdn_output(layer_p, o, z, cfg), {"S": S, "conv": conv}


# -- Kimi Delta Attention (Kimi-Linear's linear-attention mixer) --------------
# The Gated DeltaNet's rule with the decay a VECTOR over a head's key lanes:
#   S <- Diag(exp(g_t)) S;  d = beta_t (v_t - S^T k_t);  S <- S + k_t d^T;  o_t = S^T q_t
# q, k and v each behind a projection and a depthwise convolution of its own
# (one convolution over the channels [q | k | v] with the three kernels side
# by side: a slot's cache has the Gated DeltaNet's shape), the decay through a
# low-rank gate `f`, the output through a head norm and a SIGMOID of a
# low-rank gate `g`. The same two forms: `_kda_chunk_scan` and one step
# (`ops/gdn_step.py`, the decay a column over the state's sublanes).

KDA_SUB = 16


def _kda_project(layer_p: dict, x: jax.Array, cfg: ModelConfig):
    """x [..., H] -> (u [..., C] pre-convolution channels [q | k | v],
    beta [..., n] float32, g [..., n, dk] float32 <= 0)."""
    n, dk = cfg.linear_num_value_heads, cfg.linear_key_head_dim
    lead = x.shape[:-1]
    with jax.named_scope("qkv"):
        u = jnp.concatenate([
            jnp.einsum("...h,hnd->...nd", x, layer_p[name]).reshape(*lead, -1)
            for name in ("q_kernel", "k_kernel", "v_kernel")
        ], axis=-1)
    with jax.named_scope("kda_gate"):
        f = jnp.einsum(
            "...r,rc->...c", jnp.einsum("...h,hr->...r", x, layer_p["f_a_kernel"]),
            layer_p["f_b_kernel"],
        ).astype(jnp.float32)
        g = -jnp.exp(layer_p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
            f + layer_p["dt_bias"].astype(jnp.float32)
        ).reshape(*lead, n, dk)
        beta = jax.nn.sigmoid(
            jnp.einsum("...h,hn->...n", x, layer_p["b_kernel"]).astype(jnp.float32)
        )
    return u, beta, g


def _kda_conv_kernel(layer_p: dict) -> jax.Array:
    """The three convolutions' kernels over the channels [q | k | v]: [C, K]."""
    return jnp.concatenate(
        [layer_p[f"{name}_conv_kernel"] for name in ("q", "k", "v")], axis=0
    )


def _kda_output(layer_p: dict, o: jax.Array, x: jax.Array, cfg: ModelConfig):
    """o [..., n, dv] float32, x [..., H] the mixer's input -> [..., H]: per
    head `w * rmsnorm(o) * sigmoid(gate)` in float32, the gate low-rank from
    x, then the output projection."""
    with jax.named_scope("out_gate"):
        gate = jnp.einsum(
            "...r,rc->...c", jnp.einsum("...h,hr->...r", x, layer_p["g_a_kernel"]),
            layer_p["g_b_kernel"],
        ).astype(jnp.float32).reshape(o.shape)
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        o = o * jax.lax.rsqrt(var + cfg.rms_norm_eps) * layer_p["o_norm"].astype(jnp.float32)
        o = o * jax.nn.sigmoid(gate)
    with jax.named_scope("out_proj"):
        return jnp.einsum(
            "...nd,ndh->...h", o.astype(jnp.dtype(cfg.dtype)), layer_p["o_kernel"]
        )


@jax.named_scope("kda_chunk_scan")
def _kda_chunk_scan(q, k, v, g, beta, segment_ids, chunk: int = GDN_CHUNK,
                    sub: int = KDA_SUB):
    """The delta rule under a VECTOR decay over one packed stream, a chunk of
    tokens at a time: `_gdn_chunk_scan` with g [T, Hv, dk], a log decay a key
    lane. Returns (o [T, Hv, dv], S [Hv, dk, dv] after the last token).

    What the scalar decay gives as `exp(G_i - G_j)` times a product of keys is
    here `sum_l x_il k_jl exp(G_il - G_jl)`, and no `[chunk, chunk, dk]` tensor
    of it is built for the stream, nor is `exp(-G)` ever taken (a step's log
    decay reaches -20: it overflows float32 inside one chunk). Within a chunk,
    sub-blocks of `sub` tokens: a pair (i, j) of different sub-blocks goes
    through the cumulative log decay at the start b of i's sub-block,
    `(x_i exp(G_i - G_b)) . (k_j exp(G_b - G_j))`, both exponents <= 0; the
    pairs inside a sub-block take `exp(G_i - G_j)` lane by lane, a chunk's
    `[sub, sub, dk]` blocks at a time (`lax.map` over the chunks)."""
    T, Hv, dk = q.shape
    dv = v.shape[-1]
    seg = _padding_joins_the_segment_before(segment_ids)
    pad = (-T) % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in (q, k, v, g, beta)
        )
        seg = jnp.pad(seg, (0, pad), mode="edge")
    n, m = (T + pad) // chunk, chunk // sub
    hp = jax.lax.Precision.HIGHEST
    # [n, Hv, chunk, ...]: heads ahead of the chunk's tokens
    q, k, v, g = (a.reshape(n, chunk, Hv, -1).transpose(0, 2, 1, 3) for a in (q, k, v, g))
    beta = beta.reshape(n, chunk, Hv).transpose(0, 2, 1)
    seg = seg.reshape(n, chunk)
    seg_prev = jnp.concatenate(
        [jnp.full((1,), PADDING_SEGMENT - 1, seg.dtype), seg[:-1, -1]]
    )
    cont = (seg == seg_prev[:, None]).astype(jnp.float32)[:, None, :, None]
    G = jnp.cumsum(g, axis=2)  # [n, Hv, chunk, dk] log decay from the chunk's start
    # the log decay up to each sub-block's start (its first token excluded)
    Gb = jnp.concatenate(
        [jnp.zeros_like(G[:, :, :1]), G[:, :, sub - 1:-1:sub]], axis=2
    )  # [n, Hv, m, dk]
    inner = jnp.exp(G - jnp.repeat(Gb, sub, axis=2))  # from its sub-block's start to i
    # token j's key carried on to the start of sub-block a (j before it)
    k_to = k[:, :, None] * jnp.exp(
        jnp.minimum(Gb[:, :, :, None] - G[:, :, None], 0.0)
    )  # [n, Hv, m, chunk, dk]
    idx = jnp.arange(chunk)
    same = (seg[:, :, None] == seg[:, None, :])[:, None]  # [n, 1, chunk, chunk]
    lower = idx[:, None] >= idx[None, :]
    strict = idx[:, None] > idx[None, :]
    before = (idx[:, None] // sub) > (idx[None, :] // sub)  # j in an earlier sub-block

    def within(xs):
        """One chunk's pairs inside each sub-block, for the queries and for
        the keys as queries: [Hv, m, sub, sub] each."""
        q_c, k_c, G_c = (a.reshape(Hv, m, sub, dk) for a in xs)
        sub_lower = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
        decay = jnp.exp(jnp.where(
            sub_lower[:, :, None], G_c[:, :, :, None] - G_c[:, :, None, :], -jnp.inf
        ))  # [Hv, m, sub, sub, dk]
        kd = k_c[:, :, None, :] * decay
        return (jnp.sum(q_c[:, :, :, None] * kd, axis=-1),
                jnp.sum(k_c[:, :, :, None] * kd, axis=-1))

    q_in, k_in = jax.lax.map(within, (q, k, G))
    eye = jnp.eye(m, dtype=q.dtype)

    def pairs(x, x_in):
        """sum_l x_il k_jl exp(G_il - G_jl) for j <= i: [n, Hv, chunk, chunk]."""
        across = jnp.einsum(
            "nhaik,nhajk->nhaij", (x * inner).reshape(n, Hv, m, sub, dk), k_to,
            precision=hp,
        ).reshape(n, Hv, chunk, chunk)
        diag = jnp.einsum("nhaij,ab->nhaibj", x_in, eye).reshape(n, Hv, chunk, chunk)
        return jnp.where(before, across, diag)

    from_prev = jnp.exp(G) * cont  # what of the carried state token i sees, a lane
    kb = k * beta[..., None]
    A = jnp.where(strict & same, pairs(k, k_in), 0.0) * beta[..., None]
    W = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(chunk, dtype=A.dtype),
        jnp.concatenate([v * beta[..., None], kb * from_prev], axis=-1),
        lower=True, unit_diagonal=True,
    )
    attn = jnp.where(lower & same, pairs(q, q_in), 0.0)
    # what of the carried state, and of each token's write, is left at the
    # chunk's end, a lane
    keep = from_prev[:, :, -1]  # [n, Hv, dk]
    tail = jnp.where(same[:, :, -1, :, None], jnp.exp(G[:, :, -1:] - G), 0.0)

    def step(S, xs):  # S [Hv, dk, dv]
        q_c, k_c, v_c, k_cum, attn_c, keep_c = xs
        d = v_c - jnp.einsum("hik,hkv->hiv", k_cum, S, precision=hp)
        o = jnp.einsum("hik,hkv->hiv", q_c, S, precision=hp) + jnp.einsum(
            "hij,hjv->hiv", attn_c, d, precision=hp
        )
        S = S * keep_c[:, :, None] + jnp.einsum(
            "hjk,hjv->hkv", k_c, d, precision=hp
        )
        return S, o

    S, o = jax.lax.scan(
        step, jnp.zeros((Hv, dk, dv), jnp.float32),
        (q * from_prev, k * tail, W[..., :dv], W[..., dv:], attn, keep),
    )
    o = o.transpose(0, 2, 1, 3).reshape(n * chunk, Hv, dv)[:T]
    return o, S


def kimi_delta_attention(layer_p: dict, x: jax.Array, segment_ids: jax.Array,
                         cfg: ModelConfig, true_len: jax.Array | None = None):
    """The Kimi Delta Attention mixer over one packed stream x [T, H]:
    `gated_delta_net`'s contract (segments, padding, and with `true_len` the
    cache to hand over: S at the last real token and the last K-1 real
    pre-convolution rows)."""
    u, beta, g = _kda_project(layer_p, x, cfg)
    real = segment_ids != PADDING_SEGMENT
    beta = jnp.where(real[:, None], beta, 0.0)
    g = jnp.where(real[:, None, None], g, 0.0)
    with jax.named_scope("conv"):
        q, k, v = _gdn_heads(_gdn_conv(u, _kda_conv_kernel(layer_p), segment_ids), cfg)
    o, S = _kda_chunk_scan(q, k, v, g, beta, segment_ids)
    out = _kda_output(layer_p, o, x, cfg)
    if true_len is None:
        return out
    K = cfg.linear_conv_kernel_dim
    with jax.named_scope("conv_state"):
        rows = jax.lax.dynamic_slice_in_dim(
            jnp.pad(u, ((K - 1, 0), (0, 0))), true_len, K - 1, axis=0
        )
    return out, (S, rows)


def kimi_delta_step(layer_p: dict, x: jax.Array, state: dict, ci: int,
                    active: jax.Array | None, cfg: ModelConfig,
                    impl: str = "auto"):
    """One decode step of Kimi Delta Attention layer number `ci` (among the
    linear layers) for R slots: `gated_delta_step`'s contract."""
    from areal_tpu.ops.gdn_step import gdn_step

    u, beta, g = _kda_project(layer_p, x, cfg)
    mixed, conv = _conv_step(state["conv"], ci, u, _kda_conv_kernel(layer_p), active)
    q, k, v = _gdn_heads(mixed, cfg)
    with jax.named_scope("kda_step"):
        o, S = gdn_step(state["S"], q, k, v, g, beta, ci, active, impl=impl)
    return _kda_output(layer_p, o, x, cfg), {"S": S, "conv": conv}


# -- Mamba-1's selective state-space mixer (Jamba's recurrent layers) ----------
# A channel c of `ssm_inner` and a state lane n of `ssm_state_size`, float32:
#   h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]
#   y_t[c] = sum_n C_t[n] h_t[n, c] + D[c] u_t[c]
# diagonal, no matrix product in it: u behind a depthwise convolution (the
# linear mixers' `_gdn_conv` / `_conv_step`, here with a bias), dt, B and C
# functions of the token. The same two forms as the linear mixers:
# `_ssm_chunk_scan` over a packed stream and one step a slot
# (`ops/ssm_step.py`), the state held `[state lanes, channels]`.

SSM_CHUNK = 64


def _ssm_project(layer_p: dict, x: jax.Array, cfg: ModelConfig):
    """x [..., H] -> (u [..., Di] pre-convolution channels, z [..., Di] the
    output gate's input)."""
    with jax.named_scope("in_proj"):
        uz = jnp.einsum("...h,hc->...c", x, layer_p["in_kernel"])
    return uz[..., : cfg.ssm_inner], uz[..., cfg.ssm_inner :]


@jax.named_scope("ssm_params")
def _ssm_params(layer_p: dict, u: jax.Array, cfg: ModelConfig):
    """Post-convolution channels u [..., Di] float32 -> (dt [..., Di] > 0,
    B [..., N], C [..., N]) float32: one projection to [dt_r | B | C], an
    RMSNorm each (Jamba's), dt_r expanded to the channels, a bias, softplus.
    The matmuls take the compute dtype's operands and accumulate float32."""
    Rk, N = cfg.ssm_dt_rank, cfg.ssm_state_size
    dtype, f32 = jnp.dtype(cfg.dtype), jnp.float32
    proj = jnp.einsum("...c,cr->...r", u.astype(dtype), layer_p["x_kernel"],
                      preferred_element_type=f32)
    eps = cfg.rms_norm_eps
    dt_r = rms_norm(proj[..., :Rk], layer_p["dt_norm"], eps)
    B = rms_norm(proj[..., Rk : Rk + N], layer_p["b_norm"], eps)
    C = rms_norm(proj[..., Rk + N :], layer_p["c_norm"], eps)
    dt = jnp.einsum("...r,rc->...c", dt_r.astype(dtype), layer_p["dt_kernel"],
                    preferred_element_type=f32)
    return jax.nn.softplus(dt + layer_p["dt_bias"].astype(f32)), B, C


def _ssm_decay(layer_p: dict) -> jax.Array:
    """A [N, Di] float32, negative."""
    return -jnp.exp(layer_p["ssm_A_log"].astype(jnp.float32))


def _ssm_output(layer_p: dict, y: jax.Array, z: jax.Array, cfg: ModelConfig):
    """y [..., Di] float32 -> [..., H]: `y * silu(z)` in float32, then the
    output projection."""
    with jax.named_scope("out_gate"):
        y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(jnp.dtype(cfg.dtype))
    with jax.named_scope("out_proj"):
        return jnp.einsum("...c,ch->...h", y, layer_p["out_kernel"])


@jax.named_scope("ssm_scan")
def _ssm_chunk_scan(u, dt, B, C, A, segment_ids, chunk: int = SSM_CHUNK):
    """The selective scan over one packed stream, a chunk of tokens at a time.

    u, dt [T, Di], B, C [T, N], A [N, Di], all float32; `segment_ids` [T]:
    the state starts from zero at each segment's first token; a token of
    `PADDING_SEGMENT` must come with dt = 0 and then leaves the state as it
    is (decay 1, input 0). Returns (y [T, Di] without the skip term, h [N,
    Di] after the last token). Inside a chunk the recurrence's pairs (decay,
    input) go through `associative_scan`, every factor in (0, 1], the decay
    0 at a segment's first token; the scan over chunks carries h alone, and
    its backward recomputes a chunk from the h it started with."""
    T, Di = u.shape
    N = B.shape[-1]
    seg = _padding_joins_the_segment_before(segment_ids)
    first = seg != jnp.concatenate(
        [jnp.full((1,), PADDING_SEGMENT - 1, seg.dtype), seg[:-1]])
    pad = (-T) % chunk
    if pad:
        u, dt, B, C = (jnp.pad(a, ((0, pad), (0, 0))) for a in (u, dt, B, C))
        first = jnp.pad(first, (0, pad))
    n = (T + pad) // chunk
    u, dt, B, C, first = (
        a.reshape(n, chunk, *a.shape[1:]) for a in (u, dt, B, C, first))

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    def step(h, xs):  # h [N, Di]
        u_c, dt_c, B_c, C_c, first_c = xs
        decay = jnp.where(first_c[:, None, None], 0.0,
                          jnp.exp(dt_c[:, None, :] * A[None]))  # [chunk, N, Di]
        write = (dt_c * u_c)[:, None, :] * B_c[:, :, None]
        decay, write = jax.lax.associative_scan(combine, (decay, write), axis=0)
        hs = decay * h[None] + write
        return hs[-1], jnp.sum(hs * C_c[:, :, None], axis=1)

    h, y = jax.lax.scan(
        jax.checkpoint(step), jnp.zeros((N, Di), jnp.float32),
        (u, dt, B, C, first))
    return y.reshape(n * chunk, Di)[:T], h


def mamba_mixer(layer_p: dict, x: jax.Array, segment_ids: jax.Array,
                cfg: ModelConfig, true_len: jax.Array | None = None):
    """The state-space mixer over one packed stream x [T, H]:
    `gated_delta_net`'s contract (segments reset the state and the
    convolution, padding does not enter the state, and with `true_len` the
    cache to hand over: h [N, Di] float32 at the last real token and the
    last K-1 real pre-convolution rows [K-1, Di]). `forward` (and its
    gradient) runs `_ssm_chunk_scan`; a prefill `ops/ssm_scan.py`, whose XLA
    form is the same `_ssm_chunk_scan`."""
    u, z = _ssm_project(layer_p, x, cfg)
    with jax.named_scope("conv"):
        uc = _gdn_conv(u, layer_p["conv_kernel"], segment_ids,
                       layer_p.get("conv_bias"))
    dt, B, C = _ssm_params(layer_p, uc, cfg)
    dt = jnp.where((segment_ids != PADDING_SEGMENT)[:, None], dt, 0.0)
    if true_len is None:
        y, h = _ssm_chunk_scan(uc, dt, B, C, _ssm_decay(layer_p), segment_ids)
    else:
        # a prefill, one sequence from a zero state: on a TPU the scan as a
        # kernel, the state never leaving the chip's vector memory
        from areal_tpu.ops.ssm_scan import ssm_scan

        y, h = ssm_scan(uc, dt, B, C, _ssm_decay(layer_p), scan=functools.partial(
            _ssm_chunk_scan, segment_ids=segment_ids))
    out = _ssm_output(
        layer_p, y + layer_p["D"].astype(jnp.float32) * uc, z, cfg)
    if true_len is None:
        return out
    K = cfg.linear_conv_kernel_dim
    with jax.named_scope("conv_state"):
        rows = jax.lax.dynamic_slice_in_dim(
            jnp.pad(u, ((K - 1, 0), (0, 0))), true_len, K - 1, axis=0
        )
    return out, (h, rows)


def mamba_step(layer_p: dict, x: jax.Array, state: dict, ci,
               active: jax.Array | None, cfg: ModelConfig,
               impl: str = "auto", live: tuple | None = None):
    """One decode step of state-space layer number `ci` (among the recurrent
    layers; traced inside a scanned run) for R slots: `gated_delta_step`'s
    contract, `state["S"]` [n, 1 + R, N, Di] float32. `live`: the kernel's
    work list (`ops/ssm_step.py:live_slots`), taken once a token step."""
    from areal_tpu.ops.ssm_step import ssm_step

    u, z = _ssm_project(layer_p, x, cfg)
    uc, conv = _conv_step(state["conv"], ci, u, layer_p["conv_kernel"], active,
                          layer_p.get("conv_bias"))
    dt, B, C = _ssm_params(layer_p, uc, cfg)
    with jax.named_scope("ssm_step"):
        y, S = ssm_step(state["S"], dt, uc, B, C, _ssm_decay(layer_p),
                        layer_p["D"], ci, active, impl=impl, live=live)
    return _ssm_output(layer_p, y, z, cfg), {"S": S, "conv": conv}


def _linear_mixers(cfg: ModelConfig) -> tuple:
    """(the mixer over a packed stream, its decode step) of the model's
    recurrent layers."""
    if cfg.ssm_state_size:
        return mamba_mixer, mamba_step
    if cfg.linear_decay_lanes:
        return kimi_delta_attention, kimi_delta_step
    return gated_delta_net, gated_delta_step


def _maybe_remat(layer_fn, cfg: ModelConfig, kept: tuple[str, ...] = ()):
    """`layer_fn` as a `jax.checkpoint` region under `cfg.remat`; its
    backward keeps the intermediates named in `kept` (names of
    `utils/hbm.py:REMAT_SETS`, which `layer_fn` takes as `kept=` and names
    where they are made) and recomputes the rest. No names: the whole
    forward again, the program it has always been."""
    if not cfg.remat:
        return layer_fn
    policy = None
    if kept:
        layer_fn = functools.partial(layer_fn, kept=kept)
        policy = jax.checkpoint_policies.save_only_these_names(*kept)
    return jax.checkpoint(layer_fn, static_argnums=(6, 7), policy=policy)


@jax.named_scope("layer")
def decoder_layer(
    layer_p: dict,
    x: jax.Array,
    cos: jax.Array,
    sin: jax.Array,
    segment_ids: jax.Array,
    mask: jax.Array | None,
    cfg: ModelConfig,
    li: int | None = None,
    horizon: jax.Array | None = None,
    *,
    kept: tuple[str, ...] = (),
) -> tuple[jax.Array, jax.Array]:
    """Returns (hidden [T, H], router aux loss scalar — 0 for dense).
    `li` (static) is the layer's index in an unstacked tree; `horizon` is
    `block_horizon` of a block-causal model; `kept` the names this layer's
    backward keeps when it is a checkpoint region (`_maybe_remat`)."""
    h = _norm(x, layer_p["input_norm"], cfg, layer_p.get("input_norm_bias"))
    if cfg.layer_linear(li):
        with jax.named_scope("attn"):
            x = x + _linear_mixers(cfg)[0](layer_p["attn"], h, segment_ids, cfg)
    else:
        x = x + attention(
            layer_p["attn"], h, cos, sin, segment_ids, mask, cfg, li, horizon, kept
        )
    x = _keep(x, "attn_residual", kept)
    h = _norm(x, layer_p["post_attn_norm"], cfg, layer_p.get("post_attn_norm_bias"))
    if cfg.layer_sparse(li):
        y, aux = moe_mlp(
            layer_p["mlp"], h, cfg, valid=segment_ids != PADDING_SEGMENT
        )
    else:
        y, aux = mlp(layer_p["mlp"], h, cfg, kept), jnp.float32(0.0)
    return x + y, aux


def _layer_scope(cfg: ModelConfig, i: int):
    """A leading dense layer of a sparse model under a scope of its own."""
    if cfg.num_experts and not cfg.layer_sparse(i):
        return jax.named_scope("dense_layer")
    return contextlib.nullcontext()


def forward(
    params: dict,
    input_ids: jax.Array,
    position_ids: jax.Array,
    segment_ids: jax.Array,
    cfg: ModelConfig,
    *,
    with_aux: bool = False,
    return_hidden: bool = False,
    remat_kept: tuple[str, ...] = (),
) -> jax.Array:
    """Packed forward: [T] ids → [T, V] logits (f32).

    `segment_ids` mark sequence membership (PADDING_SEGMENT for pad tail);
    attention is causal within a segment. With `with_aux=True` also returns
    the summed MoE router load-balancing loss (0 for dense models).

    `return_hidden=True` stops after the final norm and returns the [T, H]
    hidden states instead of logits — the fused-LM-loss path (LMHead +
    ops/fused_xent.py) applies the head in vocab chunks so the f32 [T, V]
    tensor never exists.

    `remat_kept`: under `cfg.remat`, the named intermediates each layer's
    backward keeps in place of recomputing them (`_maybe_remat`).
    """
    compute_dtype = jnp.dtype(cfg.dtype)
    # Zig-zag context parallelism: when ring attention will shard the token
    # axis, permute the stream ONCE here (and invert on the way out) so
    # each CP shard holds a balanced (early, late) chunk pair. Positions /
    # segment ids ride along, so rope and packing see original values; all
    # per-token math in between is order-agnostic, making this exact.
    zz_inv = None
    if cfg.cp_zigzag and resolve_attn_impl(cfg) == "ring":
        from areal_tpu.ops.ring_attention import (
            cp_ring_shards,
            zigzag_eligible,
        )
        from areal_tpu.utils.data import (
            zigzag_indices,
            zigzag_inverse_indices,
        )

        T_total = input_ids.shape[0]
        if zigzag_eligible(T_total):
            n_cp = cp_ring_shards(T_total)
            zz_perm = jnp.asarray(zigzag_indices(T_total, n_cp))
            zz_inv = jnp.asarray(zigzag_inverse_indices(T_total, n_cp))
            input_ids = _cstr(input_ids[zz_perm], "tokens")
            position_ids = _cstr(position_ids[zz_perm], "tokens")
            segment_ids = _cstr(segment_ids[zz_perm], "tokens")
    # Gather from a table whose hidden dim is UNSHARDED: leaving the fsdp
    # (dp) shards on the hidden dim makes SPMD pass them through the gather
    # output, which then collides with the tokens-over-(dp,sp) layout every
    # consumer wants and forces a full-remat reshard in the backward.
    with jax.named_scope("embed"):
        table = _cstr(params["embed"]["embedding"], "vocab", None)
        x = _cstr(
            _scale_embed(table[input_ids].astype(compute_dtype), cfg),
            "tokens",
            "act_embed",
        )
        if cfg.pos_embed == "learned":
            # Same gather rule as the token table above: hidden dim must be
            # UNSHARDED going into the gather or its fsdp shards collide with
            # the tokens-over-(dp,sp) activation layout (full-remat reshard).
            ptab = _cstr(params["pos_embed"]["embedding"], None, None)
            x = _cstr(
                x + ptab[position_ids].astype(compute_dtype),
                "tokens",
                "act_embed",
            )
    cos, sin = _rope_tables(position_ids, cfg)
    # Dense path: build the [T,T] mask ONCE here (outside the per-layer remat
    # region); flash/ring never materialise it.
    # (a mixed stack: one mask for each kind of layer that runs dense)
    # (a block-causal model: each query's horizon is its block's last index)
    horizon = block_horizon(position_ids, cfg.block_length_)
    masks = {
        w: segment_causal_mask(segment_ids, w, horizon)
        for w in {cfg.layer_window(i) for i in range(cfg.num_hidden_layers)}
        if resolve_attn_impl(cfg, w) == "dense"
    }
    mask = masks.get(cfg.sliding_window)

    layer_fn = _maybe_remat(decoder_layer, cfg, remat_kept)
    # the causal models' call is what it was: no ninth argument
    more = () if horizon is None else (horizon,)

    if cfg.scan_layers:
        def body(carry, layer_p):
            h, aux_sum = carry
            h, aux = layer_fn(
                layer_p, h, cos, sin, segment_ids, mask, cfg, None, *more
            )
            return (h, aux_sum + aux), None

        (x, aux_total), _ = jax.lax.scan(
            body, (x, jnp.float32(0.0)), combine_layers_with_lora(params, cfg)
        )
    else:
        aux_total = jnp.float32(0.0)
        for key, i, past in cfg.stack_plan:
            if past - i > 1:
                # a run of like layers, stacked: one body, scanned
                def run_body(carry, layer_p, i=i):
                    h, aux = layer_fn(
                        layer_p, carry[0], cos, sin, segment_ids,
                        masks.get(cfg.layer_window(i)), cfg, i, *more,
                    )
                    return (h, carry[1] + aux), None

                (x, aux_total), _ = jax.lax.scan(
                    run_body, (x, aux_total), params[key])
                continue
            with _layer_scope(cfg, i):
                x, aux = layer_fn(
                    params[key], x, cos, sin, segment_ids,
                    masks.get(cfg.layer_window(i)), cfg, i, *more,
                )
            aux_total = aux_total + aux

    with jax.named_scope("final_norm"):
        x = _norm(x, params["final_norm"], cfg, params.get("final_norm_bias"))
    if return_hidden:
        assert not cfg.is_critic, "fused head path is for LM heads only"
        out_axes: tuple[str | None, ...] = ("tokens", "act_embed")
        out = _cstr(x, *out_axes)
    elif cfg.is_critic:
        values = (
            jnp.einsum("th,hk->tk", x, params["value_head"]["kernel"])
            + params["value_head"]["bias"]
        )
        out = values[:, 0].astype(jnp.float32)
        out_axes = ("tokens",)
    else:
        out_axes = ("tokens", "act_vocab")
        out = _cstr(_lm_head(params, x, cfg), *out_axes)
    if zz_inv is not None:
        # Invert the zig-zag layout so loss functions / callers see the
        # contiguous packed order they built the micro-batch in.
        out = _cstr(out[zz_inv], *out_axes)
    if with_aux:
        return out, aux_total
    return out


def _pp_embed(params: dict, input_ids: jax.Array, position_ids: jax.Array,
              cfg: ModelConfig) -> jax.Array:
    """Embedding for the pipelined paths: [M, T] ids → [M, T, H]."""
    compute_dtype = jnp.dtype(cfg.dtype)
    table = _cstr(params["embed"]["embedding"], "vocab", None)
    x = _scale_embed(table[input_ids].astype(compute_dtype), cfg)
    if cfg.pos_embed == "learned":
        ptab = _cstr(params["pos_embed"]["embedding"], None, None)
        x = x + ptab[position_ids].astype(compute_dtype)
    return x


def _pp_stage_fn(cfg: ModelConfig):
    """One pipeline stage: a scan over the stage-local [L/pp, ...] layers.
    aux_t = (position_ids, segment_ids) for the stage's current microbatch.

    Bitwise note: `1f1b_interleaved` promises grads bitwise-equal to
    `1f1b`, which makes a v>1 virtual chunk (a trip-count-1 layer scan
    that XLA inlines and fuses into the schedule) run the SAME per-layer
    backward as a longer scan (an isolated loop body). That holds only
    under `cfg.remat`: jax.checkpoint makes each layer's backward a
    self-contained recompute region that XLA compiles identically in
    either fusion context. Without remat the granularities drift at the
    last bit (~1e-7) and the schedules are merely allclose."""
    layer_fn = _maybe_remat(decoder_layer, cfg)

    def stage_fn(layers_local, h, aux_t):
        pos, seg = aux_t
        cos, sin = _rope_tables(pos, cfg)
        horizon = block_horizon(pos, cfg.block_length_)
        more = () if horizon is None else (horizon,)

        def body(carry, layer_p):
            h, aux_sum = carry
            h, aux = layer_fn(layer_p, h, cos, sin, seg, None, cfg, None, *more)
            return (h, aux_sum + aux), None

        (h, aux_sum), _ = jax.lax.scan(
            body, (h, jnp.float32(0.0)), layers_local
        )
        return h, aux_sum

    return stage_fn


def _pp_head_out(p: dict, y: jax.Array, cfg: ModelConfig, head_mode: str):
    """Final norm + output head on one microbatch's trunk output. `p` may be
    the full param tree or the non-layer head subtree — only head leaves are
    read. head_mode "hidden" returns the normed hidden states (fused-loss
    callers wrap them in an LMHead)."""
    compute_dtype = jnp.dtype(cfg.dtype)
    h = _norm(y, p["final_norm"], cfg, p.get("final_norm_bias"))
    if head_mode == "hidden":
        return h
    if cfg.is_critic:
        values = (
            jnp.einsum("th,hk->tk", h, p["value_head"]["kernel"])
            + p["value_head"]["bias"]
        )
        return values[:, 0].astype(jnp.float32)
    if cfg.tie_word_embeddings:
        return jnp.einsum(
            "th,vh->tv", h, p["embed"]["embedding"].astype(compute_dtype)
        ).astype(jnp.float32)
    return jnp.einsum(
        "th,hv->tv", h, p["lm_head"]["kernel"]
    ).astype(jnp.float32)


def forward_pipelined(
    params: dict,
    input_ids: jax.Array,
    position_ids: jax.Array,
    segment_ids: jax.Array,
    cfg: ModelConfig,
    mesh,
    per_mb_fn,
    mb_data: dict | None = None,
    *,
    with_aux: bool = False,
    head_mode: str = "logits",
    virtual_pp: int = 1,
):
    """Pipelined packed forward over M stacked microbatches (GPipe trunk).

    The pp>1 counterpart of `forward` (parity: the reference's pipelined
    train/generation schedules, realhf .../static_schedule.py:159): the
    decoder trunk runs through parallel/pipeline.py's stage-stacked GPipe
    schedule with the scanned layer stack sharded over the "pp" mesh axis;
    embedding runs vectorized over all microbatches up front, and the
    lm_head + caller's `per_mb_fn(logits_f32 [T, V], mb_slice)` run in a
    scan over microbatches afterward so only one [T, V] logits buffer is
    ever live. Gradients (when taken) follow the GPipe
    all-forward-then-all-backward schedule via plain autodiff — the
    memory-capped alternative is `forward_pipelined_grads` (1F1B).

    Args: input_ids/position_ids/segment_ids are [M, T]; `mb_data` is a
    pytree of [M, ...] arrays whose m-th slice is handed to per_mb_fn.
    Returns stacked per-mb outputs (and the summed MoE aux loss when
    `with_aux`).
    """
    from areal_tpu.parallel import mesh as mesh_lib
    from areal_tpu.parallel.pipeline import pipeline_trunk

    assert cfg.scan_layers, "pipeline parallelism requires scan_layers=True"
    x = _pp_embed(params, input_ids, position_ids, cfg)  # [M, T, H]

    # Trace the stage body WITHOUT the ambient mesh: (a) the stage runs
    # under a vmap whose leading dim is the pp axis, where token-axis
    # constraints would fight the stage-stacked layout pins; (b) attention
    # must not resolve to ring (its own shard_map does not nest under the
    # stage vmap) — with no mesh it resolves to flash/dense, both
    # GSPMD-partitionable along the non-pp axes.
    with mesh_lib.mesh_scope(None):
        ys, aux_total = pipeline_trunk(
            mesh,
            _pp_stage_fn(cfg),
            combine_layers_with_lora(params, cfg),
            x,
            (position_ids, segment_ids),
            virtual=virtual_pp,
        )

    def head_scan(_, inp):
        y, mb_m = inp
        return None, per_mb_fn(_pp_head_out(params, y, cfg, head_mode), mb_m)

    _, outs = jax.lax.scan(head_scan, None, (ys, mb_data))
    if with_aux:
        return outs, aux_total
    return outs


def forward_pipelined_grads(
    trainable: dict,
    frozen: dict,
    input_ids: jax.Array,
    position_ids: jax.Array,
    segment_ids: jax.Array,
    cfg: ModelConfig,
    mesh,
    per_mb_loss_fn,
    mb_data: dict,
    weights: jax.Array,
    *,
    head_mode: str = "logits",
    lora_mode: bool = False,
    virtual_pp: int = 1,
):
    """Pipelined loss AND gradients under the 1F1B schedule.

    Unlike `forward_pipelined` (differentiated from outside), this composes
    explicit vjps: the trunk loop (parallel/pipeline.pipeline_1f1b_grads)
    interleaves each microbatch's backward into the forward stream — live
    activation stash capped at 2·pp-1 stage inputs instead of growing with
    M — and hands back gradients w.r.t. (stacked layers, head subtree,
    embedded activations), which are pulled back here through the
    embedding / lora-combine / head-selection vjps onto `trainable`.

    Args:
      trainable/frozen: the engine's param split (frozen = {} unless LoRA).
      per_mb_loss_fn: (head_out, mb_m) -> (scalar_loss, stats_dict) where
        head_out is logits [T, V] / values [T] / an LMHead per `head_mode`.
      weights: [M] float32; gradients equal
        d(Σ_m weights[m]·loss_m + router_coef·aux)/d(trainable).

    Returns (losses [M], stats pytree of [M, ...], aux_total, grads) with
    `grads` shaped like `trainable`.
    """
    from areal_tpu.parallel import mesh as mesh_lib
    from areal_tpu.parallel.pipeline import (
        pipeline_1f1b_grads,
        pipeline_1f1b_interleaved_grads,
    )

    assert cfg.scan_layers, "pipeline parallelism requires scan_layers=True"

    def full(t):
        return {**frozen, "lora": t} if lora_mode else t

    # Each piece of the model around the trunk loop gets its own vjp; their
    # cotangents are what the 1F1B loop produces. Under LoRA the embedding
    # and head close over `frozen` only, so their pullbacks are symbolic
    # zeros XLA eliminates — matching the stop_gradient semantics of the
    # GPipe path.
    xs, embed_vjp = jax.vjp(
        lambda t: _pp_embed(full(t), input_ids, position_ids, cfg), trainable
    )
    layers, layers_vjp = jax.vjp(
        lambda t: combine_layers_with_lora(full(t), cfg), trainable
    )
    head_params, head_vjp = jax.vjp(
        lambda t: {
            k: v for k, v in full(t).items() if k not in ("layers", "lora")
        },
        trainable,
    )

    def head_loss(hp, y, mb_m):
        out = _pp_head_out(hp, y, cfg, head_mode)
        if head_mode == "hidden":
            out = LMHead(out, hp, cfg)
        return per_mb_loss_fn(out, mb_m)

    aux_coef = (
        float(cfg.router_aux_loss_coef)
        if (cfg.num_experts and cfg.router_aux_loss_coef > 0)
        else 0.0
    )
    # With virtual_pp > 1 the stacked layers (and their grads) are in the
    # engine's chunk-major interleaved storage layout; layers_vjp composes
    # on the same layout, so nothing here needs to know the permutation.
    with mesh_lib.mesh_scope(None):
        if virtual_pp > 1:
            losses, stats, aux_total, g_layers, g_head, g_xs = (
                pipeline_1f1b_interleaved_grads(
                    mesh,
                    _pp_stage_fn(cfg),
                    head_loss,
                    layers,
                    head_params,
                    xs,
                    (position_ids, segment_ids),
                    mb_data,
                    weights,
                    virtual=virtual_pp,
                    aux_coef=aux_coef,
                )
            )
        else:
            losses, stats, aux_total, g_layers, g_head, g_xs = (
                pipeline_1f1b_grads(
                    mesh,
                    _pp_stage_fn(cfg),
                    head_loss,
                    layers,
                    head_params,
                    xs,
                    (position_ids, segment_ids),
                    mb_data,
                    weights,
                    aux_coef=aux_coef,
                )
            )

    grads = jax.tree.map(
        lambda a, b, c: a + b + c,
        embed_vjp(g_xs)[0],
        layers_vjp(g_layers)[0],
        head_vjp(g_head)[0],
    )
    return losses, stats, aux_total, grads


def segment_ids_from_cu_seqlens(cu_seqlens: np.ndarray, total: int) -> np.ndarray:
    """Host helper: cu_seqlens → per-token segment ids ([0..n-1]); the fake
    pad segment appended by pad_packed_tensor_dict keeps its own id, callers
    mark it PADDING_SEGMENT via loss-mask logic when needed."""
    seg = np.zeros(total, dtype=np.int32)
    n = len(cu_seqlens) - 1
    for i in range(n):
        seg[cu_seqlens[i] : cu_seqlens[i + 1]] = i
    return seg


# ---------------------------------------------------------------------------
# Decode path: prefill + batched single-token decode with a slot KV cache.
# The TPU-native replacement for the reference's generation engines (SGLang
# server / realhf real_llm_generate.py): static-shape continuous batching —
# cache arrays are [L, R, S, nKV, hd] with R fixed decode slots, so XLA
# compiles the decode step once and reuses it for the whole run.
# ---------------------------------------------------------------------------


def _lm_head(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """f32 logits [..., V] of post-final-norm hidden states [..., H]."""
    with jax.named_scope("lm_head"):
        if cfg.tie_word_embeddings:
            table = params["embed"]["embedding"].astype(jnp.dtype(cfg.dtype))
            logits = jnp.einsum("...h,vh->...v", x, table)
        else:
            logits = jnp.einsum("...h,hv->...v", x, params["lm_head"]["kernel"])
        return logits.astype(jnp.float32)


def _final_logits(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    with jax.named_scope("final_norm"):
        x = _norm(x, params["final_norm"], cfg, params.get("final_norm_bias"))
    return _lm_head(params, x, cfg)


def _embed_tokens(params: dict, tokens, positions, cfg: ModelConfig) -> jax.Array:
    """Token (and learned position) embeddings of the decode-side steps."""
    compute_dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = _scale_embed(
            params["embed"]["embedding"][tokens].astype(compute_dtype), cfg
        )
        if cfg.pos_embed == "learned":
            x = x + params["pos_embed"]["embedding"][positions].astype(
                compute_dtype
            )
        return x


def _attn_out_mlp(layer_p: dict, x, attn_out, cfg: ModelConfig, valid,
                  moe_load: bool = False, li=None, projected: bool = False):
    """What every decode-side layer body ends with: the output projection of
    `attn_out` [N, nH, hd] and the MLP, each with its residual. With
    `moe_load` (MoE models only) returns (x, this layer's expert load, see
    `moe_mlp`; zeros from a leading dense layer). `li`: the layer's index,
    static in a mixed stack. `projected`: `attn_out` is a mixer's output
    [N, H], its own projection behind it (a Gated DeltaNet layer)."""
    sparse = cfg.layer_sparse(li)
    if projected:
        proj = attn_out
    else:
        with jax.named_scope("attn"), jax.named_scope("o_proj"):
            proj = _w_einsum("tnd,ndh->th", attn_out, layer_p["attn"]["o_kernel"], 2)
            if cfg.attn_out_bias:
                proj = proj + layer_p["attn"]["o_bias"]
    x = x + proj
    h = _norm(x, layer_p["post_attn_norm"], cfg, layer_p.get("post_attn_norm_bias"))
    if moe_load and sparse:
        y, _, load = moe_mlp(layer_p["mlp"], h, cfg, valid=valid, with_load=True)
        return x + y, load
    if sparse:
        y, _ = moe_mlp(layer_p["mlp"], h, cfg, valid=valid)
    else:
        y = mlp(layer_p["mlp"], h, cfg)
    if moe_load:
        return x + y, jnp.zeros(_moe_load_len(cfg), jnp.int32)
    return x + y


# kernel names of the two kinds of cache read, so a device trace tells them
# apart (the full layers keep the uniform stacks' name)
_PAGED_KERNELS = {"full": "paged_attention", "window": "paged_attention_window"}


def _moe_load_len(cfg: ModelConfig) -> int:
    """Entries of `moe_mlp`'s load vector."""
    return ((3 if cfg.num_experts_published_ != cfg.num_experts else 2)
            + (2 if cfg.moe_grouped else 0))


def decode_counts(cfg: ModelConfig) -> bool:
    """Whether a decode chunk has a load vector to return: a model with
    experts (their pairs) or a mixed stack (the rows and state updates its
    kinds of layer read); a dense uniform stack has none and its chunk
    carries no such leaf."""
    return bool(cfg.num_experts) or cfg.mixed


def decode_load_len(cfg: ModelConfig) -> int:
    """Entries of the vector `decode_step_paged` returns under `moe_load`:
    `moe_mlp`'s, for a mixed stack the cached rows read by the full and by
    the window layers, where it has linear layers their state updates (one
    entry), and where it has latent layers the latent rows read."""
    return (_moe_load_len(cfg) + (2 if cfg.mixed else 0)
            + (1 if cfg.cache_layers["state"] else 0)
            + (1 if cfg.latent else 0))


def _split_output_gate(q: jax.Array, cfg: ModelConfig) -> tuple:
    """(q,) or, under `attn_output_gate`, (q, gate): each head's projection
    is its query lanes followed by as many lanes of output gate."""
    if not cfg.attn_output_gate:
        return (q,)
    hd = cfg.head_dim_
    return q[..., :hd], q[..., hd:]


def _gate_attn_out(attn_out: jax.Array, gate: list) -> jax.Array:
    """`attn_out * sigmoid(gate)` where the layer has an output gate
    (`gate` is what `_project_qkv` returned past q, k and v)."""
    if not gate:
        return attn_out
    g = jax.nn.sigmoid(gate[0].astype(jnp.float32)).astype(attn_out.dtype)
    return attn_out * g.reshape(attn_out.shape)


def _project_qkv(layer_p: dict, x: jax.Array, cos, sin, cfg: ModelConfig,
                 rope: bool = True):
    """Shared QKV projection + norm + rope. x: [..., H] with leading dims
    matching cos/sin's leading dims. `rope` False: a layer that takes no
    rotary embedding (the full layers of a mixed stack). Returns (q, k, v),
    and the output gate as a fourth under `attn_output_gate`."""
    with jax.named_scope("qkv"):
        q = _w_einsum("...h,hnd->...nd", x, layer_p["q_kernel"], 1)
        k = _w_einsum("...h,hnd->...nd", x, layer_p["k_kernel"], 1)
        v = _w_einsum("...h,hnd->...nd", x, layer_p["v_kernel"], 1)
        if cfg.qkv_bias:
            q = q + layer_p["q_bias"]
            k = k + layer_p["k_bias"]
            v = v + layer_p["v_bias"]
        q, *gate = _split_output_gate(q, cfg)
        if cfg.qk_norm:
            q, k = _qk_norm(q, k, layer_p, cfg)
    if cos is None:  # a model with no positional encoding has no tables
        return q, k, v, *gate
    cos_b = cos[..., None, :].astype(q.dtype)
    sin_b = sin[..., None, :].astype(q.dtype)

    @jax.named_scope("rope")
    def rot(t):
        t1, t2, rest = _split_rotary(t, 2 * cos.shape[-1])
        return _rotated(t1, t2, cos_b, sin_b, rest)

    if cfg.pos_embed != "rope" or not rope:
        return q, k, v, *gate
    return rot(q), rot(k), v, *gate


_EXPERT_KERNELS = ("gate_kernel", "up_kernel", "down_kernel")

# The longest prefill bucket whose attention builds its [nKV, group, T, T]
# float32 scores whole. Above it (prompts of thousands of tokens: 64 heads at
# T = 6,144 would be 9.7 GB) the keys go a block at a time.
PREFILL_DENSE_MAX = 1024


def _scan_stacked(step, carry, params, cfg: ModelConfig, *xs):
    """`lax.scan(step, carry, (params["layers"], *xs))` over the stacked
    layers of a forward-only program, with an MoE model's expert kernels
    read in place. As `xs` each layer's `[E, H, M]` kernels would reach the
    grouped matmul as a slice of the stacked leaf; XLA:TPU runs that matmul
    as a custom call, which cannot read through a slice, so the slice was
    copied: 0.8 GB a layer a token step at OLMoE's widths, 46% of a decode
    chunk (PERF.md, PR 27). So the three leaves stay out of `xs`: the body
    closes over them whole, reshaped `[L, E, ...] -> [L*E, ...]` (a
    bitcast), and the layer's mlp carries `first_group`, the index of its
    first expert among the L*E groups (`_expert_mixture_plain`). Not for
    the trainer: the gradient of a whole stack a layer would cost it
    L-fold. A dense model has no such leaves and scans as written."""
    layers = params["layers"]
    E = cfg.num_experts
    if not E:
        return jax.lax.scan(step, carry, (layers, *xs))
    mlp = layers["mlp"]
    stack = {k: mlp[k].reshape(-1, *mlp[k].shape[2:]) for k in _EXPERT_KERNELS}
    per_layer = {k: v for k, v in mlp.items() if k not in stack}
    per_layer["first_group"] = jnp.arange(
        0, cfg.num_hidden_layers * E, E, dtype=jnp.int32
    )

    def in_place(c, inputs):
        layer_p = {**inputs[0], "mlp": {**inputs[0]["mlp"], **stack}}
        return step(c, (layer_p, *inputs[1:]))

    return jax.lax.scan(in_place, carry, ({**layers, "mlp": per_layer}, *xs))


def prefill(
    params: dict,
    input_ids: jax.Array,
    position_ids: jax.Array,
    cfg: ModelConfig,
    valid: jax.Array | None = None,
    with_logits: bool = True,
    input_embeds: jax.Array | None = None,
    rope_cos: jax.Array | None = None,
    rope_sin: jax.Array | None = None,
    prefix_k: jax.Array | None = None,
    prefix_v: jax.Array | None = None,
    prefix_len: jax.Array | None = None,
) -> tuple[jax.Array | None, jax.Array, jax.Array]:
    """Causal forward over ONE sequence [T], returning (logits [T, V],
    k_cache [L, T, nKV, hd], v_cache [L, T, nKV, hd]). A latent model's
    k_cache is its rows to cache at the pool's lanes, [L, T, 1,
    `latent_row_lanes`], its v_cache empty
    ([L, T, 1, 0]). A model with linear
    layers returns the rows of its attention layers alone (in layer order)
    and a fourth: its linear layers' state at the last real token, {"S":
    [n_lin, Hv, dk, dv] float32, "conv": [n_lin, K-1, C]}.

    `valid` [T] bool marks real (non-bucket-pad) tokens; MoE routing must
    see it so pad rows don't claim expert capacity. (Attention needs no
    mask: causality already hides the pad tail from real tokens.)

    `with_logits=False` skips the lm_head projection and returns None
    logits — the cache-warm path: the decode engine samples every token
    (including the first) inside its chunked decode loop, so prefill only
    needs to write KV.

    `input_embeds` [T, H] overrides the token-embedding lookup — the
    multimodal path: the decode engine splices vision-tower outputs over
    image-pad positions (models/qwen2_vl.splice_image_embeds) and
    prefills from embeddings. `rope_cos/rope_sin` [T, hd/2] override the
    1-D rope tables (Qwen2-VL m-rope, models/qwen2_vl.mrope_table).

    `prefix_k/prefix_v` [L, Tp, nKV, hd] + scalar `prefix_len`: cached
    context for SUFFIX prefill (partial prefix sharing) — every token
    additionally attends to prefix rows < prefix_len, and `position_ids`
    must then be the absolute positions (prefix_len + arange). One layer
    body serves both modes so the paths cannot drift apart."""
    compute_dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        if input_embeds is not None:
            x = input_embeds.astype(compute_dtype)
        else:
            x = params["embed"]["embedding"][input_ids].astype(compute_dtype)
        x = _scale_embed(x, cfg)
        if cfg.pos_embed == "learned":
            x = x + params["pos_embed"]["embedding"][position_ids].astype(
                compute_dtype
            )
    if rope_cos is not None:
        cos, sin = rope_cos, rope_sin
    else:
        cos, sin = _rope_tables(position_ids, cfg)
    T = input_ids.shape[0]
    with_prefix = prefix_k is not None
    # above `PREFILL_DENSE_MAX` tokens no [T, T] score tensor is built: the
    # attention goes a block of keys at a time (ops/chunked_attention.py)
    chunked = T > PREFILL_DENSE_MAX and not with_prefix

    # a block-causal model: a query sees to the end of its block (the rows of
    # a bucket's padding tail lie after every real token, and the engine ends
    # a prompt's cached part on a block boundary, so no real query sees one)
    horizon = block_horizon(position_ids, cfg.block_length_)
    if horizon is not None and with_prefix:
        raise NotImplementedError(
            "a suffix prefill under a block-causal mask: the cached prefix "
            "would have to end on a block boundary"
        )

    def dense_mask(window):
        if horizon is None:
            causal = jnp.tril(jnp.ones((T, T), dtype=bool))
        else:
            causal = jnp.arange(T)[None, :] <= horizon[:, None]
        band = _window_band(T, window)
        if band is not None:
            causal = causal & band
        if not with_prefix:
            return causal
        Tp = prefix_k.shape[1]
        key_pos_prefix = jnp.arange(Tp, dtype=jnp.int32)
        prefix_mask = jnp.broadcast_to(
            key_pos_prefix[None, :] < prefix_len, (T, Tp)
        )
        if window is not None:
            prefix_mask = prefix_mask & (
                key_pos_prefix[None, :] > position_ids[:, None] - window
            )
        return jnp.concatenate([prefix_mask, causal], axis=1)  # [T, Tp+T]

    # one mask for each kind of layer (a uniform stack has one kind)
    masks = {} if chunked else {
        w: dense_mask(w)
        for w in {cfg.layer_window(i) for i in range(cfg.num_hidden_layers)}
    }
    nH, nKV, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
    group = nH // nKV

    if cfg.latent and with_prefix:
        raise NotImplementedError(
            "a suffix prefill over cached latent rows: the expanded form "
            "would have to expand the prefix's rows again"
        )
    has_state = bool(cfg.cache_layers["state"])
    if has_state:
        if with_prefix:
            raise NotImplementedError(
                "a suffix prefill over cached rows: a linear layer's state at "
                "the prefix's end is not among them"
            )
        # one sequence, padding after its real tokens
        seq_segments = (
            jnp.zeros(T, jnp.int32) if valid is None
            else jnp.where(valid, 0, PADDING_SEGMENT)
        )
        true_len = T if valid is None else valid.sum(dtype=jnp.int32)

    @jax.named_scope("layer")
    def layer(x, inputs, li=None):
        layer_p, *prefix = inputs
        window = cfg.layer_window(li)
        h = _norm(x, layer_p["input_norm"], cfg, layer_p.get("input_norm_bias"))
        if cfg.layer_linear(li):
            with jax.named_scope("attn"):
                out, cache = _linear_mixers(cfg)[0](
                    layer_p["attn"], h, seq_segments, cfg, true_len
                )
            return _attn_out_mlp(
                layer_p, x, out, cfg, valid, li=li, projected=True
            ), cache
        if cfg.latent:
            with jax.named_scope("attn"):
                out, row = _latent_prefill_attention(
                    layer_p["attn"], h, cos, sin,
                    None if chunked else masks[window], cfg,
                )
            # the rows to cache as the pool stores them, as one kv head;
            # nothing on the V side
            row = _latent_pool_row(row, cfg.latent_row_lanes)
            return _attn_out_mlp(
                layer_p, x, out, cfg, valid, li=li, projected=True
            ), (row[:, None, :], row[:, None, :0])
        with jax.named_scope("attn"):
            q, k, v, *gate = _project_qkv(
                layer_p["attn"], h, cos, sin, cfg, cfg.layer_rope(li)
            )
            if chunked:
                from areal_tpu.ops.chunked_attention import chunked_attention

                with jax.named_scope("attention_chunked"):
                    # one sequence: bucket padding lies after every real
                    # token, so causality alone hides it
                    attn_out = chunked_attention(
                        q, k, v, jnp.zeros(T, jnp.int32), sliding_window=window,
                        **({} if horizon is None else {"q_horizon": horizon}),
                    )
            else:
                with jax.named_scope(_attention_scope(cfg, window)):
                    if with_prefix:
                        pk, pv = prefix
                        kk = jnp.concatenate([pk.astype(k.dtype), k], axis=0)
                        vv = jnp.concatenate([pv.astype(v.dtype), v], axis=0)
                    else:
                        kk, vv = k, v
                    qg = q.reshape(T, nKV, group, hd)
                    scores = jnp.einsum("tkgd,skd->kgts", qg, kk).astype(jnp.float32)
                    scores = scores / np.sqrt(hd)
                    scores = jnp.where(masks[window][None, None], scores, -1e30)
                    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
                    attn_out = jnp.einsum(
                        "kgts,skd->tkgd", probs, vv
                    ).reshape(T, nH, hd)
            attn_out = _gate_attn_out(attn_out, gate)
        x = _attn_out_mlp(layer_p, x, attn_out, cfg, valid, li=li)
        return x, (k, v)

    prefix = (prefix_k, prefix_v) if with_prefix else ()
    if cfg.scan_layers:
        x, (ks, vs) = _scan_stacked(layer, x, params, cfg, *prefix)
    else:
        # attention layers hand over rows of keys and values (stacked in
        # layer order), linear layers their state
        # (a state layer's cache beside whether it is a run's stack)
        rows, states = [], []
        for key, i, past in cfg.stack_plan:
            if past - i > 1:
                # a run of recurrent layers, stacked: one body, scanned, its
                # layers' caches stacked as the scan's outputs
                x, cache = jax.lax.scan(
                    lambda x, layer_p, i=i: layer(x, (layer_p,), i), x, params[key]
                )
                states.append((True, cache))
                continue
            with _layer_scope(cfg, i):
                x, cache = layer(
                    x, (params[key], *(p[i] for p in prefix)), i
                )
            if cfg.layer_linear(i):
                states.append((False, cache))
            else:
                rows.append(cache)
        ks, vs = (jnp.stack(t) for t in zip(*rows))

    logits = _final_logits(params, x, cfg) if with_logits else None
    if has_state and cfg.layer_runs:
        S, conv = (jnp.concatenate(t) for t in zip(*(
            c if stacked else tuple(t[None] for t in c) for stacked, c in states)))
        return logits, ks, vs, {"S": S, "conv": conv}
    if has_state:
        S, conv = (jnp.stack(t) for t in zip(*(c for _, c in states)))
        return logits, ks, vs, {"S": S, "conv": conv}
    return logits, ks, vs


def prefill_with_prefix(
    params: dict,
    input_ids: jax.Array,  # [T] suffix tokens (bucket-padded)
    prefix_k: jax.Array,  # [L, Tp, nKV, hd] cached prefix KV
    prefix_v: jax.Array,  # [L, Tp, nKV, hd]
    prefix_len: jax.Array,  # scalar: valid prefix rows (dynamic, <= Tp)
    cfg: ModelConfig,
    valid: jax.Array | None = None,  # [T] real (non-pad) suffix tokens
) -> tuple[jax.Array, jax.Array]:
    """Causal forward over a SUFFIX whose context is cached prefix KV.

    The partial-prefix-sharing path (the radix-tree property the reference
    inherits from SGLang): a multi-turn / tool-use request re-submits
    shared history + a short new suffix; the engine forks the history's
    KV rows from a donor slot and runs ONE parallel pass over just the
    suffix — each suffix token attends to [prefix rows < prefix_len] +
    causally to earlier suffix tokens. Returns the suffix-only
    (k_cache, v_cache) [L, T, nKV, hd] for writing at offset prefix_len.

    Thin wrapper over `prefill` (same layer body — the paths cannot
    drift): suffix token i occupies absolute position prefix_len + i, so
    rope and sliding-window distances stay exact."""
    T = input_ids.shape[0]
    positions = prefix_len + jnp.arange(T, dtype=jnp.int32)
    _, ks, vs = prefill(
        params,
        input_ids,
        positions,
        cfg,
        valid=valid,
        with_logits=False,
        prefix_k=prefix_k,
        prefix_v=prefix_v,
        prefix_len=prefix_len,
    )
    return ks, vs


@jax.named_scope("kv_write")
@jax.named_scope("pool_write")
def _write_pool_rows(pool, new, layer, dest_block, dest_off):
    """Scatter `new` [N, nKV, hd] fp rows into the WHOLE pool
    `[L, n_blocks, bsz, nKV*hd]` at `(layer, dest_block[n], dest_off[n])`.
    The pool is the operand and the result of one scatter and nothing
    else, so a donated, carried pool is updated in place. Int8 pools
    quantize AT the scatter: the int8 row and its [N, nKV] scale row land
    through the same block id (scales are [L, n_blocks, nKV, bsz])."""
    from areal_tpu.ops.kv_quant import quantize_kv, split_pool

    data, scales = split_pool(pool)
    n = new.shape[0]
    if scales is None:
        return data.at[layer, dest_block, dest_off].set(
            new.astype(data.dtype).reshape(n, -1)
        )
    q_rows, s_rows = quantize_kv(new)
    return (
        data.at[layer, dest_block, dest_off].set(q_rows.reshape(n, -1)),
        scales.at[layer, dest_block, :, dest_off].set(s_rows),
    )


def _scan_layers_carrying(layer, carry, params, cfg: ModelConfig):
    """Run `layer(carry, layer_params, layer_index) -> carry` over every
    layer. The KV pool rides in `carry` WHOLE: passed to `lax.scan` as
    xs/ys it would be sliced out and stacked back per layer, and the fresh
    ys buffer cannot alias the token-step scan's carry, which cost a copy
    of both pools every token step (PERF.md, PR 24's trace)."""
    if cfg.scan_layers:
        return _scan_stacked(
            lambda c, xs: (layer(c, *xs), None),
            carry,
            params,
            cfg,
            jnp.arange(cfg.num_hidden_layers, dtype=jnp.int32),
        )[0]
    for key, i, past in cfg.stack_plan:
        if past - i > 1:
            # a run of like layers, stacked: one body, scanned, the pools in
            # the carry whole and the layer's place in its pool traced (the
            # run's first layer names the kind, `at` counts from it)
            carry = jax.lax.scan(
                lambda c, xs, i=i: (layer(c, xs[0], i, xs[1]), None), carry,
                (params[key], jnp.arange(past - i, dtype=jnp.int32)),
            )[0]
            continue
        with _layer_scope(cfg, i):
            carry = layer(carry, params[key], i)
    return carry


# -- the window layers' cache of a mixed stack -------------------------------
# A window layer never reads past its window, so its rows live in a RING:
# `ring_pages` pages a slot, fixed, outside the allocator. The ring pool is
# `[window layers, 1 + slots * pages, bsz, nKV*hd]`: block 0 is the null
# block (inactive slots write there), slot r owns blocks
# 1 + r*pages .. 1 + r*pages + pages - 1, and the row of position p sits in
# the slot's page (p // bsz) % pages at offset p % bsz. The read is the same
# `paged_attention` over the slot's `pages` columns, with the window in
# `valid`. The full layers keep the paged pool and the block tables, over
# the full layers alone. A mixed model's pools travel as
# {"full": pool, "window": ring pool}.


def ring_pages(window: int, block_size: int) -> int:
    """Pages a slot's ring needs: the window's rows span at most this many
    pages, with one to spare so that a row of the lap before, still lying
    in the page being written, is always older than the window."""
    return -(-(int(window) - 1) // int(block_size)) + 1


def ring_slack(window: int, block_size: int) -> int:
    """Rows a slot's ring holds beyond the window: how far ahead of its
    oldest query a verify chunk may write."""
    return ring_pages(window, block_size) * int(block_size) - int(window)


def _ring_coords(positions, slots, bsz: int, pages: int):
    """(block, offset) in the ring pool of the row at `positions` [N] of
    slots `slots` [N]."""
    return 1 + slots * pages + (positions // bsz) % pages, positions % bsz


def _ring_valid(positions, window: int, bsz: int, pages: int):
    """[N, pages*bsz] bool: which cells of a slot's ring the query at
    `positions` [N] attends. Cell (c, o) holds the latest position q <= p
    with (q // bsz) % pages == c and q % bsz == o; it is read iff that row
    exists and lies inside the window."""
    cell = jnp.arange(pages * bsz, dtype=positions.dtype)
    c, o = cell // bsz, cell % bsz
    p = positions[:, None]
    page = p // bsz
    q = (page - (page - c[None, :]) % pages) * bsz + o[None, :]
    q = jnp.where(q > p, q - pages * bsz, q)
    return (q >= 0) & (p - q < window)


def _mixed_cache(cfg: ModelConfig, k_pool, positions, slots, active, full,
                 live_of):
    """Where each layer of a mixed stack writes and reads: (`index`: layer ->
    (kind, index in that kind's pool), `where`: kind -> (table, dest_block,
    dest_off, valid, live)). `full` is the paged pool's first four as a
    uniform stack computes them; the ring's are built here from the flat [N]
    `positions` / `slots` / `active` (N = R, or R*W in a verify, whose
    `valid` is [R, W, cells] like `full`'s); `live_of(valid, pool)` gives
    each kind's range of live block columns (`_live_columns`)."""
    index = {
        li: (kind, j)
        for kind, layers in cfg.cache_layers.items()
        for j, li in enumerate(layers)
    }
    # (a latent model's one pool is addressed as the paged pool is)
    where = {"latent" if "latent" in k_pool else "full": full}
    if "window" in k_pool:
        bsz = k_pool["window"].shape[2]
        window = cfg.sliding_window
        pages = ring_pages(window, bsz)
        R = (k_pool["window"].shape[1] - 1) // pages
        table = 1 + jnp.arange(R * pages, dtype=jnp.int32).reshape(R, pages)
        blk, off = _ring_coords(positions, slots, bsz, pages)
        if active is not None:
            blk = jnp.where(active, blk, 0)
            off = jnp.where(active, off, 0)
        seen = _ring_valid(positions, window, bsz, pages)
        where["window"] = (
            table, blk, off, seen.reshape(*full[3].shape[:-1], pages * bsz)
        )
    return index, {
        kind: (*w, live_of(w[3], k_pool[kind])) for kind, w in where.items()
    }


def _live_columns(valid, pool, active, attn_impl: str):
    """The kernel read's work list over `pool` (a kind's K pool): each slot's
    range of block columns that hold a row `valid` lets it attend and the
    chain that walks the ranges slot after slot, in the groups of columns the
    kernel takes there (`ops/paged_attention.work_list`). Taken once a token
    step, outside the layer loop. None for the XLA read, which gathers every
    column and keeps its program."""
    from areal_tpu.ops.paged_attention import resolve_impl, work_list

    if resolve_impl(attn_impl) != "pallas":
        return None
    return work_list(valid, pool, active)


def _mixed_attention(read, q, kp, vp, k_new, v_new, place, attn_impl):
    """One layer of a mixed stack: its new rows into its kind of pool, then
    its read of that pool. `place` = (kind, index among that kind's layers,
    (table, dest_block, dest_off, valid, live)); `read` is `paged_attention`
    or `paged_attention_qlen`. Returns (attn_out, kp, vp)."""
    kind, ci, (table, blk, off, seen, live) = place
    kp = {**kp, kind: _write_pool_rows(kp[kind], k_new, ci, blk, off)}
    vp = {**vp, kind: _write_pool_rows(vp[kind], v_new, ci, blk, off)}
    with jax.named_scope(f"attention_{kind}"):
        out = read(
            q, kp[kind], vp[kind], table, seen, ci, impl=attn_impl,
            kernel_name=_PAGED_KERNELS[kind], live=live,
        )
    return out, kp, vp


def decode_step_paged(
    params: dict,
    tokens: jax.Array,  # [R] current input token per slot
    positions: jax.Array,  # [R] logical index the new token occupies
    k_pool,  # [L, n_blocks, bsz, nKV*hd] paged KV pool, or (int8, scales)
    v_pool,  # [L, n_blocks, bsz, nKV*hd] or (int8 data, f32 scales)
    block_tables: jax.Array,  # [R, nb] int32: each slot's pool blocks
    cfg: ModelConfig,
    active: jax.Array | None = None,  # [R] bool: slot holds a live request
    rope_offset: jax.Array | None = None,  # [R] added to rope pos only
    attn_impl: str = "auto",  # ops/paged_attention.py impl select
    moe_load: bool = False,  # MoE only: also return the step's expert load
) -> tuple:
    """One batched decode step over R slots, attending DIRECTLY over the
    paged pool.

    Writes this step's K/V row per slot and attends over s <= position
    through the block table. Returns (logits [R, V], k_pool, v_pool), and
    with `moe_load` a fourth: int32 [pairs, busiest expert's pairs] summed
    over layers (`moe_mlp`). `active` keeps dead slots out of MoE routing:
    they reach no expert and are not counted.

    `rope_offset` shifts the ROTARY position only (pool row unchanged):
    Qwen2-VL m-rope compresses an image's positions to max(t, h, w) per
    span, so a VLM slot's text position = cache_len + per-request delta.
    Text tokens under m-rope use one scalar for all three sections, which
    reduces exactly to standard 1-D rope at that scalar — so the shared
    decode step stays mrope-correct with just this offset.

    - **The write is O(1).** The new row's pool coordinates
      `(layer, block_tables[r, p // bsz], p % bsz)` are computed from the
      slot position and written with a single dynamic scatter of R rows
      into the WHOLE pool, which the layer loop carries (never slices:
      `_scan_layers_carrying`). Inactive slots are redirected to the
      reserved null block 0 (never read as valid data): retired slots can
      still be prefix-KV donors and parked slots hold KV a resume needs,
      so their rows must stay untouched. Write-collision safety between
      active slots is the pool invariant: aliased (prefix-shared) blocks
      sit strictly below every writer's position and the boundary block
      is private (engine/kv_pool.py).
    - **Attention reads through the block table** (ops/paged_attention):
      no KV is gathered or copied outside the attention read itself.

    Int8 pools: `k_pool`/`v_pool` arrive as (int8 data, f32 scales)
    tuples (ops/kv_quant.py) and are returned in the same form. The new
    row is quantized HERE, at the O(1) scatter — one quantize per token
    per layer — and the scale row lands in the scale pool through the
    same block id, so every downstream byte mover (offload, export,
    migration) ships the quantized bytes as-is. Attention dequantizes
    inside ops/paged_attention, so the row just written is read back
    through its int8 representation — token streams are a pure function
    of the quantized pool state, invariant to chunk boundaries.

    `tests/test_paged_attention.py` holds the step to the trainer's
    `forward` and to this write contract.
    """
    from areal_tpu.ops.paged_attention import paged_attention

    R = tokens.shape[0]
    bsz = jax.tree.leaves(k_pool)[0].shape[2]
    nb = block_tables.shape[1]
    span = nb * bsz
    nH, hd = cfg.num_attention_heads, cfg.head_dim_
    x = _embed_tokens(params, tokens, positions, cfg)  # [R, H]
    rope_pos = positions if rope_offset is None else positions + rope_offset
    cos, sin = _rope_tables(rope_pos, cfg)
    valid = jnp.arange(span)[None, :] <= positions[:, None]  # [R, span]
    mixed = isinstance(k_pool, dict)
    if cfg.sliding_window is not None and not mixed:
        valid = valid & (
            jnp.arange(span)[None, :] > positions[:, None] - cfg.sliding_window
        )

    # the one pool row this step writes, per slot: clip keeps stale
    # inactive positions in range, and inactive slots land in null block 0
    blk_col = jnp.clip(positions // bsz, 0, nb - 1)
    dest_block = jnp.take_along_axis(block_tables, blk_col[:, None], axis=1)[
        :, 0
    ]
    dest_off = positions % bsz
    if active is not None:
        dest_block = jnp.where(active, dest_block, 0)
        dest_off = jnp.where(active, dest_off, 0)

    live_of = functools.partial(
        _live_columns, active=active, attn_impl=attn_impl
    )
    if mixed:
        index, where = _mixed_cache(
            cfg, k_pool, positions, jnp.arange(R, dtype=positions.dtype),
            active, (block_tables, dest_block, dest_off, valid), live_of,
        )
    else:
        live = live_of(valid, k_pool)

    # a state-space step's work list over the live slots, once a token step
    state_kw = {}
    if cfg.ssm_state_size:
        from areal_tpu.ops.ssm_step import live_slots

        state_kw["live"] = live_slots(active, R)

    @jax.named_scope("layer")
    def layer(carry, layer_p, li, at=0):
        x, kp, vp, load = carry
        h = _norm(x, layer_p["input_norm"], cfg, layer_p.get("input_norm_bias"))
        # (a mixer whose output is projected already: linear, latent)
        projected = cfg.layer_linear(li) or cfg.latent
        with jax.named_scope("attn"):
            if cfg.layer_linear(li):
                # no rows to write or read: the slot's state, updated in place
                # (`at`: the layer's place in a scanned run, traced)
                attn_out, state = _linear_mixers(cfg)[1](
                    layer_p["attn"], h, kp["state"], index[li][1] + at, active,
                    cfg, attn_impl, **state_kw,
                )
                kp = {**kp, "state": state}
            elif cfg.latent:
                # the absorbed form: one row written, the rows read in place
                attn_out, pool = _latent_decode_attention(
                    layer_p["attn"], h, cos, sin, kp["latent"], index[li][1],
                    where["latent"], cfg, attn_impl,
                )
                kp = {**kp, "latent": pool}
            else:
                q, k_new, v_new, *gate = _project_qkv(
                    layer_p["attn"], h, cos, sin, cfg, cfg.layer_rope(li)
                )
                if mixed:
                    kind, ci = index[li]
                    attn_out, kp, vp = _mixed_attention(
                        paged_attention, q.reshape(R, nH, hd), kp, vp, k_new,
                        v_new, (kind, ci, where[kind]), attn_impl,
                    )
                else:
                    kp = _write_pool_rows(kp, k_new, li, dest_block, dest_off)
                    vp = _write_pool_rows(vp, v_new, li, dest_block, dest_off)
                    with jax.named_scope("attention"):
                        attn_out = paged_attention(
                            q.reshape(R, nH, hd), kp, vp, block_tables, valid,
                            li, impl=attn_impl, live=live,
                        )
                attn_out = _gate_attn_out(attn_out, gate)
        if moe_load:
            x, layer_load = _attn_out_mlp(
                layer_p, x, attn_out, cfg, active, True, li, projected=projected
            )
            return x, kp, vp, load + layer_load
        return _attn_out_mlp(
            layer_p, x, attn_out, cfg, active, li=li, projected=projected
        ), kp, vp, None

    # the load rides in the carry as None (no leaf) unless asked for
    load0 = jnp.zeros(_moe_load_len(cfg), jnp.int32) if moe_load else None
    x, k_pool, v_pool, load = _scan_layers_carrying(
        layer, (x, k_pool, v_pool, load0), params, cfg
    )
    logits = _final_logits(params, x, cfg)
    if moe_load:
        if mixed:
            # cached rows this step's attention read, live slots only, counted
            # from the masks the kernels were given, a kind of layer at a time
            live = jnp.ones((R, 1), bool) if active is None else active[:, None]
            rows = jnp.stack([
                (where[kind][3] & live).sum() * len(cfg.cache_layers[kind])
                if kind in where else jnp.zeros((), jnp.int32)
                for kind in ("full", "window")
            ]).astype(load.dtype)
            load = jnp.concatenate([load, rows])
            if cfg.cache_layers["state"]:
                # live slots' state updates, a linear layer each
                updates = live.sum() * len(cfg.cache_layers["state"])
                load = jnp.concatenate([load, updates[None].astype(load.dtype)])
            if cfg.latent:
                # cached latent rows read: live slots' rows x latent layers
                rows = (where["latent"][3] & live).sum() * len(cfg.cache_layers["latent"])
                load = jnp.concatenate([load, rows[None].astype(load.dtype)])
        return logits, k_pool, v_pool, load
    return logits, k_pool, v_pool


def _qlen_step_paged(
    params: dict,
    tokens: jax.Array,  # [R, W]
    positions0: jax.Array,  # [R] base index column 0 occupies
    k_pool,
    v_pool,
    block_tables: jax.Array,  # [R, nb]
    cfg: ModelConfig,
    active: jax.Array | None,
    rope_offset: jax.Array | None,
    attn_impl: str,
    block: bool = False,
    moe_load: bool = False,
    with_logits: bool = True,
) -> tuple:
    """W positions a slot in one forward over the paged pool: the body of
    `verify_step_paged` (each query's horizon its own position) and of
    `diffusion_step_paged` (`block`: each query's horizon its block's last
    position, the read named apart). Returns (logits [R, W, V] or None,
    k_pool, v_pool) and, with `moe_load`, the forward's load vector."""
    from areal_tpu.ops.paged_attention import paged_attention_qlen

    if cfg.cache_layers["state"]:
        raise NotImplementedError(
            "a verify step over linear layers: a rejected draft would have "
            "to roll each slot's state back"
        )
    if cfg.latent:
        raise NotImplementedError(
            "a verify step over a latent pool: the absorbed attention scores "
            "one query a slot (ops/paged_attention_latent.py)"
        )
    R, W = tokens.shape
    bsz = jax.tree.leaves(k_pool)[0].shape[2]
    nb = block_tables.shape[1]
    span = nb * bsz
    nH, hd = cfg.num_attention_heads, cfg.head_dim_
    positions = positions0[:, None] + jnp.arange(W, dtype=positions0.dtype)
    flat_pos = positions.reshape(-1)
    x = _embed_tokens(params, tokens.reshape(-1), flat_pos, cfg)  # [R*W, H]
    rope_pos = (
        positions if rope_offset is None else positions + rope_offset[:, None]
    ).reshape(-1)
    cos, sin = _rope_tables(rope_pos, cfg)
    if block:
        # block-causal: every query of a block sees to the block's last row
        B = cfg.block_length_
        sees = positions - positions % B + (B - 1)
        scope, kernel = "attention_block", {"kernel_name": "paged_attention_block"}
    else:
        sees, scope, kernel = positions, "attention", {}
    valid = (
        jnp.arange(span)[None, None, :] <= sees[:, :, None]
    )  # [R, W, span]
    mixed = isinstance(k_pool, dict)
    if block and (mixed or cfg.sliding_window is not None):
        raise NotImplementedError(
            "a block step over a window's ring: the block-causal models "
            "served have full attention alone"
        )
    if cfg.sliding_window is not None and not mixed:
        valid = valid & (
            jnp.arange(span)[None, None, :]
            > positions[:, :, None] - cfg.sliding_window
        )

    # pool coordinates of each (slot, position) row; inactive slots land in
    # the null block 0 so donors/parked KV stay untouched
    blk_col = jnp.clip(positions // bsz, 0, nb - 1)  # [R, W]
    dest_block = jnp.take_along_axis(block_tables, blk_col, axis=1)
    dest_off = positions % bsz
    if active is not None:
        dest_block = jnp.where(active[:, None], dest_block, 0)
        dest_off = jnp.where(active[:, None], dest_off, 0)
    if block:
        # a slot whose request has ended denoises on until its chunk does: a
        # block past the table's last column goes to the null block, not
        # (clipped) over the finished request's rows, which a later one forks
        dest_block = jnp.where(positions // bsz < nb, dest_block, 0)
    dest_block_f = dest_block.reshape(-1)
    dest_off_f = dest_off.reshape(-1)
    active_flat = (
        None if active is None else jnp.repeat(active, W, axis=0)
    )

    live_of = functools.partial(
        _live_columns, active=active, attn_impl=attn_impl
    )
    if mixed:
        # the W rows of a slot are all written before any is read, so the
        # ring must keep the oldest query's window clear of the newest row
        if "window" in k_pool and W - 1 > ring_slack(cfg.sliding_window, bsz):
            raise ValueError(
                f"a verify chunk of {W} positions does not fit the ring's "
                f"{ring_slack(cfg.sliding_window, bsz)} rows of slack past a "
                f"window of {cfg.sliding_window} at pages of {bsz}"
            )
        index, where = _mixed_cache(
            cfg, k_pool, flat_pos,
            jnp.repeat(jnp.arange(R, dtype=flat_pos.dtype), W), active_flat,
            (block_tables, dest_block_f, dest_off_f, valid), live_of,
        )
    else:
        live = live_of(valid, k_pool)

    @jax.named_scope("layer")
    def layer(carry, layer_p, li):
        x, kp, vp, load = carry
        h = _norm(x, layer_p["input_norm"], cfg, layer_p.get("input_norm_bias"))
        with jax.named_scope("attn"):
            q, k_new, v_new, *gate = _project_qkv(
                layer_p["attn"], h, cos, sin, cfg, cfg.layer_rope(li)
            )
            if mixed:
                kind, ci = index[li]
                attn_out, kp, vp = _mixed_attention(
                    paged_attention_qlen, q.reshape(R, W, nH, hd), kp, vp,
                    k_new, v_new, (kind, ci, where[kind]), attn_impl,
                )
                attn_out = attn_out.reshape(R * W, nH, hd)
            else:
                kp = _write_pool_rows(kp, k_new, li, dest_block_f, dest_off_f)
                vp = _write_pool_rows(vp, v_new, li, dest_block_f, dest_off_f)
                with jax.named_scope(scope):
                    attn_out = paged_attention_qlen(
                        q.reshape(R, W, nH, hd), kp, vp, block_tables, valid, li,
                        impl=attn_impl, live=live, **kernel,
                    ).reshape(R * W, nH, hd)
            attn_out = _gate_attn_out(attn_out, gate)
        if moe_load:
            x, layer_load = _attn_out_mlp(
                layer_p, x, attn_out, cfg, active_flat, True, li
            )
            return x, kp, vp, load + layer_load
        x = _attn_out_mlp(layer_p, x, attn_out, cfg, active_flat, li=li)
        return x, kp, vp, None

    # the load rides in the carry as None (no leaf) unless asked for
    load0 = jnp.zeros(_moe_load_len(cfg), jnp.int32) if moe_load else None
    x, k_pool, v_pool, load = _scan_layers_carrying(
        layer, (x, k_pool, v_pool, load0), params, cfg
    )
    logits = (
        _final_logits(params, x, cfg).reshape(R, W, -1) if with_logits else None
    )
    if moe_load:
        # cached rows this forward's attention read, live slots only, counted
        # from the mask the kernel was given (one query's: a block shares it)
        live_rows = valid[:, 0] if active is None else valid[:, 0] & active[:, None]
        rows = live_rows.sum() * cfg.num_hidden_layers
        return logits, k_pool, v_pool, jnp.concatenate(
            [load, rows[None].astype(load.dtype)]
        )
    return logits, k_pool, v_pool


def verify_step_paged(
    params: dict,
    tokens: jax.Array,  # [R, W]: draft inputs, column 0 = the last token
    positions0: jax.Array,  # [R] base index column 0 occupies
    k_pool,  # [L, n_blocks, bsz, nKV*hd], or (int8 data, f32 scales)
    v_pool,  # [L, n_blocks, bsz, nKV*hd] or (int8 data, f32 scales)
    block_tables: jax.Array,  # [R, nb]
    cfg: ModelConfig,
    active: jax.Array | None = None,
    rope_offset: jax.Array | None = None,
    attn_impl: str = "auto",
) -> tuple[jax.Array, Any, Any]:
    """Speculative VERIFY step: score W token positions per slot in ONE
    forward (q_len = W self-extension) DIRECTLY over the paged pool,
    instead of W sequential `decode_step_paged` calls.

    Column j of `tokens` sits at position `positions0 + j`; its KV row is
    written there and its logits predict the token at the NEXT position —
    what `decode_step_paged` would have produced had it been fed the same
    inputs one at a time (the contract the engine's speculative accept
    relies on; tests/test_paged_attention.py and tests/test_spec_decode.py
    hold it). Rejected positions' rows are simply dead: the next write at
    that position overwrites them, and the causal mask (`s <= position`)
    hides them from every query that matters before then. Returns
    (logits [R, W, V] f32, k_pool, v_pool).

    The KV write is an O(W) row scatter through the block table (inactive
    slots redirect to the reserved null block 0, like `decode_step_paged`),
    and attention reads through the table with per-query causal masks
    (ops/paged_attention.paged_attention_qlen — the Pallas impl DMAs each
    pool block once for all W queries). Int8 pools quantize the W rows at
    this scatter and return (data, scales) tuples, exactly as
    `decode_step_paged` does for its single row."""
    return _qlen_step_paged(
        params, tokens, positions0, k_pool, v_pool, block_tables, cfg,
        active, rope_offset, attn_impl,
    )


def diffusion_step_paged(
    params: dict,
    tokens: jax.Array,  # [R, B]: the block as it stands, masks included
    positions0: jax.Array,  # [R] index the block's first position occupies
    k_pool,
    v_pool,
    block_tables: jax.Array,  # [R, nb]
    cfg: ModelConfig,
    active: jax.Array | None = None,
    rope_offset: jax.Array | None = None,
    attn_impl: str = "auto",
    moe_load: bool = False,
    with_logits: bool = True,
) -> tuple:
    """One forward of a block-diffusion decoder over the paged pool: the B
    positions of each slot's block at `positions0 + j`, written to the pool
    and attended under the block-causal mask (every query sees the pool rows
    of all earlier blocks and the block's own B rows, both directions:
    `valid = arange(span) <= block_end`). Logits [R, B, V] at a position are
    of that position's own token (no shift). `verify_step_paged`'s body,
    with the block's horizon in place of each query's own; the Pallas read
    is named `paged_attention_block`.

    A denoise forward's rows (some inputs still `mask_token_id`) are dead:
    the commit forward over the clean block writes the same B rows again,
    for good, before any later block reads them. `with_logits=False` skips
    the head (a commit pass needs no logits). `moe_load`: also the forward's
    int32 load vector, `moe_mlp`'s [pairs, busiest expert's pairs] over
    layers and after it the cached rows the live slots' blocks read over
    layers."""
    if cfg.block_length_ <= 1:
        raise ValueError("diffusion_step_paged: the model has no block_length")
    return _qlen_step_paged(
        params, tokens, positions0, k_pool, v_pool, block_tables, cfg,
        active, rope_offset, attn_impl, block=True, moe_load=moe_load,
        with_logits=with_logits,
    )
