"""Operations and bytes of a Kimi-Linear-class decoder: Kimi Delta Attention
layers (a delta rule whose decay is a vector over a head's key lanes: a
float32 state a sequence) with a latent-attention layer among every few that
takes no positional encoding and a full-rank query, a leading dense layer,
then sparse layers of which this chip holds a share of the experts, and an
ungated shared expert. What `lib/flops_linear.py` counts for Gated DeltaNet
beside paged K and V rows and `lib/flops_latent.py` for latent attention in
every layer.

Everything is computed from the model's shapes and COUNTS the caller took
from the engine's counters (live state updates, live latent rows, held pairs,
held experts touched: no expectation under an even router); the peaks are
`lib/flops.py`'s. The layer, as `benchmark/reference/kimi_linear_ref.py`
writes it. The decode step runs the latent layers ABSORBED: a live cached row
is read once (its `kv_lora_rank + qk_rope_head_dim` lanes, 1,152 B in bf16:
the pool's pad to 640 lanes is not needed work) and costs each of the heads a
score over the whole row and a weighted sum over its latent lanes; at 32 heads
that is 69,632 FLOPs a row, 60 FLOP/B, far under the v5e's ridge of 240: the
bytes decide.
"""

from __future__ import annotations

from .flops import peaks
# what a latent row and a held expert cost is DeepSeek-V2's arithmetic at
# this model's widths: one definition
from .flops_latent import (  # noqa: F401 — re-exported: the kind and the tests read them here
    expert_matmuls_needed_seconds,
    expert_params,
    latent_attention_needed_seconds,
    latent_row_bytes,
    latent_row_flops,
    published_experts,
)

STATE_ITEMSIZE = 4  # the recurrent state is float32 whatever the model's dtype


def layer_kinds(cfg) -> dict:
    """How many layers of each kind: {"kda", "latent", "dense", "sparse"}."""
    kda = sum(1 for t in cfg.layer_types if t == "linear_attention")
    dense = min(cfg.first_k_dense, cfg.num_hidden_layers)
    return {"kda": kda, "latent": cfg.num_hidden_layers - kda, "dense": dense,
            "sparse": cfg.num_hidden_layers - dense}


def kda_dims(cfg) -> dict:
    n, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    return {"heads": n, "key": n * dk, "value": n * dv, "conv": 2 * n * dk + n * dv}


def kda_mixer_params(cfg) -> int:
    """q, k, v and their convolutions, the decay's low-rank gate with `A_log`
    and `dt_bias`, `b`, the output's low-rank gate, the head norm, `o`."""
    d, dims = cfg.hidden_size, kda_dims(cfg)
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    return (d * dims["conv"] + dims["conv"] * cfg.linear_conv_kernel_dim
            + d * dk + dk * dims["key"] + dims["heads"] + dims["key"]
            + d * dims["heads"] + d * dv + dv * dims["value"] + dv + dims["value"] * d)


def latent_mixer_params(cfg) -> int:
    """A full-rank query, `kv_a` with its norm, `kv_b`, `o`."""
    d, nH = cfg.hidden_size, cfg.num_attention_heads
    C, rope, nope = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.qk_nope_head_dim
    return (d * nH * (nope + rope) + d * (C + rope) + C
            + C * nH * (nope + cfg.v_head_dim) + nH * cfg.v_head_dim * d)


def sparse_layer_params_outside_routed(cfg) -> int:
    """Router, its selection bias and the shared expert."""
    return (cfg.hidden_size * published_experts(cfg) + published_experts(cfg)
            + 3 * cfg.hidden_size * cfg.shared_expert_intermediate_size)


def weights_outside_routed(cfg) -> int:
    """Every parameter a token step reads whatever the routing: both mixers,
    norms, the dense MLP, routers, shared experts, the final norm, the head."""
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    return (kinds["kda"] * kda_mixer_params(cfg) + kinds["latent"] * latent_mixer_params(cfg)
            + cfg.num_hidden_layers * 2 * d
            + kinds["dense"] * 3 * d * cfg.intermediate_size
            + kinds["sparse"] * sparse_layer_params_outside_routed(cfg)
            + d + cfg.vocab_size * d)


def param_count(cfg) -> int:
    """Every leaf of the program's tree for this model."""
    kinds = layer_kinds(cfg)
    return (weights_outside_routed(cfg) + cfg.vocab_size * cfg.hidden_size  # + the embedding
            + kinds["sparse"] * cfg.num_experts * expert_params(cfg))


def state_bytes(cfg) -> int:
    """One sequence's recurrent state of ONE KDA layer (float32)."""
    return (cfg.linear_num_value_heads * cfg.linear_key_head_dim
            * cfg.linear_value_head_dim * STATE_ITEMSIZE)


def conv_rows_bytes(cfg, kv_itemsize: int = 2) -> int:
    """One sequence's pre-convolution rows of ONE KDA layer."""
    return (cfg.linear_conv_kernel_dim - 1) * kda_dims(cfg)["conv"] * kv_itemsize


def state_update_bytes(cfg, kv_itemsize: int = 2) -> int:
    """What one live slot's update of ONE KDA layer moves: its state and its
    convolution rows, once in and once out (`SlotCache.state_update_nbytes`)."""
    return 2 * (state_bytes(cfg) + conv_rows_bytes(cfg, kv_itemsize))


def kda_step_needed_seconds(cfg, updates: float, device_kind: str) -> dict:
    """Least time `%kda_step` takes over `updates` live state updates (slots x
    KDA layers of whatever span the caller counts): the call's own bytes, each
    state once in and once out and a head's q, k, log-decay, v and o rows (the
    convolution rows are moved by the step's `conv_state`, not by this call)."""
    pk = peaks(device_kind)
    dims = kda_dims(cfg)
    rows = (3 * dims["key"] + 2 * dims["value"]) * STATE_ITEMSIZE
    nbytes = updates * (2 * state_bytes(cfg) + rows)
    flops = updates * 8 * state_bytes(cfg) / STATE_ITEMSIZE
    # the update runs on the vector unit in float32, not on the MXU: its
    # arithmetic is not held to the bf16 matmul peak, the bytes decide
    return {"seconds": nbytes / pk["hbm_bytes_per_s"], "bytes": nbytes, "flops": flops,
            "bound": "memory"}


def token_flops_outside_cache(cfg, pairs_per_token: float) -> float:
    """Forward matmul FLOPs one token costs THIS chip besides its cached rows
    and its state: both mixers' projections (the latent ones absorbed: every
    weight once), a KDA layer's state update (decay, read, write, read: 2
    FLOPs a cell each), the dense MLP, routers, shared experts, its pairs'
    held experts, and the head."""
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    return (kinds["kda"] * (2 * kda_mixer_params(cfg) + 8 * state_bytes(cfg) / STATE_ITEMSIZE)
            + kinds["latent"] * 2 * latent_mixer_params(cfg)
            + kinds["dense"] * 6 * d * cfg.intermediate_size
            + kinds["sparse"] * 2 * sparse_layer_params_outside_routed(cfg)
            + pairs_per_token * 2 * expert_params(cfg)
            + 2 * d * cfg.vocab_size)


def decode_step_needed_seconds(cfg, running: float, updates: float, latent_rows: float,
                               pairs: float, touched: float, device_kind: str,
                               weight_itemsize: int = 2, kv_itemsize: int = 2) -> dict:
    """Least time one token step of a decode batch can take on the chip.

    `updates`: live slots x KDA layers (`gdn_state_updates_total` a step);
    `latent_rows`: live cached rows the step's attention reads, over the
    latent layers (`kv_latent_rows_read_total` a step); `pairs`: token-expert
    pairs that land on held experts, over all sparse layers (`moe_pairs_total`
    a step); `touched`: held experts with at least one pair, over all sparse
    layers (`moe_group_experts_touched_total` a step: a count, a grouped
    matmul reads nothing of an empty group). Bytes: the weights outside the
    routed experts once; the touched experts once; every live update's state
    and convolution rows in and out; the live latent rows once; one new row a
    running request and latent layer; the input lookup's embedding rows.
    FLOPs: one forward token a running request, its pairs' experts, and the
    rows' scores and sums. The larger of the two at `lib/flops.py`'s peaks."""
    pk = peaks(device_kind)
    kinds = layer_kinds(cfg)
    experts = touched * expert_params(cfg)
    row = latent_row_bytes(cfg, kv_itemsize)
    state = updates * state_update_bytes(cfg, kv_itemsize)
    nbytes = ((weights_outside_routed(cfg) + experts) * weight_itemsize + state
              + latent_rows * row
              + running * (kinds["latent"] * row + cfg.hidden_size * weight_itemsize))
    flops = (running * token_flops_outside_cache(cfg, pairs / max(running, 1e-9))
             + latent_rows * latent_row_flops(cfg))
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "expert_bytes": experts * weight_itemsize, "state_bytes": state,
            "latent_rows_bytes": latent_rows * row,
            "weights_outside_routed_bytes": weights_outside_routed(cfg) * weight_itemsize,
            "bound": "memory" if t_bytes >= t_flops else "compute"}
