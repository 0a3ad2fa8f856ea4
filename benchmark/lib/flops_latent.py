"""Operations and bytes of a latent-attention sparse decoder (DeepSeek-V2
class): a low-rank query, ONE cached row `[c_kv | k_pe]` a token and layer,
leading dense layers, then sparse layers of which this chip holds whole
routing groups of the experts, and ungated shared experts. What
`lib/flops.py` counts for a dense uniform stack and `lib/flops_hybrid.py` for
a mixed stack with K and V rows.

Everything is computed from the model's shapes; the peaks are
`lib/flops.py`'s. The layer, as `benchmark/reference/deepseek_v2_ref.py`
writes it. The decode step runs the ABSORBED form: a head's query is carried
into the row's space, so a live cached row is read once (its
`kv_lora_rank + qk_rope_head_dim` lanes) and costs each of the `nH` heads a
score over the whole row and a weighted sum over its first `kv_lora_rank`
lanes: 2 x nH x (row + kv_lora_rank) FLOPs. At the published widths that is
278,528 FLOPs for 1,152 B, 242 FLOP/B against the v5e's ridge of 240: the
LEAST time is whichever of the two is larger. (The pool stores a row at 640
lanes, 1,280 B: the pad is not needed work and is not counted here.)
"""

from __future__ import annotations

from .flops import peaks


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def published_experts(cfg) -> int:
    return cfg.num_experts_published or cfg.num_experts


def attention_leaves(cfg) -> dict:
    """The latent attention's leaves, by name, in parameters."""
    H, nH = cfg.hidden_size, cfg.num_attention_heads
    C, rope, nope = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.qk_nope_head_dim
    return {
        "q_a_kernel": H * cfg.q_lora_rank, "q_a_norm": cfg.q_lora_rank,
        "q_b_kernel": cfg.q_lora_rank * nH * (nope + rope),
        "kv_a_kernel": H * (C + rope), "kv_a_norm": C,
        "kv_b_kernel": C * nH * (nope + cfg.v_head_dim),
        "o_kernel": nH * cfg.v_head_dim * H,
    }


def attention_params(cfg) -> int:
    return sum(attention_leaves(cfg).values())


def layer_kinds(cfg) -> dict:
    dense = min(cfg.first_k_dense, cfg.num_hidden_layers)
    return {"dense": dense, "sparse": cfg.num_hidden_layers - dense}


def sparse_layer_params_outside_routed(cfg) -> int:
    """Router (no bias) and the shared experts."""
    return (cfg.hidden_size * published_experts(cfg)
            + 3 * cfg.hidden_size * cfg.shared_expert_intermediate_size)


def weights_outside_routed(cfg) -> int:
    """Every parameter a token step reads whatever the routing: attention,
    norms, the dense MLP, routers, shared experts, the final norm, the head."""
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    return (cfg.num_hidden_layers * (attention_params(cfg) + 2 * d)
            + kinds["dense"] * 3 * d * cfg.intermediate_size
            + kinds["sparse"] * sparse_layer_params_outside_routed(cfg)
            + d + cfg.vocab_size * d)


def param_count(cfg) -> int:
    """Every leaf of the program's tree for this model."""
    kinds = layer_kinds(cfg)
    return (weights_outside_routed(cfg) + cfg.vocab_size * cfg.hidden_size  # + the embedding
            + kinds["sparse"] * cfg.num_experts * expert_params(cfg))


def latent_row_bytes(cfg, kv_itemsize: int = 2) -> int:
    """One cached token of ONE layer, as much of it as is needed."""
    return (cfg.kv_lora_rank + cfg.qk_rope_head_dim) * kv_itemsize


def latent_row_flops(cfg) -> int:
    """One live cached row in the absorbed form: every head's score over the
    whole row and its weighted sum over the latent lanes."""
    return 2 * cfg.num_attention_heads * (
        cfg.kv_lora_rank + cfg.qk_rope_head_dim + cfg.kv_lora_rank)


def latent_attention_needed_seconds(cfg, rows: float, device_kind: str,
                                    kv_itemsize: int = 2) -> dict:
    """Least time the latent kernel takes over `rows` live cached rows (all
    layers and slots of whatever span the caller counts): each row's bytes
    once, or its FLOPs, the larger."""
    pk = peaks(device_kind)
    nbytes, flops = rows * latent_row_bytes(cfg, kv_itemsize), rows * latent_row_flops(cfg)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def token_flops_outside_attention_rows(cfg, pairs_per_token: float) -> float:
    """Forward matmul FLOPs one token costs THIS chip besides its cached
    rows: the absorbed projections (every attention weight once, both halves
    of `kv_b_kernel`), the dense MLP, routers, shared experts, its pairs'
    held experts, and the head."""
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    return (cfg.num_hidden_layers * 2 * attention_params(cfg)
            + kinds["dense"] * 6 * d * cfg.intermediate_size
            + kinds["sparse"] * 2 * sparse_layer_params_outside_routed(cfg)
            + pairs_per_token * 2 * expert_params(cfg)
            + 2 * d * cfg.vocab_size)


def decode_step_needed_seconds(cfg, running: float, latent_rows: float, pairs: float,
                               touched: float, device_kind: str, weight_itemsize: int = 2,
                               kv_itemsize: int = 2) -> dict:
    """Least time one token step of a decode batch can take on the chip.

    `latent_rows`: live cached rows the step's attention reads, over all
    latent layers (the engine's `kv_latent_rows_read_total` a step);
    `pairs`: token-expert pairs that land on held experts, over all sparse
    layers (`moe_pairs_total` a step); `touched`: held experts with at least
    one pair, over all sparse layers (`moe_group_experts_touched_total` a
    step: a count, a grouped matmul reads nothing of an empty group). Bytes:
    the weights outside the routed experts once; the touched experts once;
    the live rows once; one new row a running request and layer; the input
    lookup's embedding rows. FLOPs: one forward token a running request,
    its pairs' experts, and the rows' scores and sums. The larger of the two
    at `lib/flops.py`'s peaks."""
    pk = peaks(device_kind)
    experts = touched * expert_params(cfg)
    row = latent_row_bytes(cfg, kv_itemsize)
    nbytes = ((weights_outside_routed(cfg) + experts) * weight_itemsize + latent_rows * row
              + running * (cfg.num_hidden_layers * row + cfg.hidden_size * weight_itemsize))
    flops = (running * token_flops_outside_attention_rows(cfg, pairs / max(running, 1e-9))
             + latent_rows * latent_row_flops(cfg))
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "expert_bytes": experts * weight_itemsize, "latent_rows_bytes": latent_rows * row,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def expert_matmuls_needed_seconds(cfg, pairs: float, touched: float, device_kind: str,
                                  weight_itemsize: int = 2) -> dict:
    """Least time the three grouped matmuls over the HELD experts of one
    sparse layer take in one token step with `pairs` pairs landing on
    `touched` of them: the touched experts' weights once, the pairs' rows in
    and out, 2 FLOPs a weight a pair."""
    pk = peaks(device_kind)
    m = cfg.moe_intermediate_size
    weights = touched * expert_params(cfg)
    rows = pairs * (2 * cfg.hidden_size + 4 * m)  # x in, y out; gate/up out, h in
    nbytes = (weights + rows) * weight_itemsize
    flops = pairs * 2 * expert_params(cfg)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}
