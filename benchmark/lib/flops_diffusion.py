"""Operations and bytes of a block-diffusion sparse-expert decoder (SDAR-MoE
class): what `flops_moe.py` counts for a step of one token a slot, for a
FORWARD of `block_length` positions a slot. A chunk of such a model is a
number of forwards, not of token steps: a block of B tokens takes its denoise
forwards and one commit forward, each over all B positions.

Everything is computed from the model's shapes; the peaks are `lib/flops.py`'s
(`peaks`, by `device_kind`). The layer, as `benchmark/reference/sdar_ref.py`
writes it: q/k/v/o projections with a per-head q/k norm, a router of
`num_experts` columns, `num_experts` SwiGLU experts of `moe_intermediate_size`
of which each position runs `num_experts_per_tok`, an untied head. No biases.
"""

from __future__ import annotations

from .flops import head_dim, kv_bytes_per_token, peaks


def expert_params(cfg) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def layer_params_outside_experts(cfg) -> int:
    d, hd = cfg.hidden_size, head_dim(cfg)
    n_h, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
    attn = d * (n_h + 2 * n_kv) * hd + n_h * hd * d
    # q and k norms over a head's lanes, two layer norms, the router
    return attn + 2 * hd + 2 * d + d * cfg.num_experts


def param_count(cfg) -> int:
    """Every leaf of the program's tree for this model: embedding, per layer
    attention + q/k norms + two norms + router + all experts, final norm,
    untied head."""
    d = cfg.hidden_size
    layer = layer_params_outside_experts(cfg) + cfg.num_experts * expert_params(cfg)
    return cfg.num_hidden_layers * layer + d + 2 * cfg.vocab_size * d


def forward_flops_per_position(cfg, context: float, head: float = 1.0) -> float:
    """Forward matmul FLOPs of one position of a block (2*m*n per output
    element): q/k/v and output projections, scores and values over `context`
    visible rows, the router, the three matmuls of each of its experts, and
    `head` of the head's (the share of positions whose logits are needed:
    none on a commit pass). The embedding lookup is a gather."""
    d = cfg.hidden_size
    n_h, n_kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, head_dim(cfg)
    qkv = 2 * d * (n_h + 2 * n_kv) * hd
    out = 2 * n_h * hd * d
    attn = 4 * context * n_h * hd
    router = 2 * d * cfg.num_experts
    experts = cfg.num_experts_per_tok * 2 * expert_params(cfg)
    return (cfg.num_hidden_layers * (qkv + out + attn + router + experts)
            + head * 2 * d * cfg.vocab_size)


def experts_touched(cfg, positions: float) -> float:
    """Experts a forward of `positions` rows reads in each layer, in
    expectation under an even router: each row picks `num_experts_per_tok` of
    `num_experts`, so an expert goes untouched with (1 - k/E)^positions. (An
    expectation: the engine's counter vector has the pairs and the busiest
    expert's pairs, not the number of distinct experts.)"""
    e, k = cfg.num_experts, cfg.num_experts_per_tok
    return e * (1.0 - (1.0 - k / e) ** max(positions, 0.0))


def forward_needed_seconds(cfg, running: float, live_tokens: float, device_kind: str,
                           denoise_share: float = 1.0, weight_itemsize: int = 2,
                           kv_itemsize: int = 2) -> dict:
    """Least time one forward of a block-diffusion chunk can take on the chip:
    `running` slots, each its block of `block_length` positions.

    Bytes: every attention, norm and router weight once; each layer's expert
    weights once for every expert the forward's rows touch (`experts_touched`);
    the head's weights once if any slot is denoising (`denoise_share` > 0: a
    commit pass needs no logits); the input lookup's embedding rows; the cached
    rows of the live contexts ONCE for all B queries of a block; B new rows
    written a slot. FLOPs: `running` x B positions, each at its slot's context
    plus the block, the head's for the `denoise_share` of them."""
    pk = peaks(device_kind)
    B = int(cfg.block_length)
    d, layers = cfg.hidden_size, cfg.num_hidden_layers
    rows = running * B
    head = cfg.vocab_size * d if denoise_share > 0 else 0
    dense = layers * layer_params_outside_experts(cfg) + d + head
    experts = layers * experts_touched(cfg, rows) * expert_params(cfg)
    kv = kv_bytes_per_token(cfg, kv_itemsize)
    nbytes = ((dense + experts) * weight_itemsize + live_tokens * kv
              + rows * (kv + d * weight_itemsize))
    ctx = live_tokens / max(running, 1e-9) + B
    flops = rows * forward_flops_per_position(cfg, ctx, denoise_share)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "expert_bytes": experts * weight_itemsize,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def expert_matmuls_needed_seconds(cfg, running: float, device_kind: str,
                                  weight_itemsize: int = 2) -> dict:
    """Least time the three grouped expert matmuls of ONE layer take in one
    forward: the touched experts' weights once, the forward's pair rows in and
    out (hidden wide; the expert-wide intermediate is read and written once
    each), against 2 FLOPs a weight a pair."""
    pk = peaks(device_kind)
    rows = running * int(cfg.block_length)
    pairs = rows * cfg.num_experts_per_tok
    m = cfg.moe_intermediate_size
    weights = experts_touched(cfg, rows) * expert_params(cfg)
    moved = pairs * (2 * cfg.hidden_size + 4 * m)  # x in, y out; gate/up out, h in
    nbytes = (weights + moved) * weight_itemsize
    flops = pairs * 2 * expert_params(cfg)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}
