"""Operations and bytes of a sparse-expert (OLMoE-class) decoder: what
`lib/flops.py` counts for a dense one, where it would count one expert of 64.

Everything is computed from the model's shapes; the peaks are `lib/flops.py`'s
(`peaks`, by `device_kind`). The layer, as `benchmark/reference/olmoe_ref.py`
writes it: q/k/v/o projections with a full-width q/k norm, a router of
`num_experts` columns, `num_experts` SwiGLU experts of `expert_width` of which
each token runs `num_experts_per_tok`, an untied head. No biases.
"""

from __future__ import annotations

from .flops import head_dim, kv_bytes_per_token, peaks


def expert_width(cfg) -> int:
    return getattr(cfg, "moe_intermediate_size", None) or cfg.intermediate_size


def expert_params(cfg) -> int:
    """One expert: gate, up and down."""
    return 3 * cfg.hidden_size * expert_width(cfg)


def layer_params_outside_experts(cfg) -> int:
    d, hd = cfg.hidden_size, head_dim(cfg)
    n_h, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
    attn = d * (n_h + 2 * n_kv) * hd + n_h * hd * d
    qk_norm = (n_h + n_kv) * hd  # over the whole q and k projections
    return attn + qk_norm + 2 * d + d * cfg.num_experts  # two norms, the router


def param_count(cfg) -> int:
    """Every leaf of the program's tree for this model: embedding, per layer
    attention + q/k norms + two norms + router + all experts, final norm,
    untied head."""
    d = cfg.hidden_size
    layer = layer_params_outside_experts(cfg) + cfg.num_experts * expert_params(cfg)
    return cfg.num_hidden_layers * layer + d + 2 * cfg.vocab_size * d


def active_param_count(cfg) -> int:
    """What one token multiplies with: as `param_count`, with
    `num_experts_per_tok` experts a layer and the embedding as a lookup."""
    d = cfg.hidden_size
    layer = layer_params_outside_experts(cfg) + cfg.num_experts_per_tok * expert_params(cfg)
    return cfg.num_hidden_layers * layer + d + cfg.vocab_size * d


def forward_flops_per_token(cfg, avg_context: float) -> float:
    """Forward matmul FLOPs per token (2*m*n per output element): q/k/v and
    output projections, scores and values over `avg_context` kv positions,
    the router, the three matmuls of each of the token's experts, the head.
    The embedding lookup is a gather and is not counted."""
    d = cfg.hidden_size
    n_h, n_kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, head_dim(cfg)
    qkv = 2 * d * (n_h + 2 * n_kv) * hd
    out = 2 * n_h * hd * d
    attn = 4 * avg_context * n_h * hd
    router = 2 * d * cfg.num_experts
    experts = cfg.num_experts_per_tok * 2 * expert_params(cfg)
    return cfg.num_hidden_layers * (qkv + out + attn + router + experts) + 2 * d * cfg.vocab_size


def experts_touched(cfg, running: float) -> float:
    """Experts a token step of `running` requests must read in each layer:
    its running x k pairs can reach that many distinct experts and no more
    than all of them."""
    return min(float(cfg.num_experts), running * cfg.num_experts_per_tok)


def decode_step_needed_seconds(cfg, running: float, live_tokens: float,
                               device_kind: str, weight_itemsize: int = 2,
                               kv_itemsize: int = 2) -> dict:
    """Least time one token step of a decode batch can take on the chip.

    Bytes: every attention, norm, router and head weight once; each layer's
    expert weights once for every expert the step's pairs can touch
    (`experts_touched`); the input lookup's `running` embedding rows; the
    cached rows of the live contexts once; one new row written per running
    request. FLOPs: one forward token per running request at its context."""
    pk = peaks(device_kind)
    d, layers = cfg.hidden_size, cfg.num_hidden_layers
    dense = layers * layer_params_outside_experts(cfg) + d + cfg.vocab_size * d
    experts = layers * experts_touched(cfg, running) * expert_params(cfg)
    kv = kv_bytes_per_token(cfg, kv_itemsize)
    nbytes = ((dense + experts) * weight_itemsize + live_tokens * kv
              + running * (kv + d * weight_itemsize))
    ctx = live_tokens / max(running, 1e-9)
    flops = running * forward_flops_per_token(cfg, ctx)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "expert_bytes": experts * weight_itemsize,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def expert_matmuls_needed_seconds(cfg, running: float, device_kind: str,
                                  weight_itemsize: int = 2) -> dict:
    """Least time the three grouped expert matmuls of ONE layer take in one
    token step: the touched experts' weights once, the step's pair rows in
    and out (hidden wide; the `expert_width`-wide intermediate is read and
    written once each), against 2 FLOPs a weight a pair."""
    pk = peaks(device_kind)
    pairs = running * cfg.num_experts_per_tok
    m = expert_width(cfg)
    weights = experts_touched(cfg, running) * expert_params(cfg)
    rows = pairs * (2 * cfg.hidden_size + 4 * m)  # x in, y out; gate/up out, h in
    nbytes = (weights + rows) * weight_itemsize
    flops = pairs * 2 * expert_params(cfg)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}
