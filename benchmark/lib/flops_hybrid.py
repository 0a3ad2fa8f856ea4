"""Operations and bytes of a mixed-stack sparse decoder (K-EXAONE-class):
window and full attention layers in one stack, leading dense layers, then
sparse layers of which this chip holds a share of the experts, and a shared
expert. What `lib/flops.py` counts for a dense uniform stack and
`lib/flops_moe.py` for a sparse uniform one with every expert held.

Everything is computed from the model's shapes and the configuration's
per-layer lists; the peaks are `lib/flops.py`'s. The layer, as
`benchmark/reference/kexaone_ref.py` writes it: q/k/v/o projections with a
per-head q/k norm; a dense SwiGLU of `intermediate_size` in the first
`first_k_dense` layers; elsewhere a router of `num_experts_published`
columns with its bias, `num_experts` held SwiGLU experts of
`moe_intermediate_size`, and a shared expert of that width; an untied head.
A window layer caches at most `sliding_window` rows a request.
"""

from __future__ import annotations

from .flops import head_dim, peaks


def expert_width(cfg) -> int:
    return cfg.moe_intermediate_size


def expert_params(cfg) -> int:
    """One expert (routed or shared): gate, up and down."""
    return 3 * cfg.hidden_size * expert_width(cfg)


def attention_params(cfg) -> int:
    d, hd = cfg.hidden_size, head_dim(cfg)
    n_h, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
    return d * (n_h + 2 * n_kv) * hd + n_h * hd * d + 2 * hd  # q/k norms per head


def published_experts(cfg) -> int:
    return cfg.num_experts_published or cfg.num_experts


def layer_kinds(cfg) -> dict:
    """How many layers of each kind: {"dense", "sparse", "full", "window"}."""
    L = cfg.num_hidden_layers
    window = sum(1 for t in cfg.layer_types if t == "sliding_attention")
    dense = min(cfg.first_k_dense, L)
    return {"dense": dense, "sparse": L - dense, "window": window, "full": L - window}


def sparse_layer_params_outside_routed(cfg) -> int:
    """Router and its bias, and the shared expert."""
    shared = cfg.shared_expert_intermediate_size // expert_width(cfg)
    return cfg.hidden_size * published_experts(cfg) + published_experts(cfg) \
        + shared * expert_params(cfg)


def param_count(cfg) -> int:
    """Every leaf of the program's tree for this model."""
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    every = attention_params(cfg) + 2 * d  # two norms
    dense = 3 * d * cfg.intermediate_size
    sparse = sparse_layer_params_outside_routed(cfg) + cfg.num_experts * expert_params(cfg)
    return (cfg.num_hidden_layers * every + kinds["dense"] * dense + kinds["sparse"] * sparse
            + d + 2 * cfg.vocab_size * d)


def kv_row_bytes(cfg, kv_itemsize: int = 2) -> int:
    """One cached token of ONE layer: k and v, every kv head."""
    return 2 * cfg.num_key_value_heads * head_dim(cfg) * kv_itemsize


def held_pairs(cfg, running: float) -> float:
    """Token-expert pairs of a step whose expert is held here, in expectation
    under an even router: the held share of running x k."""
    return running * cfg.num_experts_per_tok * cfg.num_experts / published_experts(cfg)


def experts_touched(cfg, running: float) -> float:
    """Held experts a token step must read in each sparse layer: each once
    if the step's pairs can touch it, so no more than the pairs that land
    here (`held_pairs`, at least one)."""
    return min(float(cfg.num_experts), max(held_pairs(cfg, running), 1.0))


def forward_flops_per_token(cfg, avg_context: float) -> float:
    """Forward matmul FLOPs one token costs THIS chip: projections, scores
    and values over its context (a window layer: at most the window), the
    dense MLP, the router, the shared expert, its held experts' share of the
    k routed experts, and the head."""
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    n_h, hd = cfg.num_attention_heads, head_dim(cfg)
    proj = 2 * attention_params(cfg)
    attn = 4 * n_h * hd * (kinds["full"] * avg_context
                           + kinds["window"] * min(avg_context, cfg.sliding_window))
    dense = kinds["dense"] * 6 * d * cfg.intermediate_size
    routed = held_pairs(cfg, 1.0) * 2 * expert_params(cfg)
    sparse = kinds["sparse"] * (2 * sparse_layer_params_outside_routed(cfg) + routed)
    return cfg.num_hidden_layers * proj + attn + dense + sparse + 2 * d * cfg.vocab_size


def decode_step_needed_seconds(cfg, running: float, live_tokens: float,
                               device_kind: str, weight_itemsize: int = 2,
                               kv_itemsize: int = 2) -> dict:
    """Least time one token step of a decode batch can take on the chip.

    Bytes: every attention, norm, dense-MLP, router, shared-expert and head
    weight once; each sparse layer's held experts once for every expert the
    step's pairs can touch (`experts_touched`); the input lookup's `running`
    embedding rows; the full layers' cached rows of the live contexts once;
    at most `sliding_window` rows a request a window layer; one new row
    written per running request and layer. FLOPs: one forward token per
    running request at its context."""
    pk = peaks(device_kind)
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    outside = (cfg.num_hidden_layers * (attention_params(cfg) + 2 * d)
               + kinds["dense"] * 3 * d * cfg.intermediate_size
               + kinds["sparse"] * sparse_layer_params_outside_routed(cfg)
               + d + cfg.vocab_size * d)
    experts = kinds["sparse"] * experts_touched(cfg, running) * expert_params(cfg)
    row = kv_row_bytes(cfg, kv_itemsize)
    ctx = live_tokens / max(running, 1e-9)
    full_rows = kinds["full"] * live_tokens
    window_rows = kinds["window"] * running * min(ctx, cfg.sliding_window)
    nbytes = ((outside + experts) * weight_itemsize + (full_rows + window_rows) * row
              + running * (cfg.num_hidden_layers * row + d * weight_itemsize))
    flops = running * forward_flops_per_token(cfg, ctx)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "expert_bytes": experts * weight_itemsize,
            "full_rows_bytes": full_rows * row, "window_rows_bytes": window_rows * row,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def expert_matmuls_needed_seconds(cfg, running: float, device_kind: str,
                                  weight_itemsize: int = 2) -> dict:
    """Least time the three grouped matmuls over the HELD experts of one
    sparse layer take in one token step: the touched experts' weights once,
    the held pairs' rows in and out, 2 FLOPs a weight a pair."""
    pk = peaks(device_kind)
    pairs = held_pairs(cfg, running)
    m = expert_width(cfg)
    weights = experts_touched(cfg, running) * expert_params(cfg)
    rows = pairs * (2 * cfg.hidden_size + 4 * m)  # x in, y out; gate/up out, h in
    nbytes = (weights + rows) * weight_itemsize
    flops = pairs * 2 * expert_params(cfg)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}
