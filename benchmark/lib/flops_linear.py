"""Operations and bytes of a Qwen3-Next-class decoder: Gated DeltaNet layers
whose cache is a recurrent state a sequence, one gated full-attention layer
in every few, every layer sparse with a share of the experts held here and a
gated shared expert. What `lib/flops_hybrid.py` counts for a stack of window
and full attention layers.

Everything is computed from the model's shapes and the configuration's
per-layer list; the peaks are `lib/flops.py`'s. The layer, as
`benchmark/reference/qwen3next_ref.py` writes it. A linear layer's mixer:
one projection to [q | k | v | z], one to [b | a], a depthwise convolution
over [q | k | v], `dt_bias`, `A_log`, a gated norm over a value head's lanes,
an output projection. A full layer's: a doubled q projection (q and its
output gate), k, v, o and the per-head q/k norms. Outside the mixer: two
norms, a router of `num_experts_published` columns, `num_experts` held
SwiGLU experts of `moe_intermediate_size`, a shared expert with its
one-column gate. An untied head.
"""

from __future__ import annotations

from .flops import head_dim, peaks

STATE_ITEMSIZE = 4  # the recurrent state is float32 whatever the model's dtype


def expert_params(cfg) -> int:
    """One routed expert: gate, up and down."""
    return 3 * cfg.hidden_size * cfg.moe_intermediate_size


def published_experts(cfg) -> int:
    return cfg.num_experts_published or cfg.num_experts


def layer_kinds(cfg) -> dict:
    """How many layers of each kind: {"linear", "full", "sparse"}."""
    linear = sum(1 for t in cfg.layer_types if t == "linear_attention")
    return {"linear": linear, "full": cfg.num_hidden_layers - linear,
            "sparse": cfg.num_hidden_layers}


def linear_dims(cfg) -> dict:
    key = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    value = cfg.linear_num_value_heads * cfg.linear_value_head_dim
    return {"key": key, "value": value, "conv": 2 * key + value}


def linear_mixer_params(cfg) -> int:
    d, dims, hv = cfg.hidden_size, linear_dims(cfg), cfg.linear_num_value_heads
    return (d * (2 * dims["key"] + 2 * dims["value"]) + d * 2 * hv
            + dims["conv"] * cfg.linear_conv_kernel_dim + 2 * hv
            + cfg.linear_value_head_dim + dims["value"] * d)


def full_mixer_params(cfg) -> int:
    d, hd = cfg.hidden_size, head_dim(cfg)
    n_h, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
    return d * (2 * n_h + 2 * n_kv) * hd + n_h * hd * d + 2 * hd


def layer_params_outside_mixer_and_routed(cfg) -> int:
    """Two norms, the router, the shared expert and its gate."""
    d = cfg.hidden_size
    return (2 * d + d * published_experts(cfg)
            + 3 * d * cfg.shared_expert_intermediate_size + d)


def param_count(cfg) -> int:
    """Every leaf of the program's tree for this model."""
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    every = layer_params_outside_mixer_and_routed(cfg) + cfg.num_experts * expert_params(cfg)
    return (cfg.num_hidden_layers * every + kinds["linear"] * linear_mixer_params(cfg)
            + kinds["full"] * full_mixer_params(cfg) + d + 2 * cfg.vocab_size * d)


def kv_row_bytes(cfg, kv_itemsize: int = 2) -> int:
    """One cached token of ONE full layer: k and v, every kv head."""
    return 2 * cfg.num_key_value_heads * head_dim(cfg) * kv_itemsize


def state_bytes(cfg) -> int:
    """One sequence's recurrent state of ONE linear layer (float32)."""
    return (cfg.linear_num_value_heads * cfg.linear_key_head_dim
            * cfg.linear_value_head_dim * STATE_ITEMSIZE)


def conv_rows_bytes(cfg, kv_itemsize: int = 2) -> int:
    """One sequence's pre-convolution rows of ONE linear layer."""
    return (cfg.linear_conv_kernel_dim - 1) * linear_dims(cfg)["conv"] * kv_itemsize


def held_pairs(cfg, running: float) -> float:
    """Token-expert pairs of a step whose expert is held here, in expectation
    under an even router: the held share of running x k."""
    return running * cfg.num_experts_per_tok * cfg.num_experts / published_experts(cfg)


def experts_touched(cfg, running: float) -> float:
    """Held experts a token step's pairs touch in one layer, in expectation
    under an even router: with about as many pairs as experts (1.1 an expert
    at 57 running) a third of the experts get none, and a grouped matmul
    reads nothing of an empty group."""
    e = float(cfg.num_experts)
    return e * (1.0 - (1.0 - 1.0 / e) ** held_pairs(cfg, running))


def forward_flops_per_token(cfg, avg_context: float) -> float:
    """Forward FLOPs one token costs THIS chip: the mixers' projections, the
    full layers' scores and values over the context, a linear layer's state
    update (decay, read, write, read: 2 FLOPs a cell each), router, shared
    expert, its held experts' share of the k routed experts, and the head."""
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    n_h, hd = cfg.num_attention_heads, head_dim(cfg)
    attn = kinds["full"] * (2 * full_mixer_params(cfg) + 4 * n_h * hd * avg_context)
    linear = kinds["linear"] * (2 * linear_mixer_params(cfg)
                                + 8 * state_bytes(cfg) / STATE_ITEMSIZE)
    routed = held_pairs(cfg, 1.0) * 2 * expert_params(cfg)
    sparse = kinds["sparse"] * (2 * layer_params_outside_mixer_and_routed(cfg) + routed)
    return attn + linear + sparse + 2 * d * cfg.vocab_size


def decode_step_needed_seconds(cfg, running: float, live_tokens: float,
                               device_kind: str, weight_itemsize: int = 2,
                               kv_itemsize: int = 2) -> dict:
    """Least time one token step of a decode batch can take on the chip.

    Bytes: every weight outside the routed experts once (mixers, norms,
    routers, shared experts, head); each layer's held experts once for every
    expert the step's pairs touch (`experts_touched`); the input lookup's
    `running` embedding rows; every live slot's state and convolution rows
    of every linear layer once in and once out; the full layers' cached rows
    of the live contexts once; one new row written per running request and
    full layer. FLOPs: one forward token per running request at its context."""
    pk = peaks(device_kind)
    d, kinds = cfg.hidden_size, layer_kinds(cfg)
    outside = (cfg.num_hidden_layers * layer_params_outside_mixer_and_routed(cfg)
               + kinds["linear"] * linear_mixer_params(cfg)
               + kinds["full"] * full_mixer_params(cfg) + d + cfg.vocab_size * d)
    experts = kinds["sparse"] * experts_touched(cfg, running) * expert_params(cfg)
    row = kv_row_bytes(cfg, kv_itemsize)
    state = kinds["linear"] * running * 2 * (state_bytes(cfg) + conv_rows_bytes(cfg, kv_itemsize))
    full_rows = kinds["full"] * live_tokens
    nbytes = ((outside + experts) * weight_itemsize + state + full_rows * row
              + running * (kinds["full"] * row + d * weight_itemsize))
    ctx = live_tokens / max(running, 1e-9)
    flops = running * forward_flops_per_token(cfg, ctx)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "expert_bytes": experts * weight_itemsize, "state_bytes": state,
            "full_rows_bytes": full_rows * row,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def gdn_step_needed_seconds(cfg, running: float, device_kind: str) -> dict:
    """Least time the state update of ONE linear layer takes in one token
    step: the live slots' states once in and once out (the kernel's q, k, v,
    decay and beta rows are a thousandth of that and are left out)."""
    pk = peaks(device_kind)
    nbytes = running * 2 * state_bytes(cfg)
    flops = running * 8 * state_bytes(cfg) / STATE_ITEMSIZE
    # the update runs on the vector unit in float32, not on the MXU: its
    # arithmetic is not held to the bf16 matmul peak, the bytes decide
    return {"seconds": nbytes / pk["hbm_bytes_per_s"], "bytes": nbytes, "flops": flops,
            "bound": "memory"}


def expert_matmuls_needed_seconds(cfg, running: float, device_kind: str,
                                  weight_itemsize: int = 2) -> dict:
    """Least time the three grouped matmuls over the HELD experts of one
    layer take in one token step: the touched experts' weights once, the held
    pairs' rows in and out, 2 FLOPs a weight a pair."""
    pk = peaks(device_kind)
    pairs = held_pairs(cfg, running)
    m = cfg.moe_intermediate_size
    weights = experts_touched(cfg, running) * expert_params(cfg)
    rows = pairs * (2 * cfg.hidden_size + 4 * m)  # x in, y out; gate/up out, h in
    nbytes = (weights + rows) * weight_itemsize
    flops = pairs * 2 * expert_params(cfg)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}
