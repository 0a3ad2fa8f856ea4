"""Operations and bytes of a Jamba-class decoder: Mamba-1 state-space layers
(a diagonal recurrence over `ssm_state_size` lanes a channel: a float32 state
a sequence, constant in the context) with an attention layer among every few
(multi-query, no positional encoding), a dense SwiGLU MLP in every layer, the
head tied to the embedding.

Everything is computed from the model's shapes and COUNTS the caller took
from the engine's counters (live state updates, live attention rows: no
expectation); the peaks are `lib/flops.py`'s. The layer, as
`benchmark/reference/jamba_ref.py` writes it. A state update is arithmetic on
the vector unit and an `exp` an element, no matrix product: 7 FLOPs and one
`exp` an element of state against 8 bytes of it moved, far under any peak of
the chip's; the bytes decide.
"""

from __future__ import annotations

from .flops import peaks

STATE_ITEMSIZE = 4  # the recurrent state is float32 whatever the model's dtype
STATE_FLOPS_PER_ELEMENT = 7  # dt*A, (exp), *h, dt*u, *B, +, *C, + (the sum over lanes)


def layer_kinds(cfg) -> dict:
    """How many layers of each kind: {"ssm", "attention"}."""
    ssm = sum(1 for t in cfg.layer_types if t == "mamba")
    return {"ssm": ssm, "attention": cfg.num_hidden_layers - ssm}


def ssm_dims(cfg) -> dict:
    return {"inner": cfg.ssm_expand * cfg.hidden_size, "state": cfg.ssm_state_size,
            "dt_rank": cfg.ssm_dt_rank, "conv": cfg.linear_conv_kernel_dim}


def ssm_mixer_params(cfg) -> int:
    """`in_proj`, the convolution and its bias, `x_proj`, the three norms,
    `dt_proj` and its bias, `A_log`, `D`, `out_proj`."""
    d, s = cfg.hidden_size, ssm_dims(cfg)
    Di, N, Rk = s["inner"], s["state"], s["dt_rank"]
    return (d * 2 * Di + Di * s["conv"] + (Di if cfg.ssm_conv_bias else 0)
            + Di * (Rk + 2 * N) + Rk + 2 * N + Rk * Di + Di + Di * N + Di + Di * d)


def attention_params(cfg) -> int:
    d, hd = cfg.hidden_size, cfg.hidden_size // cfg.num_attention_heads
    return 2 * d * cfg.num_attention_heads * hd + 2 * d * cfg.num_key_value_heads * hd


def mlp_params(cfg) -> int:
    return 3 * cfg.hidden_size * cfg.intermediate_size


def param_count(cfg) -> int:
    """Every leaf of the program's tree for this model (the head is the
    embedding, once)."""
    kinds = layer_kinds(cfg)
    return (cfg.vocab_size * cfg.hidden_size + kinds["ssm"] * ssm_mixer_params(cfg)
            + kinds["attention"] * attention_params(cfg)
            + cfg.num_hidden_layers * (mlp_params(cfg) + 2 * cfg.hidden_size)
            + cfg.hidden_size)


def state_bytes(cfg) -> int:
    """One sequence's recurrent state of ONE state-space layer (float32)."""
    s = ssm_dims(cfg)
    return s["state"] * s["inner"] * STATE_ITEMSIZE


def conv_rows_bytes(cfg, kv_itemsize: int = 2) -> int:
    """One sequence's pre-convolution rows of ONE state-space layer."""
    s = ssm_dims(cfg)
    return (s["conv"] - 1) * s["inner"] * kv_itemsize


def state_update_bytes(cfg, kv_itemsize: int = 2) -> int:
    """What one live slot's update of ONE state-space layer moves: its state
    and its convolution rows, once in and once out
    (`SlotCache.state_update_nbytes`)."""
    return 2 * (state_bytes(cfg) + conv_rows_bytes(cfg, kv_itemsize))


def attention_row_bytes(cfg, kv_itemsize: int = 2) -> int:
    """A cached token's K and V rows of ONE attention layer."""
    hd = cfg.hidden_size // cfg.num_attention_heads
    return 2 * cfg.num_key_value_heads * hd * kv_itemsize


def ssm_step_needed_seconds(cfg, updates: float, device_kind: str, calls: float = 0.0) -> dict:
    """Least time `%ssm_step` takes over `updates` live state updates (slots x
    state-space layers of whatever span the caller counts) in `calls` calls:
    the call's own bytes, each live state once in and once out, a slot's rows
    of `dt`, `u` and `y` (float32, the channels) and of `B` and `C` (the state
    lanes), and `A` and `D` once a call (the convolution rows are moved by the
    step's `conv_state`, not by this call)."""
    pk = peaks(device_kind)
    s = ssm_dims(cfg)
    rows = (3 * s["inner"] + 2 * s["state"]) * STATE_ITEMSIZE
    once = (s["state"] * s["inner"] + s["inner"]) * STATE_ITEMSIZE
    nbytes = updates * (2 * state_bytes(cfg) + rows) + calls * once
    elements = updates * s["state"] * s["inner"]
    # vector-unit float32 arithmetic and an exp an element: not held to the
    # bf16 matmul peak; the bytes decide
    return {"seconds": nbytes / pk["hbm_bytes_per_s"], "bytes": nbytes,
            "flops": STATE_FLOPS_PER_ELEMENT * elements, "exps": elements, "bound": "memory"}


def decode_step_needed_seconds(cfg, running: float, updates: float, attention_rows: float,
                               device_kind: str, weight_itemsize: int = 2,
                               kv_itemsize: int = 2) -> dict:
    """Least time one token step of a decode batch can take on the chip.

    `updates`: live slots x state-space layers (`gdn_state_updates_total` a
    step); `attention_rows`: live cached rows the step's attention reads, over
    the attention layers (`kv_full_rows_read_total` a step). Bytes: every
    weight once (the tied matrix once, as the head); every live update's
    state and convolution rows in and out; the live attention rows once; one
    new row a running request and attention layer; the input lookup's
    embedding rows. FLOPs: one forward token a running request (every weight
    twice), the rows' scores and sums, the state updates' arithmetic. The
    larger of the two at `lib/flops.py`'s peaks."""
    pk = peaks(device_kind)
    kinds = layer_kinds(cfg)
    hd = cfg.hidden_size // cfg.num_attention_heads
    row = attention_row_bytes(cfg, kv_itemsize)
    state = updates * state_update_bytes(cfg, kv_itemsize)
    weights = param_count(cfg) * weight_itemsize
    nbytes = (weights + state + attention_rows * row
              + running * (kinds["attention"] * row + cfg.hidden_size * weight_itemsize))
    flops = (running * 2.0 * param_count(cfg)
             + attention_rows * 4.0 * cfg.num_attention_heads * hd
             + updates * STATE_FLOPS_PER_ELEMENT * state_bytes(cfg) / STATE_ITEMSIZE)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "weights_bytes": weights, "state_bytes": state,
            "attention_rows_bytes": attention_rows * row,
            "bound": "memory" if t_bytes >= t_flops else "compute"}
