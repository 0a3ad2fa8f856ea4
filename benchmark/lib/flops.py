"""Operations, bytes and peaks: the arithmetic behind every roofline share.

`forward_flops_per_token` and `train_flops_per_token` are COPIES of
`areal_tpu/utils/flops.py` (PR 23): a later PR may change the program, not
the yardstick. The byte counts and the peaks table exist only here.

Peaks of one chip, keyed by `device_kind` as JAX reports it. A device that is
not in the table is an error, not a default. Source: Google Cloud
documentation, "TPU v5e" system architecture: 197 TFLOP/s bf16, 819 GB/s HBM,
16 GB HBM per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks known for device kind {device_kind!r}: add it to "
            "benchmark/lib/flops.py:PEAKS with its source"
        )
    return PEAKS[device_kind]


def head_dim(cfg) -> int:
    return getattr(cfg, "head_dim", None) or cfg.hidden_size // cfg.num_attention_heads


def forward_flops_per_token(cfg, avg_context: float) -> float:
    """Forward matmul FLOPs per token (2*m*n per output element): q/k/v and
    output projections, scores and values over `avg_context` kv positions,
    the three SwiGLU matmuls, and the head (once, also when tied). The
    embedding lookup is a gather and is not counted."""
    d = cfg.hidden_size
    n_h, n_kv, hd = cfg.num_attention_heads, cfg.num_key_value_heads, head_dim(cfg)
    qkv = 2 * d * (n_h + 2 * n_kv) * hd
    out = 2 * n_h * hd * d
    attn = 4 * avg_context * n_h * hd
    mlp = 6 * d * cfg.intermediate_size
    return cfg.num_hidden_layers * (qkv + out + attn + mlp) + 2 * d * cfg.vocab_size


def train_flops_per_token(cfg, avg_context: float) -> float:
    """Forward + backward (dX and dW: twice the forward). Recomputation under
    gradient checkpointing is NOT counted: it is not needed work."""
    return 3.0 * forward_flops_per_token(cfg, avg_context)


def param_count(cfg) -> int:
    d, hd = cfg.hidden_size, head_dim(cfg)
    n_h, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
    attn = d * (n_h + 2 * n_kv) * hd + n_h * hd * d
    if getattr(cfg, "qkv_bias", True):
        attn += (n_h + 2 * n_kv) * hd
    layer = attn + 3 * d * cfg.intermediate_size + 2 * d
    total = cfg.num_hidden_layers * layer + d + cfg.vocab_size * d
    if not cfg.tie_word_embeddings:
        total += cfg.vocab_size * d
    return total


def kv_bytes_per_token(cfg, kv_itemsize: int = 2) -> int:
    """Bytes one cached token takes: k and v, every layer, every kv head."""
    return 2 * cfg.num_hidden_layers * cfg.num_key_value_heads * head_dim(cfg) * kv_itemsize


def causal_avg_context(lengths) -> float:
    """Mean number of kv positions a query attends to, over all tokens of
    sequences of these lengths under causal attention: token t sees t + 1."""
    tokens = sum(lengths)
    return sum(n * (n + 1) / 2 for n in lengths) / max(tokens, 1)


def decode_step_needed_seconds(cfg, running: float, live_tokens: float,
                               device_kind: str, weight_itemsize: int = 2,
                               kv_itemsize: int = 2) -> dict:
    """Least time one token step of a decode batch can take on the chip.

    Bytes: every weight once (the tied embedding is the head: read once; the
    input lookup reads `running` rows), the cached rows of the live contexts
    once, one new row written per running request. FLOPs: one forward token
    per running request at its context."""
    pk = peaks(device_kind)
    weights = param_count(cfg) * weight_itemsize
    kv = kv_bytes_per_token(cfg, kv_itemsize)
    nbytes = weights + live_tokens * kv + running * (kv + cfg.hidden_size * weight_itemsize)
    ctx = live_tokens / max(running, 1e-9)
    flops = running * forward_flops_per_token(cfg, ctx)
    t_bytes, t_flops = nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_bf16"]
    return {"seconds": max(t_bytes, t_flops), "bytes": nbytes, "flops": flops,
            "bound": "memory" if t_bytes >= t_flops else "compute"}


def train_needed_seconds(cfg, lengths, device_kind: str, chips: int = 1) -> dict:
    """Least time the forward and backward of these sequences can take:
    needed FLOPs over the peak of all chips used. Compute-bound at these
    sizes (thousands of tokens per weight read)."""
    flops = sum(lengths) * train_flops_per_token(cfg, causal_avg_context(lengths))
    return {"seconds": flops / (peaks(device_kind)["flops_bf16"] * chips),
            "flops": flops, "bound": "compute"}
