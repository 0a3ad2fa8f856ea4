"""Analytic per-chip HBM accounting for train + decode plans.

The reference sizes its allocations by operator experience (blog's 7B
recipe pins d16t4+d8t4 on H800s); on TPU we can do better: the GSPMD
engine's memory layout is regular enough to predict in closed form, so an
allocation plan can be *validated* against the target chip's HBM before
anything is launched (AllocationMode.check_hbm). The model:

Per chip, a training step holds
  params        n_params x param_bytes / (pp * dp * tp)     [ZeRO-3 + TP]
  grads         n_params x param_bytes / (pp * dp * tp)     [same sharding]
  opt (adamw)   2 x n_params x 4      / (pp * dp * tp)      [f32 mu + nu]
  activations   under full remat, only per-layer boundaries are saved:
                (L/pp) x T_local x d x act_bytes
                plus ONE layer's recompute working set
                T_local x (3d + 2ff/tp + 2*nH*hd/tp) x act_bytes
                plus what the backward KEEPS of every layer in place of
                recomputing it (`remat_kept_bytes`; `choose_remat_kept`
                picks the largest of `REMAT_SETS` that fits the room)
  logits        fused vocab-chunked head: T_local x chunk x 4;
                unfused: T_local x V x 4  (f32 logits)
  grad transient  a backward's gradients before they are added into the
                accumulator, n_params x param_bytes / shards; the head's,
                which the fused loss sums over token chunks in float32,
                V x d x 4 whole on every chip; under fsdp the embedding
                table gathered whole, V x d x act_bytes
                (read off XLA:TPU's plan for `jit_grad_step` at 0.5B on one
                chip and 1.5B over dp=4: 2.64 and 2.62 GB of temporaries
                where activations and logits alone give 0.85 and 0.70)
  pp stash      1f1b keeps (2*pp-1) stage inputs alive between a
                microbatch's forward and backward; the interleaved
                schedule v*(2*pp-1) virtual-chunk inputs — each entry
                T_local x d x act_bytes

With ZeRO-1 (`zero1=True`, `fsdp=False`) the f32 AdamW moments divide by
dp even though params/grads replicate; the per-chip bytes that sharding
frees are surfaced as `opt_freed_bytes` / `zero1_freed_gib`.

where T_local = per-chip microbatch tokens (dp and sp shard the token
axis; pp processes one microbatch per stage at a time). Without remat the
activation term multiplies by the ~10 saved tensors per layer instead of 1.

A decode server holds
  params        n_params x param_bytes / tp
  kv pool       2 x (L ) x pool_tokens x nKV x hd x kv_bytes / tp

Known-good anchor (unit-tested): Qwen2.5-0.5B = 0.494e9 params; the
estimator's activation model is cross-checked against XLA's own
`compile().memory_analysis()` on a tiny mesh in tests/test_hbm.py.

HBM capacities are per-chip device specs (public): v5e 16 GiB, v5p 95 GiB,
v4 32 GiB, v6e 32 GiB.
"""

from __future__ import annotations

import math
import weakref
from dataclasses import dataclass

GiB = 1024**3

# Per-chip HBM by NORMALIZED device-kind substring (first match wins).
# Normalization strips spaces/dashes/underscores so every spelling of the
# v5e family ("TPU v5 lite", "tpu-v5-lite-podslice", "v5litepod") hits the
# 16 GiB row — a substring match on the raw string would fall through to
# the plain-"v5" (v5p) row and credit a 16 GiB chip with 95 GiB.
HBM_BYTES: tuple[tuple[str, int], ...] = (
    ("v6", 32 * GiB),
    ("v5lite", 16 * GiB),
    ("v5e", 16 * GiB),
    ("v5", 95 * GiB),  # v5p reports plain "TPU v5"
    ("v4", 32 * GiB),
)


def _normalize_kind(device_kind: str) -> str:
    return (
        device_kind.lower().replace(" ", "").replace("-", "").replace("_", "")
    )


def hbm_bytes(device_kind: str) -> int:
    kind = _normalize_kind(device_kind)
    for sub, b in HBM_BYTES:
        if sub in kind:
            return b
    raise ValueError(
        f"no HBM capacity known for device kind {device_kind!r}; add it to "
        "HBM_BYTES"
    )


def _dtype_bytes(dtype) -> int:
    s = str(dtype)
    if "64" in s:
        return 8
    if "32" in s:
        return 4
    if "16" in s:
        return 2
    if "8" in s:
        return 1
    raise ValueError(f"unrecognized dtype {dtype!r}")


def expert_width(cfg) -> int:
    """Width of one routed expert, read where the model reads it
    (`ModelConfig.moe_intermediate_size_`: OLMoE and Mixtral experts are
    `intermediate_size` wide); duck-typed configs fall back to the raw keys."""
    return (
        getattr(cfg, "moe_intermediate_size_", None)
        or getattr(cfg, "moe_intermediate_size", None)
        or cfg.intermediate_size
    )


def param_count(cfg) -> int:
    """Exact decoder parameter count for models/qwen2.py's layout."""
    d = cfg.hidden_size
    nH = cfg.num_attention_heads
    nKV = cfg.num_key_value_heads
    hd = d // nH
    L = cfg.num_hidden_layers
    V = cfg.vocab_size

    attn = d * (nH + 2 * nKV) * hd + nH * hd * d
    if getattr(cfg, "qkv_bias", True):
        attn += (nH + 2 * nKV) * hd
    if getattr(cfg, "attn_out_bias", False):
        attn += d
    if getattr(cfg, "qk_norm", False):
        # per head (Qwen3) or over the whole q and k projections (OLMoE)
        full = getattr(cfg, "qk_norm_full", False)
        attn += (nH + nKV) * hd if full else 2 * hd
    n_experts = getattr(cfg, "num_experts", 0) or 0
    if n_experts:
        ff = expert_width(cfg)
        mlp = n_experts * 3 * d * ff + d * n_experts  # experts + router
        shared = getattr(cfg, "shared_expert_intermediate_size", 0) or 0
        if shared:
            mlp += 3 * d * shared + d  # shared expert + its gate
    else:
        mlp = 3 * d * cfg.intermediate_size
    norms = 2 * d
    per_layer = attn + mlp + norms
    embed = V * d
    head = 0 if getattr(cfg, "tie_word_embeddings", False) else V * d
    return L * per_layer + embed + head + d  # + final norm


def wq_elem_counts(cfg) -> tuple[int, int]:
    """(quantizable kernel elements, scale elements) for int8 weight
    serving, mirroring models/qwen2's layer map (_WQ_ATTN_AXES /
    _WQ_MLP_AXES): the dense attn + mlp matmul kernels quantize with one
    f32 scale per output channel; MoE mlp subtrees (router-marked) stay
    fp — their attn kernels still quantize — as do embed, lm_head, norms,
    biases and LoRA adapters."""
    d = cfg.hidden_size
    nH = cfg.num_attention_heads
    nKV = cfg.num_key_value_heads
    hd = d // nH
    L = cfg.num_hidden_layers
    q = d * (nH + 2 * nKV) * hd + nH * hd * d  # q/k/v + o kernels
    s = (nH + 2 * nKV) * hd + d  # one scale per output channel
    if not (getattr(cfg, "num_experts", 0) or 0):
        ff = cfg.intermediate_size
        q += 3 * d * ff  # gate + up + down
        s += 2 * ff + d
    return L * q, L * s


@dataclass
class HBMEstimate:
    params_bytes: int
    grads_bytes: int
    opt_bytes: int
    activation_bytes: int
    logits_bytes: int
    kv_bytes: int = 0
    # pipeline stash: the 1f1b schedules keep stage (or virtual-chunk)
    # inputs alive between forward and backward — 2*pp-1 entries for plain
    # 1f1b, v*(2*pp-1) for the interleaved schedule
    stash_bytes: int = 0
    # a backward's own gradients before accumulation, the head's float32
    # gradient and (fsdp) the gathered embedding table: see the module doc
    grad_transient_bytes: int = 0
    # informational: bytes the ZeRO-1 dp-sharded optimizer update freed
    # per chip vs a dp-replicated opt state (already subtracted from
    # opt_bytes; NOT part of total_bytes)
    opt_freed_bytes: int = 0
    # informational: bytes int8 weight serving freed per chip vs the fp
    # kernels (already subtracted from params_bytes; NOT part of
    # total_bytes) — headroom a fixed HBM budget can hand to the KV pool
    weight_freed_bytes: int = 0

    @property
    def total_bytes(self) -> int:
        return (
            self.params_bytes
            + self.grads_bytes
            + self.opt_bytes
            + self.activation_bytes
            + self.logits_bytes
            + self.kv_bytes
            + self.stash_bytes
            + self.grad_transient_bytes
        )

    def breakdown(self) -> dict:
        out = {
            "params_gib": round(self.params_bytes / GiB, 3),
            "grads_gib": round(self.grads_bytes / GiB, 3),
            "opt_gib": round(self.opt_bytes / GiB, 3),
            "activations_gib": round(self.activation_bytes / GiB, 3),
            "logits_gib": round(self.logits_bytes / GiB, 3),
            "kv_gib": round(self.kv_bytes / GiB, 3),
            "stash_gib": round(self.stash_bytes / GiB, 3),
            "grad_transient_gib": round(self.grad_transient_bytes / GiB, 3),
            "total_gib": round(self.total_bytes / GiB, 3),
        }
        if self.opt_freed_bytes:
            out["zero1_freed_gib"] = round(self.opt_freed_bytes / GiB, 3)
        if self.weight_freed_bytes:
            out["wquant_freed_gib"] = round(self.weight_freed_bytes / GiB, 3)
        return out


# -- what a layer's backward keeps ------------------------------------------
# Under `gradient_checkpointing` a decoder layer is a `jax.checkpoint` region:
# its backward starts from the layer's input and runs the forward again. The
# intermediates below carry a `jax.ad_checkpoint.checkpoint_name` where they
# are made (`models/qwen2.py`: `attention`, `decoder_layer`, `mlp`;
# `ops/flash_attention.py:_flash`'s forward rule for the kernel's `o` and
# `lse`, a ring's partials each step); a region built with
# `save_only_these_names(*kept)` keeps the named ones and recomputes the rest
# (norms, rotation, `act(gate) * up`: elementwise). Ordered by seconds saved a
# byte kept: the attention part ends the flash kernel's second run and the
# qkv / o projections' for 2(nH + nKV) hd + d elements a token; gate and up
# end the MLP's two wide matmuls' for 2 ff.
KEEP_ATTENTION = (
    "attn_q", "attn_k", "attn_v", "attn_out", "attn_lse", "attn_residual",
)
KEEP_MLP = ("mlp_gate", "mlp_up")
REMAT_SETS: tuple[tuple[str, ...], ...] = (
    (),  # full recompute
    KEEP_ATTENTION,
    KEEP_ATTENTION + KEEP_MLP,
)
# Share of the chip's capacity a training step may plan to fill, sized for
# the closed form's worst error against XLA:TPU's own plan for
# `jit_grad_step` with both sets kept (`bench_artifacts/pr47/aot_memory.py`,
# compiled for a described v5e): 13.67 GB read for 15.07 planned at 0.5B's
# 8,192-token micro-batch on one chip (10% under: a backward's working set at
# 64-lane heads, and 0.8 GB not explained), 13.86 for 13.32 a chip at 1.5B's
# 16,384 over dp=4. A plan filled to 0.85 and read 10% low is 0.935 of the
# chip, under the 0.98 a v5e's allocator has; a heavier loss than the fused
# SFT one those plans were made with (PPO's: about 0.5 GB more) takes the
# rest. A shape the compiler refuses all the same steps down a set
# (`engine/jax_engine.py:_run_grad_step`).
REMAT_ROOM_MARGIN = 0.85


def _lanes(n: int) -> int:
    """A TPU stores an array's minor dimension in tiles of 128 lanes."""
    return -(-n // 128) * 128


def remat_kept_bytes(
    model_cfg,
    tokens: int,
    n_sets: int,
    *,
    ring_steps: int = 1,
    tp: int = 1,
) -> int:
    """Bytes a chip holds between forward and backward for the first `n_sets`
    of `REMAT_SETS`, `tokens` a chip, summed over the layers. Sized by each layer's
    STRUCTURE: a layer whose mixer is not plain attention (linear, latent)
    names only the residual, a routed MLP names nothing, and what has no
    name is recomputed."""
    if n_sets <= 0:
        return 0
    abytes = _dtype_bytes(getattr(model_cfg, "dtype", "bfloat16"))
    d = model_cfg.hidden_size
    nH = model_cfg.num_attention_heads
    nKV = model_cfg.num_key_value_heads
    hd = getattr(model_cfg, "head_dim_", None) or d // nH
    linear = getattr(model_cfg, "layer_linear", lambda i: False)
    sparse = getattr(model_cfg, "layer_sparse", lambda i: False)
    latent = getattr(model_cfg, "latent", False)
    glu = getattr(model_cfg, "mlp_style", "glu") == "glu"
    total = 0
    for i in range(model_cfg.num_hidden_layers):
        per_token = d * abytes  # the residual after attention
        if not (latent or linear(i)):
            # q, k, v after the rotation, named `[T, heads * hd]`; the
            # kernel's o by heads, `[nH, T, hd]` (a ring keeps every step's
            # partial), and its float32 lse
            per_token += (nH + 2 * nKV) * hd * abytes // tp
            per_token += ring_steps * (nH * _lanes(hd) * abytes + nH * 4) // tp
        if n_sets >= 2 and not sparse(i):
            per_token += (2 if glu else 1) * model_cfg.intermediate_size * abytes // tp
        total += per_token * tokens
    return total


def choose_remat_kept(
    model_cfg,
    tokens: int,
    room_bytes: int,
    *,
    ring_steps: int = 1,
    tp: int = 1,
) -> tuple[int, int]:
    """(how many of `REMAT_SETS` the backward keeps, their bytes a chip): the
    largest set whose bytes fit `room_bytes`, what the chip has left beside
    the resident state and the full-recompute step (`train_room_bytes`).
    Arithmetic on static shapes: no compile, the same answer every time."""
    best = (0, 0)
    for n in range(1, len(REMAT_SETS)):
        need = remat_kept_bytes(model_cfg, tokens, n, ring_steps=ring_steps, tp=tp)
        if need > room_bytes:
            break
        best = (n, need)
    return best


def train_room_bytes(
    capacity_bytes: int, resident_bytes: int, step_bytes: int
) -> int:
    """What a chip of `capacity_bytes` has left for kept intermediates:
    `REMAT_ROOM_MARGIN` of it, less what is resident (parameters, gradients
    and optimizer state as sharded, whatever else lives on the chip) and the
    full-recompute step's own activations, logits and gradient transients."""
    return int(capacity_bytes * REMAT_ROOM_MARGIN) - resident_bytes - step_bytes


def sharded_bytes(leaves, shardings=None, dtype=None) -> int:
    """Bytes a chip holds of `leaves` (arrays, real or abstract) as sharded:
    each by its own `.sharding`, or by the matching entry of `shardings`;
    counted in `dtype` if given. Arithmetic on shapes: the same on every
    process of a multi-host mesh, whichever devices it can address."""
    if shardings is None:
        shardings = [getattr(x, "sharding", None) for x in leaves]

    def itemsize(dt) -> int:
        return getattr(dt, "itemsize", None) or _dtype_bytes(dt)

    return sum(
        math.prod(sh.shard_shape(x.shape) if sh is not None else x.shape)
        * itemsize(dtype or x.dtype)
        for x, sh in zip(leaves, shardings)
    )


# What each engine of this process holds of a chip from step to step, by its
# own account (a trainer: parameters, accumulator, optimizer state; a decode
# engine: weights and pools). A trainer plans its step's memory around the
# others' (`engine/jax_engine.py:_remat_kept`): declared bytes are the same
# in every run and on every process, which an allocator's live reading is
# not. An engine that is collected, or declares 0, is gone from the account.
_DECLARED: "weakref.WeakKeyDictionary[object, int]" = weakref.WeakKeyDictionary()


def declare_resident(owner, nbytes: int) -> None:
    """`owner` holds `nbytes` a chip from now on (0: nothing any more)."""
    if nbytes:
        _DECLARED[owner] = int(nbytes)
    else:
        _DECLARED.pop(owner, None)


def declared_resident_bytes(*, but=None) -> int:
    """Bytes a chip that engines other than `but` have declared."""
    return sum(n for owner, n in _DECLARED.items() if owner is not but)


def estimate_train_hbm(
    model_cfg,
    *,
    dp: int = 1,
    tp: int = 1,
    pp: int = 1,
    sp: int = 1,
    microbatch_tokens: int = 8192,
    remat: bool = True,
    fused_lm_head: bool = True,
    vocab_chunk: int = 8192,
    optimizer: str = "adamw",
    fsdp: bool = True,
    zero1: bool = False,
    pipeline_schedule: str = "1f1b",
    virtual_pp: int = 1,
) -> HBMEstimate:
    """Per-chip peak HBM for one training step of the GSPMD engine.

    `microbatch_tokens` is the GLOBAL token count of one microbatch (the
    unit `train_batch` runs per dispatch); dp and sp shard it.
    Under `remat` this is the FULL-recompute step; what the trainer then
    keeps of each layer (`choose_remat_kept`) it fits into the room this
    leaves, so it never moves a verdict made from `total_bytes`.

    Sharding regimes: `fsdp=True` dp-shards params, grads AND opt state
    (the ZeRO-3-ish default the estimator has always priced). With
    `fsdp=False`, params/grads replicate over dp; `zero1=True` then still
    dp-shards the f32 AdamW moments (jax.zero1_optimizer's reduce-scatter
    / sharded-update / all-gather step) — `opt_freed_bytes` records what
    that sharding saved per chip vs a replicated opt state.

    Pipelining: for pp>1 the 1f1b schedules stash stage inputs between a
    microbatch's forward and its backward — 2*pp-1 entries for "1f1b",
    `virtual_pp`*(2*pp-1) *chunk* inputs for "1f1b_interleaved" (each 1/v
    the layers but a full [T_local, d] activation, so the stash bytes grow
    ~v times while the bubble shrinks ~1/v: unmeasured on the chip).
    """
    n = param_count(model_cfg)
    pbytes = _dtype_bytes(getattr(model_cfg, "param_dtype", "float32"))
    abytes = _dtype_bytes(getattr(model_cfg, "dtype", "bfloat16"))
    shard = (dp if fsdp else 1) * tp * pp
    opt_shard = (dp if (fsdp or zero1) else 1) * tp * pp
    d = model_cfg.hidden_size
    nH = model_cfg.num_attention_heads
    hd = d // nH
    ff = model_cfg.intermediate_size
    L = model_cfg.num_hidden_layers

    t_local = max(1, microbatch_tokens // (dp * sp))
    layers_local = max(1, L // pp)
    boundary = layers_local * t_local * d * abytes
    # one decoder layer's live intermediates during (re)computation: qkv
    # streams + two ff intermediates + attn scores working set, tp-sharded
    working = t_local * (3 * d + (2 * ff + 2 * nH * hd) // tp) * abytes
    if remat:
        act = boundary + working
    else:
        # ~10 saved tensors per layer (qkv, probs-free flash residuals,
        # ff gate/up, norms) — the classic no-remat multiplier
        act = boundary * 10 + working
    if fused_lm_head:
        logits = t_local * min(vocab_chunk, model_cfg.vocab_size) * 4
    else:
        logits = t_local * model_cfg.vocab_size * 4
    stash = 0
    if pp > 1 and pipeline_schedule in ("1f1b", "1f1b_interleaved"):
        v = virtual_pp if pipeline_schedule == "1f1b_interleaved" else 1
        stash = v * (2 * pp - 1) * t_local * d * abytes
    opt_mult = 2 if optimizer == "adamw" else 0  # f32 mu + nu
    opt = opt_mult * n * 4 // opt_shard
    opt_freed = 0
    if zero1 and not fsdp and dp > 1:
        opt_freed = opt_mult * n * 4 // (tp * pp) - opt
    head = model_cfg.vocab_size * d
    grad_transient = n * pbytes // shard + (head * 4 if fused_lm_head else 0)
    if fsdp and dp > 1:
        grad_transient += head * abytes
    return HBMEstimate(
        params_bytes=n * pbytes // shard,
        grads_bytes=n * pbytes // shard,
        opt_bytes=opt,
        activation_bytes=act,
        logits_bytes=logits,
        stash_bytes=stash,
        grad_transient_bytes=grad_transient,
        opt_freed_bytes=opt_freed,
    )


def estimate_decode_hbm(
    model_cfg,
    *,
    tp: int = 1,
    pool_tokens: int | None = None,
    slots: int = 64,
    context_length: int = 32768,
    kv_cache_dtype: str = "bfloat16",
    weight_dtype: str = "fp",
) -> HBMEstimate:
    """Per-chip HBM for a decode server: tp-sharded params + paged KV pool.

    `pool_tokens=None` models dense provisioning (slots x context) — the
    difference vs a sized pool is exactly what the paged cache buys.

    `weight_dtype="int8"` (JaxDecodeConfig.weight_dtype) prices the dense
    matmul kernels at 1 byte/element plus one f32 scale per output channel
    instead of param_dtype; the per-chip bytes that frees vs fp serving
    surface as `wquant_freed_gib` in breakdown() — at a fixed HBM budget
    that headroom goes to a larger resident KV pool.
    """
    n = param_count(model_cfg)
    pbytes = _dtype_bytes(getattr(model_cfg, "param_dtype", "bfloat16"))
    kvb = _dtype_bytes(kv_cache_dtype)
    d = model_cfg.hidden_size
    hd = d // model_cfg.num_attention_heads
    nKV = max(model_cfg.num_key_value_heads, tp)  # GQA heads repeat to tp
    if pool_tokens is None:
        pool_tokens = slots * context_length
    kv = 2 * model_cfg.num_hidden_layers * pool_tokens * nKV * hd * kvb // tp
    params_bytes = n * pbytes // tp
    weight_freed = 0
    if weight_dtype == "int8":
        nq, ns = wq_elem_counts(model_cfg)
        quantized = ((n - nq) * pbytes + nq * 1 + ns * 4) // tp
        weight_freed = params_bytes - quantized
        params_bytes = quantized
    elif weight_dtype != "fp":
        raise ValueError(f"weight_dtype={weight_dtype!r} not in ('fp', 'int8')")
    return HBMEstimate(
        params_bytes=params_bytes,
        grads_bytes=0,
        opt_bytes=0,
        activation_bytes=0,
        logits_bytes=0,
        kv_bytes=kv,
        weight_freed_bytes=weight_freed,
    )


def check_fit(
    estimate: HBMEstimate,
    device_kind: str,
    *,
    utilization: float = 0.9,
) -> None:
    """Raise if the plan cannot fit the chip (90% of HBM usable by default:
    XLA needs headroom for fusion temporaries and the compiled program)."""
    cap = int(hbm_bytes(device_kind) * utilization)
    if estimate.total_bytes > cap:
        raise MemoryError(
            f"plan needs {estimate.total_bytes / GiB:.2f} GiB/chip but "
            f"{device_kind!r} offers {cap / GiB:.2f} GiB usable "
            f"({utilization:.0%} of {hbm_bytes(device_kind) / GiB:.0f} GiB): "
            f"{estimate.breakdown()}"
        )
