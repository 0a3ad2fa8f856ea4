"""Experiment configuration tree + CLI/YAML loader.

Parity target: areal/api/cli_args.py (~35 dataclasses, OmegaConf merge,
`--config file.yaml key=value` overrides). Field names are kept identical to
the reference wherever the concept carries over (GenerationHyperparameters,
OptimizerConfig, TrainEngineConfig, PPOActorConfig incl. `use_decoupled_loss`,
`recompute_logprob`, `max_head_offpolicyness`, `group_size`,
`dynamic_sampling`, SaverConfig, …) so that reference configs port with only
backend-name changes. CUDA-server configs (SGLangConfig/vLLMConfig) are
replaced by `JaxDecodeConfig` — the TPU-native decode engine.
"""

from __future__ import annotations

import argparse
import dataclasses
import getpass
import os
from dataclasses import dataclass, field

import yaml

from areal_tpu.utils import structured
from areal_tpu.utils.name_resolve import NameResolveConfig

__all__ = [
    "NormConfig",
    "MicroBatchSpec",
    "GenerationHyperparameters",
    "OptimizerConfig",
    "JaxEngineConfig",
    "TrainEngineConfig",
    "PPOActorConfig",
    "PPOCriticConfig",
    "JaxDecodeConfig",
    "InferenceEngineConfig",
    "SaverConfig",
    "EvaluatorConfig",
    "RecoverConfig",
    "WandBConfig",
    "SwanlabConfig",
    "TensorBoardConfig",
    "StatsLoggerConfig",
    "NameResolveConfig",
    "ClusterSpecConfig",
    "DatasetConfig",
    "LauncherConfig",
    "SlurmLauncherConfig",
    "BaseExperimentConfig",
    "SFTConfig",
    "RWConfig",
    "GRPOConfig",
    "PPOConfig",
    "parse_cli_args",
    "load_expr_config",
    "save_config",
]


@dataclass
class NormConfig:
    """Normalization spec for rewards/advantages (reference cli_args.py:22)."""

    mean_level: str | None = "batch"  # "batch" | "group" | None
    mean_leave1out: bool = False
    std_level: str | None = "batch"  # "batch" | "group" | None
    std_unbiased: bool = False
    eps: float = 1e-5
    group_size: int = 1


@dataclass
class MicroBatchSpec:
    """Micro-batch splitting spec (reference cli_args.py:61)."""

    n_mbs: int | None = 1
    granularity: int = 1
    max_tokens_per_mb: int | None = None


@dataclass
class GenerationHyperparameters:
    """Sampling hyperparameters (reference cli_args.py:96)."""

    n_samples: int = 1
    max_new_tokens: int = 16384
    min_new_tokens: int = 0
    max_tokens: int | None = None
    greedy: bool = False
    top_p: float = 1.0
    top_k: int = int(1e8)
    temperature: float = 1.0
    stop_token_ids: list[int] = field(default_factory=list)
    stop: list[str] | None = None
    frequency_penalty: float = 0.0

    def new(self, **kwargs) -> "GenerationHyperparameters":
        out = dataclasses.replace(self)
        for k, v in kwargs.items():
            setattr(out, k, v)
        return out


@dataclass
class OptimizerConfig:
    """Optax optimizer + schedule spec (reference cli_args.py:160).

    `type` supports "adamw" (AnyPrecision-equivalent: bf16 params, fp32
    moments by default) and "sgd"; schedules: cosine/linear/constant with
    linear warmup.
    """

    type: str = "adamw"
    lr: float = 2e-5
    weight_decay: float = 0.05
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-5
    min_lr_ratio: float = 0.0
    lr_scheduler_type: str = "constant"  # "cosine" | "linear" | "constant"
    warmup_steps_proportion: float = 0.001
    offload: bool = False
    gradient_clipping: float = 1.0
    # dtype of Adam moments; fp32 is the AnyPrecisionAdamW default.
    moment_dtype: str = "float32"


@dataclass
class JaxEngineConfig:
    """TPU/GSPMD engine knobs (replaces FSDPEngineConfig/MegatronEngineConfig).

    The reference's FSDP2 wrap policy and Megatron DDP flags have no TPU
    analogue: parameter sharding is a NamedSharding over the mesh's
    ("fsdp",) axis; rematerialisation replaces activation checkpointing.
    """

    # Which mesh axes shard parameters ZeRO-style; () replicates.
    fsdp_axes: list[str] = field(default_factory=lambda: ["fsdp"])
    # Fused LM-head loss: apply the head + cross-entropy in vocab chunks
    # (ops/fused_xent.py) so the f32 [tokens, vocab] logits never
    # materialize — lifts the micro-batch HBM ceiling the dense path hits
    # on wide-vocab models. Exact to f32 roundoff; disable to force the
    # dense logits path.
    fused_lm_loss: bool = True
    # Use scan-over-layers for fast compiles and PP-friendly stacking.
    scan_layers: bool = True
    # Offload optimizer state to host memory (jax.device_put w/ host sharding).
    offload_params: bool = False
    # Pipeline schedule under pp>1: "1f1b" interleaves each micro-batch's
    # backward right behind its forward (live activation stash capped at
    # 2*pp-1 per stage, so bigger M — smaller bubble — fits in fixed HBM);
    # "1f1b_interleaved" additionally splits each rank into
    # `virtual_pp_size` non-contiguous virtual stages (Megatron's
    # interleaved schedule), shrinking the bubble ~1/v at a stash bound of
    # v*(2*pp-1); "gpipe" is the all-forward-then-all-backward
    # reference/fallback path.
    pipeline_schedule: str = "1f1b"
    # Virtual pipeline stages per pp rank (interleaved 1F1B). 1 = one
    # contiguous stage per rank. Values > 1 require
    # pipeline_schedule "1f1b_interleaved" or "gpipe" and
    # num_hidden_layers % (pp * virtual_pp_size) == 0; the engine then
    # stores the scanned layer stack in chunk-major order (layer
    # round-robin across ranks) so chunk dispatch is a pure reshape.
    virtual_pp_size: int = 1
    # ZeRO-1: shard AdamW moments and the optimizer update over the dp
    # axis (reduce-scatter grads -> sharded update -> all-gather params,
    # expressed as shardings so XLA emits the collectives). Frees
    # 8 bytes/param of replicated fp32 moment state per dp rank; bitwise
    # identical to the replicated update (reduction order unchanged —
    # sharding only partitions the elementwise moment math).
    zero1_optimizer: bool = True
    # Hybrid ICI/DCN mesh: number of accelerator slices (pods) the trainer
    # spans. 1 = single-slice mesh (plain build_mesh). > 1 places the axes
    # named in mesh_dcn_axes across slice boundaries so only their traffic
    # (the pp stage-boundary activation hop, the dp gradient reduce)
    # crosses the slower DCN; axis order inside a slice is unchanged.
    mesh_num_slices: int = 1
    # Which mesh axes cross slice boundaries when mesh_num_slices > 1, in
    # mesh order. Product of their DCN factors must equal mesh_num_slices;
    # "pp" (outermost, least traffic) is the default, optionally with an
    # outer "dp" split.
    mesh_dcn_axes: list[str] = field(default_factory=lambda: ["pp"])
    # Zig-zag context-parallel layout: shard the packed token axis as paired
    # chunks (i, 2n-1-i) so every ring-attention shard does equal causal
    # work. Exact (a pure relabeling, inverted on outputs); applies only
    # when attention resolves to the ring path.
    cp_zigzag: bool = True


@dataclass
class TrainEngineConfig:
    """Train engine contract config (reference cli_args.py:315)."""

    experiment_name: str = ""
    trial_name: str = ""
    path: str = ""  # HF model path or local checkpoint dir
    # "auto" | "pallas" (flash kernel) | "xla" (dense mask) | "chunked"
    # (XLA online-softmax over KV chunks — the O(T)-memory path sliding-
    # window models resolve to) | "ring" (context-parallel)
    attn_impl: str = "auto"
    init_from_scratch: bool = False
    is_critic: bool = False
    mb_spec: MicroBatchSpec = field(default_factory=MicroBatchSpec)
    pad_to_maximum: bool = False
    disable_dropout: bool = True
    # the trainer may recompute a layer's forward in its backward. What it
    # keeps instead is computed a grad-step shape from the chip's room
    # (engine/jax_engine.py:_remat_kept); false keeps everything.
    gradient_checkpointing: bool = True
    dtype: str = "bfloat16"
    # dtype of the cross-micro-batch gradient accumulator. It is SHARDED
    # like the parameters (fsdp over dp), so its per-chip HBM cost is
    # params_per_chip * 4 bytes at fp32 — e.g. 7B over 8 chips ≈ 3.5 GB/chip
    # fp32, halved by "bfloat16" at the cost of accumulation precision
    # across micro-batches (the within-backward matmul accumulation stays
    # fp32 either way). The reference's Megatron fuses accumulation into
    # backward buffers; GSPMD's equivalent lever is this dtype knob.
    # Irrelevant under pp>1 (one backward, no explicit accumulator).
    grad_reduce_dtype: str = "float32"
    optimizer: OptimizerConfig | None = None
    weight_update_mode: str = "memory"  # "memory" (device_put) | "disk"
    # LoRA delta push: when LoRA is active, the "dcn" weight push ships only
    # the trainable adapter subtree (A/B matrices) and the decode servers
    # fold the delta into their pristine base kernels at commit — wire bytes
    # drop by orders of magnitude vs. pushing merged full kernels. Disable
    # to force the full merged-tree push (e.g. decode servers that did not
    # start from the same base checkpoint).
    weight_sync_delta: bool = True
    backend: str = "jax"
    jax: JaxEngineConfig = field(default_factory=JaxEngineConfig)
    use_lora: bool = False
    lora_rank: int = 32
    lora_alpha: int = 16
    target_modules: list[str] = field(default_factory=list)


@dataclass
class PPOActorConfig(TrainEngineConfig):
    """PPO/GRPO actor config (reference cli_args.py:390)."""

    group_size: int = 1
    ppo_n_minibatches: int = 4
    eps_clip: float = 0.2
    eps_clip_higher: float | None = None
    c_clip: float | None = None
    temperature: float = 1.0
    # reward shaping
    reward_norm: NormConfig | None = None
    reward_scaling: float = 1.0
    reward_bias: float = 0.0
    reward_clip: float = 20.0
    overlong_reward_penalty: bool = False
    overlong_tokens: int | None = None
    overlong_penalty_factor: float | None = None
    mask_no_eos_with_zero: bool = False
    # advantage estimation
    discount: float = 1.0
    gae_lambda: float = 1.0
    adv_norm: NormConfig | None = None
    # KL regularization
    kl_ctl: float = 0.1
    kl_estimator: str = "k1"  # "k1" | "k2" | "k3"
    # asynchronous / decoupled-PPO controls
    recompute_logprob: bool = False
    use_decoupled_loss: bool = False
    behav_imp_weight_cap: float | None = None
    dynamic_sampling: bool = False
    log_agent_stats: bool = False
    log_agent_stats_keys: list[str] = field(default_factory=list)
    max_new_tokens: int = 1024
    # AEnt clamped-entropy regularization (parity: recipe/AEnt/aent_args.py).
    # entropy_coeff > 0 adds an entropy bonus to the GRPO loss;
    # entropy_clamp > 0 excludes that fraction of the vocab (lowest logits)
    # from the bonus so it can't reward mass on the garbage tail.
    entropy_coeff: float = 0.0
    entropy_clamp: float = 0.0
    # adaptive coefficient: nudge entropy_coeff to keep measured entropy
    # inside [entropy_low, entropy_high], clipped to the box bounds
    adaptive_entropy_coeff: bool = False
    entropy_high: float = 0.5
    entropy_low: float = 0.1
    entropy_coeff_lr: float = 0.001
    entropy_coeff_box_high: float = 0.01
    entropy_coeff_box_low: float = 1e-5
    entropy_warmup_steps: int = 0


@dataclass
class PPOCriticConfig(TrainEngineConfig):
    """PPO critic config (reference cli_args.py:513)."""

    ppo_n_minibatches: int = 4
    eps_clip: float = 0.5
    mask_no_eos_with_zero: bool = False


@dataclass
class JaxDecodeConfig:
    """TPU-native decode engine config (replaces SGLangConfig/vLLMConfig).

    Continuous batching over a static [max_running_requests, pages] KV layout
    so XLA compiles once; paged KV cache with prefix reuse; interruptible
    generation via chunked decode loops.
    """

    model_path: str = ""
    random_seed: int = 1
    dtype: str = "bfloat16"
    kv_cache_dtype: str = "bfloat16"
    # Paged-pool storage scheme (parity surface: SGLang's fp8/int8 KV
    # cache serving):
    #   "fp" (default): the pool stores kv_cache_dtype verbatim — what
    #     int8 drift is measured against.
    #   "int8": the pool stores int8 with per-(row, kv-head) f32 scales
    #     (ops/kv_quant.py). Rows are quantized ONCE at the
    #     decode/verify/prefill scatters and dequantized inside the
    #     paged-attention kernels right after each block's HBM→VMEM DMA —
    #     the same MB of pool holds ~2x the
    #     sessions, and every byte-moving path (host-tier swaps, session
    #     export/import, /drain migration) ships the quantized blocks +
    #     scales as-is, halving swap and wire bytes too. Mixed-dtype
    #     fleets reject migrated sessions as tombstoned honest misses
    #     (kv_migrate_dtype_rejects_total), like the weight-version rule.
    #     Drift (logprob delta) is bounded at a tiny preset on the CPU by
    #     tests/test_kv_quant.py; at a real model's widths it is unmeasured.
    kv_dtype: str = "fp"  # "fp" | "int8"
    # Weight serving dtype for the dense transformer matmul kernels
    # (models/qwen2.py q/k/v/o + dense mlp; MoE, embed, lm_head, norms,
    # biases and LoRA adapters always stay fp):
    #   "fp" (default): kernels stored and served in `dtype` — the
    #     pre-quantization behavior, bit for bit, and the numerics oracle
    #     int8 drift is measured against.
    #   "int8": kernels stored as per-output-channel symmetric absmax
    #     int8 + f32 scales (ops/quant.py). Quantized ONCE at the push
    #     producer (the trainer keeps fp32 masters; engine/jax_engine.py
    #     ships `.../q` + `.../scale` leaves over DCN, halving wire bytes
    #     and the commit pause) or locally on full-tree installs, and
    #     dequantized inside the fused dequant-matmul
    #     (ops/quant_matmul.py) right after each weight tile's HBM→VMEM
    #     DMA — decode chunks read half the weight bytes and the freed
    #     HBM goes to the KV pool (utils/hbm.py prices it). Drift vs the
    #     fp oracle is bounded at a tiny preset on the CPU by
    #     tests/test_weight_quant.py; at a real model's widths it is
    #     unmeasured. The LoRA delta push stays fp and requantizes the
    #     folded kernels at install.
    weight_dtype: str = "fp"  # "fp" | "int8"
    # Replica role in a disaggregated fleet (launcher/decode_server.py):
    #   "unified" (default): one replica does both prefill and decode.
    #   "prefill": compute-bound role — runs prompt prefills only (via
    #     /prefill), parks the resulting KV, and streams it to a decode
    #     replica over the KV wire format (core/weight_transfer.py
    #     pack_kv_session) so long prefills never stall resident decode
    #     slots on the decode replicas.
    #   "decode": memory-bound role — imports migrated KV sessions into
    #     its host tier and resumes them through the host-tier promotion
    #     path (zero re-prefill). Any role still serves every endpoint
    #     (a prefill replica CAN decode) — the role steers the router and
    #     sizes defaults, it does not forbid traffic, so a degraded fleet
    #     keeps working.
    role: str = "unified"  # "unified" | "prefill" | "decode"
    # Frame size for migrated KV sessions (MiB per HTTP body on the
    # /kv_recv wire — same bounded-bucket rule as weight_chunked_mem_mb).
    kv_migrate_chunk_mb: float = 64.0
    # Host-tier budget a decode-role replica creates LAZILY (MiB) when it
    # receives a KV migration while kv_host_pool_mb == 0 — imported
    # sessions need a host tier to land in; this bounds it. Ignored when
    # kv_host_pool_mb already enabled the tier.
    kv_import_pool_mb: float = 256.0
    # Gen-side tensor parallelism: params + KV cache are sharded over a
    # [1,1,1,tp] decode mesh (parity: the server-side d/t/p dims of the
    # reference's allocation grammar, areal/api/alloc_mode.py:277-280 — dp
    # maps to independent server replicas, tp to this).
    tensor_parallel_size: int = 1
    context_length: int = 32768
    max_running_requests: int = 64
    page_size: int = 128  # tokens per KV page (TPU-friendly multiple of 128)
    # Paged-KV pool budget in tokens (x num_layers x kv heads). None =
    # full provisioning (max_running_requests x context_length — the dense
    # worst case). Setting it smaller is the point of paging: N concurrent
    # 32k-context slots only consume blocks for the tokens they actually
    # hold, with parked-KV eviction / donor-registry drop / active-slot
    # preemption (internal requeue) when the pool runs dry.
    kv_pool_tokens: int | None = None
    # Host-RAM tier under the paged pool (MiB; 0 disables — eviction then
    # DROPS parked/preempted KV and the resume re-prefills, exactly the
    # pre-tier behavior). When enabled, the eviction paths offload the
    # victim slot's blocks to a budgeted pinned host store
    # (engine/kv_pool.py HostKVStore, its own LRU) via async
    # device→host copies, and a resume promotes them back — fresh device
    # blocks + async upload — instead of re-running prefill. Turns
    # kv_pool_tokens from a hard capacity wall into a working-set knob;
    # resumed token streams are identical to never-evicted ones (the
    # restored bytes ARE the original KV, and the slot's sampling base
    # key travels with the entry); logprobs agree to float32 rounding —
    # the resume runs through differently-shaped compiled programs,
    # whose reductions XLA may order differently.
    kv_host_pool_mb: float = 0.0
    # Decode attends IN PLACE over the paged pool through the block table
    # (ops/paged_attention.py) with an O(1) per-token cache write. This
    # picks the kernel for that attention read: "pallas" (TPU split-KV
    # flash-decode kernel; requires page_size % 128 == 0), "xla" (gathers
    # the slot's blocks each step), or "auto" (pallas on TPU, xla
    # elsewhere).
    paged_attn_impl: str = "auto"
    hbm_utilization: float = 0.85
    max_prefill_tokens: int = 8192
    # tokens generated per decode-loop dispatch; interrupts land on chunk
    # boundaries (parity: partial rollout `new_tokens_per_chunk`)
    new_tokens_per_chunk: int = 128
    # Run-ahead decode scheduling: how many chunks the scheduler may keep
    # dispatched on the device while the host consumes the previous
    # chunk's results (stop-string scan, retire, admission, prefill
    # planning all overlap the in-flight chunk; per-slot sampling keys
    # keep the output bit-identical to the synchronous schedule). 0
    # restores the legacy dispatch-then-block loop. A slot the host
    # retires mid-run-ahead has its speculative tokens discarded and its
    # KV length rewound at the next dispatch.
    decode_runahead_chunks: int = 1
    # Draft-free speculative decoding. "ngram": a host-side prompt-lookup
    # drafter matches the trailing n-gram of each slot's (prompt +
    # generated) context against its own earlier tokens and proposes up
    # to spec_k continuation tokens; the device chunk becomes a VERIFY
    # chunk that scores all draft positions in one forward over the paged
    # pool and accepts the longest prefix matching what greedy/sampling
    # would have emitted, plus the model's own bonus token. Accepted
    # token streams are identical to spec_decode="off"
    # (fold_in(base_key, position) sampling keys are a pure function of
    # token index) and logprobs agree to float32 rounding (the verify
    # chunk is a different compiled program from the decode chunk);
    # rejected draft rows are dead KV overwritten by the
    # next write. Strong on math/code rollouts that quote their prompts
    # (and on greedy repetition); draftless passes fall back to normal
    # chunks, so non-repetitive workloads keep baseline throughput.
    spec_decode: str = "off"  # "off" | "ngram"
    # max draft tokens proposed (and verified) per chunk per slot; the
    # verify q-width is bucketed to powers of two up to spec_k + 1
    spec_k: int = 4
    # longest trailing n-gram matched against the slot's earlier context
    # (matching tries spec_ngram_max down to 1, longest match wins)
    spec_ngram_max: int = 3
    # Block-diffusion models (a ModelConfig with block_length > 1; others
    # ignore these): denoise forwards a block of block_length positions
    # before its commit forward; which masked positions a forward reveals,
    # "low_confidence_static" (the block_length // steps most confident, one
    # more in the first block_length % steps steps) or
    # "low_confidence_dynamic" (every one above diffusion_threshold, and at
    # least the static quota). Statics of the chunk program.
    diffusion_steps: int = 4
    diffusion_strategy: str = "low_confidence_static"
    diffusion_threshold: float = 0.9
    skip_tokenizer_init: bool = False
    log_level: str = "info"
    # Server-side idempotency table (launcher/decode_server.py): /generate
    # requests carrying an `xid` delivery id are deduplicated — a retry of
    # an in-flight submission awaits the SAME engine future and a replay of
    # a completed one returns the cached response, so client retry + router
    # failover-requeue can never double-generate a rollout. Entries are
    # bounded (LRU) and completed entries expire after the TTL.
    idempotency_entries: int = 4096
    idempotency_ttl_s: float = 600.0
    # Crash-mid-stage recovery: weight staging whose last frame arrived
    # more than this many seconds ago is REAPED (dropped with the push-id
    # epoch cleared) the next time any weight endpoint runs — a learner
    # that died mid-push must not leave multi-GiB staging resident until
    # an operator notices. The client additionally aborts its own
    # incomplete push on reconnect (remote_inf_engine.stage_weights).
    # 0 disables the reaper.
    weight_staging_ttl_s: float = 600.0
    # -- fleet KV fabric (core/kv_fabric.py; ISSUE 17) -------------------
    # Content-addressed prefix blocks: every complete pool block gets a
    # chained blake2b key of (token block, parent key, weight_version,
    # kv_dtype). Enables (1) intra-replica dedup — `_admit` forks from
    # ANY resident block run with matching content, regardless of which
    # rid produced it; (2) block-level host-tier lookups beside the
    # rid-exact resume path; (3) peer fetch — on a router hint, the
    # server pulls a sibling's matching block run over the /kv_recv +
    # /kv_commit migration wire instead of re-prefilling. Deduped and
    # fetched token streams are identical to the re-prefill oracle (same
    # tokens + same weights => same KV bytes; sampling keys are
    # per-request, not per-block), logprobs to float32 rounding (a
    # suffix prefill is a different compiled program from a full one).
    # False restores pre-fabric behavior.
    kv_fabric: bool = True
    # cap on content keys published in the /metrics digest (newest-chain
    # first); bounds the health-poll payload, not the index itself
    kv_fabric_digest_max: int = 512
    # minimum matched COMPLETE blocks before a fabric dedup/fetch fires
    # (tiny matches aren't worth a fork + suffix dispatch)
    kv_fabric_min_blocks: int = 1
    # deadline for one peer block fetch (the /kv_fetch round-trip incl.
    # the pushed frames); on expiry the request degrades to local prefill
    kv_fabric_fetch_timeout_s: float = 30.0


@dataclass
class FaultInjectionConfig:
    """Deterministic fault injection (core/fault_injection.py).

    When enabled, a seed-driven plan perturbs the named seams at every
    cross-component boundary (client HTTP send/recv, router poll/forward,
    server handling, weight stage/commit, host-KV swap, rollout task
    execution) so chaos benches/tests can replay a fleet trace under a
    reproducible fault schedule. `plan` is a JSON list of fault points:

        [{"site": "client.http.recv", "mode": "error_after_effect",
          "at": [3], "match": {"endpoint": "/generate"}}, ...]

    with modes abort / error_after_effect / delay / torn (see
    core/fault_injection.py for the full point schema). Disabled (the
    default), every seam is a single None-check — production pays nothing.
    """

    enabled: bool = False
    seed: int = 0
    plan: str = ""


@dataclass
class RouterConfig:
    """Fleet router (launcher/router.py) policy knobs.

    The router turns N decode-server replicas into one service: policy
    scheduling with prefix affinity, pressure-aware admission with a
    bounded queue, and exactly-once failover (parity:
    realhf/system/gserver_manager.py, grown per ROADMAP item 3).
    """

    # "prefix_affinity" (default: bucketed prompt-prefix hashing with a
    # load override), "least_token_usage", "least_requests", "round_robin"
    schedule_policy: str = "prefix_affinity"
    max_concurrent_rollouts: int = 1024
    max_head_offpolicyness: int = 1_000_000_000
    train_batch_size: int = 1
    health_poll_interval: float = 5.0
    # -- prefix affinity ------------------------------------------------
    # prompt prefixes are hashed at block granularity: the first
    # prefix_block_tokens, 2x, ... up to prefix_max_blocks blocks; the
    # LONGEST hash with a live affinity entry wins (a cheap radix-tree
    # approximation), so GRPO group members / multi-turn sessions /
    # dup-prompt forks land on the replica already holding their donor KV
    prefix_block_tokens: int = 64
    prefix_max_blocks: int = 4
    # affinity-vs-load override: the affine server is skipped when its
    # token load exceeds factor x the least-loaded admissible server's
    # (plus one block of slack) — affinity must not melt a hot replica
    affinity_load_factor: float = 1.5
    # -- pressure-aware admission --------------------------------------
    # fraction of a replica's kv pool the router may fill before the
    # replica stops being admissible (fragmented blocks are subtracted);
    # replicas whose host KV tier is enabled admit to the full pool
    # (eviction offloads instead of dropping)
    kv_pressure_high: float = 0.9
    # cap on running+queued requests per replica (0 = unlimited)
    max_inflight_per_server: int = 0
    # -- bounded queueing ----------------------------------------------
    # requests that no replica can admit wait in a bounded FIFO; past the
    # bound (or past the deadline) they are shed with 429 + Retry-After
    queue_max: int = 1024
    queue_timeout_s: float = 30.0
    retry_after_s: float = 1.0
    # -- failover -------------------------------------------------------
    # consecutive failed health polls before a replica is declared dead:
    # its in-flight qids are requeued onto survivors and its affinity
    # entries drained
    dead_after_failures: int = 2
    # -- per-replica circuit breaker ------------------------------------
    # A replica that is SLOW or erroring (but not yet dead) must be
    # probed, not hammered: after `breaker_trip_after` consecutive bad
    # polls (health/metrics failure, or health RTT above
    # `breaker_slow_s` when > 0) the breaker OPENS and the replica
    # leaves rotation. Once polls look healthy again it goes HALF-OPEN:
    # at most `breaker_probe_requests` in-flight requests are routed
    # there as probes; a completed probe closes the breaker and full
    # traffic (and the replica's surviving affinity entries) return. A
    # transient trip never drains prefix/qid affinity state — only
    # `dead_after_failures` failover does.
    breaker_enabled: bool = True
    breaker_trip_after: int = 3
    breaker_slow_s: float = 0.0
    breaker_probe_requests: int = 1
    # A half-open probe slot is freed by the probe request COMPLETING
    # (_release_qid); a probe whose client died first (deadline shed,
    # crashed caller) would otherwise hold the slot forever and wedge the
    # breaker half-open. Probe charges older than this TTL are expired by
    # the poll loop. 0 disables expiry.
    breaker_probe_ttl_s: float = 60.0
    # -- state expiry ---------------------------------------------------
    # TTL for qid/prefix affinity entries (a crashed client must not leak
    # load accounting forever); 0 disables TTL expiry. route_max_entries
    # LRU-bounds the qid and prefix maps independently of the TTL.
    route_ttl_s: float = 600.0
    route_max_entries: int = 65536
    # -- fleet KV fabric ------------------------------------------------
    # Aggregate the replicas' content-key digests (published through the
    # existing /metrics poll) into a fleet block index: scheduling prices
    # remote-fetch vs local-prefill in the marginal-cost model and ships
    # a {peer, keys} hint so the chosen server fetches the matching block
    # run from the sibling instead of re-prefilling. False restores
    # pre-fabric scheduling (and stops shipping hints).
    kv_fabric: bool = True
    # relative cost of fetching one remote-held prefix token vs
    # prefilling it locally (0 = fetch is free, 1 = no better than
    # prefill); scales the marginal-cost discount for sibling-held blocks
    kv_fabric_fetch_cost_factor: float = 0.25


@dataclass
class SupervisorConfig:
    """Self-healing fleet supervisor (launcher/supervisor.py) policy knobs.

    The supervisor closes ROADMAP item 1's control loop: it polls the
    router's /metrics and each replica's /health, freezes a
    FleetSnapshot, and runs the pure planner `plan_actions(snapshot,
    policy)` whose output drives four safe transitions — scale up (spawn
    through the launcher seam with jittered-backoff retry and crash-loop
    escalation), scale down (/drain to survivors, kill only after the
    drain commits), replace (dead / breaker-open replica drained if
    reachable, killed, respawned), and re-role (prefill<->decode flip via
    drain as the workload mix shifts). Every knob below is a planner
    input, so policy behaviour is unit-testable without a fleet.
    """

    enabled: bool = False
    # control-loop cadence; each tick polls, snapshots, plans, dispatches
    tick_interval_s: float = 1.0
    # -- capacity bounds -------------------------------------------------
    # hard floor no plan may violate (scale-down is refused at the floor;
    # replace preserves capacity and is always allowed)
    min_replicas: int = 1
    max_replicas: int = 8
    # -- SLO signals + hysteresis ---------------------------------------
    # in-flight requests per replica treated as 1.0 utilization; fleet
    # util = (running + router queue depth) / (alive * this)
    util_inflight_target: int = 8
    # hysteresis band: scale up at/above the high mark, down at/below the
    # low mark, and HOLD in between (no flapping)
    scale_up_util: float = 0.85
    scale_down_util: float = 0.30
    # router admission-queue depth that forces a scale-up regardless of
    # the util estimate (queueing is the SLO breach, not a proxy for one)
    scale_up_queue_depth: int = 4
    # -- per-action cooldowns -------------------------------------------
    scale_up_cooldown_s: float = 2.0
    scale_down_cooldown_s: float = 20.0
    replace_cooldown_s: float = 2.0
    rerole_cooldown_s: float = 30.0
    # -- spawn retry / crash-loop escalation ----------------------------
    # consecutive spawn failures on one slot before the supervisor stops
    # retrying it, records a crash_loops_total alert, and continues with
    # the degraded fleet
    spawn_max_attempts: int = 3
    spawn_backoff_s: float = 0.5
    spawn_backoff_max_s: float = 10.0
    # each backoff is scaled by uniform[1-j, 1+j] so simultaneous slot
    # retries don't hammer the launcher in lockstep
    spawn_backoff_jitter: float = 0.25
    # -- drain-as-safe-transition ---------------------------------------
    # a /drain that has not committed within this deadline is aborted and
    # its action rolled back (the victim keeps serving; drain_rollbacks
    # counts the abort) — a hung drain must never wedge the control loop
    drain_deadline_s: float = 30.0
    # -- liveness --------------------------------------------------------
    # consecutive failed /health polls before a replica counts as dead in
    # the snapshot (replace candidate)
    health_fail_threshold: int = 2
    health_timeout_s: float = 5.0
    # -- re-role ---------------------------------------------------------
    rerole_enabled: bool = True
    # |observed prefill work share - provisioned prefill replica share|
    # must exceed this band before a flip is planned (mix-shift hysteresis)
    rerole_band: float = 0.25
    # -- fleet KV fabric ------------------------------------------------
    # Cheap drain: before draining a victim, aggregate the survivors'
    # content-key digests (router pressure snapshots) and pass them as
    # `refetchable`; sessions whose blocks the fleet already holds export
    # META-ONLY (identity + sampling key, no KV bytes — siblings re-fetch
    # or the resume re-prefills). Warm start: a freshly spawned replica
    # is told to pre-fetch the fleet's hottest block runs (/warm_start)
    # before it takes traffic. False disables both fabric integrations.
    kv_fabric: bool = True
    # max sessions a cold replica pulls per surviving peer at warm start
    warm_start_sessions: int = 4


@dataclass
class InferenceEngineConfig:
    """Rollout-side engine config (reference cli_args.py:785)."""

    experiment_name: str | None = None
    trial_name: str | None = None
    max_concurrent_rollouts: None | int = None
    queue_size: None | int = None
    consumer_batch_size: int = 1
    max_head_offpolicyness: int = 0
    check_trajectory_format: bool = False
    schedule_policy: str = "round_robin"
    setup_timeout: float = 120.0
    # Per-request deadline: every generation request owns a budget of
    # `request_timeout` seconds from submission, and the REMAINING budget
    # propagates through every stage — router schedule retries, the
    # router's bounded queue wait (shipped as `deadline_s` so the router
    # sheds instead of holding a dead request), 429 Retry-After sleeps,
    # and each failover attempt's transport timeout — so a request never
    # retries past its own deadline.
    request_timeout: float = 3600.0
    request_retries: int = 3
    # Backoff jitter fraction for retry/429 sleeps: each wait is scaled
    # by uniform[1-j, 1+j] so synchronized clients (a whole fleet shed in
    # one poll round) don't retry in lockstep and re-dogpile the server.
    retry_jitter: float = 0.25
    pause_grace_period: float = 0.0
    # Overlapped weight sync: stream staged weight buckets with generation
    # LIVE and pause only around /commit_weights, so the observed generation
    # pause is O(device apply) instead of O(network transfer). Disable to
    # restore the legacy pause-for-the-whole-push behavior.
    weight_sync_overlap: bool = True
    # How many packed weight buckets may be in flight at once during the
    # staged push (device→host gather of bucket N+1 overlaps the HTTP POST
    # of bucket N; bounded so host memory stays at inflight × chunk_mb).
    weight_sync_inflight_buckets: int = 2
    # Router-aware failover: when a /generate attempt exhausts its
    # transport retries (replica died mid-request), the client re-schedules
    # via the fleet router (or the local least-load fallback, excluding the
    # failed address) and re-sends with the SAME delivery id (xid) — the
    # server-side idempotency table makes the retry exactly-once. This caps
    # how many distinct replicas one submission may fail over across.
    fleet_failover_retries: int = 2
    # per-attempt timeout for /schedule_request against the fleet router
    # (queued requests are held by the router up to its queue_timeout_s,
    # so this must comfortably exceed it)
    router_request_timeout: float = 60.0
    # Fleet router policy knobs (launcher/router.py); launchers pass these
    # through when they spawn the router job.
    router: RouterConfig = field(default_factory=RouterConfig)
    # Deterministic fault injection (chaos testing; off by default).
    fault_injection: FaultInjectionConfig = field(
        default_factory=FaultInjectionConfig
    )


@dataclass
class _Timer:
    experiment_name: str = ""
    trial_name: str = ""
    fileroot: str = ""
    freq_epochs: int | None = None
    freq_steps: int | None = None
    freq_secs: int | None = None


@dataclass
class EvaluatorConfig(_Timer):
    pass


@dataclass
class SaverConfig(_Timer):
    pass


@dataclass
class RecoverConfig(_Timer):
    """Step-level crash recovery (utils/recover.py).

    Each dump is written to a fresh `step-{G}.tmp` directory, sealed with a
    checksummed, fsynced MANIFEST.json, atomically renamed to `step-{G}`,
    and only then pruned to `keep_last` — dying at any instant leaves every
    previously committed recovery point intact. `load` walks committed
    steps newest→oldest and skips torn/manifest-mismatched candidates
    instead of crashing.
    """

    mode: str = "disabled"  # "disabled" | "auto" | "fault" | "resume"
    retries: int = 3
    # committed step-{G} recovery points retained after each successful
    # dump (newest keep_last survive pruning); >= 1. Two is the floor that
    # makes a torn newest checkpoint recoverable from its predecessor.
    keep_last: int = 2


@dataclass
class WandBConfig:
    mode: str = "disabled"
    wandb_base_url: str = ""
    wandb_api_key: str = ""
    entity: str | None = None
    project: str | None = None
    name: str | None = None
    job_type: str | None = None
    group: str | None = None
    notes: str | None = None
    tags: list[str] | None = None
    config: dict | None = None
    id_suffix: str | None = "train"


@dataclass
class SwanlabConfig:
    project: str | None = None
    name: str | None = None
    config: dict | None = None
    logdir: str | None = None
    mode: str | None = "disabled"
    api_key: str | None = None


@dataclass
class TensorBoardConfig:
    path: str | None = None


@dataclass
class StatsLoggerConfig:
    experiment_name: str = ""
    trial_name: str = ""
    fileroot: str = ""
    wandb: WandBConfig = field(default_factory=WandBConfig)
    swanlab: SwanlabConfig = field(default_factory=SwanlabConfig)
    tensorboard: TensorBoardConfig = field(default_factory=TensorBoardConfig)


@dataclass
class ClusterSpecConfig:
    name_resolve: NameResolveConfig = field(default_factory=NameResolveConfig)
    cluster_name: str = "local"
    fileroot: str = "/tmp/areal_tpu"
    n_nodes: int = 1
    n_accelerators_per_node: int = 8  # chips per host (v5p host = 4, v5e = 8)


@dataclass
class DatasetConfig:
    path: str = ""
    type: str = ""
    batch_size: int = 1
    shuffle: bool = True
    pin_memory: bool = False
    num_workers: int = 0
    drop_last: bool = True
    max_length: int | None = None


@dataclass
class SlurmLauncherConfig:
    srun_additional_args: str = ""
    additional_bash_cmds: list[str] | None = None
    container_type: str = "none"
    mount: str = ""
    trainer_image: str | None = None
    inference_server_image: str | None = None


@dataclass
class LauncherConfig:
    inference_server_cpus_per_accelerator: int = 4
    inference_server_mem_per_accelerator: int = 32 * 1024
    trainer_cpus_per_accelerator: int = 4
    trainer_mem_per_accelerator: int = 32 * 1024
    inference_server_env_vars: str = ""
    trainer_env_vars: str = ""
    # Disaggregated role fleet: of the gen data-parallel replicas, launch
    # this many with --role prefill (compute-bound: prompt prefills only,
    # KV streamed to the decode replicas) and the REST with --role decode.
    # 0 (default) launches every replica unified. Must leave at least one
    # decode replica (prefill_replicas < gen dp size).
    prefill_replicas: int = 0
    # Self-healing fleet supervisor (launcher/supervisor.py): SLO
    # autoscaling + replace/re-role over the decode fleet. Off by default;
    # when enabled the launcher runs the control loop next to the router.
    supervisor: SupervisorConfig = field(default_factory=SupervisorConfig)
    slurm: SlurmLauncherConfig = field(default_factory=SlurmLauncherConfig)


@dataclass
class BaseExperimentConfig:
    """Root experiment config (reference cli_args.py:1145)."""

    experiment_name: str = "experiment"
    trial_name: str = "trial"
    cluster: ClusterSpecConfig = field(default_factory=ClusterSpecConfig)
    allocation_mode: str = ""
    seed: int = 1
    total_train_epochs: int = 1
    total_train_steps: int | None = None
    total_train_n_seqs: int | None = None
    tokenizer_path: str = ""
    train_dataset: DatasetConfig = field(default_factory=DatasetConfig)
    valid_dataset: DatasetConfig | None = None
    saver: SaverConfig = field(default_factory=SaverConfig)
    evaluator: EvaluatorConfig = field(default_factory=EvaluatorConfig)
    stats_logger: StatsLoggerConfig = field(default_factory=StatsLoggerConfig)
    recover: RecoverConfig = field(default_factory=RecoverConfig)
    decode: JaxDecodeConfig = field(default_factory=JaxDecodeConfig)
    launcher: LauncherConfig = field(default_factory=LauncherConfig)


@dataclass
class SFTConfig(BaseExperimentConfig):
    model: TrainEngineConfig = field(default_factory=TrainEngineConfig)


@dataclass
class RWConfig(BaseExperimentConfig):
    model: TrainEngineConfig = field(default_factory=TrainEngineConfig)


@dataclass
class GRPOConfig(BaseExperimentConfig):
    async_training: bool = True
    gconfig: GenerationHyperparameters = field(
        default_factory=GenerationHyperparameters
    )
    rollout: InferenceEngineConfig = field(default_factory=InferenceEngineConfig)
    actor: PPOActorConfig = field(default_factory=PPOActorConfig)
    ref: PPOActorConfig = field(default_factory=PPOActorConfig)
    # Which rollout workflow drives episodes: single-shot verifiable reward,
    # the self-correction loop (ref: examples/multi-turn-math/train.py), or
    # the VLM variant (ref: examples/vlm/clevr_count_70k_grpo.py).
    workflow: str = "rlvr"  # "rlvr" | "multi_turn" | "vision_rlvr" | "tir"
    # multi_turn knobs (ref: areal/workflow/multi_turn.py)
    max_turns: int = 3
    turn_discount: float = 0.9
    # tir knobs (ref: examples/tir/tir_workflow.py)
    max_tool_calls: int = 4
    tool_timeout_seconds: float = 8.0


@dataclass
class PPOConfig(GRPOConfig):
    critic: PPOCriticConfig = field(default_factory=PPOCriticConfig)


# ---------------------------------------------------------------------------
# CLI / YAML loading (reference cli_args.py:1247-1314)
# ---------------------------------------------------------------------------


def parse_cli_args(argv: list[str]):
    """Parse ``--config file.yaml key=value ...`` into (dict, overrides)."""
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", type=str, default=None, help="YAML config file")
    args, overrides = parser.parse_known_args(argv)
    cfg_dict = {}
    if args.config is not None:
        with open(args.config) as f:
            cfg_dict = yaml.safe_load(f) or {}
    kv = []
    for item in overrides:
        if "=" not in item:
            raise ValueError(f"override {item!r} must be of the form key=value")
        k, v = item.split("=", 1)
        kv.append((k, v))
    return cfg_dict, kv


def load_expr_config(argv: list[str], config_cls, ignore_unknown: bool = False):
    """Load a structured experiment config from CLI argv.

    Returns (config, config_file_dict) like the reference's
    `load_expr_config` (cli_args.py:1280). `ignore_unknown` lets a
    subset-view consumer (the launcher) parse a subclass's YAML.
    """
    cfg_dict, overrides = parse_cli_args(argv)
    config = structured.from_dict(
        config_cls, cfg_dict, ignore_unknown=ignore_unknown
    )
    for k, v in overrides:
        try:
            structured.apply_override(config, k, v)
        except structured.UnknownFieldError:
            # subset view: subclass-only fields are fine to skip; bad
            # VALUES for known fields still raise below
            if not ignore_unknown:
                raise
    # propagate experiment/trial names into nested configs that need them
    for attr in ("saver", "evaluator", "stats_logger", "recover"):
        sub = getattr(config, attr, None)
        if sub is not None:
            if not sub.experiment_name:
                sub.experiment_name = config.experiment_name
            if not sub.trial_name:
                sub.trial_name = config.trial_name
            if hasattr(sub, "fileroot") and not sub.fileroot:
                sub.fileroot = config.cluster.fileroot
    for attr in ("rollout",):
        sub = getattr(config, attr, None)
        if sub is not None:
            if sub.experiment_name is None:
                sub.experiment_name = config.experiment_name
            if sub.trial_name is None:
                sub.trial_name = config.trial_name
    for attr in ("actor", "ref", "critic", "model"):
        sub = getattr(config, attr, None)
        if sub is not None:
            if not sub.experiment_name:
                sub.experiment_name = config.experiment_name
            if not sub.trial_name:
                sub.trial_name = config.trial_name
    return config, cfg_dict


def save_config(config, save_dir: str) -> str:
    """Persist the resolved config as YAML in the run directory."""
    os.makedirs(save_dir, exist_ok=True)
    path = os.path.join(save_dir, "config.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(structured.to_dict(config), f, sort_keys=False)
    return path


def get_user() -> str:
    try:
        return getpass.getuser()
    except Exception:
        return "unknown"
